"""The longest single ``TableServer.step()`` call in the window, in ms
(host clock, open-loop cells): packing, dispatch and retirement of one
slab, with any stall of the host loop inside it."""


def read(ctx):
    return ctx["steps"]["max_ms"] or None
