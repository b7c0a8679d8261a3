"""Share of dispatched lanes that were padding, over the window (the
server's ``pad_lanes`` and ``live_lanes`` counters, open-loop cells)."""


def read(ctx):
    c = ctx["counters"]
    total = c["live_lanes"] + c["pad_lanes"]
    return c["pad_lanes"] / total if total else None
