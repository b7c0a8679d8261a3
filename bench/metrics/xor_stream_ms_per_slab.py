"""Device time of the fused ``xor_stream`` kernel per dispatched slab: the
summed durations of the kernel's trace events in the window, over the
slabs the server dispatched in it."""
import trace_reduce


def read(ctx):
    tr, slabs = ctx["trace"], ctx["work"]["slabs"]
    if tr is None or not slabs:
        return None
    s = trace_reduce.kernel_seconds(tr["op_seconds"], "xor_stream")
    return s * 1e3 / slabs if s > 0 else None
