"""Device time per dispatched slab of every operation but the fused
kernel: the stream seam around it (``h3_hash``, the pass pre-pass, the
table's pack and unpack, the result permutation)."""
import trace_reduce


def read(ctx):
    tr, slabs = ctx["trace"], ctx["work"]["slabs"]
    if tr is None or not slabs:
        return None
    ops = tr["op_seconds"]
    kernel = trace_reduce.kernel_seconds(ops, "xor_stream")
    if kernel <= 0:
        return None
    return (sum(ops.values()) - kernel) * 1e3 / slabs
