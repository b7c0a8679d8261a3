"""The fused ``xor_stream`` kernel's share of its HBM roofline: the least
time the window's work needs at the chip's peak bandwidth, over the
kernel's device time.

The work is counted per live lane from the table's geometry, whatever
implements the kernel: a read of the lane's bucket column in all ``k``
planes (``k * slots * (Wk + Wv + 1)`` words), a write of that column for
each accepted mutation, and the lane's query (op, key and value words) and
result (flags and value words).  The kernel does no arithmetic worth a
compute bound, so bytes bound it."""
import trace_reduce


def work_bytes(geometry, live_lanes, accepted_mutations):
    g = geometry
    column = 4 * g["k"] * g["slots"] * (g["key_words"] + g["val_words"] + 1)
    query = 4 * (1 + g["key_words"] + g["val_words"])
    result = 4 * (1 + g["val_words"])
    return (live_lanes * (column + query + result)
            + accepted_mutations * column)


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    kernel = trace_reduce.kernel_seconds(tr["op_seconds"], "xor_stream")
    if kernel <= 0:
        return None
    w = ctx["work"]
    least = work_bytes(ctx["geometry"], w["live_lanes"],
                       w["accepted_mutations"]) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / kernel
