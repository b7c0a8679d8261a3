"""Pallas TPU kernel: fused in-kernel query streaming (DESIGN.md §3.1).

The scanned path pays a full table round trip per step: every ``lax.scan``
iteration launches ``xor_probe``, bounces ``ProbeResult``/``MutationPlan``
through jnp stages, then launches ``xor_commit``.  This kernel is the
paper's PE pipeline proper: one ``pallas_call`` processes the whole
``[T, N]`` query stream while the table stays in VMEM.  Per step it fuses

  probe    k-store read of each lane's bucket + search XOR tree + slot
           resolution (match / open / stagger)
  plan     op decode (insert/delete acceptance, slot choice)
  encode   non-search XOR tree against the *pre-step* table state
  commit   per-lane stores of the encodings, in lane order

Table layout: the kernel takes one replica of the table's stored array
as it is, the lane-dense layout of ``kernels/packed.py`` (``[G, C, 128]``:
bucket groups on the leading axis, plane/word/slot rows on sublanes,
buckets on lanes), and returns it updated in place (the output aliases
the input).  No layout conversion happens around the call.

Last-wins commit.  Same-step duplicate ``(port, bucket, slot)`` write
targets must resolve last-in-program-order, matching the jnp oracle's
``_scatter_records``.  The commit walks its lanes in order, so a later lane
overwrites an earlier one.  Lanes are sorted by sweep pass (below) with a
stable sort, so two lanes that share a target (hence a bucket, hence a
pass) keep their program order.

Sweep passes (the HashGraph bin-then-process move).  An XLA-side pre-pass
stable-sorts each step's lanes by the pass that owns their bucket and
hands the kernel a ``[P+1, T]`` table of per-(pass, step) lane offsets as
a scalar-prefetch operand, together with the lanes' partition-relative
buckets ``[T, N]``.  ``grid = (P,)``; grid step ``p``

  * DMAs its span of ``G/P`` bucket groups from the HBM table into a VMEM
    scratch buffer ONCE, runs all T steps against it with an in-kernel
    loop, and DMAs it back once: one full-table round trip per stream;
  * touches only its own lane window ``[offs[p, t], offs[p+1, t])`` per
    step: gather and commit walk just those lanes, so their total work
    equals the live lane count;
  * reads queries from ONE packed ``[T, 1+Wk+Wv, N]`` operand (op|port|legal
    word, key words, value words; lanes on the minor axis) and merges its
    windows into a resident ``[T, 1+Wv, N]`` result block in sorted order —
    the caller inverse-permutes back to program order.

``P == 1`` is the VMEM-resident table (the whole table is the span).  A
table larger than VMEM sweeps ``P > 1`` spans, each sized to the VMEM
budget by the caller (``kernels.ops.xor_stream``).  A lane's bucket fixes
both where it probes and where it commits, so mutations in one span never
touch another.

Bucket-base offset (the sharded regime, DESIGN.md §2): ``bucket_base`` is a
*traced* scalar — under ``shard_map`` it is ``axis_index * local_buckets``
— marking the global bucket range ``[base, base + B)`` this table partition
owns.  Lane buckets stay GLOBAL; lanes outside the partition sort behind
every pass window (sentinel pass id P), so they are inert: no writes,
found/ok False, value 0.  ``base == 0`` with a full table recovers the
single-domain kernel bit-exactly.

The kernel lowers under Mosaic for TPU and runs in Pallas interpret mode off
the chip (``packed.interpret_mode`` picks which).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hash_table import OP_DELETE, OP_INSERT, OP_SEARCH
from repro.kernels import packed as pk


def _plan_lanes(op, legal, found, hopen, mslot, oslot, qk, qv, active):
    """Mutation plan for one step's lanes (op decode + slot choice + new
    record words + per-lane ok) on ``[1, n]`` rows.  Mirrors
    ``engine.mutation_plan`` exactly."""
    is_ins = op == OP_INSERT
    is_del = op == OP_DELETE
    ins_ok = is_ins & (found | hopen) & legal
    del_ok = is_del & found & legal
    do_write = (ins_ok | del_ok) & active
    slot = jnp.where(is_del | found, mslot, oslot)
    new = jnp.concatenate([
        jnp.where(is_del, jnp.uint32(0), qk),
        jnp.where(is_del, jnp.uint32(0), qv),
        jnp.where(is_del, jnp.uint32(0), jnp.uint32(1)).astype(jnp.uint32)],
        axis=0)                                            # [Wtot, n]
    # the three op classes are disjoint, so this is the oracle's nested where
    lane_ok = ins_ok | del_ok | (op == OP_SEARCH)
    return do_write, slot, new, lane_ok


def _xor_stream_kernel(offs_ref, bkt_ref, q_ref, tab_hbm, out_hbm, res_ref,
                       span, sem, *, k: int, slots: int, key_words: int,
                       val_words: int, span_groups: int, steps: int, n: int,
                       stagger: bool):
    p = pl.program_id(0)
    S, Wk, Wv = slots, key_words, val_words
    W = Wk + Wv + 1
    g0 = p * span_groups
    b0 = g0 * pk.LANES

    load = pltpu.make_async_copy(tab_hbm.at[pl.ds(g0, span_groups)], span,
                                 sem)
    load.start()
    load.wait()

    @pl.when(p == 0)
    def _():
        res_ref[...] = jnp.zeros(res_ref.shape, res_ref.dtype)

    col = pk.lane_ids(1, n)

    def step(t, carry):
        off = offs_ref[p, t]
        end = offs_ref[p + 1, t]
        cols = pk.gather(span, lambda i: bkt_ref[t, i] - b0, off, end, n)
        q = q_ref[t]                                       # [1+Wk+Wv, n]
        opw = pk.i32(q[0:1])
        op = opw & 0xFF
        port = (opw >> 8) & 0xFF
        legal = ((opw >> 16) & 1) != 0
        qk, qv = q[1:1 + Wk], q[1 + Wk:]
        active = (col >= off) & (col < end)

        found, mslot, oslot, hopen, value, rem = pk.probe(
            cols, qk, port, k=k, slots=S, key_words=Wk, val_words=Wv,
            stagger=stagger)
        do_write, slot, new, lane_ok = _plan_lanes(
            op, legal, found, hopen, mslot, oslot, qk, qv, active)
        enc = pk.i32(new ^ pk.select_slot(rem, slot, S))   # [Wtot, n]

        flags = found.astype(jnp.uint32) | (lane_ok.astype(jnp.uint32) << 1)
        res = jnp.concatenate([flags, value], axis=0)      # [1+Wv, n]
        res_ref[t] = jnp.where(active, res, res_ref[t])

        dw = do_write.astype(jnp.int32)

        def commit(i, c):
            b = bkt_ref[t, i] - b0
            e = pk.pick_lane(enc, i)                       # [Wtot, 1]
            pk.write_slot(span.at[b // pk.LANES], b % pk.LANES,
                          pk.pick_scalar(port, i), pk.pick_scalar(slot, i),
                          [e[w:w + 1] for w in range(W)], slots=S, words=W,
                          enable=pk.pick_scalar(dw, i) != 0)
            return c

        jax.lax.fori_loop(off, end, commit, 0)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)

    store = pltpu.make_async_copy(span, out_hbm.at[pl.ds(g0, span_groups)],
                                  sem)
    store.start()
    store.wait()


@functools.partial(jax.jit, static_argnames=("k", "buckets", "slots",
                                             "passes", "interpret", "stagger",
                                             "vmem_limit"))
def xor_stream_pallas(bucket: jnp.ndarray, port: jnp.ndarray,
                      legal: jnp.ndarray, ops: jnp.ndarray,
                      qkeys: jnp.ndarray, qvals: jnp.ndarray,
                      table: jnp.ndarray, *, k: int, buckets: int,
                      slots: int, passes: int = 1, interpret=None,
                      stagger: bool = False, bucket_base=0,
                      vmem_limit: int | None = None):
    """Stream T steps of N queries through one fused kernel.

    bucket/ops ``[T, N]``; port/legal ``[N]`` (step-invariant lanes) or
    ``[T, N]`` (per-step lanes — the bounded router re-bins lanes so a
    routed slot's origin varies by step); qkeys ``[T, N, Wk]``;
    qvals ``[T, N, Wv]``; ``table`` one replica's stored array ``[G, C,
    128]`` holding ``buckets`` buckets of ``k`` planes and ``slots`` slots.
    Returns ``(table', found[T, N] bool, ok[T, N] bool, value[T, N, Wv])``
    with ``table'`` updated in place.  ``bucket_base`` (traced scalar)
    marks the global bucket range this table partition owns; lanes outside
    ``[base, base + buckets)`` are inert.  ``passes`` — a divisor of ``G``,
    sized from the VMEM budget by ``kernels.ops.stream_bucket_tiles`` — is
    the number of sweep passes (module docstring).
    """
    T, N = ops.shape
    S, B = slots, buckets
    Wk, Wv = qkeys.shape[-1], qvals.shape[-1]
    G, C, _ = table.shape
    if passes < 1 or G % passes:
        raise ValueError(f"passes={passes} must divide the {G} bucket groups")
    if T == 0:
        return (table, jnp.zeros((0, N), jnp.bool_),
                jnp.zeros((0, N), jnp.bool_), jnp.zeros((0, N, Wv), jnp.uint32))
    P = passes
    Gs = G // P
    base = jnp.asarray(bucket_base).astype(jnp.int32)
    if port.ndim == 1:
        port = jnp.broadcast_to(port[None], (T, N))
    if legal.ndim == 1:
        legal = jnp.broadcast_to(legal[None], (T, N))

    # ---- XLA-side pre-pass: stable-sort each step's lanes by sweep pass ----
    with jax.named_scope("kv.prepass"):
        rel = bucket.astype(jnp.int32) - base
        in_part = (rel >= 0) & (rel < B)
        pass_id = jnp.where(in_part, rel // (Gs * pk.LANES), P)
        perm = jnp.argsort(pass_id, axis=1, stable=True)         # [T, N]
        # offs[j, t] == #lanes of step t with pass id < j: pass p's window is
        # [offs[p, t], offs[p+1, t]) and sentinel lanes fall past every window
        offs = jnp.sum(pass_id[:, :, None]
                       < jnp.arange(1, P + 1, dtype=jnp.int32)[None, None, :],
                       axis=1, dtype=jnp.int32)
        offs = jnp.concatenate([jnp.zeros((T, 1), jnp.int32), offs], axis=1).T
        opw = (ops.astype(jnp.uint32) & 0xFF) \
            | (port.astype(jnp.uint32) << 8) \
            | (legal.astype(jnp.uint32) << 16)
        q = jnp.concatenate([opw[..., None], qkeys.astype(jnp.uint32),
                             qvals.astype(jnp.uint32)], axis=-1)  # [T, N, Wq]
        q_s = jnp.take_along_axis(q, perm[..., None],
                                  axis=1).transpose(0, 2, 1)
        bkt_s = jnp.take_along_axis(jnp.where(in_part, rel, 0), perm, axis=1)

    Wq = 1 + Wk + Wv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(P,),
        in_specs=[
            pl.BlockSpec((T, Wq, N), lambda p, offs, bkt: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),             # HBM-resident
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((T, 1 + Wv, N), lambda p, offs, bkt: (0, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((Gs, C, pk.LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
    )
    tab, out = pl.pallas_call(
        functools.partial(_xor_stream_kernel, k=k, slots=S, key_words=Wk,
                          val_words=Wv, span_groups=Gs, steps=T, n=N,
                          stagger=stagger),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct((T, 1 + Wv, N), jnp.uint32)),
        # the table updates in place — a fresh HBM buffer would double the
        # stream's only full-table traffic
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_limit),
        interpret=pk.interpret_mode(interpret),
        name="xor_stream",
    )(offs, bkt_s, q_s, table)

    with jax.named_scope("kv.unpermute"):
        inv = jnp.argsort(perm, axis=1)                    # sorted -> program
        out = jnp.take_along_axis(out, inv[:, None, :], axis=2)
        found = (out[:, 0] & 1) != 0
        ok = ((out[:, 0] >> 1) & 1) != 0
        value = out[:, 1:].transpose(0, 2, 1)
    return tab, found, ok, value
