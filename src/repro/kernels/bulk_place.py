"""Pallas TPU kernel: bulk placement of planned records (DESIGN.md §3.2).

The commit half of the count-then-place bulk build (``engine.bulk_build``).
The plan (``engine.plan_bulk_build``) has already resolved duplicates and
assigned every surviving representative record a pairwise-distinct
``(bucket, slot)`` cell in the port-0 plane, so this kernel is pure
placement: no probe, no XOR encode (the target stores are empty, so the
encode basis is zero and plaintext IS the encoding), no supersession mask.

Layout: the kernel writes the port-0 plane rows of one replica's stored
array (``[G, C, 128]``, ``kernels/packed.py``), left in HBM.  An XLA-side pre-pass sorts
the records by bucket (masked records, ``bucket == B``, sort last) and
splits them into chunks of ``chunk`` records; ``grid = (chunks,)`` streams
each chunk's targets and payload into SMEM.  The kernel walks the records
in order and keeps ONE bucket-group block in VMEM: when a record's group
differs from the held one, the held block is DMA'd back and the new one
DMA'd in.  Sorted records visit every group once, so the build moves each
touched group block in and out once; untouched groups never move (the
plane output aliases its input).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packed as pk

DEFAULT_CHUNK = 1024


def _bulk_place_kernel(bkt_ref, slot_ref, pay_ref, tab_hbm, out_hbm, blk,
                       held, sem, *, buckets: int, slots: int,
                       payload_words: int, chunk: int):
    c = pl.program_id(0)
    W = payload_words + 1

    @pl.when(c == 0)
    def _():
        held[0] = -1

    def move(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def place(j, carry):
        b = bkt_ref[j]

        @pl.when(b < buckets)
        def _():
            g = b // pk.LANES

            @pl.when(g != held[0])
            def _():
                @pl.when(held[0] >= 0)
                def _():
                    move(blk, out_hbm.at[held[0]])
                move(tab_hbm.at[g], blk)
                held[0] = g

            words = [pay_ref[j * payload_words + w]
                     for w in range(payload_words)] + [jnp.int32(1)]
            pk.write_slot(blk, b % pk.LANES, 0, slot_ref[j], words,
                          slots=slots, words=W)
        return carry

    jax.lax.fori_loop(0, chunk, place, 0)

    @pl.when((c == pl.num_programs(0) - 1) & (held[0] >= 0))
    def _():
        move(blk, out_hbm.at[held[0]])


@functools.partial(jax.jit, static_argnames=("buckets", "slots", "chunk",
                                             "interpret"))
def bulk_place_pallas(w_bucket: jnp.ndarray, w_slot: jnp.ndarray,
                      keys: jnp.ndarray, vals: jnp.ndarray,
                      table: jnp.ndarray, *, buckets: int, slots: int,
                      chunk: int = DEFAULT_CHUNK, interpret=None):
    """Place ``n`` pre-planned records into the port-0 plane.

    ``w_bucket``/``w_slot`` ``[n]`` int32 (``bucket == buckets`` marks a
    masked record); ``keys [n, Wk]`` / ``vals [n, Wv]`` uint32 plaintext;
    ``table`` one replica's stored array ``[G, C, 128]`` of ``slots``
    slots a bucket.  A bucket here is a column of the array (group ``b //
    128``, lane ``b % 128``: ``packed.stored_column`` maps a partitioned
    table's buckets onto them), and ``buckets <= G * 128``.  ``chunk``
    records stream through SMEM per grid step.  Returns the updated table
    (in place).
    """
    B, S = buckets, slots
    Wk, Wv = keys.shape[-1], vals.shape[-1]
    n = w_bucket.shape[0]
    nch = max(-(-n // chunk), 1)
    pad = nch * chunk - n

    # ---- XLA-side pre-pass: sort records by bucket, pad to whole chunks --
    wb = jnp.where(w_bucket < B, w_bucket.astype(jnp.int32), B)
    order = jnp.argsort(wb, stable=True)
    bkt = jnp.pad(wb[order], (0, pad), constant_values=B)
    slot = jnp.pad(w_slot.astype(jnp.int32)[order], (0, pad))
    pay = jnp.concatenate([keys, vals], axis=-1).astype(jnp.uint32)[order]
    pay = pk.i32(jnp.pad(pay, ((0, pad), (0, 0)))).reshape(-1)

    C = table.shape[1]
    smem = lambda size: pl.BlockSpec((size,), lambda c: (c,),
                                     memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_bulk_place_kernel, buckets=B, slots=S,
                          payload_words=Wk + Wv, chunk=chunk),
        grid=(nch,),
        in_specs=[smem(chunk), smem(chunk), smem(chunk * (Wk + Wv)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=[pltpu.VMEM((C, pk.LANES), jnp.uint32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        # the table updates in place: untouched groups never move
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pk.interpret_mode(interpret),
    )(bkt, slot, pay, table)
