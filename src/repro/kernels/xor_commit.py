"""Pallas TPU kernel: masked store of pre-encoded mutation records.

The mutation half of the paper's PE pipeline (§IV-C.3): for every write lane
the new plaintext entry is XOR-encoded against the *other* k-1 partial stores
(the non-search XOR tree) and the encoding is written into the own-port
store of EVERY replica (inter-PE propagation).

Replicas are byte-identical at step boundaries, so the encoding is the same
for every replica — the engine computes it ONCE from the ``ProbeResult`` rem
basis the probe stage already produced (``engine.encode_records``), and this
kernel only stores it: into replica 0's stored array (``kernels/packed.py``),
which the engine then broadcasts to all replicas.

Timing matches the FPGA (and the jnp oracle) exactly: encodings come from the
pre-step snapshot (via the probe), then all write ports commit.  The commit
is a sequential masked store over lanes (lane order = program order, so
duplicate (port, bucket, slot) targets resolve last-wins).  Lane targets
arrive as SMEM operands; the table is DMA'd whole into VMEM scratch and back.
Tables beyond the VMEM budget take the jnp fallback in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packed as pk


def _xor_commit_kernel(port_ref, bkt_ref, slot_ref, enc_ref, tab_hbm, out_hbm,
                       tab, sem, *, buckets: int, slots: int, words: int,
                       n: int):
    load = pltpu.make_async_copy(tab_hbm, tab, sem)
    load.start()
    load.wait()
    enc = pk.i32(enc_ref[...])                             # [W, N]

    def body(i, c):
        @pl.when(bkt_ref[i] < buckets)
        def _():
            b = bkt_ref[i]
            e = pk.pick_lane(enc, i)                       # [W, 1]
            pk.write_slot(tab.at[b // pk.LANES], b % pk.LANES,
                          port_ref[i], slot_ref[i],
                          [e[w:w + 1] for w in range(words)], slots=slots,
                          words=words)
        return c

    jax.lax.fori_loop(0, n, body, 0)
    store = pltpu.make_async_copy(tab, out_hbm, sem)
    store.start()
    store.wait()


@functools.partial(jax.jit, static_argnames=("buckets", "slots",
                                             "interpret", "vmem_limit"))
def xor_commit_pallas(table: jnp.ndarray, port: jnp.ndarray,
                      bucket: jnp.ndarray, slot: jnp.ndarray,
                      enc_k: jnp.ndarray, enc_v: jnp.ndarray,
                      enc_b: jnp.ndarray, *, buckets: int, slots: int,
                      interpret=None, vmem_limit: int | None = None):
    """Masked store of encoded records into one replica's stored array.

    ``table`` ``[G, C, 128]`` holding ``buckets`` buckets of ``slots``
    slots; port/bucket/slot ``[N]`` (``bucket >= buckets`` marks a masked
    lane — dropped); enc_* the XOR-encoded rows ``[N, Wk] / [N, Wv] /
    [N]`` from ``engine.encode_records``.  Returns the updated table
    (in place).
    """
    N = port.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    enc = jnp.concatenate([enc_k, enc_v, enc_b[:, None]], axis=-1).T
    return pl.pallas_call(
        functools.partial(_xor_commit_kernel, buckets=buckets, slots=slots,
                          words=enc.shape[0], n=N),
        in_specs=[smem, smem, smem, pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=[pltpu.VMEM(table.shape, jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
        # stores update in place — without aliasing every step would
        # round-trip the full table through a fresh output buffer
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=pk.interpret_mode(interpret),
    )(port.astype(jnp.int32), bucket.astype(jnp.int32),
      slot.astype(jnp.int32), enc.astype(jnp.uint32), table)
