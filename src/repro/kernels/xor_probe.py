"""Pallas TPU kernel: fused XOR-decode + slot probe (the paper's PE pipeline).

Fuses the PE stages of §IV-C.2 — parallel Partial-XOR-Store read, the two XOR
reduction trees, and the result-resolution unit — into a single VMEM-resident
kernel.  The table (one replica: k partial stores) is held whole in VMEM in
the packed layout of ``kernels/packed.py``, mirroring the FPGA's on-chip
URAM residency.

Per query:
  rows    = stores[:, bucket[q]]          k x S x words   (column gather)
  dec     = XOR-tree(rows)                S x words       (search XOR tree)
  match   = valid(dec) & key-compare      S
  found, match_slot, open_slot, value
  rem     = dec ^ rows[port]              (non-search XOR tree output:
                                           XOR of all stores EXCEPT the
                                           querying port — the encode basis)

Bucket indices arrive as a scalar-prefetch operand; each query reads its
bucket's column from the group block of the stored array (``packed.gather``).  The
kernel lowers under Mosaic for TPU and runs in Pallas interpret mode off
the chip.  Tables larger than the VMEM budget take the jnp fallback in
ops.py (HBM gathers, same semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packed as pk


def _xor_probe_kernel(bkt_ref, port_ref, qkey_ref, tab_ref, flags_ref,
                      value_ref, rem_ref, *, k: int, slots: int,
                      key_words: int, val_words: int, n: int, stagger: bool):
    cols = pk.gather(tab_ref, lambda i: bkt_ref[i], 0, n, n)
    found, mslot, oslot, hopen, value, rem = pk.probe(
        cols, qkey_ref[...], port_ref[...], k=k, slots=slots,
        key_words=key_words, val_words=val_words, stagger=stagger)
    flags_ref[...] = jnp.concatenate(
        [found.astype(jnp.int32), mslot, oslot, hopen.astype(jnp.int32)],
        axis=0)
    value_ref[...] = value
    rem_ref[...] = rem


@functools.partial(jax.jit, static_argnames=("k", "slots", "val_words",
                                             "interpret", "stagger",
                                             "vmem_limit"))
def xor_probe_pallas(bucket: jnp.ndarray, port: jnp.ndarray, qkeys: jnp.ndarray,
                     table: jnp.ndarray, *, k: int, slots: int,
                     val_words: int, interpret=None, stagger: bool = False,
                     vmem_limit: int | None = None):
    """Probe one replica for a batch of queries.

    bucket [N] uint32, port [N] int32, qkeys [N, Wk] uint32, ``table`` one
    replica's stored array ``[G, C, 128]`` (``k`` planes, ``slots`` slots,
    ``val_words``-word values).  Returns (found[N] bool, match_slot[N] i32,
    open_slot[N] i32, has_open[N] bool, value[N, Wv], rem_keys[N, S, Wk],
    rem_vals[N, S, Wv], rem_valid[N, S]).
    """
    N = bucket.shape[0]
    S, Wk, Wv = slots, qkeys.shape[-1], val_words
    W = Wk + Wv + 1
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    flags, value, rem = pl.pallas_call(
        functools.partial(_xor_probe_kernel, k=k, slots=S, key_words=Wk,
                          val_words=Wv, n=N, stagger=stagger),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem],
        out_specs=(vmem, vmem, vmem),
        out_shape=(jax.ShapeDtypeStruct((4, N), jnp.int32),
                   jax.ShapeDtypeStruct((Wv, N), jnp.uint32),
                   jax.ShapeDtypeStruct((S * W, N), jnp.uint32)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=pk.interpret_mode(interpret),
    )(bucket.astype(jnp.int32), port.astype(jnp.int32)[None],
      qkeys.astype(jnp.uint32).T, table)
    rem = rem.reshape(W, S, N).transpose(2, 1, 0)          # [N, S, W]
    return (flags[0] != 0, flags[1], flags[2], flags[3] != 0, value.T,
            rem[..., :Wk], rem[..., Wk:Wk + Wv], rem[..., W - 1])
