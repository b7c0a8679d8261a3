"""Pallas TPU kernel: Class-H3 universal hashing (the paper's hashing unit).

GF(2) matvec realised as AND + XOR-parity folds — pure VPU integer ops.  Keys
arrive word-transposed ``[W, N]`` so the query dimension lies on the 128-lane
axis; the Q matrix ``[J, W]`` is tiny and lives unblocked in VMEM.

Block layout:
  keys   [W, N]  -> blocks [W, BN]   (grid over N)
  q      [J, W]  -> unblocked (constant across grid steps)
  out    [N]     -> blocks [BN]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.packed import interpret_mode

DEFAULT_BLOCK_N = 1024


def _parity32(v):
    v = v ^ (v >> 16)
    v = v ^ (v >> 8)
    v = v ^ (v >> 4)
    v = v ^ (v >> 2)
    v = v ^ (v >> 1)
    return v & jnp.uint32(1)


def _h3_kernel(keys_ref, q_ref, out_ref, *, index_bits: int, key_words: int):
    acc = jnp.zeros(out_ref.shape, dtype=jnp.uint32)
    for j in range(index_bits):                    # static unroll: J <= ~20
        bit = jnp.zeros(out_ref.shape, dtype=jnp.uint32)
        for w in range(key_words):                 # static unroll: W in {1,2,4}
            bit = bit ^ _parity32(keys_ref[w, :] & q_ref[j, w])
        acc = acc | (bit << jnp.uint32(j))
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def h3_hash_pallas(keys_t: jnp.ndarray, q_masks: jnp.ndarray,
                   block_n: int = DEFAULT_BLOCK_N,
                   interpret=None) -> jnp.ndarray:
    """keys_t: [W, N] uint32 (word-transposed), q_masks: [J, W] uint32 -> [N]."""
    W, N = keys_t.shape
    J = q_masks.shape[0]
    bn = min(block_n, N)
    if N % bn:
        raise ValueError(f"N={N} not divisible by block {bn}")
    grid = (N // bn,)
    return pl.pallas_call(
        functools.partial(_h3_kernel, index_bits=J, key_words=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((W, bn), lambda i: (0, i)),
            pl.BlockSpec(q_masks.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((N,), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(keys_t, q_masks)
