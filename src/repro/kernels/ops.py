"""Jit'd public wrappers for the Pallas kernels, with shape guards and a
pure-jnp fallback for the per-step probe/commit kernels (used when the table
exceeds the VMEM-resident regime).

The jnp fallbacks ARE the engine's jnp backend (``repro.core.engine``) — there
is exactly one jnp and one Pallas implementation of each stage.

The kernels compile under Mosaic on a TPU backend and run the same kernel
code in Pallas interpret mode everywhere else: ``packed.interpret_mode``
is the one switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashing import h3_hash as _h3_jnp
from repro.kernels import packed as pk
from repro.kernels.h3_hash import h3_hash_pallas
from repro.kernels.xor_probe import xor_probe_pallas
from repro.kernels.xor_commit import xor_commit_pallas
from repro.kernels.xor_stream import xor_stream_pallas
from repro.kernels.bulk_place import bulk_place_pallas

# Scoped-VMEM limit handed to the kernels that hold a table in VMEM (v5e has
# 128 MiB of VMEM; Mosaic's default scoped limit is far below it).
VMEM_LIMIT_BYTES = 120 * 1024 * 1024
# On-chip bytes one table span may take (``table_vmem_bytes``), leaving the
# rest of the limit to query/result blocks and kernel temporaries.
VMEM_TABLE_BUDGET_BYTES = 96 * 1024 * 1024


def table_vmem_bytes(table) -> int:
    """On-chip bytes of ONE replica of a stored table (``[G, C, 128]``, or
    ``[R, G, C, 128]``; ``kernels/packed.py``) — the single source of truth
    for every VMEM-budget check (engine backend resolution, the
    probe/commit fallbacks, stream sweep passes)."""
    G, C, lanes = table.shape[-3:]
    return 4 * G * C * lanes


def fits_vmem(table) -> bool:
    return table_vmem_bytes(table) <= VMEM_TABLE_BUDGET_BYTES


def stream_bucket_tiles(table) -> int:
    """Sweep passes of the fused stream kernel: the smallest power-of-two
    pass count whose span fits ``VMEM_TABLE_BUDGET_BYTES`` (1 == the whole
    replica is VMEM-resident), capped at the stored table's bucket-group
    count — one 128-bucket group is the smallest span the kernel moves."""
    rb = table_vmem_bytes(table)
    groups = table.shape[-3]
    tiles = 1
    while rb // tiles > VMEM_TABLE_BUDGET_BYTES and tiles < groups:
        tiles *= 2
    return tiles


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_n"))
def h3_hash(keys: jnp.ndarray, q_masks: jnp.ndarray, use_pallas: bool = True,
            block_n: int = 1024) -> jnp.ndarray:
    """Hash ``[N, W]`` uint32 keys -> ``[N]`` uint32 bucket indices.  The
    batch is zero-padded to a whole number of kernel blocks."""
    n = keys.shape[0]
    # index_bits == 0 (single-bucket table) has an empty Q matrix — the
    # kernel's J-dim block would be zero-sized; the jnp path returns zeros.
    if not use_pallas or q_masks.shape[0] == 0 or n == 0:
        return _h3_jnp(keys, q_masks)
    bn = min(block_n, n)
    pad = -n % bn
    keys_t = jnp.pad(keys.T, ((0, 0), (0, pad)))
    return h3_hash_pallas(keys_t, q_masks, block_n=bn)[:n]


@functools.partial(jax.jit, static_argnames=("k", "buckets", "slots",
                                             "val_words", "use_pallas",
                                             "stagger"))
def xor_probe(bucket: jnp.ndarray, port: jnp.ndarray, qkeys: jnp.ndarray,
              table: jnp.ndarray, *, k: int, buckets: int, slots: int,
              val_words: int, use_pallas: bool = True, stagger: bool = False):
    """Fused decode+probe of one replica's stored array ``[G, C, 128]``.
    See xor_probe_pallas; past the VMEM budget the engine's jnp probe
    gathers the lanes' columns of the same array."""
    if not use_pallas or not fits_vmem(table):
        from repro.core.engine import probe_jnp
        return probe_jnp(bucket, port, qkeys, table[None], k=k, slots=slots,
                         val_words=val_words, part_buckets=buckets,
                         stagger=stagger)
    return xor_probe_pallas(bucket, port, qkeys, table, k=k, slots=slots,
                            val_words=val_words, stagger=stagger,
                            vmem_limit=VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("k", "buckets", "slots",
                                             "use_pallas"))
def xor_commit(table: jnp.ndarray, port: jnp.ndarray, bucket: jnp.ndarray,
               slot: jnp.ndarray, enc_k: jnp.ndarray, enc_v: jnp.ndarray,
               enc_b: jnp.ndarray, *, k: int, buckets: int, slots: int,
               use_pallas: bool = True):
    """Masked commit of pre-encoded mutation records into one replica's
    stored array ``[G, C, 128]`` (the engine broadcasts it to every
    replica).  enc_* come from ``engine.encode_records``; ``bucket >=
    buckets`` marks a masked lane.  Past the VMEM budget the engine's jnp
    record scatter writes the records' rows of the same array."""
    if not use_pallas or not fits_vmem(table):
        from repro.core.engine import _scatter_records
        rec = dict(port=port, bucket=bucket, slot=slot,
                   enc_k=enc_k, enc_v=enc_v, enc_b=enc_b)
        return _scatter_records(table, rec, buckets=buckets, slots=slots,
                                part_buckets=buckets)
    return xor_commit_pallas(table, port, bucket, slot, enc_k, enc_v, enc_b,
                             buckets=buckets, slots=slots,
                             vmem_limit=VMEM_LIMIT_BYTES)


def xor_stream(bucket: jnp.ndarray, port: jnp.ndarray, legal: jnp.ndarray,
               ops: jnp.ndarray, qkeys: jnp.ndarray, qvals: jnp.ndarray,
               table: jnp.ndarray, *, k: int, buckets: int, slots: int,
               stagger: bool = False, bucket_base=0):
    """Fused in-kernel query streaming over one replica's stored array
    ``[G, C, 128]``: probe + plan + non-search XOR encode + last-wins
    commit for a whole ``[T, N]`` stream in a single Pallas kernel, table
    VMEM-resident across steps (swept in :func:`stream_bucket_tiles` spans
    when one replica exceeds the VMEM budget; the budget is read at trace
    time) and updated in place.  ``port``/``legal`` may be ``[N]`` lane
    vectors or ``[T, N]`` per-step rows (the bounded router re-bins routed
    lanes, so a slot's origin varies by step — engine.route_stream_bounded).
    ``bucket_base`` (traced scalar) offsets a shard-local partition of
    ``buckets`` buckets into the global bucket space; lanes outside the
    partition are inert (DESIGN.md §3.1).  See xor_stream_pallas; the
    scanned per-step engine path is the semantic oracle.
    """
    return xor_stream_pallas(bucket, port, legal, ops, qkeys, qvals, table,
                             k=k, buckets=buckets, slots=slots,
                             passes=stream_bucket_tiles(table),
                             stagger=stagger, bucket_base=bucket_base,
                             vmem_limit=VMEM_LIMIT_BYTES)


def bulk_place(w_bucket: jnp.ndarray, w_slot: jnp.ndarray, keys: jnp.ndarray,
               vals: jnp.ndarray, table: jnp.ndarray, *, buckets: int,
               slots: int):
    """Bulk placement of pre-planned records into the port-0 plane of one
    replica's stored array (the commit half of ``engine.bulk_build`` — see
    bulk_place_pallas).  Engine's jnp backend scatter is the oracle."""
    return bulk_place_pallas(w_bucket, w_slot, keys, vals, table,
                             buckets=buckets, slots=slots)
