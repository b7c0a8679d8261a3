"""Backend-pluggable probe/commit engine — the single query dataflow seam.

Every consumer of the hash table (``apply_step``/``run_stream``, the
shard_map distributed step, the consistency checker, the serving prefix
cache) funnels through this module, which splits the paper's PE pipeline
(§IV-C) into two stages with exactly one jnp and one Pallas implementation
each (DESIGN.md §3):

  probe(table, batch)          hashing unit + parallel Partial-XOR-Store read
                               + search XOR tree + result resolution.
  commit(table, probe, batch)  non-search XOR tree encode + masked scatter
                               into the own-port store of every replica.

plus a third, stream-granular stage (the StreamBackend protocol, DESIGN.md
§3.1):

  run_stream(table, ops, keys, vals)  a whole [T, N] query stream — the
                               scanned per-step oracle on jnp; on pallas one
                               fused xor_stream kernel with the table
                               VMEM-persistent across steps, double-buffered
                               query DMA, and bucket-axis blocking past the
                               VMEM budget.

and a fourth, bucket-sharded stage (DESIGN.md §2) used under shard_map by
``core.distributed.make_distributed_stream`` when ``cfg.shards > 1``:

  route_stream / run_stream_local / inverse_route
                               bucket -> owner shard via the high H3 index
                               bits, queries exchanged with all_to_all, each
                               partition streamed locally (the fused kernel
                               with a bucket-base offset), results returned
                               to origin lanes by the inverse permutation.

Backends
--------
``jnp``     Pure jax.numpy — the bit-exact semantic oracle (the former
            ``kernels/ref.py`` collapsed into :func:`probe_jnp` /
            :func:`encode_records` / :func:`commit_records`).
``pallas``  Routes through the Pallas kernels (``kernels.ops.h3_hash``,
            ``kernels.ops.xor_probe`` and the fused ``kernels.ops.xor_commit``)
            — interpret mode on CPU, compiled on TPU.

Backend selection is ``HashTableConfig.backend`` ("auto" picks pallas on TPU,
jnp elsewhere) with an automatic fallback to jnp whenever the table exceeds
``VMEM_TABLE_BUDGET_BYTES`` (the kernels keep one replica VMEM-resident,
mirroring the FPGA's URAM residency; larger tables take HBM gathers).

Replica invariant: every commit writes the same encoded row into *all*
replicas, so replicas are byte-identical at every step boundary.  The Pallas
probe exploits this by reading replica 0 only; the jnp probe keeps the
paper-faithful per-PE replica gather.  Both decode identical values.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.config import HashTableConfig, round_up_lanes as _round_up_lanes
from repro.core.hash_table import (OP_DELETE, OP_INSERT, OP_SEARCH,
                                   QueryBatch, StepResults, XorHashTable,
                                   packed_shape)
from repro.core.hashing import h3_hash as _h3_jnp, make_h3_params
from repro.core.xor_memory import xor_reduce

__all__ = [
    "ProbeResult", "MutationPlan",
    "probe", "commit", "step", "run_stream",
    "probe_jnp", "commit_jnp", "mutation_plan", "encode_records",
    "commit_records", "staggered_open_slot",
    "shard_owner", "route_stream", "inverse_route", "run_stream_local",
    "BoundedRoutePlan", "plan_bounded_route", "route_load_pass",
    "route_stream_bounded",
    "inverse_route_bounded",
    "replica_layout", "plan_replication", "replica_copy_mask",
    "route_stream_grouped", "route_stream_grouped_bounded",
    "route_load_pass_grouped",
    "BulkBuildReport", "plan_bulk_build", "bulk_place_records",
    "bulk_build", "extract_records", "compact", "reconfigure",
    "RECONFIGURE_FROZEN_FIELDS",
    "ResizeState", "successor_masks", "begin_resize", "run_stream_resize",
    "migrate_slab", "finish_resize", "slab_records", "zero_slab",
    "register_backend", "get_backend", "resolve_backend", "available_backends",
]


# ---------------------------------------------------------------------------
# Stage outputs
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ProbeResult:
    """Everything the search dataflow produces for one step of N lanes.

    ``rem_*`` is the non-search XOR tree *basis*: the XOR of all k partial
    stores EXCEPT the lane's own port (paper: "this excludes the encoded-data
    in Partial XOR Store (M)") for every slot of the lane's bucket.
    """
    bucket: jnp.ndarray       # [N] uint32
    pe: jnp.ndarray           # [N] int32 — initiating PE per lane
    found: jnp.ndarray        # [N] bool
    match_slot: jnp.ndarray   # [N] int32
    open_slot: jnp.ndarray    # [N] int32 (staggered when cfg.stagger_slots)
    has_open: jnp.ndarray     # [N] bool
    value: jnp.ndarray        # [N, Wv] uint32 (0 where not found)
    rem_keys: jnp.ndarray     # [N, S, Wk] uint32
    rem_vals: jnp.ndarray     # [N, S, Wv] uint32
    rem_valid: jnp.ndarray    # [N, S]     uint32 (full word, not masked)

    def tree_flatten(self):
        return (self.bucket, self.pe, self.found, self.match_slot,
                self.open_slot, self.has_open, self.value,
                self.rem_keys, self.rem_vals, self.rem_valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MutationPlan:
    """Per-lane mutation decision (op decode + slot choice), plaintext form."""
    ok: jnp.ndarray           # [N] bool — op accepted
    do_write: jnp.ndarray     # [N] bool
    port: jnp.ndarray         # [N] int32 — own write port (min(pe, k-1))
    bucket: jnp.ndarray       # [N] int32 — == cfg.buckets (OOB) when masked
    slot: jnp.ndarray         # [N] int32
    new_key: jnp.ndarray      # [N, Wk] uint32 (0 for delete)
    new_val: jnp.ndarray      # [N, Wv] uint32 (0 for delete)
    new_valid: jnp.ndarray    # [N] uint32 (plaintext valid bit)

    def tree_flatten(self):
        return (self.ok, self.do_write, self.port, self.bucket, self.slot,
                self.new_key, self.new_val, self.new_valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# ---------------------------------------------------------------------------
# Shared pure stages (one implementation, used by every backend)
# ---------------------------------------------------------------------------

def _lane_pe(cfg: HashTableConfig, n: int) -> jnp.ndarray:
    """Default positional query->PE map: lane n belongs to PE n % p."""
    return jnp.arange(n, dtype=jnp.int32) % cfg.p


def staggered_open_slot(open_mask: jnp.ndarray, port: jnp.ndarray) -> jnp.ndarray:
    """Beyond-paper port-staggered slot choice: write port j claims the
    (j mod n_open)-th open slot, so same-step inserts to one bucket from
    distinct ports land in distinct slots while the bucket has room."""
    n_open = jnp.sum(open_mask, axis=-1).astype(jnp.int32)          # [N]
    rank = jnp.where(n_open > 0,
                     port.astype(jnp.int32) % jnp.maximum(n_open, 1), 0)
    csum = jnp.cumsum(open_mask, axis=-1)                           # [N, S]
    sel = open_mask & (csum == (rank[:, None] + 1))
    return jnp.argmax(sel, axis=-1).astype(jnp.int32)


def probe_jnp(bucket: jnp.ndarray, port: jnp.ndarray, qkeys: jnp.ndarray,
              packed: jnp.ndarray, *, k: int, slots: int, val_words: int,
              part_buckets: int, replica: Optional[jnp.ndarray] = None,
              stagger: bool = False):
    """The jnp probe stage (semantic oracle for ``xor_probe_pallas``).

    ``packed`` is the stored array ``[R, G, C, 128]`` in partitions of
    ``part_buckets`` buckets; each lane gathers its bucket's column only.
    ``replica`` maps each lane to the replica it reads (None == replica 0
    for all lanes).  Returns the same tuple as the Pallas kernel: (found,
    match_slot, open_slot, has_open, value, rem_keys, rem_vals, rem_valid).
    """
    from repro.kernels import packed as pk
    Wk = qkeys.shape[-1]
    W = Wk + val_words + 1
    col = pk.stored_column(bucket.astype(jnp.int32), part_buckets)
    # parallel partial-store read: [N, k, S, W] gather
    enc = pk.planes(pk.columns(packed, col, replica), k, slots, W)
    enc_keys, enc_vals = enc[..., :Wk], enc[..., Wk:W - 1]
    enc_valid = enc[..., W - 1]
    # search XOR reduction trees
    dec_keys = xor_reduce(enc_keys, axis=1)                        # [N, S, Wk]
    dec_vals = xor_reduce(enc_vals, axis=1)                        # [N, S, Wv]
    dec_validw = xor_reduce(enc_valid, axis=1)                     # [N, S]

    # result resolution
    key_eq = jnp.all(dec_keys == qkeys[:, None, :], axis=-1)       # [N, S]
    occ = (dec_validw & 1).astype(bool)
    match = key_eq & occ
    found = jnp.any(match, axis=-1)
    mslot = jnp.argmax(match, axis=-1).astype(jnp.int32)
    open_mask = ~occ
    hopen = jnp.any(open_mask, axis=-1)
    if stagger:
        oslot = staggered_open_slot(open_mask, port)
    else:
        oslot = jnp.argmax(open_mask, axis=-1).astype(jnp.int32)
    value = jnp.take_along_axis(dec_vals, mslot[:, None, None], axis=1)[:, 0]
    value = jnp.where(found[:, None], value, jnp.uint32(0))

    # non-search XOR tree basis: XOR of all stores except the own port
    p32 = port.astype(jnp.int32)
    own_k = jnp.take_along_axis(enc_keys, p32[:, None, None, None], axis=1)[:, 0]
    own_v = jnp.take_along_axis(enc_vals, p32[:, None, None, None], axis=1)[:, 0]
    own_b = jnp.take_along_axis(enc_valid, p32[:, None, None], axis=1)[:, 0]
    return (found, mslot, oslot, hopen, value,
            dec_keys ^ own_k, dec_vals ^ own_v, dec_validw ^ own_b)


def mutation_plan(cfg: HashTableConfig, batch: QueryBatch, pr: ProbeResult
                  ) -> MutationPlan:
    """Op decode + slot choice (shared by all backends — pure elementwise)."""
    pe = pr.pe
    port = jnp.minimum(pe, cfg.k - 1).astype(jnp.int32)
    is_ins = batch.op == OP_INSERT
    is_del = batch.op == OP_DELETE
    legal_port = pe < cfg.k                     # search-only PEs reject NSQs
    ins_ok = is_ins & (pr.found | pr.has_open) & legal_port
    del_ok = is_del & pr.found & legal_port
    do_write = ins_ok | del_ok
    slot = jnp.where(is_del | pr.found, pr.match_slot, pr.open_slot)
    new_key = jnp.where(is_del[:, None], jnp.uint32(0), batch.key)
    new_val = jnp.where(is_del[:, None], jnp.uint32(0), batch.val)
    new_valid = jnp.where(is_del, jnp.uint32(0), jnp.uint32(1))
    ok = jnp.where(is_ins, ins_ok,
                   jnp.where(is_del, del_ok, batch.op == OP_SEARCH))
    w_bucket = jnp.where(do_write, pr.bucket.astype(jnp.int32),
                         jnp.int32(cfg.buckets))          # OOB => scatter drop
    return MutationPlan(ok=ok, do_write=do_write, port=port, bucket=w_bucket,
                        slot=slot, new_key=new_key, new_val=new_val,
                        new_valid=new_valid)


def _pick_slot(x: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Select the per-lane slot along axis 1: [N, S, ...] -> [N, ...]."""
    idx = slot[:, None, None] if x.ndim == 3 else slot[:, None]
    return jnp.take_along_axis(x, idx, axis=1)[:, 0]


def encode_records(pr: ProbeResult, plan: MutationPlan) -> Dict[str, jnp.ndarray]:
    """jnp non-search XOR tree encode: the flat mutation-record batch.

    This is exactly what the distributed step all-gathers over the ICI ring —
    the payload is independent of table size (DESIGN.md §3)."""
    enc_k = plan.new_key ^ _pick_slot(pr.rem_keys, plan.slot)
    enc_v = plan.new_val ^ _pick_slot(pr.rem_vals, plan.slot)
    enc_b = plan.new_valid ^ _pick_slot(pr.rem_valid, plan.slot)
    return dict(port=plan.port, bucket=plan.bucket, slot=plan.slot,
                enc_k=enc_k, enc_v=enc_v, enc_b=enc_b)


def _scatter_records(packed, rec, *, buckets: int, slots: int,
                     part_buckets: int):
    """Masked scatter of encoded records into every replica of the stored
    array ``packed`` (``[R, G, C, 128]`` or one replica ``[G, C, 128]``,
    ``buckets`` buckets in partitions of ``part_buckets``) — the inter-PE
    propagation, writing only the records' own rows.  Masked lanes carry an
    out-of-range bucket (``>= buckets``) -> dropped.

    Duplicate (port, bucket, slot) targets resolve **last-wins in record
    order** (program order), matching the Pallas commit kernel's sequential
    loop exactly — XLA's scatter leaves duplicate ordering undefined, so
    all but the last record per target are masked out first.  (At
    queries_per_pe == 1 write lanes have distinct ports and this is a no-op;
    duplicates only arise beyond the paper's one-write-per-port-per-cycle
    regime.)"""
    from repro.kernels import packed as pk
    port, bucket, slot = rec["port"], rec["bucket"], rec["slot"]
    B, S = buckets, slots
    tgt = (port * (B + 1) + bucket) * S + slot                      # [N]
    live = bucket < B                                               # write lanes
    # lane i is superseded iff it is not the segment-max lane index of its
    # target.  A stable sort groups equal targets in lane order, so a lane is
    # superseded exactly when its successor in sorted order shares its target
    # — O(N log N) and independent of table size, so the oracle scales to
    # stream-sized batches.  Dead lanes get unique negative keys so they
    # never join (or split) a live segment.
    lane = jnp.arange(tgt.shape[0], dtype=jnp.int32)
    tgt_eff = jnp.where(live, tgt.astype(jnp.int32), -1 - lane)
    order = jnp.argsort(tgt_eff, stable=True)
    stgt = tgt_eff[order]
    sup_sorted = jnp.concatenate(
        [stgt[:-1] == stgt[1:], jnp.zeros((1,), jnp.bool_)])
    superseded = jnp.zeros(tgt.shape, jnp.bool_).at[order].set(sup_sorted)
    write = live & ~superseded
    words = jnp.concatenate(
        [rec["enc_k"], rec["enc_v"], rec["enc_b"][:, None]], axis=-1)
    col = jnp.where(write, pk.stored_column(bucket.astype(jnp.int32),
                                            part_buckets),
                    packed.shape[-3] * pk.LANES)                    # drop
    rows = pk.entry_rows(port.astype(jnp.int32), slot.astype(jnp.int32), S,
                         words.shape[-1])
    return pk.set_words(packed, col, rows, words)


def commit_records(table: XorHashTable, rec: Dict[str, jnp.ndarray]
                   ) -> XorHashTable:
    """Apply a flat batch of encoded mutation records to a table (the jnp
    oracle: an O(records) scatter into the stored array)."""
    cfg = table.cfg
    packed = _scatter_records(table.packed, rec, buckets=table.buckets,
                              slots=cfg.slots,
                              part_buckets=cfg.local_buckets)
    return XorHashTable(table.q_masks, packed, cfg)


def commit_jnp(packed, port, bucket, slot, do_write, new_key, new_val,
               new_valid, *, k: int, buckets: int, slots: int):
    """Raw-array jnp encode+commit (semantic oracle for ``xor_commit_pallas``).

    ``packed`` the stored array ``[R, G, C, 128]`` of ``buckets`` buckets;
    lane vectors as in the kernel (``bucket == buckets`` marks a masked
    lane).  Recomputes the encode basis from the snapshot — use
    :func:`encode_records` when a ProbeResult is already in hand.
    """
    B = buckets
    idx = jnp.minimum(bucket, B - 1).astype(jnp.int32)
    _, _, _, _, _, remk, remv, remb = probe_jnp(
        idx, port, new_key, packed, k=k, slots=slots,
        val_words=new_val.shape[-1], part_buckets=B)
    rec = dict(port=port,
               bucket=jnp.where(do_write, bucket.astype(jnp.int32),
                                jnp.int32(B)),
               slot=slot,
               enc_k=new_key ^ _pick_slot(remk, slot),
               enc_v=new_val ^ _pick_slot(remv, slot),
               enc_b=new_valid ^ _pick_slot(remb, slot))
    return _scatter_records(packed, rec, buckets=B, slots=slots,
                            part_buckets=B)


# ---------------------------------------------------------------------------
# Backends
#
# StreamBackend protocol: in addition to probe/commit, a backend may expose
#   run_stream(table, ops, keys, vals)
#       -> (table', StepResults[T, N])
# processing a whole [T, N] query stream at once.  The jnp implementation is
# the scanned per-step oracle; the pallas implementation is the fused
# xor_stream kernel (table VMEM-persistent across steps, query blocks
# double-buffered, bucket-axis blocking past the VMEM budget — DESIGN.md
# §3.1).  ``engine.run_stream`` dispatches between them.
# ---------------------------------------------------------------------------

def _scan_stream(table: XorHashTable, ops: jnp.ndarray, keys: jnp.ndarray,
                 vals: jnp.ndarray, backend: Optional[str] = None
                 ) -> Tuple[XorHashTable, "StepResults"]:
    """The scanned per-step stream: one engine.step per [N] slice (the
    semantic oracle for the fused stream kernel)."""
    def body(tab, xs):
        op, key, val = xs
        tab, res = step(tab, QueryBatch(op, key, val), backend=backend)
        return tab, res
    return jax.lax.scan(body, table, (ops, keys, vals))


def _empty_stream_results(cfg: HashTableConfig, n: int) -> StepResults:
    return StepResults(found=jnp.zeros((0, n), jnp.bool_),
                       value=jnp.zeros((0, n, cfg.val_words), jnp.uint32),
                       ok=jnp.zeros((0, n), jnp.bool_),
                       bucket=jnp.zeros((0, n), jnp.uint32))


def _kernel_buckets(table: XorHashTable) -> int:
    """Buckets of the one partition a single-domain kernel addresses as
    group ``b // 128``, lane ``b % 128``.  A sharded table whose partitions
    do not fill whole groups runs its partitions under shard_map
    (core.distributed) instead."""
    from repro.kernels.packed import packed_groups
    B = table.buckets
    if table.packed.shape[1] != packed_groups(B):
        raise ValueError(
            f"the table's {table.packed.shape[1]} bucket groups hold "
            f"{B // table.cfg.local_buckets} partitions of "
            f"{table.cfg.local_buckets} buckets; stream each partition "
            f"through core.distributed (shard_map)")
    return B


class JnpBackend:
    """Pure jax.numpy dataflow — current semantics, the bit-exact oracle."""

    name = "jnp"

    def probe(self, table: XorHashTable, batch: QueryBatch,
              pe: Optional[jnp.ndarray] = None) -> ProbeResult:
        cfg = table.cfg
        n = batch.op.shape[0]
        pe = _lane_pe(cfg, n) if pe is None else jnp.broadcast_to(
            jnp.asarray(pe, jnp.int32), (n,))
        replica = pe if cfg.replicate_reads else jnp.zeros_like(pe)
        port = jnp.minimum(pe, cfg.k - 1).astype(jnp.int32)
        bucket = _h3_jnp(batch.key, table.q_masks)
        outs = probe_jnp(bucket, port, batch.key, table.packed, k=cfg.k,
                         slots=cfg.slots, val_words=cfg.val_words,
                         part_buckets=cfg.local_buckets, replica=replica,
                         stagger=cfg.stagger_slots)
        return ProbeResult(bucket, pe, *outs)

    def commit(self, table: XorHashTable, pr: ProbeResult, batch: QueryBatch,
               plan: Optional[MutationPlan] = None) -> XorHashTable:
        plan = mutation_plan(table.cfg, batch, pr) if plan is None else plan
        return commit_records(table, encode_records(pr, plan))

    def run_stream(self, table: XorHashTable, ops: jnp.ndarray,
                   keys: jnp.ndarray, vals: jnp.ndarray
                   ) -> Tuple[XorHashTable, StepResults]:
        return _scan_stream(table, ops, keys, vals, backend=self.name)

    def bulk_place(self, table, w_bucket, w_slot, keys, vals, *,
                   buckets: int, slots: int):
        """Plaintext placement of pre-planned records into the port-0 plane
        of one replica's stored array ``[G, C, 128]`` — the jnp oracle for
        the placement kernel.  ``w_bucket`` addresses the array's columns
        (``packed.stored_column``; ``>= buckets`` masks a record).  Targets
        are pairwise distinct by construction (plan_bulk_build), so a plain
        masked scatter needs no supersession pass."""
        from repro.kernels import packed as pk
        words = jnp.concatenate(
            [keys, vals, jnp.ones((keys.shape[0], 1), jnp.uint32)], axis=-1)
        b = w_bucket.astype(jnp.int32)
        col = jnp.where(b < buckets, b, table.shape[0] * pk.LANES)  # drop
        rows = pk.entry_rows(0, w_slot.astype(jnp.int32), slots,
                             words.shape[1])
        return pk.set_words(table, col, rows, words)


class PallasBackend:
    """Routes the hot path through the Pallas kernels (interpret on CPU)."""

    name = "pallas"

    def probe(self, table: XorHashTable, batch: QueryBatch,
              pe: Optional[jnp.ndarray] = None) -> ProbeResult:
        from repro.kernels import ops as kops
        cfg = table.cfg
        n = batch.op.shape[0]
        pe = _lane_pe(cfg, n) if pe is None else jnp.broadcast_to(
            jnp.asarray(pe, jnp.int32), (n,))
        port = jnp.minimum(pe, cfg.k - 1).astype(jnp.int32)
        bucket = kops.h3_hash(batch.key, table.q_masks)
        # Replicas are byte-identical (commit writes all of them), so the
        # kernel probes replica 0 — one VMEM-resident table per core.
        outs = kops.xor_probe(bucket, port, batch.key, table.packed[0],
                              k=cfg.k, buckets=_kernel_buckets(table),
                              slots=cfg.slots, val_words=cfg.val_words,
                              stagger=cfg.stagger_slots)
        return ProbeResult(bucket, pe, *outs)

    def commit(self, table: XorHashTable, pr: ProbeResult, batch: QueryBatch,
               plan: Optional[MutationPlan] = None) -> XorHashTable:
        from repro.kernels import ops as kops
        plan = mutation_plan(table.cfg, batch, pr) if plan is None else plan
        # Replicas are byte-identical, so one encoding serves every replica:
        # compute it ONCE from the ProbeResult rem basis the probe already
        # produced, leaving the kernel only the masked stores (instead of R
        # identical gather+XOR-tree encodes); ops.xor_commit falls back to
        # the jnp scatter past the VMEM budget.
        rec = encode_records(pr, plan)
        cfg = table.cfg
        tab = kops.xor_commit(
            table.packed[0], rec["port"], rec["bucket"], rec["slot"],
            rec["enc_k"], rec["enc_v"], rec["enc_b"], k=cfg.k,
            buckets=_kernel_buckets(table), slots=cfg.slots)
        return XorHashTable(
            table.q_masks, jnp.broadcast_to(tab[None], table.packed.shape),
            cfg)

    def run_stream(self, table: XorHashTable, ops: jnp.ndarray,
                   keys: jnp.ndarray, vals: jnp.ndarray
                   ) -> Tuple[XorHashTable, StepResults]:
        """The fused stream kernel: one pallas_call for the whole [T, N]
        stream, table VMEM-persistent across steps.  Unlike the per-step
        kernels this path does NOT fall back to jnp past the VMEM budget —
        HBM-resident tables run compiled Pallas in VMEM-sized sweep passes
        (``kernels.ops.stream_bucket_tiles``; the budget is read at trace
        time).

        The kernel takes replica 0 of the stored array as it is and updates
        it in place: at ``R == 1`` no layout op touches the table.  Replicas
        are byte-identical at step boundaries (commit writes all of them),
        so at ``R > 1`` the one stream over replica 0 is broadcast back to
        all R replicas."""
        from repro.kernels import ops as kops
        cfg = table.cfg
        T, N = ops.shape
        if T == 0:
            return table, _empty_stream_results(cfg, N)
        pe = _lane_pe(cfg, N)
        port = jnp.minimum(pe, cfg.k - 1).astype(jnp.int32)
        legal = (pe < cfg.k).astype(jnp.int32)
        with jax.named_scope("kv.h3"):
            bucket = kops.h3_hash(keys.reshape(T * N, cfg.key_words),
                                  table.q_masks).reshape(T, N)
        tab, found, ok, value = kops.xor_stream(
            bucket, port, legal, ops, keys, vals, table.packed[0], k=cfg.k,
            buckets=_kernel_buckets(table), slots=cfg.slots,
            stagger=cfg.stagger_slots)
        # one replica: a reshape; more: the replica invariant's broadcast
        new_table = XorHashTable(
            table.q_masks, jnp.broadcast_to(tab[None], table.packed.shape),
            cfg)
        return new_table, StepResults(found=found, value=value, ok=ok,
                                      bucket=bucket)

    def bulk_place(self, table, w_bucket, w_slot, keys, vals, *,
                   buckets: int, slots: int):
        """The placement kernel (kernels.bulk_place): records sorted by
        stored column, each touched bucket group moved in and out of VMEM
        once.  Interpret mode off-TPU."""
        from repro.kernels import ops as kops
        return kops.bulk_place(w_bucket, w_slot, keys, vals, table,
                               buckets=buckets, slots=slots)


_BACKENDS: Dict[str, object] = {}


def register_backend(name: str, backend) -> None:
    _BACKENDS[name] = backend


def get_backend(name: str):
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown hash-table backend {name!r}; "
                         f"registered: {sorted(_BACKENDS)}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


register_backend("jnp", JnpBackend())
register_backend("pallas", PallasBackend())


def _resolve_name(cfg: HashTableConfig, backend: Optional[str] = None) -> str:
    """The shared auto-selection policy: explicit arg > cfg.backend; ``auto``
    picks pallas on TPU and jnp elsewhere (interpret-mode Pallas on CPU is a
    correctness harness, not a fast path)."""
    name = backend if backend is not None else getattr(cfg, "backend", "auto")
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "jnp"
    return name


def resolve_backend(cfg: HashTableConfig, table: Optional[XorHashTable] = None):
    """Pick the backend for this step (trace-time: shapes are static).

    Auto-selection via :func:`_resolve_name`, plus the per-step VMEM
    fallback: an explicit ``pallas`` falls back to jnp when one replica of
    the table would not fit the VMEM budget the kernels assume
    (HBM-resident tables take the jnp gathers).  The stream path
    (:func:`run_stream`) shares the name resolution but deliberately skips
    this fallback — it bucket-blocks instead.
    """
    from repro.kernels import ops as kops
    name = _resolve_name(cfg)
    if name == "pallas" and table is not None:
        if not kops.fits_vmem(table.packed):
            name = "jnp"
    return get_backend(name)


# ---------------------------------------------------------------------------
# Engine entry points
# ---------------------------------------------------------------------------

def probe(table: XorHashTable, batch: QueryBatch,
          pe: Optional[jnp.ndarray] = None, backend: Optional[str] = None
          ) -> ProbeResult:
    be = get_backend(backend) if backend else resolve_backend(table.cfg, table)
    return be.probe(table, batch, pe=pe)


def commit(table: XorHashTable, pr: ProbeResult, batch: QueryBatch,
           backend: Optional[str] = None) -> XorHashTable:
    be = get_backend(backend) if backend else resolve_backend(table.cfg, table)
    return be.commit(table, pr, batch)


def step(table: XorHashTable, batch: QueryBatch,
         pe: Optional[jnp.ndarray] = None, backend: Optional[str] = None
         ) -> Tuple[XorHashTable, StepResults]:
    """One full probe+commit step; the engine form of ``apply_step``."""
    cfg = table.cfg
    be = get_backend(backend) if backend else resolve_backend(cfg, table)
    pr = be.probe(table, batch, pe=pe)
    plan = mutation_plan(cfg, batch, pr)
    new_table = be.commit(table, pr, batch, plan=plan)
    results = StepResults(found=pr.found, value=pr.value, ok=plan.ok,
                          bucket=pr.bucket)
    return new_table, results


def run_stream(table: XorHashTable, ops: jnp.ndarray, keys: jnp.ndarray,
               vals: jnp.ndarray, backend: Optional[str] = None,
               fused: Optional[bool] = None
               ) -> Tuple[XorHashTable, StepResults]:
    """Stream a whole ``[T, N]`` query trace through the engine seam.

    ``fused`` selects the third stage of the seam (DESIGN.md §3.1):
      None   dispatch to the resolved backend's StreamBackend implementation
             — the fused xor_stream kernel on pallas, the scanned per-step
             oracle on jnp.
      True   force the fused Pallas stream kernel (bucket-blocked past the
             VMEM budget; interpret mode off-TPU).
      False  force the scanned per-step path (the semantic oracle).

    Note the fused path does not use :func:`resolve_backend`'s VMEM fallback:
    tables beyond the budget run compiled Pallas in VMEM-sized sweep passes.
    """
    cfg = table.cfg
    if ops.ndim != 2 or ops.shape[1] != cfg.queries_per_step:
        raise ValueError(f"stream shape {ops.shape} != [T, p*qpp="
                         f"{cfg.queries_per_step}]")
    name = _resolve_name(cfg, backend)
    if fused is True:
        return get_backend("pallas").run_stream(table, ops, keys, vals)
    if fused is False:
        return _scan_stream(table, ops, keys, vals, backend=name)
    return get_backend(name).run_stream(table, ops, keys, vals)


# ---------------------------------------------------------------------------
# Stage four: the bucket-sharded routing seam (DESIGN.md §2)
#
# When the table is partitioned by bucket ownership across a mesh
# (``cfg.shards`` partitions of ``cfg.local_buckets`` buckets each), queries
# must execute on the shard that owns their bucket.  The three functions
# below are the shard_map-side dataflow used by
# ``core.distributed.make_distributed_stream``:
#
#   route_stream       bucket -> owner shard (high H3 index bits), queries
#                      exchanged with all_to_all in program order
#   run_stream_local   the whole routed [T, Nr] stream against one partition
#                      — the fused xor_stream kernel (bucket-base offset) on
#                      pallas, the scanned jnp oracle elsewhere
#   inverse_route      per-lane results returned to origin lanes by the
#                      inverse permutation
# ---------------------------------------------------------------------------

def shard_owner(cfg: HashTableConfig, bucket: jnp.ndarray) -> jnp.ndarray:
    """Owner shard of each global bucket index — the high index bits."""
    return bucket.astype(jnp.int32) >> cfg.local_index_bits


def _pack_u32(arrays):
    """Pack ``[T, n]`` / ``[T, n, W]`` word-typed arrays into one
    ``[T, n, Wtot]`` uint32 tensor (so a routing exchange is ONE collective
    on one buffer, not one per payload).  Returns (packed, meta) where meta
    replays dtypes/shapes for :func:`_unpack_u32`."""
    meta, cols = [], []
    for x in arrays:
        col = x[..., None] if x.ndim == 2 else x
        meta.append((x.dtype, x.ndim == 2, col.shape[-1]))
        cols.append(col.astype(jnp.uint32))
    return jnp.concatenate(cols, axis=-1), meta


def _unpack_u32(packed, meta):
    outs, off = [], 0
    for dtype, squeeze, w in meta:
        col = packed[..., off:off + w]
        off += w
        outs.append((col[..., 0] if squeeze else col).astype(dtype))
    return outs


def route_stream(cfg: HashTableConfig, axis: str, bucket: jnp.ndarray,
                 *arrays: jnp.ndarray):
    """Exchange per-step query payloads with their owner shards (shard_map
    collective).

    ``bucket`` ``[T, n]``: global H3 bucket of each local lane; its high
    index bits name the owner shard.  The payload ``[T, n(, W)]`` arrays are
    packed into one uint32 buffer and scattered into a ``[T, D*n, Wtot]``
    send buffer — destination-major with capacity ``n`` per destination, so
    arbitrary key skew (up to every lane owned by one shard) cannot drop
    queries; unused slots stay zero, i.e. ``OP_NOP`` — then exchanged with
    ONE ``all_to_all`` covering all T steps and every payload.

    Routed arrays arrive in (origin-device, origin-lane) order, which equals
    global program order, so the owner's sequential last-wins commit resolves
    duplicate targets exactly like the replicated oracle.  Also returns
    ``tgt [T, n]``, each lane's position in the routed stream; pass it to
    :func:`inverse_route` to bring results home.
    """
    owner = shard_owner(cfg, bucket)                                # [T, n]
    D = jax.lax.psum(1, axis)
    T, n = owner.shape
    onehot = owner[:, :, None] == jnp.arange(D, dtype=jnp.int32)    # [T, n, D]
    rank = jnp.cumsum(onehot, axis=1)                               # [T, n, D]
    pos = jnp.take_along_axis(rank, owner[:, :, None], axis=2)[..., 0] - 1
    tgt = owner * n + pos                                           # [T, n]
    packed, meta = _pack_u32(arrays)
    buf = jnp.zeros((T, D * n, packed.shape[-1]), jnp.uint32)
    buf = buf.at[jnp.arange(T)[:, None], tgt].set(packed)
    routed = jax.lax.all_to_all(buf, axis, split_axis=1, concat_axis=1,
                                tiled=True)
    return _unpack_u32(routed, meta), tgt


def inverse_route(axis: str, tgt: jnp.ndarray, *arrays: jnp.ndarray):
    """Return routed per-lane results to their origin lanes — the inverse of
    :func:`route_stream`: pack, ONE all_to_all back, gather by send
    position."""
    packed, meta = _pack_u32(arrays)
    back = jax.lax.all_to_all(packed, axis, split_axis=1, concat_axis=1,
                              tiled=True)
    idx = jnp.broadcast_to(tgt[..., None], tgt.shape + (packed.shape[-1],))
    return _unpack_u32(jnp.take_along_axis(back, idx, axis=1), meta)


def run_stream_local(cfg: HashTableConfig, packed: jnp.ndarray,
                     pe: jnp.ndarray, bucket: jnp.ndarray, ops: jnp.ndarray,
                     keys: jnp.ndarray, vals: jnp.ndarray, *,
                     bucket_base, backend: Optional[str] = None,
                     fused: Optional[bool] = None):
    """Stream ``[T, Nr]`` routed queries through ONE bucket-shard partition.

    ``packed`` ``[R, G, C, 128]`` is the partition's stored array, holding
    the ``cfg.local_buckets`` global buckets ``[bucket_base, bucket_base +
    local_buckets)``; ``bucket`` carries the precomputed GLOBAL indices.
    Lanes outside the partition (router padding or foreign shards) are
    inert: no writes, found/ok False, value 0.  ``pe``
    is per routed lane — ``[Nr]`` (skew-proof routing: lane -> origin is
    step-invariant) or ``[T, Nr]`` (bounded routing: rows are re-binned
    mixtures, so the origin varies per step).  On the pallas backend this is
    the fused ``xor_stream`` kernel with the bucket-base offset (the
    sweep-pass sizing reused unchanged); elsewhere the scanned jnp oracle
    with the same partition masking, on the stored array.
    Returns ``(packed', found, ok, value)``.
    """
    name = _resolve_name(cfg, backend)
    use_fused = fused if fused is not None else (name == "pallas")
    k, Bl = cfg.k, cfg.local_buckets
    port = jnp.minimum(pe, k - 1).astype(jnp.int32)
    base = jnp.asarray(bucket_base).astype(jnp.int32)
    if use_fused:
        from repro.kernels import ops as kops
        legal = (pe < k).astype(jnp.int32)
        tab, found, ok, value = kops.xor_stream(
            bucket, port, legal, ops, keys, vals, packed[0], k=k,
            buckets=Bl, slots=cfg.slots, stagger=cfg.stagger_slots,
            bucket_base=base)
        return jnp.broadcast_to(tab[None], packed.shape), found, ok, value

    pe_t = jnp.broadcast_to(pe, ops.shape) if pe.ndim == 1 else pe
    port_t = jnp.broadcast_to(port, ops.shape) if port.ndim == 1 else port

    def body(tab, xs):
        op, key, val, bkt, pe_s, port_s = xs
        rel = bkt.astype(jnp.int32) - base
        in_part = (rel >= 0) & (rel < Bl)
        idx = jnp.clip(rel, 0, Bl - 1)
        (found, mslot, oslot, hopen, value,
         remk, remv, remb) = probe_jnp(idx, port_s, key, tab, k=k,
                                       slots=cfg.slots,
                                       val_words=cfg.val_words,
                                       part_buckets=Bl,
                                       stagger=cfg.stagger_slots)
        # mask the probe to the partition, then reuse the single-domain
        # mutation semantics verbatim (one source of truth): out-of-partition
        # lanes can't match, can't claim a slot, and scatter-drop via the OOB
        # bucket marker (cfg.buckets >= Bl).  Masked found flips the slot
        # CHOICE vs the fused kernel only on inert lanes (do_write False, no
        # observable effect).
        found = found & in_part
        value = jnp.where(found[:, None], value, jnp.uint32(0))
        pr = ProbeResult(bucket=idx, pe=pe_s, found=found, match_slot=mslot,
                         open_slot=oslot, has_open=hopen & in_part,
                         value=value, rem_keys=remk, rem_vals=remv,
                         rem_valid=remb)
        plan = mutation_plan(cfg, QueryBatch(op, key, val), pr)
        ok = plan.ok & jnp.where(op == OP_SEARCH, in_part, True)
        tab = _scatter_records(tab, encode_records(pr, plan), buckets=Bl,
                               slots=cfg.slots, part_buckets=Bl)
        return tab, (found, ok, value)

    packed, (found, ok, value) = jax.lax.scan(
        body, packed, (ops, keys, vals, bucket, pe_t, port_t))
    return packed, found, ok, value


# ---------------------------------------------------------------------------
# Stage four, bounded: the capacity-bounded two-pass router (DESIGN.md §2.2)
#
# The skew-proof router above reserves ``n_local`` send lanes per (origin,
# owner) pair — routed width ``D * n_local`` per owner per step — while the
# mean per-owner load is exactly ``n_local`` (BENCH_distributed.json
# ``routed_occupancy``).  The bounded router shrinks both dimensions to the
# *measured* trace:
#
#   pass 1  :func:`plan_bounded_route` (host side, cheap) histograms the
#           trace's (step, owner) loads and (origin, owner) totals and picks
#           the static shapes: routed width ``Nr`` = max per-(step, owner)
#           load rounded to ``cfg.routed_lane_tile`` (optionally capped by the
#           static ``cfg.routed_slack`` for jit-stable shapes), send-queue
#           capacity ``Q`` per pair = max pair total, and the owner-row count
#           ``T' >= T`` needed to drain every FIFO.
#   pass 2  :func:`route_stream_bounded` (shard_map side) packs each
#           (origin -> owner) pair's lanes into a flat FIFO of ``Q`` slots in
#           program order — step boundaries ride along as a tag word — does
#           ONE ``all_to_all``, and the owner re-bins arrivals back into
#           ``[T', Nr]`` step rows by tag, serving each owner-FIFO at ``Nr``
#           lanes per row.
#
# Ordering: an owner's service order is its arrival order, which is
# (step, origin, lane) == global program order, so the sequential last-wins
# commit is preserved verbatim.  When ``Nr`` >= the max (step, owner) load
# (always, in auto mode) every lane is served at exactly its own step and the
# routed stream is the skew-proof stream minus dead padding — bit-exact with
# the replicated oracle.  When a static ``routed_slack`` cap binds, overflow
# lanes carry over to the next routed row(s), still in program order: no
# query is dropped (``T'`` adds drain rows) and last-wins still holds, but a
# carried lane probes a *fresher* snapshot than the oracle's (its visibility
# window narrows), so byte-exactness is guaranteed only while the buckets it
# touches are quiescent over the rows it skips — the documented carry
# contract (DESIGN.md §2.2).
# ---------------------------------------------------------------------------


def _round_up_pow2_lanes(x: int, tile: int) -> int:
    """Round up to a power-of-two multiple of the lane tile — bounds the
    number of distinct jit-specializing shapes to O(log) of the range."""
    x = _round_up_lanes(x, tile)
    return tile * (1 << (-(-x // tile) - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class BoundedRoutePlan:
    """Static shapes + load stats from the bounded router's measurement pass
    (host-side values; the jitted exchange specializes on the three shape
    fields, so equal-shaped plans share one compile)."""
    pair_capacity: int        # Q: send-queue slots per (origin, owner) pair
    routed_width: int         # Nr: routed lanes per owner per step row
    routed_steps: int         # T': owner-side rows (T + drain rows)
    steps: int                # T: stream steps measured
    n_local: int              # lanes per origin device per step
    shards: int               # D: route DESTINATIONS — owner shards on the
                              # 1-D mesh, mesh devices under replica_groups
    max_owner_load: int       # max lanes routed to one dest in one step
    mean_owner_load: float
    carried_lanes: int        # lanes served after their arrival step
    total_lanes: int

    @property
    def skewproof_width(self) -> int:
        return self.shards * self.n_local

    @property
    def width_ratio(self) -> float:
        return self.routed_width / max(self.skewproof_width, 1)

    @property
    def carry_rate(self) -> float:
        return self.carried_lanes / max(self.total_lanes, 1)

    def covers(self, max_owner_load: int, max_pair_total: int) -> bool:
        """True when this plan's static shapes can serve a batch with the
        given measured maxima *bit-exactly* — the plan-cache safety check
        (DESIGN.md §4).  Three conditions, all load-bearing:

        * the plan itself must be carry-free (a carry plan's ``routed_steps``
          drain rows are specific to the trace it was measured on);
        * ``routed_width >= max_owner_load`` — every lane is served at its
          own step, so nothing queues and last-wins order is the oracle's;
        * ``pair_capacity >= max_pair_total`` — the send-side FIFOs never
          fill (``_bounded_send_slots`` silently parks past-capacity lanes
          at the sentinel slot, i.e. DROPS them; a cached plan must never
          let a batch reach that).
        """
        return (self.carried_lanes == 0
                and self.routed_width >= max_owner_load
                and self.pair_capacity >= max_pair_total)


def route_load_pass(cfg: HashTableConfig, owner: jnp.ndarray):
    """The in-graph half of the bounded router's pass 1: histogram the
    ``[T, N]`` owner matrix into per-(step, owner) loads ``[T, D]`` and
    whole-trace per-(origin, owner) totals ``[D, D]`` (lanes origin-major:
    origin = lane // n_local).  jit-friendly — the host wrapper runs this
    compiled and hands the two small arrays to :func:`plan_bounded_route`.
    """
    T, N = owner.shape
    D = cfg.shards
    onehot = (owner.astype(jnp.int32)[:, :, None]
              == jnp.arange(D, dtype=jnp.int32)).astype(jnp.int32)
    loads = onehot.sum(axis=1)                              # [T, D]
    pair = onehot.reshape(T, D, N // D, D).sum(axis=(0, 2))  # [D, D]
    return loads, pair


def plan_bounded_route(cfg: HashTableConfig, owner=None,
                       slack: Optional[int] = None,
                       tile: Optional[int] = None,
                       loads=None, pair=None,
                       n_local: Optional[int] = None) -> BoundedRoutePlan:
    """Pass 1 of the bounded router: measure the trace, pick static shapes.

    ``owner`` is the GLOBAL ``[T, N]`` owner-shard matrix (``shard_owner`` of
    the H3 buckets; ``N = shards * n_local``, lanes origin-major) — or pass
    the precomputed ``loads [T, D]`` / ``pair [D, D]`` histograms from a
    jitted :func:`route_load_pass` (or :func:`route_load_pass_grouped`, in
    which case ``D`` is the MESH DEVICE count and the entries count copies,
    mutation broadcast included) to keep the hot path off the eager
    interpreter.  Pure numpy on the host from there — the caller reads the
    plan's static fields and dispatches the jitted exchange specialized on
    them.  ``slack``/``tile`` default to ``cfg.routed_slack`` /
    ``cfg.routed_lane_tile``.  ``n_local`` (lanes per origin per step) is
    inferred from the histograms when omitted — pass it explicitly for
    grouped histograms, where copies outnumber lanes and the inference is
    wrong.
    """
    import numpy as np

    D = cfg.shards
    slack = cfg.routed_slack if slack is None else slack
    tile = cfg.routed_lane_tile if tile is None else tile
    if loads is None or pair is None:
        owner = np.asarray(owner)
        T, N = owner.shape
        n = N // D if n_local is None else n_local
        if T == 0:
            w = min(_round_up_lanes(1, tile), D * n)
            return BoundedRoutePlan(pair_capacity=min(tile, n),
                                    routed_width=w, routed_steps=0, steps=0,
                                    n_local=n, shards=D, max_owner_load=0,
                                    mean_owner_load=0.0, carried_lanes=0,
                                    total_lanes=0)
        loads = np.zeros((T, D), np.int64)      # lanes per (step, owner)
        for t in range(T):
            loads[t] = np.bincount(owner[t], minlength=D)
        pair = np.zeros((D, D), np.int64)       # whole-trace (origin, owner)
        for o in range(D):
            pair[o] = np.bincount(owner[:, o * n:(o + 1) * n].ravel(),
                                  minlength=D)
    else:
        loads, pair = np.asarray(loads), np.asarray(pair)
        T = loads.shape[0]
        D = loads.shape[1]          # dest count: shards (1-D) or mesh devices
        if n_local is not None:
            n = n_local
        else:
            n = int(pair.sum()) // max(T * D, 1) if T else 1
        if T == 0:
            w = min(_round_up_lanes(1, tile), D * n)
            return BoundedRoutePlan(pair_capacity=min(tile, n),
                                    routed_width=w, routed_steps=0, steps=0,
                                    n_local=n, shards=D, max_owner_load=0,
                                    mean_owner_load=0.0, carried_lanes=0,
                                    total_lanes=0)
    max_load = int(loads.max())
    nr = cfg.bounded_routed_width(max_load, n, slack=slack, tile=tile)
    # pair capacity quantizes to power-of-two tile multiples (vs exact tile
    # rounding) so fluctuating traffic mints O(log(n*T/tile)) jit
    # specializations, not one per distinct load — the same move the prefix
    # cache makes on its step count; the overshoot is dead send padding
    q = min(_round_up_pow2_lanes(int(pair.max()), tile), n * T)
    # exact FIFO sim per owner: drain rows needed + carried-lane count under
    # service rate nr per row — skipped entirely when the width covers the
    # max load (the auto-mode hot path: nothing can ever queue)
    carried, extra = 0, 0
    for d in range(D if nr < max_load else 0):
        tot = int(loads[:, d].sum())
        if tot == 0:
            continue
        arr = np.repeat(np.arange(T), loads[:, d])
        cum, backlog, t_row = [], 0, 0
        while t_row < T or backlog > 0:
            pending = backlog + (int(loads[t_row, d]) if t_row < T else 0)
            served = min(pending, nr)
            backlog = pending - served
            cum.append((cum[-1] if cum else 0) + served)
            t_row += 1
        dep = np.searchsorted(np.asarray(cum), np.arange(tot), side="right")
        carried += int((dep > arr).sum())
        extra = max(extra, t_row - T)
    if extra:       # drain rows quantize to powers of two too (shape churn)
        extra = 1 << (extra - 1).bit_length()
    return BoundedRoutePlan(pair_capacity=q, routed_width=nr,
                            routed_steps=T + extra, steps=T, n_local=n,
                            shards=D, max_owner_load=max_load,
                            mean_owner_load=float(loads.mean()),
                            carried_lanes=carried,
                            total_lanes=int(loads.sum()))


def _bounded_send_slots(owner: jnp.ndarray, shards: int, pair_capacity: int):
    """Origin-side FIFO packing: each lane's slot in the ``[D * Q]`` send
    buffer — pair queues are contiguous ``Q``-slot blocks, filled in program
    order ((step, lane)-major).  Lanes past a full queue get the
    out-of-range sentinel ``D * Q`` (never happens when ``Q`` comes from
    :func:`plan_bounded_route`).  Pure; property-tested without collectives.
    """
    T, n = owner.shape
    D, Q = shards, pair_capacity
    ow = owner.reshape(T * n).astype(jnp.int32)
    onehot = (ow[:, None] == jnp.arange(D, dtype=jnp.int32)).astype(jnp.int32)
    csum = jnp.cumsum(onehot, axis=0)                       # [T*n, D]
    q = jnp.take_along_axis(csum, ow[:, None], axis=1)[:, 0] - 1
    slot = jnp.where(q < Q, ow * Q + q, D * Q)
    return slot.reshape(T, n)


def _bounded_recv_binning(tags: jnp.ndarray, shards: int, pair_capacity: int,
                          steps: int, routed_steps: int, routed_width: int):
    """Owner-side re-binning: map each received FIFO slot to its routed
    ``(row, lane)`` cell.

    ``tags`` ``[D * Q]``: step+1 of the lane in each slot (0 == empty); slot
    ``o * Q + j`` is position ``j`` of origin ``o``'s queue, which is packed
    in program order.  Arrival order per owner is (step, origin, lane) ==
    program order; the owner FIFO serves ``Nr`` lanes per row, so a lane's
    row is its own step whenever ``Nr`` covers that step's load, and later
    rows (carry-over) otherwise.  Returns ``(idx, origin)``: ``idx`` is each
    slot's flat index into the ``[T' * Nr]`` routed stream (``T' * Nr`` ==
    dead/unserved sentinel), ``origin`` the slot's origin device.  Pure;
    property-tested without collectives.
    """
    D, Q, T, Tr, Nr = (shards, pair_capacity, steps, routed_steps,
                       routed_width)
    tagw = tags.astype(jnp.int32)
    live = tagw > 0
    t_arr = jnp.clip(tagw - 1, 0, max(T - 1, 0))
    slot_ids = jnp.arange(D * Q, dtype=jnp.int32)
    o_arr, j_arr = slot_ids // Q, slot_ids % Q
    onehot = (live[:, None]
              & (t_arr[:, None] == jnp.arange(T, dtype=jnp.int32))
              ).astype(jnp.int32)                           # [D*Q, T]
    cnt = onehot.reshape(D, Q, T).sum(axis=1)               # [D, T]
    start = jnp.cumsum(cnt, axis=1) - cnt      # origin's arrivals before t
    rank = j_arr - start[o_arr, t_arr]
    row_before = jnp.cumsum(cnt, axis=0) - cnt  # earlier origins' lanes at t
    rowpos = row_before[o_arr, t_arr] + rank
    arrivals = cnt.sum(axis=0)                              # [T]
    g = (jnp.cumsum(arrivals) - arrivals)[t_arr] + rowpos   # FIFO queue index
    a_pad = jnp.concatenate(
        [arrivals, jnp.zeros((Tr - T,), arrivals.dtype)]) if Tr > T \
        else arrivals[:Tr]

    def serve(backlog, a):
        pending = backlog + a
        s = jnp.minimum(pending, Nr)
        return pending - s, s

    _, served = jax.lax.scan(serve, jnp.asarray(0, arrivals.dtype), a_pad)
    cum = jnp.cumsum(served)                                # [Tr] inclusive
    dep = jnp.sum(cum[None, :] <= g[:, None], axis=1)       # service row
    pos = g - (cum - served)[jnp.clip(dep, 0, max(Tr - 1, 0))]
    ok_slot = live & (dep < Tr)
    idx = jnp.where(ok_slot, dep * Nr + pos, Tr * Nr)
    return idx, o_arr


def route_stream_bounded(cfg: HashTableConfig, axis: str, bucket: jnp.ndarray,
                         *arrays: jnp.ndarray, pair_capacity: int,
                         routed_width: int, routed_steps: int):
    """Pass 2 of the bounded router (shard_map collective): exchange query
    payloads with their owner shards through capacity-``Q`` pair FIFOs and
    re-bin them into ``[T', Nr]`` owner step rows.

    Same contract as :func:`route_stream` with the widths shrunk to the
    measured trace (static args from :func:`plan_bounded_route`).  Returns
    ``(routed_arrays, pe, carry)``: routed arrays ``[T', Nr(, W)]``, ``pe``
    ``[T', Nr]`` — the ORIGIN device of every routed lane (``D`` on dead
    padding, i.e. search-only, so padding can never write) — and the opaque
    ``carry`` to hand :func:`inverse_route_bounded`.
    """
    D = jax.lax.psum(1, axis)
    T, n = bucket.shape
    Q, Nr, Tr = pair_capacity, routed_width, routed_steps
    owner = shard_owner(cfg, bucket)                        # [T, n]
    packed, meta = _pack_u32(arrays)                        # [T, n, W]
    W = packed.shape[-1]
    slot = _bounded_send_slots(owner, D, Q)                 # [T, n]
    tag = jnp.broadcast_to(
        (jnp.arange(T, dtype=jnp.int32) + 1)[:, None, None], (T, n, 1)
    ).astype(jnp.uint32)
    payload = jnp.concatenate([tag, packed], axis=-1).reshape(T * n, W + 1)
    send = jnp.zeros((D * Q, W + 1), jnp.uint32)
    send = send.at[slot.reshape(T * n)].set(payload, mode="drop")
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)                   # chunk o = o's FIFO
    idx, origin = _bounded_recv_binning(recv[:, 0], D, Q, T, Tr, Nr)
    routed = jnp.zeros((Tr * Nr, W), jnp.uint32)
    routed = routed.at[idx].set(recv[:, 1:], mode="drop").reshape(Tr, Nr, W)
    pe = jnp.full((Tr * Nr,), D, jnp.int32)
    pe = pe.at[idx].set(origin, mode="drop").reshape(Tr, Nr)
    return _unpack_u32(routed, meta), pe, (slot, idx)


def inverse_route_bounded(axis: str, carry, *arrays: jnp.ndarray):
    """Return ``[T', Nr]`` routed results to their origin lanes: gather each
    received FIFO slot's result from its routed cell, one ``all_to_all``
    back, gather by send slot.  The inverse of :func:`route_stream_bounded`
    (``carry`` is its third output)."""
    slot, idx = carry
    packed, meta = _pack_u32(arrays)                        # [T', Nr, W]
    tr, nr, w = packed.shape
    flat = jnp.concatenate(
        [packed.reshape(tr * nr, w), jnp.zeros((1, w), jnp.uint32)])
    per_slot = flat[jnp.clip(idx, 0, tr * nr)]              # [D*Q, W]
    back = jax.lax.all_to_all(per_slot, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    backp = jnp.concatenate([back, jnp.zeros((1, w), jnp.uint32)])
    res = backp[jnp.clip(slot.reshape(-1), 0, back.shape[0])]
    return _unpack_u32(res.reshape(slot.shape + (w,)), meta)


# ---------------------------------------------------------------------------
# Stage four, grouped: the 2-D (shard x replica) mesh (DESIGN.md §2.3)
#
# Under ``cfg.replica_groups`` the route destination is a DEVICE, not a
# shard: shard ``s``'s partition lives on the ``group_sizes[s]`` contiguous
# devices starting at ``group_offsets[s]``.  Every query lane expands into a
# set of COPIES —
#
#   search (and NOP padding): exactly one copy, to the lane's SERVING device
#       — ``group_offsets[s] + serving_rank % group_sizes[s]`` where the
#       serving rank is the lane's per-origin round-robin counter over prior
#       same-shard lanes in (step, lane) program order (all ops count, so
#       the host measurement pass can replay it without device state);
#   mutation: one copy to EVERY device in the owner group (broadcast), so
#       each group member applies the identical mutation sequence in program
#       order and the partitions stay byte-identical — the serving device's
#       copy carries the result home, the rest are discarded (they are
#       identical anyway).
#
# Each origin lane sends at most one copy per destination, so the skew-proof
# capacity argument (``n`` slots per (origin, dest) per step) survives
# unchanged, and per-dest arrival order remains a program-order subsequence
# — the bit-exactness argument of §2.1/§2.2 goes through verbatim with
# D := mesh_devices.  ``inverse_route`` / ``inverse_route_bounded`` are
# reused as-is: the carry addresses the serving copy only.
# ---------------------------------------------------------------------------


def replica_layout(cfg: HashTableConfig):
    """Static device layout of the 2-D mesh: ``(shard_of, rank_of)`` tuples
    of length ``cfg.mesh_devices`` — device ``d`` holds shard ``shard_of[d]``
    as replica ``rank_of[d]`` (shard-major contiguous groups)."""
    shard_of, rank_of = [], []
    for s, g in enumerate(cfg.group_sizes):
        shard_of.extend([s] * g)
        rank_of.extend(range(g))
    return tuple(shard_of), tuple(rank_of)


def plan_replication(cfg: HashTableConfig, shard_loads,
                     n_devices: int) -> Tuple[int, ...]:
    """Convert measured per-shard load into per-shard replica degrees — the
    bounded router's discarded skew histogram fed forward (ISSUE: hot shards
    get more replicas, cold shards fewer, total devices fixed).

    ``shard_loads`` ``[shards]``: any nonnegative load measure (the column
    sums of :func:`route_load_pass`'s ``loads``, a search count, QPS...).
    Largest-remainder proportional allocation with a floor of one device per
    shard; deterministic (ties resolve to the lower shard id).  Returns a
    tuple suitable for ``HashTableConfig.replica_groups`` with
    ``sum == n_devices``.
    """
    S = cfg.shards
    loads = np.asarray(shard_loads, np.float64).reshape(-1)
    if loads.shape[0] != S:
        raise ValueError(f"shard_loads has {loads.shape[0]} entries but "
                         f"shards={S}")
    if n_devices < S:
        raise ValueError(f"n_devices={n_devices} < shards={S}: every shard "
                         f"needs at least one device")
    if loads.min() < 0:
        raise ValueError("shard_loads must be nonnegative")
    if loads.sum() <= 0:
        loads = np.ones(S)
    share = loads / loads.sum() * n_devices
    deg = np.maximum(np.floor(share).astype(np.int64), 1)
    rem = n_devices - int(deg.sum())
    if rem > 0:
        # +1 to the most under-allocated shards (largest share - deg, NOT
        # the raw fractional part: a min-floor-bumped cold shard is already
        # over its share and must not outrank the hot shard); ties resolve
        # to the hotter share then the lower shard id
        order = sorted(range(S),
                       key=lambda s: (-(share[s] - deg[s]), -share[s], s))
        for s in order[:rem]:
            deg[s] += 1
    while rem < 0:
        # the min-1 floor over-allocated: reclaim from the most
        # over-provisioned replicable shards (smallest share first)
        cand = [s for s in range(S) if deg[s] > 1]
        s = min(cand, key=lambda s: (share[s] - deg[s] + 1, s))
        deg[s] -= 1
        rem += 1
    return tuple(int(g) for g in deg)


def replica_copy_mask(cfg: HashTableConfig, owner: jnp.ndarray,
                      mut: jnp.ndarray):
    """Expand a ``[T, n]`` owner-shard matrix into the per-device copy mask.

    Returns ``(mask [T, n, Dv] bool, serve [T, n] int32)``: ``mask[t, j, d]``
    is True when lane ``(t, j)`` sends a copy to device ``d``; ``serve`` is
    the lane's serving device (always masked).  ``mut`` ``[T, n]`` marks
    mutations (``ops >= OP_INSERT``), which broadcast to the whole owner
    group.  The serving rank counts ALL prior lanes of the same owner shard
    on this origin in (step, lane) program order — identical arithmetic to
    ``serving.serve_loop.measure_loads_host``'s numpy mirror, which is what
    lets host-side plan caching replay it.
    """
    T, n = owner.shape
    S, Dv = cfg.shards, cfg.mesh_devices
    sizes = jnp.asarray(cfg.group_sizes, jnp.int32)             # [S]
    offs = jnp.asarray(cfg.group_offsets, jnp.int32)            # [S]
    shard_of = jnp.asarray(replica_layout(cfg)[0], jnp.int32)   # [Dv]
    ow = owner.reshape(T * n).astype(jnp.int32)
    oneh = (ow[:, None] == jnp.arange(S, dtype=jnp.int32)).astype(jnp.int32)
    csum = jnp.cumsum(oneh, axis=0)                             # [T*n, S]
    rank = jnp.take_along_axis(csum, ow[:, None], axis=1)[:, 0] - 1
    serve = offs[ow] + rank % sizes[ow]                         # [T*n]
    same = shard_of[None, :] == ow[:, None]                     # [T*n, Dv]
    dev = jnp.arange(Dv, dtype=jnp.int32)
    mask = same & (mut.reshape(T * n)[:, None]
                   | (dev[None, :] == serve[:, None]))
    return mask.reshape(T, n, Dv), serve.reshape(T, n).astype(jnp.int32)


def route_stream_grouped(cfg: HashTableConfig, axis: str, bucket: jnp.ndarray,
                         mut: jnp.ndarray, *arrays: jnp.ndarray):
    """Skew-proof exchange on the 2-D mesh: :func:`route_stream` with the
    owner-shard destination replaced by the per-device copy set of
    :func:`replica_copy_mask`.  Capacity stays ``n`` slots per (origin,
    dest) pair per step — each origin lane contributes at most one copy per
    device — so arbitrary skew still cannot drop queries.  Returns
    ``(routed_arrays, tgt)`` where ``tgt [T, n]`` addresses the SERVING
    copy's routed position; pass it to :func:`inverse_route` unchanged.
    """
    D = jax.lax.psum(1, axis)                       # == cfg.mesh_devices
    T, n = bucket.shape
    owner = shard_owner(cfg, bucket)
    mask, serve = replica_copy_mask(cfg, owner, mut)            # [T, n, D]
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1        # [T, n, D]
    dev = jnp.arange(D, dtype=jnp.int32)
    tgt = jnp.where(mask, dev[None, None, :] * n + pos, D * n)  # [T, n, D]
    packed, meta = _pack_u32(arrays)                            # [T, n, W]
    buf = jnp.zeros((T, D * n, packed.shape[-1]), jnp.uint32)
    buf = buf.at[jnp.arange(T)[:, None, None], tgt].set(
        packed[:, :, None, :], mode="drop")
    routed = jax.lax.all_to_all(buf, axis, split_axis=1, concat_axis=1,
                                tiled=True)
    pos_serve = jnp.take_along_axis(pos, serve[..., None], axis=2)[..., 0]
    return _unpack_u32(routed, meta), serve * n + pos_serve


def route_stream_grouped_bounded(cfg: HashTableConfig, axis: str,
                                 bucket: jnp.ndarray, mut: jnp.ndarray,
                                 *arrays: jnp.ndarray, pair_capacity: int,
                                 routed_width: int, routed_steps: int):
    """Bounded exchange on the 2-D mesh: per-(origin, device) FIFOs over the
    copy set.  Identical contract to :func:`route_stream_bounded` (plan the
    shapes with :func:`plan_bounded_route` on
    :func:`route_load_pass_grouped` histograms — they count copies, so the
    mutation broadcast is priced into width and capacity); the returned
    ``carry`` addresses the serving copy and feeds
    :func:`inverse_route_bounded` unchanged.
    """
    D = jax.lax.psum(1, axis)                       # == cfg.mesh_devices
    T, n = bucket.shape
    Q, Nr, Tr = pair_capacity, routed_width, routed_steps
    owner = shard_owner(cfg, bucket)
    mask, serve = replica_copy_mask(cfg, owner, mut)
    L = T * n
    m = mask.reshape(L, D)
    q = jnp.cumsum(m.astype(jnp.int32), axis=0) - 1             # [L, D]
    dev = jnp.arange(D, dtype=jnp.int32)
    slotm = jnp.where(m & (q < Q), dev[None, :] * Q + q, D * Q)  # [L, D]
    packed, meta = _pack_u32(arrays)
    W = packed.shape[-1]
    tag = jnp.repeat(jnp.arange(T, dtype=jnp.int32) + 1, n).astype(jnp.uint32)
    payload = jnp.concatenate([tag[:, None], packed.reshape(L, W)], axis=-1)
    send = jnp.zeros((D * Q, W + 1), jnp.uint32)
    send = send.at[slotm].set(payload[:, None, :], mode="drop")
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    idx, origin = _bounded_recv_binning(recv[:, 0], D, Q, T, Tr, Nr)
    routed = jnp.zeros((Tr * Nr, W), jnp.uint32)
    routed = routed.at[idx].set(recv[:, 1:], mode="drop").reshape(Tr, Nr, W)
    pe = jnp.full((Tr * Nr,), D, jnp.int32)
    pe = pe.at[idx].set(origin, mode="drop").reshape(Tr, Nr)
    slot_serve = jnp.take_along_axis(slotm.reshape(T, n, D),
                                     serve[..., None], axis=2)[..., 0]
    return _unpack_u32(routed, meta), pe, (slot_serve, idx)


def route_load_pass_grouped(cfg: HashTableConfig, owner: jnp.ndarray,
                            mut: jnp.ndarray):
    """The grouped measurement pass: histogram the GLOBAL ``[T, N]`` owner
    matrix (lanes origin-major, ``N = mesh_devices * n_local``) into
    per-(step, device) copy loads ``[T, Dv]`` and per-(origin, device)
    totals ``[Dv, Dv]``.  Entries count COPIES — a mutation lands in every
    member of its owner group — so ``pair.sum()`` exceeds the lane count;
    pass ``n_local`` explicitly to :func:`plan_bounded_route`.
    """
    T, N = owner.shape
    Dv = cfg.mesh_devices
    n = N // Dv
    ob = owner.reshape(T, Dv, n).transpose(1, 0, 2)             # [Dv, T, n]
    mb = mut.reshape(T, Dv, n).transpose(1, 0, 2)
    masks = jax.vmap(
        lambda o, mm: replica_copy_mask(cfg, o, mm)[0])(ob, mb)
    mi = masks.astype(jnp.int32)                        # [Dv, T, n, Dv]
    return mi.sum(axis=(0, 2)), mi.sum(axis=(1, 2))


# ---------------------------------------------------------------------------
# Stage five: bulk build + compaction (count-then-place, DESIGN.md §3.2)
#
# All table population above streams inserts through the query path — one
# probe/commit round per step even when every key is known up front.  The
# HashGraph move (PAPERS.md) builds the whole table in a constant number of
# counting-sort sweeps instead: hash all keys, resolve intra-batch duplicates
# with one stable sort (last value wins, first occurrence fixes the slot),
# histogram-rank distinct keys within their bucket, and place everything with
# ONE pass over the table.  The result is defined to be byte-identical to
# streaming the records through the insert path one record per step on lane 0
# (the serialized-insert oracle): every record initiates from PE 0, so all
# data lands in partial store 0 of every replica, the XOR encode basis is
# zero (enc == plaintext), and a distinct key's slot is its first-occurrence
# rank in the bucket.  Records whose bucket overflows (rank >= slots) SPILL:
# they are reported per record in the BulkBuildReport instead of silently
# dropped — exactly the records whose streamed insert would return ok=False.
#
# The same sweep over an existing table's occupied slots is ``compact()``:
# extract live plaintext records in (bucket, slot) order, rebuild into zeroed
# stores.  Slots densify to 0..count-1 per bucket, every live record
# survives, and the output is a fixed point (compact . compact == compact) —
# the migration inner loop the online-resize roadmap item needs.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BulkBuildReport:
    """Per-record outcome of a count-then-place sweep.

    ``placed`` mirrors the streamed-insert oracle's per-record ``ok``: True
    iff the record's key is resident after the build (its value may have been
    superseded by a later duplicate — last wins).  ``spilled`` marks live
    records whose bucket overflowed (``rank >= slots``); the spill list is
    the caller's records masked by it.  Arrays keep the caller's record
    layout (flat ``[n]`` from :func:`bulk_build`, ``[T, N]`` step tensors
    from the distributed builder)."""
    bucket: jnp.ndarray    # int32 — global H3 bucket per record
    slot: jnp.ndarray      # int32 — resident slot of the record's key
    placed: jnp.ndarray    # bool — key resident (== streamed-insert ok)
    spilled: jnp.ndarray   # bool — live record lost to bucket overflow
    first: jnp.ndarray     # bool — first occurrence of its key in the batch
    max_load: jnp.ndarray  # [] int32 — max distinct keys hashed to one bucket

    def tree_flatten(self):
        return (self.bucket, self.slot, self.placed, self.spilled,
                self.first, self.max_load), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def spill_count(self) -> jnp.ndarray:
        return jnp.sum(self.spilled.astype(jnp.int32))

    def spill_indices(self):
        """Host-side indices of spilled records (the reported spill list)."""
        import numpy as np
        return np.nonzero(np.asarray(self.spilled))[0]


def _plan_bulk_build_np(keys, vals, bucket, live, buckets: int, slots: int):
    """Host (numpy) implementation of the count-then-place plan — the same
    two-sort algorithm as :func:`_plan_bulk_build_xla`, field-for-field
    bit-exact (tests/test_bulk_build pins the equality).

    Exists because the plan is sort-bound and host sorts beat XLA:CPU's
    variadic comparison sort by ~4x (the same host-pass economics as the
    bounded router's ``plan_bounded_route`` load pass).  Where the packed
    sort key fits one uint64 word the variadic lexsort collapses to a single
    quicksort; numpy's indirect sorts are stable, so the explicit program-
    order tiebreak operand disappears entirely.
    """
    keys = np.asarray(keys)
    vals = np.asarray(vals)
    bucket = np.asarray(bucket, np.int32)
    live = np.asarray(live, bool)
    n, Wk = keys.shape
    B, S = buckets, slots
    idx = np.arange(n, dtype=np.int32)

    # --- sort 1: group identical live keys, program order within group ----
    if Wk == 1:
        # dead-last bit + the key word in one uint64 quicksort (stable)
        k1 = ((~live).astype(np.uint64) << np.uint64(32)
              | keys[:, 0].astype(np.uint64))
        order = np.argsort(k1, kind="stable").astype(np.int32)
    else:
        order = np.lexsort(tuple(keys[:, w] for w in range(Wk))
                           + ((~live).astype(np.int8),)).astype(np.int32)
    ks, live_s = keys[order], live[order]
    prev_same = np.zeros(n, bool)
    prev_same[1:] = ((ks[1:] == ks[:-1]).all(axis=-1)
                     & live_s[1:] & live_s[:-1])
    newg = live_s & ~prev_same
    segfirst = np.maximum.accumulate(np.where(newg, idx, -1))
    rep_s = order[np.clip(segfirst, 0, n - 1)]
    is_end = live_s & np.concatenate([~prev_same[1:], [True]])
    segend = np.minimum.accumulate(
        np.where(is_end, idx, n)[::-1])[::-1]
    val_last_s = vals[order][np.clip(segend, 0, n - 1)]

    is_rep = np.zeros(n, bool)
    is_rep[order] = newg
    grp_rep = np.zeros(n, np.int32)
    grp_rep[order] = rep_s
    val_w = np.zeros_like(vals)
    val_w[order] = val_last_s

    # --- sort 2: rank representatives per bucket by first occurrence ------
    b_bits = max(int(B - 1).bit_length(), 1)
    i_bits = max(int(n - 1).bit_length(), 1)
    if 1 + b_bits + i_bits <= 64:
        k2 = ((~is_rep).astype(np.uint64) << np.uint64(b_bits + i_bits)
              | bucket.astype(np.uint64) << np.uint64(i_bits)
              | idx.astype(np.uint64))
        order2 = np.argsort(k2).astype(np.int32)
    else:                                     # pragma: no cover - B*n > 2^63
        order2 = np.lexsort(
            (bucket, (~is_rep).astype(np.int8))).astype(np.int32)
    rep2, b2 = is_rep[order2], bucket[order2]
    newb = rep2 & np.concatenate([[True], b2[1:] != b2[:-1]])
    bstart = np.maximum.accumulate(np.where(newb, idx, -1))
    rank = np.zeros(n, np.int32)
    rank[order2] = idx - bstart

    # --- placement + spill ------------------------------------------------
    placed_rep = is_rep & (rank < S)
    spilled_rep = is_rep & (rank >= S)
    slot_per = rank[grp_rep]
    spilled = live & spilled_rep[grp_rep]
    placed = live & ~spilled
    return dict(
        w_bucket=np.where(placed_rep, bucket, np.int32(B)).astype(np.int32),
        w_slot=np.where(placed_rep, rank, 0).astype(np.int32),
        val_w=val_w,
        slot=np.where(placed, slot_per, 0).astype(np.int32),
        placed=placed, spilled=spilled, first=live & is_rep,
        max_load=np.max(np.where(is_rep, rank + 1, 0),
                        initial=0).astype(np.int32))


def plan_bulk_build(keys: jnp.ndarray, vals: jnp.ndarray, bucket: jnp.ndarray,
                    live: Optional[jnp.ndarray] = None, *, buckets: int,
                    slots: int, host: Optional[bool] = None):
    """The count-then-place plan, shared by every backend (the backends
    differ only in how the planned records are placed).

    Resolves ``n`` records (``keys [n, Wk]``, ``vals [n, Wv]``, ``bucket
    [n]``; ``live`` masks padding lanes) into at most one write per distinct
    key:

      sort 1  stable-group identical live keys (program order within a
              group): the group's FIRST occurrence is its representative —
              it fixes the slot — and its LAST occurrence carries the
              committed value (the streamed oracle's last-wins overwrite).
      sort 2  rank representatives within their bucket by first occurrence;
              rank == the slot a serialized insert stream would claim, since
              port-0 inserts always take the first open slot.
      spill   representatives with ``rank >= slots`` overflow; every
              occurrence of such a key is reported spilled (its streamed
              insert would find no match and no open slot -> ok=False).

    ``host`` picks the implementation: the direct numpy pass (the default
    for concrete arrays on every backend — host sorts are ~4x faster than
    XLA:CPU's, and compiling the two-lexsort plan for a v5e took 7-9
    minutes per record count on an 8-core host, against ~17 s for the
    numpy pass over 10M two-word keys on the same host), or the pure-XLA
    two-lexsort path (``host=False``).
    Both are field-for-field bit-exact.  The host pass needs CONCRETE
    arrays, so under a trace (jit / scan / shard_map) the XLA path always
    runs — callers that want the host plan keep ``bulk_build`` itself out
    of ``jax.jit`` and let its internally-jitted placement stage do the
    compiling.  (A
    ``jax.pure_callback`` bridge was tried and abandoned: XLA:CPU executes
    the callback on the intra-op pool and ``pure_callback_impl``'s
    ``device_put`` of large operands deadlocks against it.)

    Returns a dict: ``w_bucket``/``w_slot`` ``[n]`` int32 write targets
    (``buckets`` == masked, only representatives write), ``val_w [n, Wv]``
    the group-last value at representative positions, and the report fields
    ``slot``/``placed``/``spilled``/``first``/``max_load``.
    """
    n, Wk = keys.shape
    B, S = buckets, slots
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        zb = jnp.zeros((0,), jnp.bool_)
        return dict(w_bucket=z, w_slot=z, val_w=jnp.zeros_like(vals),
                    slot=z, placed=zb, spilled=zb, first=zb,
                    max_load=jnp.zeros((), jnp.int32))
    live = jnp.ones((n,), jnp.bool_) if live is None else live
    tracing = any(isinstance(x, jax.core.Tracer)
                  for x in (keys, vals, bucket, live))
    if host is not False and not tracing:
        # numpy outputs flow straight into the jitted placement call (its
        # implicit device_put) — eagerly wrapping them here would just add
        # eight dispatches
        return _plan_bulk_build_np(np.asarray(keys), np.asarray(vals),
                                   np.asarray(bucket), np.asarray(live),
                                   buckets=B, slots=S)
    idx = jnp.arange(n, dtype=jnp.int32)
    pos = idx

    # --- sort 1: group identical live keys, program order within group ----
    order = jnp.lexsort((idx,) + tuple(keys[:, w] for w in range(Wk))
                        + ((~live).astype(jnp.int32),))
    ks, live_s, idx_s = keys[order], live[order], idx[order]
    prev_same = jnp.concatenate([
        jnp.zeros((1,), jnp.bool_),
        jnp.all(ks[1:] == ks[:-1], axis=-1) & live_s[1:] & live_s[:-1]])
    newg = live_s & ~prev_same                       # group representatives
    # first occurrence (the representative) per sorted position
    segfirst = jax.lax.cummax(jnp.where(newg, pos, -1))
    rep_s = idx_s[jnp.clip(segfirst, 0, n - 1)]
    # last occurrence carries the committed value (last-wins)
    is_end = live_s & jnp.concatenate([~prev_same[1:],
                                       jnp.ones((1,), jnp.bool_)])
    segend = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(is_end, pos, n))))
    val_last_s = vals[order][jnp.clip(segend, 0, n - 1)]

    is_rep = jnp.zeros((n,), jnp.bool_).at[idx_s].set(newg)
    grp_rep = jnp.zeros((n,), jnp.int32).at[idx_s].set(rep_s)
    val_w = jnp.zeros_like(vals).at[idx_s].set(val_last_s)

    # --- sort 2: rank representatives per bucket by first occurrence ------
    bkt = bucket.astype(jnp.int32)
    order2 = jnp.lexsort((idx, bkt, (~is_rep).astype(jnp.int32)))
    rep2, b2 = is_rep[order2], bkt[order2]
    newb = rep2 & jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                   b2[1:] != b2[:-1]])
    bstart = jax.lax.cummax(jnp.where(newb, pos, -1))
    rank = jnp.zeros((n,), jnp.int32).at[order2].set(pos - bstart)

    # --- placement + spill --------------------------------------------------
    placed_rep = is_rep & (rank < S)
    spilled_rep = is_rep & (rank >= S)
    slot_per = rank[grp_rep]                  # group slot, at every occurrence
    spilled = live & spilled_rep[grp_rep]
    placed = live & ~spilled
    return dict(
        w_bucket=jnp.where(placed_rep, bkt, jnp.int32(B)),
        w_slot=jnp.where(placed_rep, rank, 0),
        val_w=val_w,
        slot=jnp.where(placed, slot_per, 0),
        placed=placed, spilled=spilled, first=live & is_rep,
        max_load=jnp.max(jnp.where(is_rep, rank + 1, 0)).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("backend_name", "buckets",
                                             "part_buckets", "slots"))
def _place_into_stores(packed, w_bucket, w_slot, keys, val_w, *,
                       backend_name: str, buckets: int, part_buckets: int,
                       slots: int):
    """The device half of a bulk placement: scatter the planned records
    (``w_bucket == buckets`` masks one) into the port-0 plane of replica
    0's stored array, in partitions of ``part_buckets``, and broadcast it to
    every replica.  Jitted so the eager host-planned path (``bulk_build``
    outside ``jax.jit``) pays one fused dispatch, not one per op; under an
    outer trace it inlines."""
    from repro.kernels import packed as pk
    cols = packed.shape[-3] * pk.LANES
    col = jnp.where(w_bucket < buckets,
                    pk.stored_column(w_bucket, part_buckets), cols)
    tab = get_backend(backend_name).bulk_place(
        packed[0], col, w_slot, keys, val_w, buckets=cols, slots=slots)
    # every record writes port 0 of every replica (replica invariant)
    return jnp.broadcast_to(tab[None], packed.shape)


def bulk_place_records(cfg: HashTableConfig, packed, bucket, keys, vals,
                       live: Optional[jnp.ndarray] = None, *,
                       bucket_base=0, backend: Optional[str] = None):
    """Count-then-place a flat record batch into (a partition of) an empty
    stored array — the raw-array core shared by :func:`bulk_build`,
    :func:`compact` and the shard_map distributed builder.

    ``packed`` ``[R, G, C, 128]`` holds one partition of ``B`` global
    buckets ``[bucket_base, bucket_base + B)`` (the whole table of a single
    memory domain) and must be EMPTY over the placed range (all records
    land in partial store 0, encode basis zero — the serialized-insert
    oracle's layout).  ``bucket`` carries GLOBAL indices; records outside
    the partition are treated as dead.  Returns ``(packed', placed,
    spilled, slot, first, max_load)``.
    """
    from repro.kernels.packed import stored_buckets
    Bl = stored_buckets(packed, cfg.local_buckets)
    rel = bucket.astype(jnp.int32) - jnp.asarray(bucket_base, jnp.int32)
    in_part = (rel >= 0) & (rel < Bl)
    live = in_part if live is None else (live & in_part)
    plan = plan_bulk_build(keys, vals, jnp.clip(rel, 0, Bl - 1), live,
                           buckets=Bl, slots=cfg.slots)
    packed = _place_into_stores(
        packed, plan["w_bucket"], plan["w_slot"], keys, plan["val_w"],
        backend_name=_resolve_name(cfg, backend), buckets=Bl,
        part_buckets=cfg.local_buckets, slots=cfg.slots)
    return (packed, plan["placed"], plan["spilled"], plan["slot"],
            plan["first"], plan["max_load"])


def bulk_build(table: XorHashTable, keys: jnp.ndarray, vals: jnp.ndarray,
               live: Optional[jnp.ndarray] = None,
               backend: Optional[str] = None
               ) -> Tuple[XorHashTable, BulkBuildReport]:
    """Construct table state from a flat record batch in O(1) sweeps.

    ``keys [n, Wk]`` / ``vals [n, Wv]`` (``live`` masks padding records).
    The table must be EMPTY (fresh from ``init_table``); the result is
    byte-identical to streaming the records through the insert path one
    record per step (the serialized-insert oracle — tests/test_bulk_build).
    Intra-batch duplicate keys resolve last-wins; bucket overflow degrades
    to per-record spill reporting (``report.spilled``), never a silent
    drop.  ``backend`` as in :func:`run_stream`.
    """
    cfg = table.cfg
    keys = jnp.asarray(keys).astype(jnp.uint32).reshape(-1, cfg.key_words)
    vals = jnp.asarray(vals).astype(jnp.uint32).reshape(-1, cfg.val_words)
    n = keys.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        zb = jnp.zeros((0,), jnp.bool_)
        return table, BulkBuildReport(bucket=z, slot=z, placed=zb,
                                      spilled=zb, first=zb,
                                      max_load=jnp.zeros((), jnp.int32))
    name = _resolve_name(cfg, backend)
    if name == "pallas":
        from repro.kernels import ops as kops
        bucket = kops.h3_hash(keys, table.q_masks)
    else:
        bucket = _h3_jnp(keys, table.q_masks)
    packed, placed, spilled, slot, first, max_load = bulk_place_records(
        cfg, table.packed, bucket, keys, vals, live, backend=name)
    report = BulkBuildReport(bucket=bucket.astype(jnp.int32), slot=slot,
                             placed=placed, spilled=spilled, first=first,
                             max_load=max_load)
    return XorHashTable(table.q_masks, packed, cfg), report


def extract_records(table: XorHashTable):
    """Decode a table's live plaintext records in (bucket, slot) order.

    Returns ``(keys [B*S, Wk], vals [B*S, Wv], live [B*S], bucket [B*S])``
    — the input layout :func:`plan_bulk_build` expects, with ``bucket``
    taken from slot POSITION (no rehash: a resident key already lives in
    its H3 bucket, and position survives even without the H3 matrix)."""
    pk, pv, pvalid = table.plaintext()
    B, S, Wk = pk.shape
    return (pk.reshape(B * S, Wk), pv.reshape(B * S, -1),
            pvalid.reshape(B * S).astype(jnp.bool_),
            jnp.repeat(jnp.arange(B, dtype=jnp.int32), S))


def compact(table: XorHashTable, backend: Optional[str] = None
            ) -> XorHashTable:
    """Rewrite a fragmented table into dense slot occupancy: the bulk-build
    sweep run over the table's own occupied slots.  Every live record
    survives at its bucket (slots densify to ``0..count-1`` in slot order),
    deleted/stale encodings vanish, and the output is canonical: idempotent
    under re-compaction and a fixed point of fresh bulk builds.  Spill is
    impossible (at most S live records per bucket come out of S slots)."""
    cfg = table.cfg
    keys, vals, live, bucket = extract_records(table)
    packed = bulk_place_records(cfg, jnp.zeros_like(table.packed), bucket,
                                keys, vals, live, backend=backend)[0]
    return XorHashTable(table.q_masks, packed, cfg)


# ---------------------------------------------------------------------------
# Stage six: online resize/rehash without stopping the stream (DESIGN.md §6)
#
# Capacity grows by adding H3 index bits: the successor table's q_masks are
# the predecessor's with ``g`` fresh random rows INSERTED at bit position
# ``lib == cfg.local_index_bits`` (for shards == 1 that is the top of the
# index, the textbook split-in-place scheme).  Row ``j`` of the H3 matrix has
# weight ``2^j`` (hashing.h3_hash), so:
#
#   * the low ``lib`` bits of every key's bucket are UNCHANGED — each old
#     local bucket ``b`` splits in place into the 2^g successor buckets
#     ``(e << lib) | b`` for the ``g`` new parity bits ``e``;
#   * the high (owner-shard) bits are unchanged too — ``b_new >> (lib + g)
#     == b_old >> lib`` — so a record's owner shard NEVER moves: routing is
#     computed once from the predecessor hash, migration is shard-local, and
#     the successor partitions land on the same replica groups.
#
# Live queries route by a per-bucket migration WATERMARK ``w`` over the old
# LOCAL bucket index: bucket ``b`` is migrated iff ``(b & (Bl_old - 1)) <
# w``.  ``run_stream_resize`` runs the trace through BOTH tables with the
# other side's lanes masked to NOP (the repo-wide dead-lane contract makes
# them inert) and merges per-lane results — one watermark scalar traces
# through, so advancing it never recompiles.  ``migrate_slab`` moves rows
# ``[w, w + n)``: decode the predecessor rows' plaintext, hash only the ``g``
# new bits, count-then-place into the successor (those successor rows are
# guaranteed empty — in-flight mutations only ever touch successor rows
# below the watermark — and spill is impossible: one pred bucket's <= S
# records fan out across 2^g successor buckets), zero the migrated
# predecessor rows, advance ``w``.
#
# Replay rule (the mutation-record seam): encoded mutation records are
# XOR-basis-relative to the snapshot they probed, so they cannot be
# re-applied to the successor.  Instead the migration sweep consumes the
# post-commit chained table VALUE of every dispatched slab — jax's
# functional state threading replays in-flight mutations in value order by
# construction, which is exactly program order.  Hence the stream contract:
# results are bit-exact with a twin table born at the final capacity (same
# successor q_masks) for any interleaving of slabs and migration, provided
# no bucket overflows mid-resize (a not-yet-split predecessor bucket carries
# its 2^g successors' combined load, so an insert can spill there where the
# born-big twin still has room — tests/test_resize.py pins the contract).
# ---------------------------------------------------------------------------


def successor_masks(q_masks: jnp.ndarray, old_cfg: HashTableConfig,
                    new_cfg: HashTableConfig, rng) -> jnp.ndarray:
    """The successor table's H3 matrix: ``g = new - old`` index-bit rows
    drawn from ``rng`` and inserted at bit position ``old_cfg.
    local_index_bits``, preserving both the low in-partition bits and the
    high owner-shard bits of every key's bucket (section comment above).
    Exposed so a born-at-final-capacity twin can be built with byte-identical
    q_masks (the resize conformance tests' oracle)."""
    g = new_cfg.index_bits - old_cfg.index_bits
    if g <= 0:
        raise ValueError(f"successor needs more index bits than the "
                         f"predecessor ({new_cfg.index_bits} vs "
                         f"{old_cfg.index_bits})")
    lib = old_cfg.local_index_bits
    new_rows = make_h3_params(rng, old_cfg.key_words, g)
    return jnp.concatenate([q_masks[:lib], new_rows, q_masks[lib:]], axis=0)


@dataclasses.dataclass
class ResizeState:
    """An in-flight online resize: predecessor + successor table values and
    the migration watermark (host int over the old LOCAL bucket index —
    buckets below it serve from the successor).  The table values chain
    functionally through :func:`run_stream_resize` / :func:`migrate_slab`;
    the state is cheap to replace (arrays are shared, never copied)."""
    pred: XorHashTable
    succ: XorHashTable
    watermark: int = 0

    @property
    def grow_bits(self) -> int:
        """g: index bits added by this resize."""
        return self.succ.cfg.index_bits - self.pred.cfg.index_bits

    @property
    def insert_bit(self) -> int:
        """Bit position the new rows were inserted at (old local bits)."""
        return self.pred.cfg.local_index_bits

    @property
    def done(self) -> bool:
        return self.watermark >= self.pred.cfg.local_buckets

    @property
    def progress(self) -> float:
        return self.watermark / self.pred.cfg.local_buckets


def begin_resize(table: XorHashTable, new_buckets: int,
                 rng=None) -> ResizeState:
    """Open an online resize: allocate the empty successor (extended H3
    matrix, ``new_buckets`` capacity, otherwise identical geometry) next to
    the live predecessor at watermark 0.  Single-memory-domain tables only —
    a sharded mesh resizes through ``distributed.make_distributed_resize``,
    which places the successor partitions on the same devices.  ``rng``
    draws the new H3 rows (deterministic default from ``new_buckets``)."""
    cfg = table.cfg
    if cfg.shards > 1:
        raise ValueError(
            "begin_resize drives a single memory domain; a bucket-sharded "
            "table resizes through distributed.make_distributed_resize "
            "(same watermark contract, shard-local migration slabs)")
    if new_buckets & (new_buckets - 1) or new_buckets <= cfg.buckets:
        raise ValueError(f"new_buckets must be a power of two above "
                         f"buckets={cfg.buckets}, got {new_buckets}")
    new_cfg = dataclasses.replace(cfg, buckets=new_buckets)
    if rng is None:
        rng = jax.random.PRNGKey(new_buckets)
    qm = successor_masks(table.q_masks, cfg, new_cfg, rng)
    succ = XorHashTable(qm, jnp.zeros(packed_shape(new_cfg), jnp.uint32),
                        new_cfg)
    return ResizeState(pred=table, succ=succ, watermark=0)


def resize_buckets(b_old: jnp.ndarray, extra: jnp.ndarray, lib: int, g: int,
                   bl_old: int) -> jnp.ndarray:
    """Successor bucket of a key: insert its ``g`` new parity bits ``extra``
    into ``b_old`` at bit ``lib`` — low in-partition bits and high owner
    bits survive (the split-in-place map)."""
    low = b_old & jnp.uint32(bl_old - 1)
    return (((b_old >> lib) << (lib + g)) | (extra << lib) | low)


def _resize_stream(pred, succ, w, ops, keys, vals, *,
                   backend=None, fused=None):
    """The dual-table step body (jitted below): watermark ``w`` rides in as
    a traced uint32 scalar, so migration progress never mints a recompile."""
    cfg, new_cfg = pred.cfg, succ.cfg
    lib = cfg.local_index_bits
    g = new_cfg.index_bits - cfg.index_bits
    bl = cfg.local_buckets
    T, N = ops.shape
    flat = keys.reshape(T * N, cfg.key_words)
    b_old = _h3_jnp(flat, pred.q_masks).reshape(T, N)
    extra = _h3_jnp(flat, succ.q_masks[lib:lib + g]).reshape(T, N)
    mig = (b_old & jnp.uint32(bl - 1)) < w
    # mask each side's foreign lanes to the dead-lane sentinel (op NOP,
    # key 0): inert by the engine contract on every backend, and the masked
    # results are discarded by the merge below anyway
    zk = jnp.zeros_like(keys)
    pred, rp = run_stream(pred, jnp.where(mig, 0, ops),
                          jnp.where(mig[..., None], zk, keys), vals,
                          backend=backend, fused=fused)
    succ, rs = run_stream(succ, jnp.where(mig, ops, 0),
                          jnp.where(mig[..., None], keys, zk), vals,
                          backend=backend, fused=fused)
    res = StepResults(
        found=jnp.where(mig, rs.found, rp.found),
        value=jnp.where(mig[..., None], rs.value, rp.value),
        ok=jnp.where(mig, rs.ok, rp.ok),
        bucket=resize_buckets(b_old, extra, lib, g, bl))
    return pred, succ, res


_resize_stream_jit = functools.partial(
    jax.jit, static_argnames=("backend", "fused")
)(_resize_stream)
# donated twin: pred/succ buffers update in place instead of being copied
# per step — a full-table copy per dispatch would dominate the resize
# window.  Only for linear-use callers (the serving loop rebinds the state
# every call and never touches the stale one); the default stays copying.
_resize_stream_jit_donated = functools.partial(
    jax.jit, static_argnames=("backend", "fused"),
    donate_argnums=(0, 1),
)(_resize_stream)


def run_stream_resize(state: ResizeState, ops: jnp.ndarray,
                      keys: jnp.ndarray, vals: jnp.ndarray,
                      backend: Optional[str] = None,
                      fused: Optional[bool] = None,
                      donate: bool = False
                      ) -> Tuple[ResizeState, StepResults]:
    """Stream a ``[T, N]`` trace through an in-flight resize.

    Lanes whose (predecessor-hash) bucket is below the watermark run against
    the successor, the rest against the predecessor; each table sees the
    other side's lanes as dead NOP padding and the per-lane results merge by
    the same mask.  Cost is both streams for the duration of the resize
    window — the 2x factor ``perfmodel.resize_migration_seconds`` prices.
    Results are bit-exact with the born-at-final-capacity twin under the
    no-mid-resize-overflow proviso (section comment); ``results.bucket``
    reports the SUCCESSOR bucket (== the twin's) for every lane.

    ``donate=True`` hands the state's pred/succ buffers to XLA for in-place
    update (no per-step full-table copy).  Linear-use callers only — the
    passed-in ``state`` is dead after the call; the serving loop's dispatch
    path opts in, library callers that keep the old state must not."""
    cfg = state.pred.cfg
    if ops.ndim != 2 or ops.shape[1] != cfg.queries_per_step:
        raise ValueError(f"stream shape {ops.shape} != [T, p*qpp="
                         f"{cfg.queries_per_step}]")
    step = _resize_stream_jit_donated if donate else _resize_stream_jit
    pred, succ, res = step(
        state.pred, state.succ, jnp.uint32(state.watermark), ops, keys, vals,
        backend=backend, fused=fused)
    return dataclasses.replace(state, pred=pred, succ=succ), res


@functools.partial(jax.jit,
                   static_argnames=("n", "backend"),
                   donate_argnums=(0, 1))
def _migrate_slab_jit(pred, succ, w, *, n, backend=None):
    """The jitted slab body: pred/succ buffers are DONATED so XLA updates
    the stores in place — an eager sweep would copy both full tables per
    slab, turning the per-slab pause O(table) instead of O(slab) and
    erasing online migration's whole latency advantage over a
    stop-the-world rebuild.  ``w`` rides in traced (uint32), ``n`` is
    static (one compile per distinct slab size, like the distributed
    factory's per-``n`` cache)."""
    cfg, new_cfg = pred.cfg, succ.cfg
    lib = cfg.local_index_bits
    g = new_cfg.index_bits - cfg.index_bits
    keys, vals, live, rows = slab_records(pred, w, n)
    # single-domain: local bucket == global bucket (the distributed factory
    # runs its own shard-local copy of this sweep with the owner offset)
    b_old = w.astype(jnp.uint32) + rows
    extra = _h3_jnp(keys, succ.q_masks[lib:lib + g])
    b_new = resize_buckets(b_old, extra, lib, g, cfg.local_buckets)
    packed = bulk_place_records(new_cfg, succ.packed, b_new, keys, vals,
                                live, backend=backend)[0]
    return (zero_slab(pred, w, n),
            XorHashTable(succ.q_masks, packed, new_cfg))


def slab_records(table: XorHashTable, w, n: int):
    """The live plaintext records of local buckets ``[w, w + n)`` (replica
    0 — replicas are identical), decoded from those buckets' columns of the
    stored array: ``(keys [n*S, Wk], vals [n*S, Wv], live [n*S], row
    [n*S])`` with ``row`` the record's bucket offset from ``w``."""
    from repro.kernels import packed as pk
    cfg = table.cfg
    S, Wk, Wv = cfg.slots, cfg.key_words, cfg.val_words
    W = Wk + Wv + 1
    row = jnp.arange(n, dtype=jnp.uint32)
    b = (w.astype(jnp.uint32) + row).astype(jnp.int32)
    cols = pk.columns(table.packed,
                      pk.stored_column(b, cfg.local_buckets))   # [n, C]
    dec = xor_reduce(pk.planes(cols, cfg.k, S, W), axis=1)    # [n, S, W]
    return (dec[..., :Wk].reshape(n * S, Wk),
            dec[..., Wk:W - 1].reshape(n * S, Wv),
            (dec[..., W - 1] & 1).reshape(n * S).astype(jnp.bool_),
            jnp.repeat(row, S))


def zero_slab(table: XorHashTable, w, n: int) -> XorHashTable:
    """The table with local buckets ``[w, w + n)`` zeroed in every plane and
    replica (an O(slab) scatter into the stored array)."""
    from repro.kernels import packed as pk
    b = w.astype(jnp.int32) + jnp.arange(n, dtype=jnp.int32)
    C = table.packed.shape[-2]
    rows = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (n, C))
    packed = pk.set_words(table.packed,
                          pk.stored_column(b, table.cfg.local_buckets), rows,
                          jnp.zeros((n, C), jnp.uint32))
    return XorHashTable(table.q_masks, packed, table.cfg)


def migrate_slab(state: ResizeState, n_buckets: int,
                 backend: Optional[str] = None) -> ResizeState:
    """Migrate the next ``n_buckets`` predecessor rows ``[w, w + n)`` into
    the successor and advance the watermark.

    Decode the rows' live plaintext (replica 0 — replicas are identical),
    hash only the ``g`` new index bits, count-then-place into the successor
    (the target rows are empty and spill impossible — section comment), and
    zero the migrated predecessor rows.  Runs jitted with donated buffers
    (O(slab) in-place updates; the caller must drop the old state, which
    every chaining caller does); interleave calls with
    :func:`run_stream_resize` dispatches at whatever slab size the latency
    budget allows (``config.GrowthPolicy.migrate_buckets_per_slab``)."""
    cfg = state.pred.cfg
    w = state.watermark
    n = min(n_buckets, cfg.local_buckets - w)
    if n <= 0:
        return state
    pred, succ = _migrate_slab_jit(state.pred, state.succ, jnp.uint32(w),
                                   n=n, backend=backend)
    return ResizeState(pred=pred, succ=succ, watermark=w + n)


def finish_resize(state: ResizeState) -> XorHashTable:
    """Close a completed resize: returns the successor table (the live
    value — all mutations since ``begin_resize`` chained into it)."""
    if not state.done:
        raise ValueError(
            f"resize incomplete: watermark {state.watermark}/"
            f"{state.pred.cfg.local_buckets} — migrate_slab the remaining "
            f"buckets before finishing")
    return state.succ


RECONFIGURE_FROZEN_FIELDS = ("p", "key_words", "val_words",
                             "queries_per_pe", "stagger_slots",
                             "shards", "replica_groups")


def _shrunk_masks(q_masks: jnp.ndarray, old_cfg: HashTableConfig,
                  new_cfg: HashTableConfig) -> jnp.ndarray:
    """Inverse of :func:`successor_masks`: delete the index-bit rows
    ``[new_lib, old_lib)`` so the table shrinks along the same in-place
    split axis growth uses."""
    return jnp.concatenate([q_masks[:new_cfg.local_index_bits],
                            q_masks[old_cfg.local_index_bits:]], axis=0)


def reconfigure(table: XorHashTable, new_cfg: HashTableConfig,
                backend: Optional[str] = None,
                rng=None) -> XorHashTable:
    """Migrate a live table into a different geometry or capacity.

    Two migration regimes, picked by what ``new_cfg`` changes:

    **Geometry** (``k``, ``replicate_reads`` — the lattice
    ``perfmodel.plan_geometry`` searches — plus non-layout knobs): the H3
    matrix, bucket indices and slot positions survive unchanged, so the
    migration is :func:`extract_records` (decode live plaintext in (bucket,
    slot) order) through the count-then-place sweep into freshly-zeroed
    stores of the new ``(replicas, k)`` shape.  The record SET is exact
    (spill impossible: at most S live records per bucket re-place into S
    slots); the byte layout is the canonical compacted one.  Works on a
    shard's local partition too (the bucket dimension is taken from the
    store arrays), which is what ``distributed.make_distributed_reconfigure``
    maps over the mesh.

    **Capacity** (``buckets``, ``slots`` — single-memory-domain tables
    only): the stop-the-world cousin of the online-resize seam.  Growth
    extends the H3 matrix exactly like :func:`begin_resize`
    (:func:`successor_masks`, ``rng`` draws the new rows), shrink deletes
    the same rows; every live record is rehashed at the new index width and
    re-placed in one sweep.  A shrink that cannot hold every live record
    raises (reporting the spill count) instead of dropping records.  A
    sharded mesh changes capacity through the live migration path
    (``distributed.make_distributed_resize`` / ``TableServer`` growth)
    instead — this entry raises with that pointer.

    Genuinely frozen fields (``RECONFIGURE_FROZEN_FIELDS``: hash-input
    width, value width, lane layout, mesh shape) still raise a fix-it error.
    """
    old = table.cfg
    diffs = [f for f in RECONFIGURE_FROZEN_FIELDS
             if getattr(old, f) != getattr(new_cfg, f)]
    if diffs:
        raise ValueError(
            f"reconfigure migrates geometry (k, replicate_reads) and "
            f"capacity (buckets, slots), but {diffs} differ between the "
            f"live table's config and new_cfg — those fields are baked into "
            f"every record (key/value widths, lane layout, mesh shape); "
            f"build a fresh table and bulk_build the extracted records into "
            f"it instead")
    capacity = (old.buckets != new_cfg.buckets or old.slots != new_cfg.slots)
    if not capacity:
        keys, vals, live, bucket = extract_records(table)
        empty = jnp.zeros(packed_shape(new_cfg, table.buckets), jnp.uint32)
        packed = bulk_place_records(new_cfg, empty, bucket, keys, vals, live,
                                    backend=backend)[0]
        return XorHashTable(table.q_masks, packed, new_cfg)
    if old.shards > 1:
        raise ValueError(
            f"capacity reconfigure (buckets {old.buckets}->{new_cfg.buckets}"
            f", slots {old.slots}->{new_cfg.slots}) drives a single memory "
            f"domain; a bucket-sharded table changes capacity through the "
            f"online-resize seam (distributed.make_distributed_resize, or "
            f"TableServer growth) — per-partition reconfigure cannot "
            f"re-home records across shards")
    if new_cfg.buckets > old.buckets:
        if rng is None:
            rng = jax.random.PRNGKey(new_cfg.buckets)
        q_masks = successor_masks(table.q_masks, old, new_cfg, rng)
    elif new_cfg.buckets < old.buckets:
        q_masks = _shrunk_masks(table.q_masks, old, new_cfg)
    else:
        q_masks = table.q_masks
    keys, vals, live, _ = extract_records(table)
    bucket = _h3_jnp(keys, q_masks)
    B, S = new_cfg.buckets, new_cfg.slots
    packed, _, spilled, _, _, _ = bulk_place_records(
        new_cfg, jnp.zeros(packed_shape(new_cfg), jnp.uint32), bucket, keys,
        vals, live, backend=backend)
    spill_ct = jnp.sum(spilled.astype(jnp.int32))
    if not isinstance(spill_ct, jax.core.Tracer) and int(spill_ct):
        raise ValueError(
            f"capacity reconfigure to (buckets={B}, slots={S}) would drop "
            f"{int(spill_ct)} live records to bucket overflow — grow slots "
            f"or buckets, or delete records first")
    return XorHashTable(q_masks, packed, new_cfg)
