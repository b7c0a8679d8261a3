"""The paper's parallel hash table: p PEs, XOR partial stores, S/I/U/D queries.

Architecture recap (paper §IV-C):

  * The table is *replicated* once per PE (conflict-free reads).
  * Each replica is split into ``k`` **Partial XOR Stores**; the plaintext entry
    at (bucket, slot) is the XOR over the k stores.  NSQ-capable PE ``j`` owns
    partial store ``j`` — a mutation initiated by PE j writes *only* store j
    (in every replica), so same-step mutations from different PEs are
    conflict-free **by construction**, independent of the access pattern.
  * Search: hash -> parallel read of k stores -> XOR reduction tree -> slot
    compare -> value.   Insert/Update/Delete: search dataflow first, then the
    new entry is XOR-encoded against the *other* k-1 stores and written to the
    initiating PE's store in all replicas (inter-PE propagation).

Vectorization model (see DESIGN.md §2): one ``apply_step`` call processes
``p * queries_per_pe`` queries with **no data-dependent control flow** — the
step latency is shape-only, which is the TPU expression of the paper's
"p queries per cycle in the worst case".  Query position ``n`` maps to PE
``n % p``; the host-side router (:func:`schedule_queries`) enforces the
workload contract that at most ``k`` of every ``p`` consecutive queries are
non-search queries (paper Definition 1: NSQ ratio).

Consistency: all encodings are computed against the pre-step snapshot and all
writes commit at the end of the step — the relaxed-consistency window of the
paper (Theorem 1), with the FPGA's ``p + t0`` cycles becoming one step.
``repro.core.consistency`` contains the cycle-accurate simulator that measures
``n_err`` against the bound.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.config import HashTableConfig
from repro.core.hashing import make_h3_params
from repro.core.xor_memory import xor_reduce

__all__ = [
    "OP_NOP", "OP_SEARCH", "OP_INSERT", "OP_DELETE",
    "XorHashTable", "QueryBatch", "StepResults",
    "init_table", "packed_shape", "apply_step", "run_stream", "bulk_build",
    "compact", "reconfigure", "schedule_queries", "pack_trace",
]

# Operation codes (OP_INSERT covers the paper's fused Insert/Update).
OP_NOP = 0
OP_SEARCH = 1
OP_INSERT = 2
OP_DELETE = 3


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class XorHashTable:
    """Functional state: XOR-encoded partial stores across replicas.

    ``packed`` is the table's one stored format: ``[R, G, C, 128]`` uint32,
    each replica's ``k`` partial stores in the kernels' lane-dense layout
    (``kernels/packed.py``; ``G = ceil(B / 128)`` bucket groups, ``C =
    packed_rows(k, S, Wk + Wv + 1)`` plane/word/slot rows).  A sharded
    table stores each ``cfg.local_buckets`` partition packed on its own.
    R == p for the paper-faithful layout, 1 for the compact TPU layout.
    Code that wants the logical ``[R, k, B, S, W]`` view converts through
    :meth:`stores` and :meth:`from_stores`.
    """
    q_masks: jnp.ndarray      # [index_bits, key_words] uint32 — H3 matrix
    packed: jnp.ndarray       # [R, G, C, 128] uint32 (XOR-encoded)
    cfg: HashTableConfig      # static

    def tree_flatten(self):
        return (self.q_masks, self.packed), self.cfg

    @classmethod
    def tree_unflatten(cls, cfg, children):
        return cls(*children, cfg=cfg)

    @property
    def buckets(self) -> int:
        """Buckets the stored array holds (one shard's partition when the
        table is a shard_map-local view)."""
        from repro.kernels import packed as pk
        return pk.stored_buckets(self.packed, self.cfg.local_buckets)

    def stores(self):
        """The logical view ``(keys [R, k, B, S, Wk], vals [R, k, B, S, Wv],
        valid [R, k, B, S])`` — an O(table) transpose (oracle/test edge)."""
        from repro.kernels import packed as pk
        cfg = self.cfg
        return pk.unpack_stores(self.packed, cfg.k, cfg.local_buckets,
                                cfg.slots, cfg.key_words, cfg.val_words)

    @classmethod
    def from_stores(cls, q_masks, keys, vals, valid,
                    cfg: HashTableConfig) -> "XorHashTable":
        """Inverse of :meth:`stores`."""
        from repro.kernels import packed as pk
        return cls(q_masks, pk.pack_stores(keys, vals, valid,
                                           cfg.local_buckets), cfg)

    # Convenience plaintext views (debug/test only; not used in the hot path).
    def plaintext(self, replica: int = 0):
        """``(keys [B, S, Wk], vals [B, S, Wv], valid [B, S])``: the XOR of
        the k planes, taken on the stored array, then every bucket's column
        of the one decoded plane."""
        from repro.kernels import packed as pk
        cfg = self.cfg
        S, Wk, W = cfg.slots, cfg.key_words, cfg.entry_words
        tab = self.packed[replica]
        one = xor_reduce(tab[:, :cfg.k * S * W].reshape(
            tab.shape[0], cfg.k, S * W, tab.shape[2]), axis=1)
        b = jnp.arange(self.buckets, dtype=jnp.int32)
        cols = pk.columns(one[None], pk.stored_column(b, cfg.local_buckets))
        dec = pk.planes(cols, 1, S, W)[:, 0]
        return dec[..., :Wk], dec[..., Wk:W - 1], dec[..., W - 1] & 1

    @property
    def memory_bytes(self) -> int:
        """Bytes of the logical stores (the paper's accounting,
        ``config.memory_bytes``), not of the lane padding."""
        cfg = self.cfg
        return (4 * self.packed.shape[0] * cfg.k * self.buckets * cfg.slots
                * cfg.entry_words)


def packed_shape(cfg: HashTableConfig, buckets: int | None = None):
    """Shape of the stored array of ``buckets`` (default ``cfg.buckets``)
    buckets, in partitions of ``cfg.local_buckets``."""
    from repro.kernels import packed as pk
    bl = cfg.local_buckets
    buckets = cfg.buckets if buckets is None else buckets
    return (cfg.replicas, buckets // bl * pk.packed_groups(bl),
            pk.packed_rows(cfg.k, cfg.slots, cfg.entry_words), pk.LANES)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QueryBatch:
    """One step's worth of queries: ``N = p * queries_per_pe`` lanes."""
    op: jnp.ndarray    # [N] int32 in {NOP, SEARCH, INSERT, DELETE}
    key: jnp.ndarray   # [N, Wk] uint32
    val: jnp.ndarray   # [N, Wv] uint32

    def tree_flatten(self):
        return (self.op, self.key, self.val), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StepResults:
    """Per-lane outcome of a step."""
    found: jnp.ndarray     # [N] bool — key present at snapshot time
    value: jnp.ndarray     # [N, Wv] uint32 — search/delete: old value
    ok: jnp.ndarray        # [N] bool — op accepted (insert: had slot; del: found)
    bucket: jnp.ndarray    # [N] uint32 — debug/routing info

    def tree_flatten(self):
        return (self.found, self.value, self.ok, self.bucket), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

def init_table(cfg: HashTableConfig, rng: jax.Array) -> XorHashTable:
    return XorHashTable(
        q_masks=make_h3_params(rng, cfg.key_words, cfg.index_bits),
        packed=jnp.zeros(packed_shape(cfg), jnp.uint32),
        cfg=cfg,
    )


# ---------------------------------------------------------------------------
# The step: p parallel queries, data-agnostic latency
# ---------------------------------------------------------------------------

@jax.jit
def apply_step(table: XorHashTable,
               batch: QueryBatch) -> Tuple[XorHashTable, StepResults]:
    """Process one step of ``N = p * queries_per_pe`` queries.

    Entirely branch-free: every lane executes the full search dataflow and the
    mutation dataflow is masked per-lane (masked lanes scatter with
    ``mode='drop'`` via an out-of-bounds bucket index).

    The dataflow itself lives in :mod:`repro.core.engine` (DESIGN.md §3):
    ``probe`` (hashing + partial-XOR read + XOR trees + result resolution)
    then ``commit`` (non-search XOR encode + masked scatter), on the backend
    selected by ``cfg.backend`` (jnp oracle or the Pallas kernels).
    """
    from repro.core.engine import step as _engine_step
    cfg = table.cfg
    N = batch.op.shape[0]
    if N != cfg.queries_per_step:
        raise ValueError(f"batch width {N} != p*qpp {cfg.queries_per_step}")
    return _engine_step(table, batch)


def run_stream(table: XorHashTable, ops: jnp.ndarray, keys: jnp.ndarray,
               vals: jnp.ndarray, backend: str | None = None,
               fused: bool | None = None
               ) -> Tuple[XorHashTable, StepResults]:
    """Stream a [T, N]-shaped query trace through the engine seam.

    ``fused=None`` routes to the resolved backend's StreamBackend
    implementation — the fused Pallas xor_stream kernel (table
    VMEM-persistent across steps, swept in VMEM-sized passes past the VMEM
    budget) on the pallas backend, the scanned per-step oracle on jnp.
    ``fused=True`` / ``False`` force one side (DESIGN.md §3.1)."""
    from repro.core.engine import run_stream as _engine_run_stream
    return _engine_run_stream(table, ops, keys, vals, backend=backend,
                              fused=fused)


def bulk_build(table: XorHashTable, keys: jnp.ndarray, vals: jnp.ndarray,
               live: jnp.ndarray | None = None, backend: str | None = None):
    """Construct an EMPTY table's state from a flat ``[n, Wk]``/``[n, Wv]``
    record batch in O(1) count-then-place sweeps instead of O(n) streamed
    insert steps — byte-identical to the serialized insert stream, with
    last-wins duplicate resolution and per-record spill reporting.  Returns
    ``(table, BulkBuildReport)``; see ``engine.bulk_build`` (DESIGN.md
    §3.2)."""
    from repro.core.engine import bulk_build as _engine_bulk_build
    return _engine_bulk_build(table, keys, vals, live=live, backend=backend)


def compact(table: XorHashTable, backend: str | None = None
            ) -> XorHashTable:
    """Rewrite a fragmented table into dense slot occupancy — the bulk-build
    sweep over the table's own live records.  Idempotent; preserves every
    live record.  See ``engine.compact`` (DESIGN.md §3.2)."""
    from repro.core.engine import compact as _engine_compact
    return _engine_compact(table, backend=backend)


def reconfigure(table: XorHashTable, new_cfg: HashTableConfig,
                backend: str | None = None,
                rng=None) -> XorHashTable:
    """Migrate a live table into a different (k, replicate_reads) geometry
    or a different (buckets, slots) capacity — record-set-exact, canonical
    compacted layout.  The lattice of legal geometry targets and the scoring
    that picks one live in ``perfmodel.plan_geometry``; capacity changes
    rehash at the new index width (``rng`` draws the extra H3 rows on
    growth); see ``engine.reconfigure`` (DESIGN.md §5, §6).
    """
    from repro.core.engine import reconfigure as _engine_reconfigure
    return _engine_reconfigure(table, new_cfg, backend=backend, rng=rng)


# ---------------------------------------------------------------------------
# Host-side router: enforce the NSQ-ratio workload contract (Definition 1)
# ---------------------------------------------------------------------------

def schedule_queries(op: np.ndarray, key: np.ndarray, val: np.ndarray,
                     cfg: HashTableConfig, return_placement: bool = False,
                     pe_of_lane=None):
    """Pack an arbitrary query trace into [T, N] step tensors.

    Preserves program order (required by the consistency model) while placing
    every NSQ on a lane whose PE id is < k.  Lane n of a step belongs to PE
    ``n % p``; a step therefore accepts at most ``k * queries_per_pe`` NSQs.
    Greedy packing: walk the trace, open a new step when either the NSQ
    capacity or the step width is exhausted.  Unused lanes become NOPs.

    The lane classes re-derive from whatever ``cfg.k`` is passed, so a table
    migrated by :func:`reconfigure` just routes subsequent traces through
    the same call with the new config.  ``pe_of_lane`` overrides the
    single-domain ``lane % p`` PE mapping for layouts that assign PEs
    differently (the sharded mesh maps ``pe = lane // n_local`` — the
    origin DEVICE); it takes the lane index and returns its PE id.
    """
    p, k, qpp = cfg.p, cfg.k, cfg.queries_per_pe
    N = cfg.queries_per_step
    pe = (lambda n: n % p) if pe_of_lane is None else pe_of_lane
    key = np.asarray(key, dtype=np.uint32).reshape(len(op), cfg.key_words)
    val = np.asarray(val, dtype=np.uint32).reshape(len(op), cfg.val_words)

    steps_op, steps_key, steps_val = [], [], []
    cur_op = np.zeros(N, np.int32)
    cur_key = np.zeros((N, cfg.key_words), np.uint32)
    cur_val = np.zeros((N, cfg.val_words), np.uint32)
    # lanes for NSQs: pe < k; lanes for searches: prefer pe >= k
    nsq_lanes = [n for n in range(N) if pe(n) < k]
    srch_lanes = [n for n in range(N) if pe(n) >= k] + nsq_lanes
    ni = si = 0

    def flush():
        nonlocal cur_op, cur_key, cur_val, ni, si
        steps_op.append(cur_op); steps_key.append(cur_key); steps_val.append(cur_val)
        cur_op = np.zeros(N, np.int32)
        cur_key = np.zeros((N, cfg.key_words), np.uint32)
        cur_val = np.zeros((N, cfg.val_words), np.uint32)
        ni = si = 0

    used = set()
    placement = []                      # (step, lane) per input query
    for t in range(len(op)):
        o = int(op[t])
        if o in (OP_INSERT, OP_DELETE):
            while True:
                if ni < len(nsq_lanes) and nsq_lanes[ni] not in used:
                    lane = nsq_lanes[ni]; ni += 1; break
                if ni >= len(nsq_lanes):
                    used.clear(); flush(); continue
                ni += 1
        else:
            while True:
                if si < len(srch_lanes) and srch_lanes[si] not in used:
                    lane = srch_lanes[si]; si += 1; break
                if si >= len(srch_lanes):
                    used.clear(); flush(); continue
                si += 1
        used.add(lane)
        placement.append((len(steps_op), lane))
        cur_op[lane] = o
        cur_key[lane] = key[t]
        cur_val[lane] = val[t]
        if len(used) == N:
            used.clear(); flush()
    if cur_op.any():
        flush()
    out = (np.stack(steps_op) if steps_op else np.zeros((0, N), np.int32),
           np.stack(steps_key) if steps_key else np.zeros((0, N, cfg.key_words), np.uint32),
           np.stack(steps_val) if steps_val else np.zeros((0, N, cfg.val_words), np.uint32))
    if return_placement:
        return out + (np.array(placement, np.int32).reshape(-1, 2),)
    return out


# The NSQ packing router under the name the geometry-planning layer uses
# (DESIGN.md §5): "pack a trace for this geometry".
pack_trace = schedule_queries
