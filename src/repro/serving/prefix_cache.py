"""KV prefix-block cache keyed by the paper's parallel hash table.

vLLM-style prefix caching mapped onto the hash table's native workload: every
decode step, ALL active request slots probe the table in one parallel batch
(hot prefixes make many probes hit the same bucket — the partitioned
baseline's worst case, and exactly where the XOR design's data-agnostic
guarantee pays off).  Admission = INSERT, reuse accounting = UPDATE (the
paper's insert/update fusion), eviction = DELETE.

Key   = 64-bit rolling content hash of (parent_key, block_tokens).
Value = (page_id, refcount) packed in two uint32 words.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH,
                        engine, init_table, pack_trace)

__all__ = ["PrefixCache", "chain_key", "PREFIX_CACHE_MIX"]

# The page table's declared workload (HashTableConfig.op_mix input): prefix
# probing is read-mostly — every decode step's lookup fan-out vs occasional
# admission inserts and LRU-eviction deletes.  Declaring it lets k="auto"
# plan the compact geometry (paper Definition 1: ~1/8 NSQ traffic needs
# ~p/8 write ports) instead of paying worst-case partial stores.
PREFIX_CACHE_MIX = (0.875, 0.1, 0.0, 0.025)

_MIX = np.uint64(0x9E3779B97F4A7C15)


def chain_key(parent: int, block_tokens: np.ndarray) -> int:
    """Rolling 64-bit hash chaining a block onto its prefix."""
    h = np.uint64(parent)
    for t in np.asarray(block_tokens, np.uint64):
        h = np.uint64(((int(h) ^ int(t)) * int(_MIX)) & 0xFFFFFFFFFFFFFFFF)
        h = np.uint64(int(h) ^ (int(h) >> 29))
    return int(h)


class PrefixCache:
    """Hash-table-backed page table for KV blocks.

    ``shards > 1`` partitions the page table's bucket axis across a device
    mesh (``core.distributed``): each device owns ``buckets/shards`` buckets
    and probes/commits ride the routed distributed stream, so the page table
    can exceed one device's memory.  Requires ``shards`` devices and
    ``p % shards == 0`` (lanes split evenly over the mesh).  ``router``
    picks the sharded exchange (DESIGN.md §2.2): the default ``"bounded"``
    two-pass router shrinks the routed width to each batch's measured
    per-owner load — admission/lookup batches are padded with NOP rows whose
    zero keys all hash to one owner, exactly the mild skew the bounded
    router absorbs without reserving skew-proof worst-case lanes.
    """

    def __init__(self, num_pages: int = 4096, block_tokens: int = 16,
                 p: int = 8, seed: int = 0, backend: str = "auto",
                 shards: int = 1, router: str = "bounded",
                 replica_groups: Optional[Tuple[int, ...]] = None,
                 plan_cache_plans: int = 16, k="auto",
                 op_mix: Optional[Tuple[float, ...]] = PREFIX_CACHE_MIX):
        buckets = 1 << max(int(np.ceil(np.log2(max(num_pages, 2) * 2))), 4)
        # under replica_groups (the 2-D hot-shard read fan-out mesh,
        # DESIGN.md §2.3 — lookup_batch is search-only, the replicated
        # sweet spot) lanes split over the replica total, not the shards
        mesh_devices = sum(replica_groups) if replica_groups else shards
        if p % mesh_devices:
            raise ValueError(f"need p % mesh_devices == 0, got p={p} "
                             f"mesh devices={mesh_devices} (shards={shards}"
                             f", replica_groups={replica_groups})")
        # k="auto" (the default): the declared read-mostly mix resolves the
        # compact write-port count via perfmodel.plan_geometry, and _run
        # routes batches through the pack_trace lane classes whenever k < p
        self.cfg = HashTableConfig(
            p=p, k=k, buckets=buckets, slots=4, key_words=2, val_words=2,
            replicate_reads=False, stagger_slots=True, backend=backend,
            shards=shards, replica_groups=replica_groups, router=router,
            op_mix=op_mix)
        # probe+commit through the pluggable query engine (DESIGN.md §3/§4);
        # multi-step batches ride the stream seam — the fused xor_stream
        # kernel on pallas-capable backends, the scanned oracle on jnp.
        # (retraces once per distinct step count T; admission/lookup batch
        # shapes repeat, so the cache stays warm)
        self._plan_cache = None
        self._qm_host = None
        if shards > 1:
            from repro.core.distributed import (init_distributed_table,
                                                make_distributed_stream,
                                                make_ht_mesh)
            self.mesh = make_ht_mesh(self.cfg.mesh_devices)
            self.table = init_distributed_table(self.cfg, jax.random.key(seed),
                                                self.mesh)
            self._stream = make_distributed_stream(self.mesh, self.cfg)
            # amortize the bounded router's per-batch measurement pass across
            # the steady stream of same-shaped admission/lookup batches: the
            # load histograms run on the HOST (serve_loop.measure_loads_host,
            # no device sync) and the frozen plan comes from the LRU
            # PlanCache, falling back to a replan when the coverage check
            # fails (DESIGN.md §4)
            if (plan_cache_plans
                    and getattr(self._stream, "router", None) == "bounded"):
                from repro.serving.serve_loop import PlanCache
                self._plan_cache = PlanCache(self.cfg,
                                             plans=plan_cache_plans,
                                             slack=self._stream.slack)
        else:
            self.table = init_table(self.cfg, jax.random.key(seed))
            self._stream = jax.jit(engine.run_stream,
                                   static_argnames=("backend", "fused"))
        self.block_tokens = block_tokens
        self.free_pages: List[int] = list(range(num_pages - 1, -1, -1))
        self.lru: Dict[int, int] = {}       # key64 -> last-touch counter
        self.clock = 0
        self.hits = 0
        self.misses = 0

    # ------------------------------------------------------------------ utils
    def _run(self, ops: np.ndarray, keys64: np.ndarray,
             vals: Optional[np.ndarray] = None):
        n = len(ops)
        N = self.cfg.queries_per_step
        if n == 0:
            return np.zeros(0, bool), np.zeros((0, 2), np.uint32)
        if vals is None:
            vals = np.zeros((n, 2), np.uint32)
        keys = np.zeros((n, 2), np.uint32)
        keys[:, 0] = (keys64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        keys[:, 1] = (keys64 >> np.uint64(32)).astype(np.uint32)
        # pack to [T, N] step tensors (pad lanes are NOPs) and run the whole
        # batch through engine.run_stream — one fused kernel launch instead
        # of one probe+commit dispatch per step on pallas-capable backends.
        # T rounds up to a power of two so fluctuating batch sizes compile
        # O(log max_T) stream programs instead of one per distinct T.
        # At the planned compact geometry (k < p) the contiguous fill would
        # put mutations on search-only lanes — port-illegal, silently
        # rejected — so those batches route through the pack_trace lane
        # classes instead, with the PE map of the actual layout (origin
        # device on a mesh) and results gathered back via the placement.
        if self.cfg.k < self.cfg.p:
            pe_of = None
            if self.cfg.mesh_devices > 1:
                n_loc = N // self.cfg.mesh_devices
                pe_of = lambda lane: lane // n_loc
            op_s, kk_s, vv_s, placement = pack_trace(
                ops, keys, vals, self.cfg, return_placement=True,
                pe_of_lane=pe_of)
            T = 1 << (max(op_s.shape[0], 1) - 1).bit_length()
            op_t = np.zeros(T * N, np.int32)
            kk_t = np.zeros((T * N, 2), np.uint32)
            vv_t = np.zeros((T * N, 2), np.uint32)
            op_t[:op_s.size] = op_s.reshape(-1)
            kk_t[:op_s.size] = kk_s.reshape(-1, 2)
            vv_t[:op_s.size] = vv_s.reshape(-1, 2)
            flat = placement[:, 0].astype(np.int64) * N + placement[:, 1]
        else:
            T = -(-n // N)
            T = 1 << (T - 1).bit_length()
            op_t = np.zeros(T * N, np.int32); op_t[:n] = ops
            kk_t = np.zeros((T * N, 2), np.uint32); kk_t[:n] = keys
            vv_t = np.zeros((T * N, 2), np.uint32); vv_t[:n] = vals
            flat = np.arange(n)
        extra = {}
        if self._plan_cache is not None:
            # host-side measurement (microseconds, no device sync) + LRU plan
            # reuse: repeat shapes/mixes skip plan_bounded_route entirely
            from repro.serving.serve_loop import (measure_loads_host,
                                                  op_mix_bucket)
            if self._qm_host is None:
                self._qm_host = np.asarray(jax.device_get(self.table.q_masks))
            loads, pair = measure_loads_host(self.cfg, self._qm_host,
                                             kk_t.reshape(T, N, 2),
                                             op_t.reshape(T, N))
            plan, _ = self._plan_cache.lookup(
                loads, pair, op_mix_bucket(op_t),
                n_local=N // self.cfg.mesh_devices)
            extra["plan"] = plan
        self.table, res = self._stream(
            self.table, jnp.array(op_t.reshape(T, N)),
            jnp.array(kk_t.reshape(T, N, 2)), jnp.array(vv_t.reshape(T, N, 2)),
            **extra)
        found = np.asarray(res.found).reshape(T * N)[flat]
        value = np.asarray(res.value).reshape(T * N, 2)[flat]
        return found, value

    # ---------------------------------------------------------------- lookup
    def lookup_batch(self, keys64: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Parallel probe for a batch of block keys -> (hit_mask, page_ids)."""
        keys64 = np.asarray(keys64, np.uint64)
        found, value = self._run(np.full(len(keys64), OP_SEARCH, np.int32),
                                 keys64)
        self.hits += int(found.sum())
        self.misses += int((~found).sum())
        self.clock += 1
        for k in keys64[found]:
            self.lru[int(k)] = self.clock
        return found, value[:, 0].astype(np.int64)

    # ----------------------------------------------------------------- admit
    def bulk_admit(self, keys64: np.ndarray) -> np.ndarray:
        """Cold-cache bulk admission: construct the whole page table in ONE
        count-then-place sweep (engine.bulk_build, DESIGN.md §3.2) instead of
        streamed INSERT rounds — the warm-start path when a serving process
        boots with a known prefix corpus.  Requires an EMPTY cache.  Page
        allocation stays host-side (pages are the inserted values, so they
        must exist before the sweep); duplicate keys share their first
        occurrence's page.  Spilled records degrade exactly like a failed
        streamed insert: the page returns to the free list and the record
        reports -1.  Returns page ids per input record (-1 == not admitted).
        """
        if self.lru:
            raise ValueError("bulk_admit requires a cold (empty) cache")
        keys64 = np.asarray(keys64, np.uint64)
        n = len(keys64)
        pages = np.full(n, -1, np.int64)
        if n == 0:
            return pages
        vals = np.zeros((n, 2), np.uint32)
        live = np.zeros(n, bool)
        page_of: Dict[int, int] = {}
        for i, k in enumerate(map(int, keys64)):
            if k in page_of or not self.free_pages:
                continue
            pg = self.free_pages.pop()
            page_of[k] = pg
            vals[i, 0], vals[i, 1] = pg, 1
            live[i] = True
        keys = np.zeros((n, 2), np.uint32)
        keys[:, 0] = (keys64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        keys[:, 1] = (keys64 >> np.uint64(32)).astype(np.uint32)
        if self.cfg.shards > 1:
            from repro.core.distributed import make_distributed_bulk_build
            N = self.cfg.queries_per_step
            T = -(-n // N)
            kk = np.zeros((T * N, 2), np.uint32); kk[:n] = keys
            vv = np.zeros((T * N, 2), np.uint32); vv[:n] = vals
            lv = np.zeros(T * N, bool); lv[:n] = live
            build = make_distributed_bulk_build(self.mesh, self.cfg)
            self.table, report = build(
                self.table, jnp.array(kk.reshape(T, N, 2)),
                jnp.array(vv.reshape(T, N, 2)),
                jnp.array(lv.reshape(T, N)))
            spilled = np.asarray(report.spilled).reshape(T * N)[:n]
        else:
            from repro.core import bulk_build
            self.table, report = bulk_build(self.table, keys, vals,
                                            live=jnp.array(live))
            spilled = np.asarray(report.spilled)
        for i in np.nonzero(live & spilled)[0]:
            self.free_pages.append(int(page_of.pop(int(keys64[i]))))
        self.clock += 1
        for k, pg in page_of.items():
            self.lru[k] = self.clock
        resident = np.array([page_of.get(int(k), -1) for k in keys64],
                            np.int64)
        return resident

    def admit_batch(self, keys64: np.ndarray) -> np.ndarray:
        """Insert blocks, allocating pages (evicting LRU if needed).
        Returns page ids (-1 when allocation failed)."""
        keys64 = np.asarray(keys64, np.uint64)
        pages = np.full(len(keys64), -1, np.int64)
        vals = np.zeros((len(keys64), 2), np.uint32)
        todo = []

        def flush():
            # pending admits must hit the table before an eviction may need
            # to delete one of them
            if todo:
                idx = np.array(todo)
                self._run(np.full(len(idx), OP_INSERT, np.int32),
                          keys64[idx], vals[idx])
                todo.clear()

        for i, k in enumerate(keys64):
            if not self.free_pages:
                flush()
                self._evict_one()
            if self.free_pages:
                pg = self.free_pages.pop()
                pages[i] = pg
                vals[i, 0] = pg
                vals[i, 1] = 1
                todo.append(i)
                self.clock += 1          # fresh admits must outrank old LRU
                self.lru[int(k)] = self.clock
        flush()
        return pages

    def _evict_one(self):
        if not self.lru:
            return
        victim = min(self.lru, key=self.lru.get)
        del self.lru[victim]
        found, value = self._run(np.array([OP_SEARCH], np.int32),
                                 np.array([victim], np.uint64))
        if found[0]:
            self.free_pages.append(int(value[0, 0]))
            self._run(np.array([OP_DELETE], np.int32),
                      np.array([victim], np.uint64))

    # ------------------------------------------------------------------ stats
    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0
