"""Batched decode engine with hash-table prefix caching, and the table's own
continuous-batching serve loop (``TableServer``).

Continuous-batching-lite (``Engine``): a fixed pool of decode slots; finished
requests are replaced from the queue; every step runs ONE jitted decode for
the whole pool.  Prefix reuse: prompts are split into blocks; block keys
chain-hash the prefix; cached blocks (hash-table hits) skip prefill
recomputation — per-request prefill work is proportional to the *novel*
suffix only.

``TableServer`` is the steady-state admission loop for the hash table itself
(DESIGN.md §4): arriving S/I/U/D requests are packed into fixed ``[T, N]``
NOP-padded slabs (recompile-free by construction — serve_loop.SlabQueue), the
bounded router's per-slab measurement pass is amortized through an LRU plan
cache with a coverage-check fallback (serve_loop.PlanCache), and dispatch is
double-buffered: slab *k+1* is packed, measured and planned on the host while
slab *k*'s fused stream is still executing on the device — the host only
``block_until_ready``s the slab leaving a two-deep in-flight window, so the
device queue never drains between slabs.

This is the serving-side integration of the paper (DESIGN.md §4); the engine
itself stays deliberately simple (greedy sampling, single host) — the
interesting part is the table in the loop.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.config import GrowthPolicy
from repro.core.hash_table import OP_DELETE, OP_INSERT
from repro.models.lm import init_cache, lm_decode_step, lm_prefill
from repro.models.model_config import ModelConfig
from repro.models.stack import cache_batch_slice, cache_batch_update
from repro.serving.prefix_cache import PrefixCache, chain_key
from repro.serving.serve_loop import (PlanCache, SlabQueue, SlabRequest,
                                      measure_loads_host, op_mix_bucket)

__all__ = ["Request", "ServeConfig", "StepReport", "Engine", "TableServer"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cached_blocks: int = 0              # prefix blocks served from cache


@dataclasses.dataclass
class ServeConfig:
    slots: int = 4
    s_max: int = 256
    block_tokens: int = 16
    eos_token: int = -1                 # -1: run to max_new_tokens
    cache_shards: int = 1               # bucket-shard the prefix-cache page
                                        # table across this many devices
                                        # (PrefixCache(shards=); 1 == local)
    cache_router: str = "bounded"       # sharded page-table exchange policy
                                        # (PrefixCache(router=); DESIGN.md
                                        # §2.2): "bounded" two-pass width or
                                        # the "skewproof" worst-case width
    cache_replica_groups: Optional[Tuple[int, ...]] = None
                                        # per-shard replica degrees for the
                                        # 2-D (shard x replica) page-table
                                        # mesh (PrefixCache(replica_groups=);
                                        # DESIGN.md §2.3) — read-mostly
                                        # prefix probing is exactly the
                                        # workload hot-shard read fan-out
                                        # pays off on.  None == 1-D
    # ---- TableServer / steady-state admission loop (DESIGN.md §4) ----
    slab_steps: int = 4                 # T: step rows per packed slab — every
                                        # dispatch sees the same [T, N] shape
    queue_requests: int = 0             # admission-queue depth bound
                                        # (submit raises beyond; 0 = unbounded)
    plan_cache_plans: int = 16          # LRU router-plan cache entries
                                        # (PlanCache; 0 disables — every slab
                                        # replans, the cold-plan A/B).  Only
                                        # engages when the stream's router is
                                        # "bounded" (cache_router interplay:
                                        # "skewproof" has nothing to plan)
    serve_double_buffer: Optional[bool] = None
                                        # two-deep in-flight dispatch window:
                                        # True forces it, False retires each
                                        # slab before dispatching the next,
                                        # None (auto) engages it only when
                                        # the host has a spare hardware
                                        # thread — on a 1-CPU host the
                                        # "overlapped" host work just
                                        # contends with the in-flight slab's
                                        # compute for the same core, so the
                                        # window degrades to synchronous
                                        # dispatch
    # ---- op-mix-adaptive geometry (DESIGN.md §5) ----
    geometry_replan: bool = True        # re-run perfmodel.plan_geometry on
                                        # the accumulated served op mix at
                                        # slab boundaries (the plan is always
                                        # reported in stats(); migration
                                        # additionally needs the hysteresis
                                        # and a single-domain table)
    geometry_hysteresis: float = 1.1    # migrate only when the planned
                                        # geometry's modeled MOPS >= this
                                        # factor x the current geometry's —
                                        # keeps a drifting mix from thrashing
                                        # reconfigure back and forth
    geometry_min_slabs: int = 2         # served slabs before the first
                                        # replan: one slab's mix is noise
    geometry_vmem_budget: Optional[int] = None
                                        # VMEM budget handed to plan_geometry
                                        # (None == the kernel dispatch's
                                        # VMEM_TABLE_BUDGET_BYTES); benchmarks
                                        # scale it down to measure the
                                        # blocked->resident crossing on
                                        # CPU-sized tables
    # ---- online growth (DESIGN.md §6) ----
    growth: Optional[GrowthPolicy] = None
                                        # when set, the server watches its
                                        # live-record count at slab
                                        # boundaries and opens an online
                                        # resize once the load factor
                                        # reaches the policy trigger;
                                        # migration slabs interleave with
                                        # the dispatch window and the served
                                        # results stay bit-exact with a
                                        # born-at-final-capacity twin.  None
                                        # keeps capacity fixed at init


@dataclasses.dataclass
class StepReport:
    """What one serve-loop step did: the requests it finished plus the
    occupancy the caller's termination condition needs (``run()`` stops on
    ``quiescent`` instead of sweeping once more to discover emptiness)."""
    finished: List
    queued: int                         # requests still waiting for admission
    occupied: int                       # slots / in-flight slabs still live
    resizing: bool = False              # an online resize window still open

    @property
    def quiescent(self) -> bool:
        return self.queued == 0 and self.occupied == 0 and not self.resizing


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.prefix_cache = PrefixCache(block_tokens=scfg.block_tokens,
                                        shards=scfg.cache_shards,
                                        router=scfg.cache_router,
                                        replica_groups=scfg.cache_replica_groups,
                                        plan_cache_plans=scfg.plan_cache_plans)
        self._closed = False
        self.queue: List[Request] = []
        self.slots: List[Optional[Request]] = [None] * scfg.slots
        self.pos = np.zeros(scfg.slots, np.int32)
        cache, _ = init_cache(cfg, scfg.slots, scfg.s_max)
        self.kv = cache
        self._decode = jax.jit(
            lambda p, c, t, pos: lm_decode_step(p, cfg, c, t, pos))
        self._prefill1 = jax.jit(
            lambda p, c, toks: lm_prefill(p, cfg, {"tokens": toks}, c))

    def submit(self, req: Request) -> None:
        if self._closed:
            raise RuntimeError("Engine.run() already drained this engine; a "
                               "request submitted now would be silently "
                               "stranded — submit before run()")
        self.queue.append(req)

    # ------------------------------------------------------------------ admit
    def _admit(self, slot: int, req: Request) -> None:
        prompt = np.asarray(req.prompt, np.int32)
        bt = self.scfg.block_tokens
        # chain block keys; count cached prefix blocks (hash-table probes)
        nb = len(prompt) // bt
        keys, parent = [], 0
        for b in range(nb):
            parent = chain_key(parent, prompt[b * bt:(b + 1) * bt])
            keys.append(parent)
        if keys:
            hit, _ = self.prefix_cache.lookup_batch(np.array(keys, np.uint64))
            n_cached = int(np.cumprod(hit).sum()) if len(hit) else 0
            miss_keys = np.array(keys, np.uint64)[~hit]
            if len(miss_keys):
                self.prefix_cache.admit_batch(miss_keys)
        else:
            n_cached = 0
        req.cached_blocks = n_cached
        # single-sequence prefill into slot's cache rows.  (For simplicity we
        # prefill the full prompt; cached blocks are accounted for in stats —
        # per-slot KV reuse across requests needs paged KV, see DESIGN.md.)
        slot_cache = cache_batch_slice(self.kv, slot, 1)
        logits, slot_cache = self._prefill1(self.params, slot_cache,
                                            jnp.array(prompt[None]))
        self.kv = cache_batch_update(self.kv, slot_cache, slot)
        nxt = int(jnp.argmax(logits[0, -1]))
        req.out_tokens.append(nxt)
        self.pos[slot] = len(prompt)
        self.slots[slot] = req

    def _report(self, finished: List[Request]) -> StepReport:
        return StepReport(finished=finished, queued=len(self.queue),
                          occupied=sum(s is not None for s in self.slots))

    # ------------------------------------------------------------------- step
    def step(self) -> StepReport:
        """Admit + one batched decode step.  Returns a :class:`StepReport`
        carrying the requests that finished (and freed their slot) this step
        plus the queue/slot occupancy ``run()`` terminates on."""
        for i in range(len(self.slots)):
            if self.slots[i] is None and self.queue:
                self._admit(i, self.queue.pop(0))
        active = [i for i, r in enumerate(self.slots) if r is not None]
        finished: List[Request] = []
        if not active:
            return self._report(finished)
        toks = np.zeros((self.scfg.slots, 1), np.int32)
        for i in active:
            toks[i, 0] = self.slots[i].out_tokens[-1]
        # single shared position frontier: pos per slot varies; decode uses the
        # max and per-slot masks would be the production path — here we step
        # slots at equal pos by construction (same-length demo prompts) or pad.
        pos = int(self.pos[active].max())
        logits, self.kv = self._decode(self.params, self.kv,
                                       jnp.array(toks), pos)
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for i in active:
            r = self.slots[i]
            r.out_tokens.append(int(nxt[i]))
            self.pos[i] += 1
            if (len(r.out_tokens) >= r.max_new_tokens
                    or int(nxt[i]) == self.scfg.eos_token):
                r.done = True
                self.slots[i] = None
                finished.append(r)
        return self._report(finished)

    def run(self) -> List[Request]:
        """Drain the queue and every occupied slot; returns the requests that
        actually finished during this call — including ones already sitting
        in slots when ``run()`` was invoked, which a queue snapshot misses.
        Terminates on the :class:`StepReport` occupancy of the step that
        drained the last request — no extra empty sweep — and closes the
        engine: a later ``submit`` raises instead of stranding its request.
        """
        finished: List[Request] = []
        report = self._report([])
        while not report.quiescent:
            report = self.step()
            finished.extend(report.finished)
        self._closed = True
        return finished


# ---------------------------------------------------------------------------
# TableServer: the hash table's own continuous-batching serve loop
# ---------------------------------------------------------------------------


class _EngineResize:
    """Single-domain resize driver: adapts the ``engine`` seam
    (begin_resize / run_stream_resize / migrate_slab / finish_resize) to the
    begin/stream/migrate/finish interface
    ``distributed.DistributedResize`` exposes, so ``TableServer`` drives
    both through one code path."""

    def __init__(self, new_buckets: int):
        self._new_buckets = new_buckets

    def begin(self, table, rng=None):
        from repro.core import engine as _core_engine
        return _core_engine.begin_resize(table, self._new_buckets, rng=rng)

    @staticmethod
    def stream(state, ops, keys, vals):
        from repro.core import engine as _core_engine
        # linear use: the serve loop rebinds its state every dispatch and
        # never reads the stale one, so donate the table buffers (a full
        # pred+succ copy per step would dominate the resize window)
        return _core_engine.run_stream_resize(state, ops, keys, vals,
                                              donate=True)

    @staticmethod
    def migrate(state, n_buckets):
        from repro.core import engine as _core_engine
        return _core_engine.migrate_slab(state, n_buckets)

    @staticmethod
    def finish(state):
        from repro.core import engine as _core_engine
        return _core_engine.finish_resize(state)


class TableServer:
    """Steady-state admission loop over the hash-table stream seam.

    ``stream`` is any ``f(table, ops, keys, vals) -> (table, results)`` over
    ``[T, N]`` step tensors — the jitted ``engine.run_stream`` (single
    domain) or a ``make_distributed_stream`` callable (sharded/replicated).
    When the stream is the bounded-router host wrapper (feature-detected via
    its ``.router``/``.dispatch`` attributes), the serve loop takes over its
    measurement pass: slab loads are histogrammed on the HOST from the
    still-host-resident query arrays (serve_loop.measure_loads_host — no
    device sync, so it overlaps in-flight device work for free), resolved
    through the LRU plan cache, and the frozen plan is handed to
    ``stream.dispatch``.  On a cache hit the per-slab planning cost is a
    numpy histogram plus a dict probe; ``plan.covers`` misses fall back to a
    replan (DESIGN.md §4).

    Dispatch is double-buffered (``scfg.serve_double_buffer``): ``step()``
    dispatches slab *k* and then blocks only on slabs beyond a two-deep
    in-flight window, so slab *k-1* streams on the device while slab *k* is
    packed, measured and planned on the host.  The default (``None``) is
    adaptive: the window engages only when the host has more than one
    hardware thread — on a 1-CPU host the "overlapped" host work merely
    contends with the in-flight slab's compute for the same core, so the
    loop degrades to synchronous dispatch (``window`` reports the effective
    depth).  Retirement order is dispatch order (``jax.block_until_ready``
    on the oldest in-flight slab), so per-request results and completion
    times are exact.

    The loop never reorders lanes: slabs pack in arrival order and the
    table state chains through dispatches, so the served results are
    bit-exact with running the identical concatenated trace through the
    one-shot path (tests/test_serve_loop.py).

    **Online growth** (``scfg.growth``, DESIGN.md §6): retirement tracks
    the live-record count (accepted first-time inserts minus accepted
    deletes), and once the load factor reaches the policy trigger at a slab
    boundary the server opens an online resize — dispatch switches to the
    dual-table watermark stream, one migration slab
    (``growth.migrate_buckets_per_slab`` predecessor buckets) runs between
    consecutive dispatches on the chained table value, and when the
    watermark closes the successor swaps in.  All of it is invisible to
    retirement order (the in-flight window and span scatter are untouched)
    and the retired results are bit-exact with a twin server born at the
    final capacity (tests/test_resize.py).  The trigger/target gap in
    ``GrowthPolicy`` is the growth hysteresis.  ``stream_factory`` rebuilds
    the stream for the growing config after a swap (required when the
    stream closure bakes the config — every ``make_distributed_stream``
    wrapper does; the default keeps the existing stream, which is correct
    for plain ``engine.run_stream``); ``resize_factory(cfg, new_buckets)``
    builds the resize driver (default: the single-domain engine seam; a
    sharded mesh passes ``lambda cfg, nb:
    make_distributed_resize(mesh, cfg, nb)``).
    """

    def __init__(self, cfg, table, stream, scfg: Optional[ServeConfig] = None,
                 *, stream_factory=None, resize_factory=None, rng=None):
        self.cfg = cfg
        self.scfg = scfg or ServeConfig()
        self.table = table
        self._stream = stream
        self._stream_factory = stream_factory
        self._resize_factory = resize_factory
        self._rng = jax.random.PRNGKey(0x5e51e) if rng is None else rng
        self._queue = SlabQueue(self.scfg.slab_steps, cfg.queries_per_step,
                                cfg.key_words, cfg.val_words,
                                max_requests=self.scfg.queue_requests,
                                nsq_lanes=self._nsq_mask(cfg))
        self._bounded = getattr(stream, "router", None) == "bounded"
        self.plan_cache = (
            PlanCache(cfg, plans=self.scfg.plan_cache_plans,
                      slack=stream.slack)
            if self._bounded else None)
        dbl = self.scfg.serve_double_buffer
        if dbl is None:                 # auto: overlap needs a host core of
            dbl = (os.cpu_count() or 1) > 1     # its own to be a win
        self._window = 2 if dbl else 1
        self._inflight = collections.deque()    # (seq, slab, results)
        self._qm_host: Optional[np.ndarray] = None
        self._next_rid = 0
        self._closed = False
        self.slabs = 0
        self.live_lanes = 0
        self.pad_lanes = 0
        # op-mix-adaptive geometry (DESIGN.md §5): accumulated S/I/U/D
        # histogram of served (live) lanes, the latest geometry plan drawn
        # from it, and per-dest routed loads for the would-be replication plan
        self._op_counts = np.zeros(4, np.int64)
        self._dest_loads: Optional[np.ndarray] = None
        self.geometry_plan = None
        self.migrations = 0
        # online growth (DESIGN.md §6): retirement-tracked occupancy, the
        # open resize driver + state (None when capacity is steady)
        self.live_records = 0
        self._resize = None
        self._resize_state = None
        self.resizes = 0

    @staticmethod
    def _nsq_mask(cfg) -> Optional[np.ndarray]:
        """Lane-class mask for the slab packer at this geometry: None at
        k == p (every lane is NSQ-capable — contiguous packing), else the
        lanes whose PE id is < k.  Single domain maps ``pe = lane % p``;
        the sharded mesh maps ``pe = lane // n_local`` (the origin DEVICE,
        the mapping the distributed mutation-legality check uses)."""
        if cfg.k >= cfg.p:
            return None
        n = np.arange(cfg.queries_per_step)
        if cfg.mesh_devices > 1:
            pe = n // (cfg.queries_per_step // cfg.mesh_devices)
        else:
            pe = n % cfg.p
        return pe < cfg.k

    # ---------------------------------------------------------------- submit
    def submit(self, ops, keys, vals=None) -> SlabRequest:
        """Queue a flat request of ``n`` lanes (``ops [n]``, ``keys [n, Wk]``,
        ``vals [n, Wv]`` — vals default to zeros for read-only traffic).
        Returns the :class:`SlabRequest` whose result arrays fill as its
        slabs retire."""
        if tracing.enabled:             # the per-request site: one flag test
            with tracing.span("kv.submit"):
                return self._submit(ops, keys, vals)
        return self._submit(ops, keys, vals)

    def _submit(self, ops, keys, vals) -> SlabRequest:
        if self._closed:
            raise RuntimeError("TableServer.run() already drained this "
                               "server; a request submitted now would be "
                               "silently stranded — submit before run()")
        ops = np.ascontiguousarray(np.asarray(ops, np.int32).reshape(-1))
        n = len(ops)
        keys = np.ascontiguousarray(
            np.asarray(keys, np.uint32).reshape(n, self.cfg.key_words))
        if vals is None:
            vals = np.zeros((n, self.cfg.val_words), np.uint32)
        vals = np.ascontiguousarray(
            np.asarray(vals, np.uint32).reshape(n, self.cfg.val_words))
        req = SlabRequest(rid=self._next_rid, ops=ops, keys=keys, vals=vals,
                          submit_s=time.perf_counter())
        self._next_rid += 1
        self._queue.submit(req)
        return req

    # -------------------------------------------------------------- dispatch
    def _resolve_plan(self, slab):
        if self.plan_cache is None:
            return None
        if self._qm_host is None:
            self._qm_host = np.asarray(jax.device_get(self.table.q_masks))
        loads, pair = measure_loads_host(self.cfg, self._qm_host, slab.keys,
                                         slab.ops)
        # accumulate per-dest routed load for the would-be replication plan
        dest = np.asarray(loads).sum(axis=0)
        self._dest_loads = (dest if self._dest_loads is None
                            else self._dest_loads + dest)
        plan, _ = self.plan_cache.lookup(
            loads, pair, op_mix_bucket(slab.ops),
            n_local=slab.keys.shape[1] // self.cfg.mesh_devices)
        return plan

    def _dispatch(self, slab) -> None:
        args = (jnp.asarray(slab.ops), jnp.asarray(slab.keys),
                jnp.asarray(slab.vals))
        if self._resize_state is not None:
            # resize window: the dual-table watermark stream, bypassing the
            # plan cache (the bounded widths are measured against one table;
            # the resize stream runs skew-proof for its short window)
            self._resize_state, res = self._resize.stream(
                self._resize_state, *args)
        elif self._bounded:
            plan = self._resolve_plan(slab)
            if plan is not None:
                self.table, res = self._stream.dispatch(self.table, *args,
                                                        plan)
            else:        # plan cache disabled: the wrapper measures per call
                self.table, res = self._stream(self.table, *args)
        else:
            self.table, res = self._stream(self.table, *args)
        self._inflight.append((self.slabs, slab, res))
        self.slabs += 1
        self.live_lanes += slab.live
        self.pad_lanes += slab.ops.size - slab.live
        ops = slab.ops.reshape(-1)
        self._op_counts += np.bincount(ops[ops > 0], minlength=4)

    def _retire_one(self) -> List[SlabRequest]:
        seq, slab, res = self._inflight.popleft()
        with tracing.span("kv.retire", slab=seq):
            with tracing.span("kv.retire.wait", slab=seq):
                jax.block_until_ready(res)
            T, N = slab.ops.shape
            found = np.asarray(res.found).reshape(T * N)
            ok = np.asarray(res.ok).reshape(T * N)
            value = np.asarray(res.value).reshape(T * N, -1)
            # occupancy tracking for the growth trigger, on the PHYSICAL
            # layout (slab.ops is physical; the perm gather below reorders
            # results to logical).  Counts accepted first-time inserts minus
            # accepted deletes — same-step duplicate inserts of one new key
            # each count (both probed the pre-step snapshot), so this can
            # overcount under duplicate-heavy ingest: fine for a grow
            # trigger, which only needs to err toward growing earlier
            ops_phys = slab.ops.reshape(T * N)
            self.live_records += int(((ops_phys == OP_INSERT) & ok
                                      & ~found).sum())
            self.live_records -= int(((ops_phys == OP_DELETE) & ok).sum())
            if slab.perm is not None:   # NSQ-aware packing: logical -> phys
                found = found[slab.perm]
                ok = ok[slab.perm]
                value = value[slab.perm]
            finished, now = [], time.perf_counter()
            for req, r_off, f_off, cnt in slab.spans:
                req.found[r_off:r_off + cnt] = found[f_off:f_off + cnt]
                req.ok[r_off:r_off + cnt] = ok[f_off:f_off + cnt]
                req.value[r_off:r_off + cnt] = value[f_off:f_off + cnt]
                req.lanes_done += cnt
                if req.done:
                    req.done_s = now
                    finished.append(req)
            return finished

    # ------------------------------------------------- geometry replanning
    @property
    def served_mix(self):
        """The accumulated served op mix as a ``perfmodel.OpMix`` (the
        50:50 default until any live lane has been served)."""
        from repro.core.perfmodel import OpMix
        c = self._op_counts
        if c.sum() == 0:
            return OpMix()
        return OpMix.from_counts(search=int(c[1]), insert=int(c[2]),
                                 delete=int(c[3]))

    def _maybe_replan(self) -> None:
        """Slab-boundary geometry replan (DESIGN.md §5): score the lattice
        against the accumulated served mix, record the plan for stats, and
        migrate the live table through ``engine.reconfigure`` when (a) the
        table is single-domain (a sharded stream's exchange shapes are baked
        into its jitted wrapper, so mesh migration stays report-only), and
        (b) the plan clears the hysteresis margin.  Runs between dispatches
        — never mid-slab — and the table value chains functionally through
        the in-flight window, so no drain or sync is needed."""
        from repro.core import engine as _core_engine
        from repro.core.perfmodel import plan_geometry
        if not self.scfg.geometry_replan:
            return
        if self.slabs < self.scfg.geometry_min_slabs:
            return
        with tracing.span("kv.migrate"):
            plan = plan_geometry(self.cfg, self.served_mix,
                                 vmem_budget=self.scfg.geometry_vmem_budget)
            self.geometry_plan = plan
            if (self.cfg.mesh_devices > 1 or not plan.changed
                    or plan.improvement < self.scfg.geometry_hysteresis
                    or self._resize_state is not None):
                return
            new_cfg = plan.apply(self.cfg)
            self.table = _core_engine.reconfigure(self.table, new_cfg)
            self.cfg = new_cfg
            self._queue.set_nsq_lanes(self._nsq_mask(new_cfg))
            if self.plan_cache is not None:     # routed widths keyed on old k
                self.plan_cache = PlanCache(new_cfg,
                                            plans=self.scfg.plan_cache_plans,
                                            slack=self.plan_cache.slack)
            self.migrations += 1

    # ------------------------------------------------------- online growth
    def _maybe_grow(self) -> None:
        """Slab-boundary growth trigger (DESIGN.md §6): open an online
        resize once the retirement-tracked load factor reaches the policy
        trigger.  The trigger/target gap in :class:`GrowthPolicy` is the
        hysteresis — after a grow the table sits well below the trigger."""
        pol = self.scfg.growth
        if pol is None or self._resize_state is not None:
            return
        if self.live_records < (pol.grow_load_factor
                                * self.cfg.buckets * self.cfg.slots):
            return
        new_buckets = pol.target_buckets(self.cfg, self.live_records)
        if self._resize_factory is not None:
            self._resize = self._resize_factory(self.cfg, new_buckets)
        elif self.cfg.mesh_devices > 1:
            raise RuntimeError(
                "growing a sharded TableServer needs resize_factory= (e.g. "
                "lambda cfg, nb: make_distributed_resize(mesh, cfg, nb)) — "
                "the default driver is the single-domain engine seam")
        else:
            self._resize = _EngineResize(new_buckets)
        self._rng, sub = jax.random.split(self._rng)
        self._resize_state = self._resize.begin(self.table, sub)

    def _advance_resize(self) -> None:
        """One background migration slab between dispatches, on the chained
        table value; on watermark close, swap the successor in — rebuilding
        the stream (config-baking closures), q_masks mirror and plan cache
        for the new capacity."""
        if self._resize_state is None:
            return
        with tracing.span("kv.migrate"):
            self._resize_state = self._resize.migrate(
                self._resize_state, self.scfg.growth.migrate_buckets_per_slab)
            if not self._resize_state.done:
                return
            self.table = self._resize.finish(self._resize_state)
            self._resize_state = None
            self._resize = None
            self.cfg = self.table.cfg
            self._qm_host = None            # host mirror of the OLD q_masks
            if self._stream_factory is not None:
                self._stream = self._stream_factory(self.cfg)
                self._bounded = (getattr(self._stream, "router", None)
                                 == "bounded")
            if self._bounded:               # cached widths measured at old B
                slack = getattr(self._stream, "slack",
                                None if self.plan_cache is None
                                else self.plan_cache.slack)
                self.plan_cache = PlanCache(self.cfg,
                                            plans=self.scfg.plan_cache_plans,
                                            slack=slack)
            self.resizes += 1

    # ------------------------------------------------------------------ step
    def step(self) -> StepReport:
        """Pack + dispatch at most one slab, then retire anything past the
        in-flight window (all of it once the queue is quiescent).  Returns
        the :class:`StepReport` ``run()`` terminates on."""
        with tracing.span("kv.step"):
            finished: List[SlabRequest] = []
            if self._queue.pending_requests:
                with tracing.span("kv.pack", slab=self.slabs):
                    slab = self._queue.next_slab()
                with tracing.span("kv.dispatch", slab=self.slabs):
                    self._dispatch(slab)
                self._advance_resize()      # one migration slab per dispatch
                self._maybe_replan()
            # double-buffer discipline: block only on slabs leaving the window,
            # so the newest dispatch keeps executing while the host packs on
            while len(self._inflight) >= self._window:
                finished.extend(self._retire_one())
            if not self._queue.pending_requests:
                while self._inflight:               # quiescent queue: drain
                    finished.extend(self._retire_one())
                # idle: ONE background slab, never a drain-it-all loop — a
                # request arriving mid-drain would eat the very stop-the-world
                # stall the watermark walk exists to avoid.  The report says
                # ``resizing`` so run() keeps stepping until the walk closes.
                self._advance_resize()
            self._maybe_grow()
            return StepReport(finished=finished,
                              queued=self._queue.pending_requests,
                              occupied=len(self._inflight),
                              resizing=self._resize_state is not None)

    # ------------------------------------------------------------------- run
    def run(self) -> List[SlabRequest]:
        """Serve until quiescent (no queued requests, no in-flight slabs) —
        the termination comes from ``step()``'s occupancy report, not an
        extra empty sweep — then close the server (``submit`` raises after).
        Returns every request finished during the call, in retire order."""
        finished: List[SlabRequest] = []
        report = StepReport(finished=[], queued=self._queue.pending_requests,
                            occupied=len(self._inflight),
                            resizing=self._resize_state is not None)
        while not report.quiescent:
            report = self.step()
            finished.extend(report.finished)
        self._closed = True
        return finished

    # ------------------------------------------------------------------ stats
    @property
    def window(self) -> int:
        """Effective in-flight window depth (2 = double-buffered)."""
        return self._window

    @property
    def pad_fraction(self) -> float:
        tot = self.live_lanes + self.pad_lanes
        return self.pad_lanes / tot if tot else 0.0

    def replication_plan(self) -> Optional[Tuple[int, ...]]:
        """The would-be per-shard replica degrees ``engine.plan_replication``
        picks from the accumulated slab load histograms (None until any
        bounded sharded slab has been measured).  Report-only: replication
        migration itself stays offline — the degrees change the mesh's
        device count, which a live table cannot do."""
        from repro.core import engine as _core_engine
        if self._dest_loads is None or self.cfg.shards < 2:
            return None
        if self.cfg.replica_groups is not None:
            # grouped histograms count per-DEVICE copies: fold each shard's
            # group back onto the shard before planning new degrees
            shard_of = np.asarray(
                jax.device_get(_core_engine.replica_layout(self.cfg)[0]))
            loads = np.zeros(self.cfg.shards, np.int64)
            np.add.at(loads, shard_of, self._dest_loads.astype(np.int64))
        else:
            loads = self._dest_loads
        return _core_engine.plan_replication(self.cfg, [int(x) for x in loads],
                                             self.cfg.mesh_devices)

    def stats(self) -> Dict[str, Any]:
        """Serve-loop counters + the op-mix-adaptive geometry state: the
        accumulated served mix, the latest ``GeometryPlan`` (with migration
        count), and the would-be replication plan for sharded tables."""
        mix = self.served_mix
        plan = self.geometry_plan
        out = {
            "slabs": self.slabs,
            "live_lanes": self.live_lanes,
            "pad_lanes": self.pad_lanes,
            "pad_fraction": self.pad_fraction,
            "window": self.window,
            "op_mix": mix.as_tuple(),
            "nsq_fraction": mix.nsq_fraction,
            "migrations": self.migrations,
            "live_records": self.live_records,
            "load_factor": (self.live_records
                            / (self.cfg.buckets * self.cfg.slots)),
            "resizes": self.resizes,
            "resize_progress": (None if self._resize_state is None
                                else self._resize_state.progress),
            "geometry": None if plan is None else {
                "k": plan.k,
                "replicate_reads": plan.replicate_reads,
                "table_bytes": plan.table_bytes,
                "replica_bytes": plan.replica_bytes,
                "fits_vmem": plan.fits_vmem,
                "modeled_mops": plan.modeled_mops,
                "baseline_mops": plan.baseline_mops,
                "improvement": plan.improvement,
                "memory_saving": plan.memory_saving,
                "changed": plan.changed,
            },
            "replication_plan": self.replication_plan(),
        }
        if self.plan_cache is not None:
            out["plan_cache"] = self.plan_cache.stats()
        return out
