"""Analytic performance models: FPGA cycle model (paper Figs 5-10) and the
TPU roofline for the hash-table step (DESIGN.md §2).

FPGA model (calibrated to the paper's U250 numbers):
  * search latency  = t0 cycles (hash + partial-XOR read + resolution);
    paper: 14 ns at 370 MHz with 16 PEs  ->  t0 ≈ 5 cycles.
  * insert latency  = t0_w + p cycles (search dataflow + p-cycle inter-PE
    propagation); paper: 54 ns at 370 MHz -> t0_w ≈ 4, p = 16.
  * throughput      = p * fclk  (data-agnostic: never stalls).
  * partitioned baseline throughput = p * fclk / E[max partition load / mean]
    (serializes within partitions; worst case p-x slower).

TPU model (v5e constants, used by benchmarks/roofline):
  The hash-table step is integer/VPU + gather dominated -> memory-bound.
  bytes/step = N * (k*S*entry_bytes [gather reads] + entry_bytes [scatter])
  steady-state MOPS ≈ N / (bytes_per_step / BW_effective).

Fused-stream model (:func:`stream_modeled_mops`): adds a commit-cost term
(serial scalar chain vs the supersession-masked vectorized commit) and the
blocked-regime term (the per-stream table sweep over HBM) so benchmarks/roofline.py can report
measured-vs-modeled for every BENCH_stream.json column.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

from repro.core.config import HashTableConfig, memory_bytes

__all__ = [
    "TPUSpec", "V5E", "FPGA_U250", "FpgaSpec",
    "OpMix", "MIX_DEFAULT", "as_mix",
    "GeometryPlan", "plan_geometry", "geometry_modeled_mops",
    "fpga_latency_ns", "fpga_throughput_mops", "table_step_bytes",
    "tpu_modeled_mops", "stream_commit_seconds", "stream_modeled_mops",
    "routed_width_lanes", "routed_exchange_bytes",
    "sharded_stream_modeled_mops",
    "replica_copy_factor", "replicated_read_mops",
    "serve_plan_seconds", "serve_loop_modeled",
    "bulk_build_seconds", "bulk_build_modeled_mops",
    "RESIZE_STREAM_FACTOR", "resize_migration_seconds",
    "resize_total_seconds",
]


# ---------------------------------------------------------------------------
# OpMix: the single definition of a workload's search:NSQ composition.
# Every model term that used to take a bare ``nsq_fraction`` float takes a
# mix (floats still coerce via :func:`as_mix`, so call sites that only know
# an NSQ fraction keep working); ``plan_geometry`` sizes the XOR memory
# from it (paper Definition 1 / §V: fewer NSQ-capable PEs -> fewer partial
# stores and fewer read replicas).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpMix:
    """Fractions of search/insert/update/delete in a query stream.

    Normalized to sum 1 at construction (an all-zero mix degenerates to
    pure search).  ``update`` exists for declared mixes; measured traces
    fold updates into ``insert`` (the paper's fused Insert/Update — one op
    code, ``OP_INSERT``).  ``nsq_fraction`` — the paper's non-search-query
    fraction, the only number the roofline terms consume — is derived, so
    there is exactly one definition of the mix.
    """
    search: float = 0.5
    insert: float = 0.5
    update: float = 0.0
    delete: float = 0.0

    def __post_init__(self):
        parts = (self.search, self.insert, self.update, self.delete)
        if any(f < 0 for f in parts):
            raise ValueError(f"op-mix fractions must be nonnegative, "
                             f"got {parts}")
        tot = float(sum(parts))
        if tot <= 0.0:
            object.__setattr__(self, "search", 1.0)
            tot = 1.0
        for name in ("search", "insert", "update", "delete"):
            object.__setattr__(self, name, float(getattr(self, name)) / tot)

    @property
    def nsq_fraction(self) -> float:
        """Non-search-query fraction (paper Definition 1)."""
        return self.insert + self.update + self.delete

    @classmethod
    def from_nsq(cls, nsq_fraction: float) -> "OpMix":
        """Lift a bare NSQ fraction (the legacy float) into a mix; the
        mutation mass lands on ``insert`` (measured traces cannot split
        insert/update either — same op code)."""
        f = float(nsq_fraction)
        if not 0.0 <= f <= 1.0:
            raise ValueError(f"nsq_fraction must be in [0, 1], got {f}")
        return cls(search=1.0 - f, insert=f, update=0.0, delete=0.0)

    @classmethod
    def from_ops(cls, ops) -> "OpMix":
        """Measure the mix of a trace (any shape of op codes; NOP padding
        is excluded — it is dead capacity, not workload)."""
        import numpy as np
        ops = np.asarray(ops).reshape(-1)
        counts = np.bincount(ops[ops > 0], minlength=4)
        return cls.from_counts(search=int(counts[1]), insert=int(counts[2]),
                               delete=int(counts[3]))

    @classmethod
    def from_counts(cls, search: float = 0, insert: float = 0,
                    update: float = 0, delete: float = 0) -> "OpMix":
        """Build a mix from accumulated op counts (e.g. a ``TableServer``'s
        per-slab histogram); normalization happens in the constructor."""
        return cls(search=float(search), insert=float(insert),
                   update=float(update), delete=float(delete))

    def as_tuple(self):
        return (self.search, self.insert, self.update, self.delete)


MIX_DEFAULT = OpMix()           # 50:50 — the historical nsq_fraction=0.5


def as_mix(mix: Union["OpMix", float, Sequence, None]) -> "OpMix":
    """Coerce a model-term argument into an :class:`OpMix`: an OpMix passes
    through, a bare float is an NSQ fraction (the pre-OpMix signature), a
    4-sequence is (search, insert, update, delete), None is the 50:50
    default."""
    if mix is None:
        return MIX_DEFAULT
    if isinstance(mix, OpMix):
        return mix
    if isinstance(mix, (int, float)):
        return OpMix.from_nsq(mix)
    return OpMix(*mix)


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    name: str = "tpu-v5e"
    peak_bf16_tflops: float = 197.0
    hbm_gbps: float = 819.0
    ici_link_gbps: float = 50.0       # per link per direction
    vmem_bytes: int = 128 * 1024 * 1024  # per-chip VMEM pool
    vmem_gbps: float = 8000.0          # order-of-magnitude VMEM bandwidth
    hbm_bytes: int = 16 * 1024**3


V5E = TPUSpec()


@dataclasses.dataclass(frozen=True)
class FpgaSpec:
    name: str = "xilinx-u250"
    fmax_mhz: float = 370.0
    sram_bytes: int = 45 * 1024 * 1024   # 360 Mb URAM
    t0_search: int = 5                   # cycles, calibrated to 14ns@370MHz
    t0_write: int = 4                    # insert = t0_write + p cycles


FPGA_U250 = FpgaSpec()


def fpga_latency_ns(op: str, p: int, spec: FpgaSpec = FPGA_U250) -> float:
    cycles = spec.t0_search if op == "search" else spec.t0_write + p
    return cycles * 1e3 / spec.fmax_mhz


def fpga_throughput_mops(p: int, fclk_mhz: float) -> float:
    """Data-agnostic guarantee: p queries/cycle."""
    return p * fclk_mhz


def table_step_bytes(cfg: HashTableConfig,
                     mix: Union[OpMix, float, None] = None) -> float:
    """HBM/VMEM bytes moved by one apply_step (per query averages)."""
    mix = as_mix(mix)
    entry_bytes = 4 * cfg.entry_words
    n = cfg.queries_per_step
    gather = cfg.k * cfg.slots * entry_bytes          # read k stores x S slots
    scatter = mix.nsq_fraction * cfg.replicas * entry_bytes
    return n * (gather + scatter)


def tpu_modeled_mops(cfg: HashTableConfig, spec: TPUSpec = V5E,
                     mix: Union[OpMix, float, None] = None) -> float:
    """Bandwidth-roofline MOPS for one chip.

    If the table fits in VMEM (the paper's on-chip regime) the gather stream
    runs at VMEM bandwidth, else HBM bandwidth.
    """
    fits_vmem = memory_bytes(cfg) <= spec.vmem_bytes
    bw = spec.vmem_gbps if fits_vmem else spec.hbm_gbps
    bytes_per_query = table_step_bytes(cfg, mix) / cfg.queries_per_step
    return bw * 1e9 / bytes_per_query / 1e6


# ---------------------------------------------------------------------------
# Fused-stream model: commit cost + the blocked (bucket-tiled) regime
# (DESIGN.md §3.1).  benchmarks/roofline.py reports measured-vs-modeled for
# BENCH_stream.json rows from these terms.
# ---------------------------------------------------------------------------

SCALAR_STORE_NS = 8.0       # one serialized (port, bucket, slot) store
VECTOR_LANE_NS = 0.25       # one lane's share of a data-parallel pass


def stream_commit_seconds(cfg: HashTableConfig,
                          vectorized: bool = True) -> float:
    """Commit time for one step of N lanes.

    serial      the pre-supersession design: N scalar stores in lane order,
                the chain IS the last-wins semantics -> N * t_store.
    vectorized  the supersession-mask design: an [N, N] triangular
                same-target pass (data-parallel, N lanes wide) plus one
                conflict-free store burst -> ~2 vector passes.
    """
    n = cfg.queries_per_step
    if not vectorized:
        return n * SCALAR_STORE_NS * 1e-9
    return (n + n) * VECTOR_LANE_NS * 1e-9      # supersession + store burst


def stream_modeled_mops(cfg: HashTableConfig, steps: int,
                        bucket_tiles: int = 1,
                        vectorized_commit: bool = True, fused: bool = True,
                        mix: Union[OpMix, float, None] = None,
                        spec: TPUSpec = V5E) -> float:
    """Roofline MOPS for a ``[T, N]`` stream through the stream seam.

    Three terms per stream (DESIGN.md §3.1):

      lane work     per-query probe gather + encode bytes at VMEM bandwidth,
                    run once per step (each sweep pass touches only its own
                    lane window).
      commit        :func:`stream_commit_seconds` per step (serial scalar
                    chain vs supersession + burst).
      table traffic ``fused=False`` (the scanned per-step path): a full
                    table round trip over HBM EVERY step — each probe/commit
                    dispatch re-streams the table, the cost the fused kernel
                    exists to remove.  Fused blocked regime: ONE full-replica
                    round trip per stream (pass DMA in + out), amortized
                    over the T steps that share the sweep.  Fused unblocked:
                    none (aliased VMEM-resident tiles).
    """
    mix = as_mix(mix)
    n = cfg.queries_per_step
    entry_bytes = 4 * cfg.entry_words
    gather = cfg.k * cfg.slots * entry_bytes
    scatter = mix.nsq_fraction * entry_bytes
    lane_bytes = n * (gather + scatter)
    lane_s = lane_bytes / (spec.vmem_gbps * 1e9)
    commit_s = stream_commit_seconds(cfg, vectorized=vectorized_commit)
    replica = memory_bytes(cfg) / cfg.replicas
    if not fused:
        sweep_s = 2.0 * replica / (spec.hbm_gbps * 1e9)
    elif bucket_tiles > 1:
        sweep_s = 2.0 * replica / (spec.hbm_gbps * 1e9) / max(steps, 1)
    else:
        sweep_s = 0.0
    step_s = lane_s + commit_s + sweep_s
    return n / step_s / 1e6


# ---------------------------------------------------------------------------
# Bulk-build (count-then-place) model, DESIGN.md §3.2.  The whole table is
# constructed in O(1) sweeps over the record arrays plus ONE table round
# trip, so the per-record cost is sort passes at memory bandwidth instead of
# the streamed path's per-step dispatch + table traffic.  benchmarks/
# roofline.py reports measured-vs-modeled for BENCH_bulk.json rows; the
# streamed side of that A/B is :func:`stream_modeled_mops` with
# ``fused=False`` (the scanned per-step insert path it replaces).
# ---------------------------------------------------------------------------

PLAN_SCAN_PASSES = 6.0      # segment/rank/scatter passes between the sorts


def bulk_build_seconds(cfg: HashTableConfig, n: int,
                       spec: TPUSpec = V5E) -> float:
    """Count-then-place build time for ``n`` records.

    Three terms:

      sorts   two stable key sorts (group duplicates; rank per bucket), each
              ``~log2 n`` data-parallel passes over the packed record rows
              (key + value + bucket/slot words) at VMEM bandwidth — the
              asymptotically dominant term, O(n log n) lane work in O(1)
              dispatches.
      plan    the fixed segment/cummax/scatter passes between the sorts
              (:data:`PLAN_SCAN_PASSES` sweeps of the record rows).
      sweep   ONE port-0 plane round trip over HBM (zeroed plane out, placed
              plane in) — a replica/k of the table, once per BUILD, vs the
              streamed path's full-table round trip per STEP.
    """
    import math
    if n <= 0:
        return 0.0
    rec_bytes = n * 4 * (cfg.key_words + cfg.val_words + 2)
    passes = 2 * max(math.log2(n), 1.0) + PLAN_SCAN_PASSES
    sort_s = passes * rec_bytes / (spec.vmem_gbps * 1e9)
    plane = memory_bytes(cfg) / cfg.replicas / cfg.k
    sweep_s = 2.0 * plane / (spec.hbm_gbps * 1e9)
    return sort_s + sweep_s


def bulk_build_modeled_mops(cfg: HashTableConfig, n: int,
                            spec: TPUSpec = V5E) -> float:
    """Records per second (in MOPS) for one count-then-place build."""
    s = bulk_build_seconds(cfg, n, spec=spec)
    return n / s / 1e6 if s else 0.0


# ---------------------------------------------------------------------------
# Online-resize migration model (DESIGN.md §6).  A growing table pays two
# costs: background migration slabs (the count-then-place sweep over
# ``buckets_per_slab`` predecessor rows, interleaved between dispatches) and
# the dual-table stream during the window (every slab runs against BOTH the
# predecessor and the successor until the watermark closes —
# :data:`RESIZE_STREAM_FACTOR` on the stream terms).  The serve loop's
# growth policy picks ``migrate_buckets_per_slab`` so the per-slab pause
# fits its latency budget; the A/B against a stop-the-world rebuild is
# benchmarks/resize_migration.py (BENCH_resize.json).
# ---------------------------------------------------------------------------

RESIZE_STREAM_FACTOR = 2.0      # both tables stream during the window


def resize_migration_seconds(cfg: HashTableConfig,
                             buckets_per_slab: int = 64,
                             spec: TPUSpec = V5E) -> float:
    """Cost of ONE background migration slab — the growth pause a dispatch
    eats between slabs.

    Terms (per slab of ``buckets_per_slab * slots`` candidate records):

      decode  XOR-fold the slab rows' k partial stores into plaintext
              (k reads per entry over HBM).
      plan    the count-then-place sorts over the slab's records (the
              :func:`bulk_build_seconds` sort/scan passes at VMEM
              bandwidth — the slab is the build's n).
      place   scatter the placed records into the successor: the port-0
              plane write broadcast to all replicas.
      zero    write back the migrated predecessor rows as zeros (all
              ``replicas * k`` planes — the split-in-place invariant needs
              the source rows dead).
    """
    import math
    n = buckets_per_slab * cfg.slots
    if n <= 0:
        return 0.0
    entry_bytes = 4 * cfg.entry_words
    decode_bytes = cfg.k * n * entry_bytes
    zero_bytes = cfg.replicas * cfg.k * n * entry_bytes
    place_bytes = cfg.replicas * n * entry_bytes
    passes = 2 * max(math.log2(max(n, 2)), 1.0) + PLAN_SCAN_PASSES
    rec_bytes = n * 4 * (cfg.key_words + cfg.val_words + 2)
    sort_s = passes * rec_bytes / (spec.vmem_gbps * 1e9)
    hbm_s = (decode_bytes + zero_bytes + place_bytes) / (spec.hbm_gbps * 1e9)
    return sort_s + hbm_s


def resize_total_seconds(cfg: HashTableConfig,
                         buckets_per_slab: int = 64,
                         spec: TPUSpec = V5E) -> float:
    """Whole-table background migration time: every shard walks its own
    ``local_buckets`` in lockstep slabs (shard-locality makes the sharded
    resize no slower per slab than the single-domain one)."""
    import math
    slabs = math.ceil(cfg.local_buckets / buckets_per_slab)
    return slabs * resize_migration_seconds(cfg, buckets_per_slab, spec=spec)


# ---------------------------------------------------------------------------
# Routed-width terms for the sharded distributed stream (DESIGN.md §2.2).
# The skew-proof router fixes the per-owner routed width at D * n_local; the
# capacity-bounded two-pass router shrinks it to the measured max
# per-(step, owner) load rounded to cfg.routed_lane_tile (optionally capped
# by cfg.routed_slack).  Owner-side lane work AND the all_to_all payload both
# scale with that width, which is what BENCH_distributed.json's
# --bounded/--skewproof A/B measures.
# ---------------------------------------------------------------------------


def routed_width_lanes(cfg: HashTableConfig, n_local: int,
                       max_owner_load: int | None = None) -> int:
    """Routed lanes per owner per step row.

    ``cfg.router == "skewproof"`` (or no measured load): the data-agnostic
    worst case ``D * n_local``.  ``"bounded"``: the measured max per-(step,
    owner) load, rounded/clamped by ``cfg.bounded_routed_width`` — the same
    code path ``engine.plan_bounded_route`` uses, so model and router
    cannot drift.
    """
    if cfg.router == "skewproof" or max_owner_load is None:
        return cfg.shards * n_local
    return cfg.bounded_routed_width(max_owner_load, n_local)


def routed_exchange_bytes(cfg: HashTableConfig, steps: int, n_local: int,
                          routed_width: int | None = None) -> int:
    """Per-device all_to_all payload of one routed stream (queries out +
    results back), in bytes.  Skew-proof query slots carry (bucket, op word,
    key, value); the bounded router (``routed_width`` given) adds the
    step-tag word its FIFO re-binning rides on.  Result slots carry
    (found, ok, value) either way.  Both directions scale with the routed
    width — the bounded router's shrink is payload savings exactly as much
    as owner-compute savings."""
    bounded = routed_width is not None
    width = routed_width if bounded else cfg.shards * n_local
    q_words = (3 if bounded else 2) + cfg.key_words + cfg.val_words
    r_words = 2 + cfg.val_words
    return 4 * steps * width * (q_words + r_words)


def sharded_stream_modeled_mops(cfg: HashTableConfig, steps: int,
                                n_local: int,
                                routed_width: int | None = None,
                                routed_steps: int | None = None,
                                mix: Union[OpMix, float, None] = None,
                                spec: TPUSpec = V5E) -> float:
    """Roofline MOPS for the routed distributed stream across the mesh.

    Three per-device terms: owner-side lane work (probe gather + encode at
    VMEM bandwidth) over ``routed_steps x routed_width`` lanes, the
    supersession-masked commit per routed row, and the two all_to_all hops
    over one ICI link.  Aggregate queries are ``steps * D * n_local``; a
    narrower routed width cuts the first two terms AND the exchange, which
    is why the bounded router's shrink shows up as throughput, not just
    buffer bytes."""
    mix = as_mix(mix)
    d = cfg.shards
    width = d * n_local if routed_width is None else routed_width
    rows = steps if routed_steps is None else routed_steps
    entry_bytes = 4 * cfg.entry_words
    gather = cfg.k * cfg.slots * entry_bytes
    scatter = mix.nsq_fraction * entry_bytes
    lane_s = rows * width * (gather + scatter) / (spec.vmem_gbps * 1e9)
    commit_s = rows * 2 * width * VECTOR_LANE_NS * 1e-9
    ici_s = routed_exchange_bytes(cfg, steps, n_local, width) \
        / (spec.ici_link_gbps * 1e9)
    return steps * d * n_local / (lane_s + commit_s + ici_s) / 1e6


# ---------------------------------------------------------------------------
# 2-D (shard x replica) mesh terms (DESIGN.md §2.3).  Replicating a hot
# shard's partition over a group of g devices divides its SEARCH traffic by
# g (round-robin fan-out), so the bounded router's measured max per-(step,
# dest) load — hence the routed width every per-device term scales with —
# shrinks toward the mean.  The price is the mutation broadcast: every
# insert/delete ships one copy per group member, inflating routed traffic by
# :func:`replica_copy_factor`.  The crossover is exactly the read-mix knob:
# search-heavy skewed streams win, mutation-heavy ones pay g x the exchange
# for no width relief.  benchmarks/roofline.py reports measured-vs-modeled
# for BENCH_distributed.json's replication_ab section from these terms.
# ---------------------------------------------------------------------------


def replica_copy_factor(cfg: HashTableConfig,
                        mix: Union[OpMix, float, None] = None,
                        shard_load_fraction: list | None = None) -> float:
    """Mean routed copies per source lane under ``cfg.replica_groups``.

    A search/NOP lane ships exactly one copy (to its round-robin serving
    replica); a mutation lane broadcasts one copy per member of its owner
    shard's group.  ``shard_load_fraction`` weights the per-shard group
    sizes by the stream's measured owner distribution (uniform when None) —
    a hot shard with a big group drags the factor up faster than a cold
    one.  Degenerates to 1.0 on the 1-D mesh."""
    mix = as_mix(mix)
    nsq_fraction = mix.nsq_fraction
    if not cfg.replicated:
        return 1.0
    sizes = cfg.group_sizes
    if shard_load_fraction is None:
        w = [1.0 / len(sizes)] * len(sizes)
    else:
        tot = float(sum(shard_load_fraction))
        w = ([1.0 / len(sizes)] * len(sizes) if tot <= 0
             else [f / tot for f in shard_load_fraction])
    mean_group = sum(ws * g for ws, g in zip(w, sizes))
    return (1.0 - nsq_fraction) + nsq_fraction * mean_group


def replicated_read_mops(cfg: HashTableConfig, steps: int, n_local: int,
                         max_dest_load: int | None = None,
                         routed_steps: int | None = None,
                         mix: Union[OpMix, float, None] = None,
                         shard_load_fraction: list | None = None,
                         spec: TPUSpec = V5E) -> float:
    """Roofline MOPS for the routed stream on the 2-D grouped mesh.

    Same three per-device terms as :func:`sharded_stream_modeled_mops`, with
    the 2-D substitutions: destinations are the ``cfg.mesh_devices`` flat
    devices (not owner shards), the routed width tracks the measured max
    per-(step, DEST) load — the quantity replication shrinks, since a group
    of g splits its shard's search load g ways — and the query-side exchange
    carries :func:`replica_copy_factor` copies per lane while results return
    only from each lane's serving replica.  Aggregate useful queries stay
    ``steps * mesh_devices * n_local``: broadcast copies are overhead, not
    throughput."""
    import math
    mix = as_mix(mix)
    dv = cfg.mesh_devices
    copies = replica_copy_factor(cfg, mix, shard_load_fraction)
    # broadcast floor: mean per-(step, dest) load is copies * n_local, so no
    # measurement can shrink the width below it — the mutation-broadcast
    # cost term, rising with the load-weighted mean group size
    floor = cfg.bounded_routed_width(int(math.ceil(copies * n_local)),
                                     n_local)
    width = dv * n_local if max_dest_load is None \
        else max(cfg.bounded_routed_width(max_dest_load, n_local), floor)
    rows = steps if routed_steps is None else routed_steps
    entry_bytes = 4 * cfg.entry_words
    gather = cfg.k * cfg.slots * entry_bytes
    scatter = mix.nsq_fraction * entry_bytes
    lane_s = rows * width * (gather + scatter) / (spec.vmem_gbps * 1e9)
    commit_s = rows * 2 * width * VECTOR_LANE_NS * 1e-9
    q_words = 3 + cfg.key_words + cfg.val_words
    r_words = 2 + cfg.val_words
    ici_bytes = 4 * (rows * width * q_words + steps * n_local * r_words)
    ici_s = ici_bytes / (spec.ici_link_gbps * 1e9)
    return steps * dv * n_local / (lane_s + commit_s + ici_s) / 1e6


# ---------------------------------------------------------------------------
# Continuous-batching serve loop (DESIGN.md §4): the admission loop packs
# arrivals into fixed [slab_steps, N] slabs, resolves each slab's bounded
# route plan via a host-side measurement + LRU plan cache, and (when
# double-buffered) overlaps the host work for slab k+1 with the device
# stream of slab k.  benchmarks/roofline.py reports measured-vs-modeled for
# BENCH_serve.json rows from these terms.
# ---------------------------------------------------------------------------

HOST_MEASURE_NS_PER_LANE = 20.0   # numpy H3 + bincount per slab lane
HOST_PLAN_SECONDS = 5e-3          # plan_bounded_route on a cache miss


def serve_plan_seconds(lanes: int, hit_rate: float,
                       plan_seconds: float = HOST_PLAN_SECONDS,
                       measure_ns_per_lane: float = HOST_MEASURE_NS_PER_LANE,
                       ) -> float:
    """Amortized host routing cost for one slab of ``lanes`` lanes.

    The host measurement pass runs on EVERY slab (the plan cache's coverage
    check needs the measured loads even on a hit); the full
    ``plan_bounded_route`` replan only runs on the ``1 - hit_rate`` fraction
    of slabs that miss.  At ``hit_rate -> 1`` the per-slab cost collapses to
    the microsecond-scale measurement — the amortization the plan cache
    exists for."""
    measure_s = lanes * measure_ns_per_lane * 1e-9
    return measure_s + (1.0 - hit_rate) * plan_seconds


def serve_loop_modeled(cfg: HashTableConfig, slab_steps: int,
                       hit_rate: float = 1.0, pad_fraction: float = 0.0,
                       double_buffer: bool = True,
                       overlap_efficiency: float = 0.9,
                       plan_seconds: float = HOST_PLAN_SECONDS,
                       measure_ns_per_lane: float = HOST_MEASURE_NS_PER_LANE,
                       mix: Union[OpMix, float, None] = None,
                       spec: TPUSpec = V5E) -> dict:
    """Model one steady-state slab of the continuous-batching serve loop.

    Terms:

      device      ``slab_steps x N`` lanes through the stream roofline —
                  :func:`sharded_stream_modeled_mops` when the table is
                  sharded (the serve loop rides the bounded distributed
                  stream), :func:`stream_modeled_mops` otherwise.
      host        :func:`serve_plan_seconds` — measurement every slab, a
                  replan on the miss fraction.
      overlap     double-buffered dispatch hides ``overlap_efficiency`` of
                  the host term behind the in-flight slab's device time
                  (1.0 = perfect pipelining; single-buffered dispatch hides
                  nothing — host and device strictly alternate).

    Returns ``{"slab_seconds", "host_seconds", "mops", "p50_seconds",
    "p99_seconds"}``.  MOPS counts only live (non-NOP-padding) lanes, so
    ``pad_fraction`` is pure throughput loss.  p50 is the steady-state
    retire latency — a request rides its slab through the
    ``window``-deep in-flight pipeline; p99 adds the cold-replan spike a
    cache-miss slab eats on top."""
    mix = as_mix(mix)
    n = cfg.queries_per_step
    lanes = slab_steps * n
    if cfg.shards > 1:
        dev_mops = sharded_stream_modeled_mops(
            cfg, slab_steps, n // cfg.shards, mix=mix, spec=spec)
    else:
        dev_mops = stream_modeled_mops(cfg, slab_steps, mix=mix, spec=spec)
    device_s = lanes / (dev_mops * 1e6)
    host_s = serve_plan_seconds(lanes, hit_rate, plan_seconds,
                                measure_ns_per_lane)
    hidden = overlap_efficiency if double_buffer else 0.0
    slab_s = device_s + (1.0 - hidden) * host_s
    window = 2 if double_buffer else 1
    live = (1.0 - pad_fraction) * lanes
    p50 = window * slab_s
    return {
        "slab_seconds": slab_s,
        "host_seconds": host_s,
        "mops": live / slab_s / 1e6,
        "p50_seconds": p50,
        "p99_seconds": p50 + plan_seconds,
    }


# ---------------------------------------------------------------------------
# Geometry planning (paper Definition 1 / §V, DESIGN.md §5).  The XOR memory
# costs replicas * k * bucket-planes: k partial stores for k NSQ-capable PEs
# plus a full read replica per PE when replicate_reads.  A measured OpMix
# bounds how many NSQ-capable PEs the workload actually needs — the greedy
# packing router (hash_table.pack_trace) fits an NSQ fraction f into lane
# classes as long as f <= k/p per step on average — so a read-mostly table
# can shed stores AND replicas.  Memory saved is capacity gained: dropping a
# replica under VMEM_TABLE_BUDGET_BYTES moves the stream kernel from the
# blocked (tiled HBM sweep) regime back to VMEM-resident, the 20x cliff
# PR 4 measured.
# ---------------------------------------------------------------------------


def _planner_vmem_budget() -> int:
    # the kernel dispatch's actual residency threshold; lazy import keeps
    # core/ importable without the kernels package
    from repro.kernels.ops import VMEM_TABLE_BUDGET_BYTES
    return VMEM_TABLE_BUDGET_BYTES


def _planner_bucket_tiles(replica_bytes: int, buckets: int,
                          vmem_budget: int) -> int:
    """Mirror of kernels.ops.stream_bucket_tiles on planned (not yet built)
    geometry: double tiles until one tile's replica span fits the budget."""
    tiles = 1
    while replica_bytes // tiles > vmem_budget and tiles < buckets:
        tiles *= 2
    return tiles


def geometry_modeled_mops(cfg: HashTableConfig,
                          mix: Union[OpMix, float, None] = None,
                          steps: int = 16,
                          vmem_budget: int | None = None,
                          spec: TPUSpec = V5E) -> float:
    """Modeled stream MOPS of ``cfg``'s geometry under ``mix``, with the two
    geometry-sensitive effects the plain roofline call misses:

      residency   bucket_tiles is derived from the candidate's own replica
                  bytes vs the VMEM budget, so a geometry that drops under
                  the budget sheds the blocked regime's HBM sweep term — the
                  discrete win :func:`plan_geometry` hunts for.
      packing     a k < p geometry has only k NSQ-capable PEs, so a stream
                  with NSQ fraction f > k/p stretches by f/(k/p) steps in
                  the packing router.  Effective MOPS multiply by
                  min(1, (k/p)/f) — the term that stops the planner from
                  always answering k=1.
    """
    mix = as_mix(mix)
    if vmem_budget is None:
        vmem_budget = _planner_vmem_budget()
    replica = memory_bytes(cfg) // cfg.replicas
    tiles = _planner_bucket_tiles(replica, cfg.buckets, vmem_budget)
    base = stream_modeled_mops(cfg, steps, bucket_tiles=tiles, mix=mix,
                               spec=spec)
    cap = cfg.nsq_ratio                       # k/p, paper Definition 1
    f = mix.nsq_fraction
    stretch = 1.0 if f <= cap else cap / f
    return base * stretch


@dataclasses.dataclass(frozen=True)
class GeometryPlan:
    """One point of the legal (k, replicate_reads) lattice, scored."""
    k: int
    replicate_reads: bool
    replicas: int
    mix: OpMix
    table_bytes: int            # all replicas
    replica_bytes: int          # one replica — the VMEM residency unit
    bucket_tiles: int           # modeled kernel tiling at this geometry
    fits_vmem: bool             # replica_bytes <= vmem_budget
    modeled_mops: float
    baseline_k: int
    baseline_replicate_reads: bool
    baseline_table_bytes: int
    baseline_mops: float
    vmem_budget: int

    @property
    def improvement(self) -> float:
        return (self.modeled_mops / self.baseline_mops
                if self.baseline_mops else float("inf"))

    @property
    def memory_saving(self) -> float:
        return (self.baseline_table_bytes / self.table_bytes
                if self.table_bytes else float("inf"))

    @property
    def changed(self) -> bool:
        return (self.k != self.baseline_k
                or self.replicate_reads != self.baseline_replicate_reads)

    def apply(self, cfg: HashTableConfig) -> HashTableConfig:
        """The planned geometry as a config.  Capacity is untouched — this
        plan moves only (k, replicate_reads); growing buckets/slots is the
        online-resize seam's job (``engine.begin_resize`` /
        ``TableServer`` growth, priced by
        :func:`resize_migration_seconds`)."""
        return dataclasses.replace(cfg, k=self.k,
                                   replicate_reads=self.replicate_reads)


def plan_geometry(cfg: HashTableConfig,
                  mix: Union[OpMix, float, None] = None,
                  vmem_budget: int | None = None,
                  steps: int = 16,
                  spec: TPUSpec = V5E) -> GeometryPlan:
    """Pick the cheapest-memory legal geometry whose modeled throughput
    under ``mix`` is no worse than ``cfg``'s current one.

    The lattice is ``k in 1..p`` crossed with ``replicate_reads in {False,
    True}``; replicated reads are only legal on the single-partition layout
    (``shards == 1``, no replica_groups — the mesh mappings pin their own
    replica axis).  Each candidate is scored by
    :func:`geometry_modeled_mops`, which prices both the VMEM-residency
    cliff and the packing stretch of starving the NSQ lanes; ties on bytes
    break toward higher modeled MOPS, then larger k (port headroom)."""
    mix = as_mix(mix)
    if vmem_budget is None:
        vmem_budget = _planner_vmem_budget()
    baseline_mops = geometry_modeled_mops(cfg, mix, steps=steps,
                                          vmem_budget=vmem_budget, spec=spec)
    rep_options = [False]
    if cfg.shards == 1 and not cfg.replicated:
        rep_options.append(True)
    best = None
    for k in range(1, cfg.p + 1):
        for rep in rep_options:
            cand = dataclasses.replace(cfg, k=k, replicate_reads=rep)
            mops = geometry_modeled_mops(cand, mix, steps=steps,
                                         vmem_budget=vmem_budget, spec=spec)
            if mops < baseline_mops * (1.0 - 1e-9):
                continue
            total = memory_bytes(cand)
            replica = total // cand.replicas
            score = (total, -mops, -k)
            if best is None or score < best[0]:
                best = (score, cand, mops, total, replica)
    if best is None:
        # no candidate met the baseline (possible when the current geometry
        # sits outside the enumerable lattice, e.g. grouped replicas):
        # keep what we have
        total = memory_bytes(cfg)
        best = (None, cfg, baseline_mops, total, total // cfg.replicas)
    _, cand, mops, total, replica = best
    tiles = _planner_bucket_tiles(replica, cand.buckets, vmem_budget)
    return GeometryPlan(
        k=cand.k, replicate_reads=cand.replicate_reads,
        replicas=cand.replicas, mix=mix,
        table_bytes=total, replica_bytes=replica,
        bucket_tiles=tiles, fits_vmem=replica <= vmem_budget,
        modeled_mops=mops,
        baseline_k=cfg.k, baseline_replicate_reads=cfg.replicate_reads,
        baseline_table_bytes=memory_bytes(cfg), baseline_mops=baseline_mops,
        vmem_budget=vmem_budget,
    )
