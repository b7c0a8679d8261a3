"""Configuration for the XOR-based parallel hash table (paper §IV).

Terminology maps 1:1 onto the paper:
  p      — number of processing engines = parallel queries per cycle/step.
  k      — max non-search queries (insert/update/delete) per cycle; the number
           of Partial XOR Stores per replica and of NSQ-capable PEs.  NSQ
           ratio = k/p (paper Definition 1).
  buckets— hash table entries (closed addressing).
  slots  — slots per bucket for collision resolution (paper: 2-4 typical).
  key_words / val_words — key/value width in uint32 words (32/64/128-bit ==
           1/2/4 words, the paper's evaluated sizes).
  shards — bucket-shard partitions across a device mesh (beyond-paper scale
           axis): each shard owns buckets/shards contiguous buckets, selected
           by the high bits of the H3 index (core.distributed; DESIGN.md
           §2.1).  1 == single memory domain.
  replica_groups — per-shard device replica counts for the 2-D
           (shard x replica) mesh (DESIGN.md §2.3): shard ``s`` is held by
           ``replica_groups[s]`` devices, searches fan out round-robin across
           them while mutations broadcast within the group.  The degrees may
           differ per shard (load-aware hot-shard replication,
           ``engine.plan_replication``), which is why the replica axis is a
           logical addressing layer over a flat mesh rather than a
           rectangular mesh dimension.  None == one device per shard (the
           1-D mesh).
  replicate_reads — True  = paper-faithful: one replica per PE (p replicas).
                    False = TPU-native ('compact') variant: a single replica
                    per device; vector gathers are natively multi-ported on
                    TPU so read replication is dropped *within* a chip while
                    the k-way XOR write-port decomposition is kept.  This is
                    the beyond-paper memory optimisation measured in §Perf.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

__all__ = ["HashTableConfig", "GrowthPolicy", "sram_blocks_ours",
           "sram_blocks_laforest", "memory_bytes", "round_up_lanes"]


def round_up_lanes(x: int, tile: int) -> int:
    """Round a lane count up to the routed lane tile (>= 1 lane)."""
    return -(-max(x, 1) // tile) * tile


@dataclasses.dataclass(frozen=True)
class HashTableConfig:
    p: int = 4                      # PEs == parallel queries per step-slice
    k: Union[int, str] = 4          # NSQ-capable PEs == partial XOR stores;
                                    # "auto" resolves the cheapest legal k via
                                    # perfmodel.plan_geometry from op_mix (or
                                    # the 50:50 default mix) at construction —
                                    # requires replicate_reads=False (the
                                    # planner owns the replica decision)
    buckets: int = 1024             # power of two
    slots: int = 2
    key_words: int = 1              # uint32 words: 1/2/4 == 32/64/128-bit
    val_words: int = 1
    replicate_reads: bool = True    # paper-faithful replicas
    queries_per_pe: int = 1         # vector width per PE per step (1 == cycle-accurate)
    stagger_slots: bool = False     # beyond-paper: port j inserts into the
                                    # (j mod n_open)-th open slot, so same-step
                                    # same-bucket inserts from distinct ports
                                    # never collide while slots remain (§Perf)
    backend: str = "auto"           # query-engine backend (repro.core.engine):
                                    # "jnp" | "pallas" | "auto" (pallas on TPU,
                                    # jnp elsewhere; pallas auto-falls-back to
                                    # jnp when a replica exceeds the VMEM
                                    # table budget)
    shards: int = 1                 # bucket-shard partitions across a device
                                    # mesh (core.distributed): the bucket axis
                                    # splits into `shards` contiguous ranges of
                                    # `local_buckets` each, one per device; the
                                    # high bits of the H3 bucket index select
                                    # the owner shard.  1 == single memory
                                    # domain (replicated when distributed).
    replica_groups: Optional[Tuple[int, ...]] = None
                                    # 2-D (shard x replica) mesh (DESIGN.md
                                    # §2.3): replica_groups[s] devices hold
                                    # identical copies of shard s's partition
                                    # — searches fan out round-robin across
                                    # the group, mutations broadcast within
                                    # it.  Degrees may differ per shard
                                    # (engine.plan_replication feeds the
                                    # bounded router's measured skew forward:
                                    # hot shards get more replicas).  None ==
                                    # one device per shard (the 1-D mesh).
    router: str = "skewproof"       # sharded-stream routing policy
                                    # (DESIGN.md §2.2):
                                    # "skewproof" — fixed D*n_local routed
                                    #   lanes per owner per step (worst-case
                                    #   capacity, fully jit-internal);
                                    # "bounded"  — capacity-bounded two-pass
                                    #   router: a load pass measures the trace
                                    #   and the routed width shrinks to the
                                    #   actual max per-(step, owner) load
                                    #   (rounded to routed_lane_tile), with a
                                    #   FIFO carry-over absorbing anything a
                                    #   static routed_slack cap cuts off.
    routed_slack: Optional[int] = None
                                    # bounded router only: static cap on the
                                    # routed width (lanes per owner per step)
                                    # for jit-stable shapes across streams;
                                    # loads above the cap carry over to later
                                    # routed rows in program order.  None ==
                                    # auto (width == measured max load; no
                                    # carry ever, bit-exact always).
    routed_lane_tile: int = 8       # rounding granularity for the bounded
                                    # router's measured widths/capacities —
                                    # coarser tiles mean fewer jit
                                    # specializations (and TPU-friendly lane
                                    # alignment), finer tiles a tighter fit
    op_mix: Optional[Tuple[float, ...]] = None
                                    # declared workload mix (search, insert,
                                    # update, delete) fractions — the input to
                                    # k="auto" geometry planning and the
                                    # default mix the perfmodel terms assume
                                    # for this table.  None == unknown (the
                                    # 50:50 search:NSQ default).

    def __post_init__(self):
        if self.op_mix is not None:
            mx = tuple(float(f) for f in self.op_mix)
            if len(mx) != 4 or any(f < 0 for f in mx) or sum(mx) <= 0:
                raise ValueError(
                    f"op_mix must be 4 nonnegative (search, insert, update, "
                    f"delete) fractions with a positive sum, got {self.op_mix}")
            object.__setattr__(self, "op_mix", mx)
        if self.k == "auto":
            if self.replicate_reads:
                raise ValueError(
                    "k='auto' with replicate_reads=True: the geometry "
                    "planner owns the replica decision and plans the compact "
                    "per-device layout — set replicate_reads=False (or pick "
                    "an explicit k for the paper-faithful replicated table)")
            # lazy import: perfmodel imports this module at its top level
            from repro.core.perfmodel import plan_geometry
            base = dataclasses.replace(self, k=self.p)
            plan = plan_geometry(base, self.op_mix)
            object.__setattr__(self, "k", plan.k)
        if not isinstance(self.k, int):
            raise ValueError(f"k must be an int or 'auto', got {self.k!r}")
        if self.k < 1 or self.k > self.p:
            raise ValueError(f"need 1 <= k <= p, got k={self.k} p={self.p}")
        if self.backend not in ("auto", "jnp", "pallas"):
            raise ValueError(f"backend must be auto|jnp|pallas, "
                             f"got {self.backend!r}")
        if self.buckets & (self.buckets - 1):
            raise ValueError(f"buckets must be a power of two, got {self.buckets}")
        if self.slots < 1:
            raise ValueError("slots >= 1")
        if self.shards < 1 or self.shards & (self.shards - 1):
            raise ValueError(f"shards must be a power of two >= 1, "
                             f"got {self.shards}")
        if self.shards > self.buckets:
            raise ValueError(f"need shards <= buckets, got shards={self.shards}"
                             f" buckets={self.buckets}")
        if self.replica_groups is not None:
            if not isinstance(self.replica_groups, tuple):
                object.__setattr__(self, "replica_groups",
                                   tuple(int(g) for g in self.replica_groups))
            if self.replicate_reads:
                raise ValueError(
                    f"replica_groups={self.replica_groups} with "
                    f"replicate_reads=True: the distributed table uses the "
                    f"compact per-device layout (replication happens across "
                    f"devices via replica_groups, not within a chip) — set "
                    f"replicate_reads=False")
            if self.shards < 2:
                raise ValueError(
                    f"replica_groups={self.replica_groups} needs shards > 1 "
                    f"(a shards=1 table is already fully replicated by the "
                    f"distributed oracle — drop replica_groups or set "
                    f"shards to the partition count)")
            if len(self.replica_groups) != self.shards:
                raise ValueError(
                    f"replica_groups has {len(self.replica_groups)} degrees "
                    f"but shards={self.shards}: give one replica degree per "
                    f"shard (e.g. replica_groups={(1,) * self.shards} for "
                    f"the unreplicated 1-D mesh)")
            if any(g < 1 for g in self.replica_groups):
                raise ValueError(
                    f"replica_groups={self.replica_groups}: every shard "
                    f"needs at least one replica (degree >= 1)")
        if self.router not in ("skewproof", "bounded"):
            raise ValueError(f"router must be skewproof|bounded, "
                             f"got {self.router!r}")
        if self.routed_slack is not None and self.routed_slack < 1:
            raise ValueError(f"routed_slack must be >= 1 lane, "
                             f"got {self.routed_slack}")
        if self.routed_lane_tile < 1:
            raise ValueError(f"routed_lane_tile must be >= 1, "
                             f"got {self.routed_lane_tile}")

    @property
    def index_bits(self) -> int:
        return (self.buckets - 1).bit_length()

    @property
    def global_buckets(self) -> int:
        """The full hash space (== buckets): the H3 index always spans every
        shard; a shard owns the `local_buckets`-sized range selected by the
        high `index_bits - local_index_bits` bits."""
        return self.buckets

    @property
    def local_buckets(self) -> int:
        """Buckets held by one shard partition (buckets/shards)."""
        return self.buckets // self.shards

    @property
    def local_index_bits(self) -> int:
        """Low bucket-index bits that address within a shard; the remaining
        high bits are the owner shard id."""
        return (self.local_buckets - 1).bit_length()

    @property
    def replicas(self) -> int:
        return self.p if self.replicate_reads else 1

    # -- 2-D (shard x replica) mesh geometry (DESIGN.md §2.3) ---------------
    # The mesh stays physically 1-D; the replica axis is logical addressing
    # because load-aware degrees are ragged (a hot shard may hold 4 devices
    # while a cold one holds 1), which no rectangular mesh axis can express.
    # Device order is shard-major: group s owns the contiguous device range
    # [group_offsets[s], group_offsets[s] + group_sizes[s]).

    @property
    def group_sizes(self) -> Tuple[int, ...]:
        """Replica degree per shard (all-ones when unreplicated)."""
        return (self.replica_groups if self.replica_groups is not None
                else (1,) * self.shards)

    @property
    def group_offsets(self) -> Tuple[int, ...]:
        """First device id of each shard's replica group (shard-major)."""
        offs, acc = [], 0
        for g in self.group_sizes:
            offs.append(acc)
            acc += g
        return tuple(offs)

    @property
    def mesh_devices(self) -> int:
        """Devices the distributed table occupies: sum of replica degrees
        (== shards for the 1-D mesh, 1 for the undistributed table)."""
        return sum(self.group_sizes) if self.shards > 1 else 1

    @property
    def max_group(self) -> int:
        """Largest replica degree across shards."""
        return max(self.group_sizes)

    @property
    def replicated(self) -> bool:
        """True when any shard has cross-device replicas (degree > 1)."""
        return self.replica_groups is not None and self.max_group > 1

    def validate_mesh(self, n_dev: int, axis: str = "ht") -> None:
        """The single distributed-entry validation path: every consumer of a
        mesh (`init_distributed_table`, `make_distributed_stream`,
        `make_distributed_bulk_build`, `make_distributed_compact`) calls this
        so inconsistent configs fail in one place with a fix-it message."""
        if self.shards <= 1:
            return
        if self.replicate_reads:
            raise ValueError(
                f"shards={self.shards} with replicate_reads=True: the "
                f"distributed table uses the compact per-device layout "
                f"(cross-device replication is replica_groups' job) — set "
                f"replicate_reads=False")
        if n_dev != self.mesh_devices:
            want = (f"replica_groups={self.replica_groups} needs "
                    f"sum(replica_groups)={self.mesh_devices} devices"
                    if self.replica_groups is not None
                    else f"shards={self.shards} needs one device per shard")
            raise ValueError(
                f"mesh axis {axis!r} has {n_dev} devices but {want} — build "
                f"the mesh with make_ht_mesh({self.mesh_devices}) or adjust "
                f"shards/replica_groups to match the device count")

    @property
    def nsq_ratio(self) -> float:
        return self.k / self.p

    @property
    def replica_bytes(self) -> int:
        """Bytes of ONE read replica of this geometry (k partial-store
        planes of buckets x slots entries) — the unit the VMEM residency
        check tiles against.  Computable for a planned-but-not-yet-built
        geometry: no arrays needed, and for a built table it equals the
        bytes of one replica's logical stores (``XorHashTable.stores``)."""
        return self.k * self.buckets * self.slots * 4 * self.entry_words

    @property
    def table_bytes(self) -> int:
        """Total storage across replicas (== ``memory_bytes(cfg)``)."""
        return self.replicas * self.replica_bytes

    @property
    def queries_per_step(self) -> int:
        return self.p * self.queries_per_pe

    @property
    def entry_words(self) -> int:
        # key + value + 1 packed valid word per slot (valid is XOR-encoded too)
        return self.key_words + self.val_words + 1

    def bounded_routed_width(self, max_owner_load: int, n_local: int,
                             slack=None, tile=None) -> int:
        """The bounded router's routed width (DESIGN.md §2.2): the measured
        max per-(step, dest) load rounded up to the lane tile, clamped by
        ``routed_slack`` and the skew-proof ceiling ``mesh_devices *
        n_local`` (== ``shards * n_local`` on the 1-D mesh; under
        replica_groups the dests are devices, not shards).
        The single source of this arithmetic — ``engine.plan_bounded_route``
        picks the real exchange shape with it and
        ``perfmodel.routed_width_lanes`` models it, so the two cannot
        drift."""
        slack = self.routed_slack if slack is None else slack
        tile = self.routed_lane_tile if tile is None else tile
        nr = round_up_lanes(max_owner_load, tile)
        if slack is not None:
            nr = max(1, min(nr, slack))
        return min(nr, self.mesh_devices * n_local)

    def tree_flatten(self):  # static-only dataclass; handy for jit static args
        return (), self

    @classmethod
    def tree_unflatten(cls, aux, _):
        return aux


# ---------------------------------------------------------------------------
# Online-growth policy (DESIGN.md §6)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GrowthPolicy:
    """When and how far a serving table grows online (``TableServer``).

    ``grow_load_factor`` is the trigger: at a slab boundary, if live records
    / (buckets * slots) reaches it, a resize opens.  ``grow_target_occupancy``
    sizes the successor: the smallest power-of-two bucket count (at least a
    doubling) whose load factor lands at or below the target.  The gap
    between trigger and target IS the hysteresis — after a grow the table
    sits well below the trigger, so bursty traffic cannot thrash
    grow-after-grow.  ``migrate_buckets_per_slab`` is the background slab
    size: predecessor buckets moved between consecutive dispatches
    (perfmodel.resize_migration_seconds prices the per-slab pause so a
    latency budget can pick it)."""
    grow_load_factor: float = 0.7
    grow_target_occupancy: float = 0.35
    migrate_buckets_per_slab: int = 64

    def __post_init__(self):
        if not (0.0 < self.grow_target_occupancy
                < self.grow_load_factor <= 1.0):
            raise ValueError(
                f"need 0 < grow_target_occupancy < grow_load_factor <= 1 "
                f"(the gap is the growth hysteresis), got target="
                f"{self.grow_target_occupancy}, trigger="
                f"{self.grow_load_factor}")
        if self.migrate_buckets_per_slab < 1:
            raise ValueError("migrate_buckets_per_slab must be >= 1")

    def target_buckets(self, cfg: HashTableConfig, live_records: int) -> int:
        """Successor bucket count: next power of two, at least a doubling,
        such that ``live_records`` sits at or below the target occupancy."""
        b = cfg.buckets * 2
        while live_records > self.grow_target_occupancy * b * cfg.slots:
            b *= 2
        return b


# ---------------------------------------------------------------------------
# Memory-requirement models (paper §IV-B, §IV-D; Fig 4)
# ---------------------------------------------------------------------------

def sram_blocks_laforest(m_read: int, n_write: int) -> int:
    """LaForest et al. [25]: an mR nW XOR memory costs n*(n-1+m) 1R1W blocks."""
    return n_write * (n_write - 1 + m_read)


def sram_blocks_ours(m_read: int, n_write: int) -> int:
    """Paper Fig 1(b): shared read ports reduce the cost to m*n blocks."""
    return m_read * n_write


def memory_bytes(cfg: HashTableConfig) -> int:
    """Total table storage (paper §IV-D): replicas x partial stores x table."""
    bytes_per_slot = 4 * cfg.entry_words
    table = cfg.buckets * cfg.slots * bytes_per_slot
    return cfg.replicas * cfg.k * table
