"""Multi-device XOR hash table: the paper's PE array mapped onto a TPU mesh.

Two mappings share one seam (``make_distributed_stream``; DESIGN.md §2):

**Bucket-sharded** (``cfg.shards == n_dev`` — the scaling design).  The
bucket axis is partitioned by ownership: device ``d`` holds global buckets
``[d * local_buckets, (d+1) * local_buckets)`` — the high bits of the H3
bucket index name the owner, the low bits address within the partition.
Per stream:

  route    each device hashes its local ``[T, n]`` lane block, scatters
           queries into a destination-major send buffer (capacity ``n`` per
           owner, so arbitrary skew cannot drop queries) and exchanges them
           with ONE ``all_to_all`` for all T steps (engine.route_stream)
  stream   the owner runs its whole routed ``[T, D*n]`` stream against its
           partition in one go — the fused ``xor_stream`` Pallas kernel with
           a bucket-base offset on the pallas backend (one compiled launch,
           partition VMEM-persistent across steps), the scanned jnp oracle
           elsewhere (engine.run_stream_local)
  return   results ride the inverse ``all_to_all`` and land on their origin
           lanes via the saved send permutation (engine.inverse_route)

``cfg.router == "bounded"`` swaps the route/return stages for the
capacity-bounded two-pass router (DESIGN.md §2.2): a host-side load pass
(engine.plan_bounded_route) measures the trace and the exchange runs at the
measured widths — routed rows shrink from ``[T, D*n]`` to ``[T', Nr]`` with
``Nr`` = max per-(step, owner) load rounded to ``cfg.routed_lane_tile`` —
with a FIFO carry-over absorbing anything a static ``cfg.routed_slack`` cap
cuts off.  The returned callable is then a thin host wrapper (pass 1 +
dispatch to a jit specialized per measured width), not itself jit-traceable.

Capacity grows with the mesh (each device holds ``buckets/shards`` of the
table) and the per-stream collective payload is ``2 * T * n_dev * shards *
n * query_bytes`` (the ``shards`` factor is the skew-proof per-owner
capacity padding) — independent of table size.  Routed order is
(origin-device, origin-lane) == program order, so the owner's sequential
last-wins commit resolves duplicate targets exactly like the replicated
oracle: the two mappings are bit-exact (tests/test_distributed_sharded.py).

**2-D grouped** (``cfg.replica_groups`` — hot-shard read fan-out,
DESIGN.md §2.3).  Same seam, but the route destination is a DEVICE: shard
``s``'s partition is copied onto ``replica_groups[s]`` contiguous devices.
Searches are served by ONE group member chosen per-origin round-robin
(read fan-out: a hot shard's search load divides by its degree, shrinking
the bounded router's measured width); mutations broadcast to every member
(identical commit sequences on identical state keep the copies
byte-identical), and ``engine.plan_replication`` turns the bounded
router's measured per-shard skew into the degrees.  The mesh stays
physically 1-D — degrees are ragged, so the replica axis is logical
addressing (``HashTableConfig.group_offsets``).

**Replicated** (``cfg.shards == 1`` — the semantic oracle, and the paper's
literal PE array).  Every device holds the entire table; one ring
``all_gather`` of encoded mutation records per step (the FPGA inter-PE
pipeline on ICI) keeps replicas identical.  Capacity is capped at one
device's memory — which is why the sharded mapping exists.

Common to both: device == PE (``pe = axis_index``), so NSQ capability lives
with the *origin* device — ``axis_index < k`` owns write port ``axis_index``
and mutations it initiates write partial store ``port`` wherever the bucket
lives; search-only devices (``>= k``) never initiate mutations, the analogue
of dropping the Partial-XOR-Store-(M) write machinery in the paper's
Fig 3(b).  Consistency keeps the paper's relaxed model: encodings are
computed against the pre-step snapshot, commits land at step end, the
visibility window is exactly one step — in both mappings, since a bucket's
whole history lives on one owner processed in step order.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine as _engine
from repro.core.config import HashTableConfig
from repro.core.hash_table import (QueryBatch, StepResults, XorHashTable,
                                   init_table, packed_shape)
from repro.core.hashing import h3_hash as _h3, make_h3_params

__all__ = ["make_ht_mesh", "init_distributed_table", "make_distributed_step",
           "make_distributed_stream", "make_distributed_bulk_build",
           "make_distributed_compact", "make_distributed_reconfigure",
           "make_distributed_resize", "DistributedResize"]


def _parts(axis: str) -> P:
    """The stored array's sharding: its bucket-group axis (dim 1) split
    over the mesh, one packed partition per device."""
    return P(None, axis)


def _empty_partitions(mesh: Mesh, axis: str, cfg: HashTableConfig):
    """A zero stored array of ``cfg``'s geometry with one packed
    ``cfg.local_buckets`` partition on each device of ``axis`` (under
    ``replica_groups`` every group member holds its shard's copy)."""
    shape = packed_shape(cfg, mesh.shape[axis] * cfg.local_buckets)
    return jax.jit(lambda: jnp.zeros(shape, jnp.uint32),
                   out_shardings=NamedSharding(mesh, _parts(axis)))()


def make_ht_mesh(n_devices: int | None = None, axis: str = "ht",
                 replica_groups: tuple[int, ...] | None = None) -> Mesh:
    """Build the table's device mesh.

    The mesh is physically 1-D even under ``replica_groups`` (the 2-D
    (shard x replica) mapping, DESIGN.md §2.3): load-aware replica degrees
    are ragged — a hot shard may hold 4 devices while a cold one holds 1 —
    which no rectangular mesh axis can express, so the replica axis is
    logical addressing over device order (shard-major contiguous groups,
    ``HashTableConfig.group_offsets``).  Pass ``replica_groups`` (or
    ``n_devices = cfg.mesh_devices``) to size the axis.
    """
    devs = jax.devices()
    if n_devices is None and replica_groups is not None:
        n_devices = sum(replica_groups)
    n = n_devices or len(devs)
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def init_distributed_table(cfg: HashTableConfig, rng: jax.Array,
                           mesh: Mesh | None = None,
                           axis: str = "ht") -> XorHashTable:
    """Build the distributed table state.

    ``cfg.shards == 1``: one replica's state; shard_map replicates it per
    device (capacity = one device).  ``cfg.shards > 1``: the GLOBAL table
    with its bucket axis sharded over ``mesh``'s ``axis`` — each device
    materializes only its ``cfg.local_buckets``-bucket partition, so
    capacity scales with the mesh.  The H3 matrix spans the global index
    space either way and is replicated.  Under ``cfg.replica_groups`` the
    physical bucket dim is ``mesh_devices * local_buckets``: every device in
    shard ``s``'s replica group holds an identical copy of ``s``'s
    partition (they start identical — all zeros — and the grouped exchange
    broadcasts every mutation within the group, DESIGN.md §2.3).
    """
    if cfg.shards == 1:
        if cfg.replicate_reads:
            raise ValueError(
                "distributed table uses the compact per-device layout; set "
                "replicate_reads=False (replication happens across devices "
                "instead)")
        return init_table(cfg, rng)
    if mesh is None:
        raise ValueError("a bucket-sharded table (cfg.shards > 1) needs the "
                         "mesh to place its partitions")
    n_dev = mesh.shape[axis]
    cfg.validate_mesh(n_dev, axis)
    return XorHashTable(
        q_masks=jax.device_put(
            make_h3_params(rng, cfg.key_words, cfg.index_bits),
            NamedSharding(mesh, P())),
        packed=_empty_partitions(mesh, axis, cfg),
        cfg=cfg,
    )


def make_distributed_stream(mesh: Mesh, cfg: HashTableConfig,
                            axis: str = "ht",
                            fused: bool | None = None,
                            router: str | None = None,
                            routed_slack: int | None = None):
    """Build the multi-device stream.

    Returns ``f(table, ops, keys, vals) -> (table, results)`` over ``[T, N]``
    step tensors, queries sharded over ``axis`` (``N = n_dev * n_local``).
    ``cfg.shards`` selects the mapping (module docstring): ``n_dev`` =
    bucket-sharded route+stream+return, ``1`` = the replicated per-step
    all-gather oracle scanned over T.  ``fused`` pins the sharded
    local-stream regime exactly as in ``engine.run_stream``;
    ``router``/``routed_slack`` override ``cfg.router``/``cfg.routed_slack``
    for the sharded mapping.  The skew-proof/replicated callables are jitted
    end to end; the bounded callable is a host wrapper (measurement pass +
    dispatch to a jit specialized on the measured routed widths) that also
    accepts an explicit ``plan=`` (a :class:`BoundedRoutePlan`, skipping the
    per-call measurement) and carries the staged entry points a serve loop
    caches plans through: ``.measure`` (async pass 1), ``.plan`` (blocking
    pass 1), ``.dispatch`` (pass 2 under an explicit plan), plus
    ``.router``/``.cfg``/``.slack`` for feature detection (DESIGN.md §4).
    """
    n_dev = mesh.shape[axis]
    if cfg.shards != 1:
        cfg.validate_mesh(n_dev, axis)
    router = cfg.router if router is None else router

    if cfg.shards == 1:
        def local_stream(table, ops, keys, vals):
            my = jax.lax.axis_index(axis)   # device index == the paper's PE id

            def body(tab, xs):
                op, key, val = xs
                batch = QueryBatch(op, key, val)
                be = _engine.resolve_backend(cfg, tab)
                pr = be.probe(tab, batch, pe=my)
                plan = _engine.mutation_plan(cfg, batch, pr)
                rec = _engine.encode_records(pr, plan)
                # inter-PE propagation: ring all-gather of mutation records
                rec_all = jax.tree.map(
                    lambda x: jax.lax.all_gather(x, axis, tiled=True), rec)
                tab = _engine.commit_records(tab, rec_all)
                return tab, StepResults(found=pr.found, value=pr.value,
                                        ok=plan.ok, bucket=pr.bucket)

            return jax.lax.scan(body, table, (ops, keys, vals))

        table_spec = XorHashTable(P(), P(), cfg)

        fn = jax.shard_map(
            local_stream, mesh=mesh,
            in_specs=(table_spec, P(None, axis), P(None, axis),
                      P(None, axis)),
            out_specs=(table_spec, P(None, axis)),
            check_vma=False,
        )
        return jax.jit(fn)

    table_spec = XorHashTable(P(), _parts(axis), cfg)
    shmap = lambda body: jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(table_spec, P(None, axis), P(None, axis), P(None, axis)),
        out_specs=(table_spec, P(None, axis)),
        check_vma=False,
    ))

    # device d's partition start: shard_of[d] (2-D grouped mapping) or d
    # itself (1-D, where shard_of is the identity)
    _shard_of = jnp.asarray(_engine.replica_layout(cfg)[0], jnp.int32)

    @functools.lru_cache(maxsize=None)
    def _skewproof_stream():
        def local_stream(table, ops, keys, vals):
            d = jax.lax.axis_index(axis)
            T, n = ops.shape
            bucket = _h3(keys.reshape(T * n, cfg.key_words),
                         table.q_masks).reshape(T, n)
            if cfg.replicated:
                mut = ops >= _engine.OP_INSERT
                (r_op, r_key, r_val, r_bkt), tgt = \
                    _engine.route_stream_grouped(cfg, axis, bucket, mut,
                                                 ops, keys, vals, bucket)
            else:
                (r_op, r_key, r_val, r_bkt), tgt = _engine.route_stream(
                    cfg, axis, bucket, ops, keys, vals, bucket)
            # routed lane r belongs to origin device r // n == its PE
            pe = jnp.repeat(jnp.arange(n_dev, dtype=jnp.int32), n)
            packed, found, ok, value = _engine.run_stream_local(
                cfg, table.packed, pe, r_bkt, r_op, r_key, r_val,
                bucket_base=_shard_of[d] * cfg.local_buckets,
                fused=fused)
            f_l, ok_l, v_l = _engine.inverse_route(axis, tgt, found, ok, value)
            table = XorHashTable(table.q_masks, packed, cfg)
            return table, StepResults(found=f_l, value=v_l, ok=ok_l,
                                      bucket=bucket)

        return shmap(local_stream)

    if router == "skewproof":
        return _skewproof_stream()

    # bounded two-pass router (module docstring; DESIGN.md §2.2): the
    # returned callable measures each trace on the host (pass 1) and
    # dispatches to a jitted exchange specialized on the measured widths —
    # rounding to cfg.routed_lane_tile keeps the specialization count low.
    slack = cfg.routed_slack if routed_slack is None else routed_slack

    @jax.jit
    def _measure(keys, q_masks):
        T, N = keys.shape[:2]
        bucket = _h3(keys.reshape(T * N, cfg.key_words),
                     q_masks).reshape(T, N)
        return _engine.route_load_pass(cfg, _engine.shard_owner(cfg, bucket))

    @jax.jit
    def _measure_grouped(keys, ops, q_masks):
        T, N = keys.shape[:2]
        bucket = _h3(keys.reshape(T * N, cfg.key_words),
                     q_masks).reshape(T, N)
        return _engine.route_load_pass_grouped(
            cfg, _engine.shard_owner(cfg, bucket), ops >= _engine.OP_INSERT)

    # pass 1 should not run as an n_dev-way SPMD program just because
    # q_masks is mesh-replicated (per-call dispatch over the mesh costs more
    # than the whole measurement): when the query tensors live on ONE
    # device, measure there with a single-device copy of the LAST table's
    # q_masks (one slot — chained streaming mints a fresh q_masks object per
    # call, so an id-keyed dict would never hit and only grow; the strong
    # ref in the slot pins the id so it cannot be recycled while cached).
    # Mesh-committed query tensors (the sharded layout the stream itself
    # advertises) keep the native q_masks — mixing them with a pinned copy
    # is an incompatible-devices error.
    _qm_slot: list = [None, None, None]     # [source array, device, copy]

    def _measure_loads(keys, q_masks, ops=None):
        if cfg.replicated and ops is None:
            raise ValueError(
                "measuring a replicated (replica_groups) stream needs the "
                "ops tensor: copy loads depend on which lanes are mutations "
                "(the group broadcast) — pass ops to measure()/plan()")
        run = ((lambda k_, qm: _measure_grouped(k_, ops, qm))
               if cfg.replicated else _measure)
        devs = keys.devices() if isinstance(keys, jax.Array) else None
        if devs is None or len(devs) != 1:
            return run(keys, q_masks)           # sharded queries: SPMD pass
        dev = next(iter(devs))
        if _qm_slot[0] is not q_masks or _qm_slot[1] != dev:
            _qm_slot[0] = q_masks
            _qm_slot[1] = dev
            _qm_slot[2] = jax.device_put(jax.device_get(q_masks), dev)
        return run(keys, _qm_slot[2])

    @functools.lru_cache(maxsize=None)
    def _bounded_inner(q_cap: int, nr: int, tr: int):
        def local_stream(table, ops, keys, vals):
            d = jax.lax.axis_index(axis)
            T, n = ops.shape
            bucket = _h3(keys.reshape(T * n, cfg.key_words),
                         table.q_masks).reshape(T, n)
            if cfg.replicated:
                mut = ops >= _engine.OP_INSERT
                routed, pe, carry = _engine.route_stream_grouped_bounded(
                    cfg, axis, bucket, mut, ops, keys, vals, bucket,
                    pair_capacity=q_cap, routed_width=nr, routed_steps=tr)
            else:
                routed, pe, carry = _engine.route_stream_bounded(
                    cfg, axis, bucket, ops, keys, vals, bucket,
                    pair_capacity=q_cap, routed_width=nr, routed_steps=tr)
            r_op, r_key, r_val, r_bkt = routed
            packed, found, ok, value = _engine.run_stream_local(
                cfg, table.packed, pe, r_bkt, r_op, r_key, r_val,
                bucket_base=_shard_of[d] * cfg.local_buckets,
                fused=fused)
            f_l, ok_l, v_l = _engine.inverse_route_bounded(
                axis, carry, found, ok, value)
            table = XorHashTable(table.q_masks, packed, cfg)
            return table, StepResults(found=f_l, value=v_l, ok=ok_l,
                                      bucket=bucket)

        return shmap(local_stream)

    # plan-as-value entry points (DESIGN.md §4): a serve loop measures,
    # plans and dispatches as separate stages so it can cache the frozen
    # (hashable) BoundedRoutePlan across same-shaped slabs instead of
    # re-deriving it inside the wrapper on every call.
    def measure(table, keys, ops=None):
        """Pass 1, async: enqueue the jitted load histogram for ``keys``
        (``[T, N, Wk]``) and return the ``(loads [T, D], pair [D, D])``
        device arrays WITHOUT syncing — callers overlap the transfer with
        in-flight stream work and ``device_get`` when they need values.
        ``D`` is the dest count: shards on the 1-D mesh, mesh devices under
        ``replica_groups`` (which also needs ``ops`` — copy loads depend on
        which lanes broadcast)."""
        return _measure_loads(keys, table.q_masks, ops)

    def make_plan(table, keys, ops=None):
        """Pass 1, blocking: measure ``keys`` and return the frozen
        :class:`~repro.core.engine.BoundedRoutePlan`."""
        loads, pair = jax.device_get(measure(table, keys, ops))
        return _engine.plan_bounded_route(
            cfg, slack=slack, loads=loads, pair=pair,
            n_local=keys.shape[1] // n_dev)

    def dispatch(table, ops, keys, vals, plan):
        """Pass 2: run the stream under an explicit ``plan`` (this wrapper's
        own, or a cached one whose ``plan.covers(...)`` check passed —
        caller's responsibility; an under-sized plan drops lanes)."""
        T, N = ops.shape
        if plan.steps != T or plan.shards != cfg.mesh_devices:
            raise ValueError(f"plan measured [T={plan.steps}, D="
                             f"{plan.shards}] but batch is [T={T}, D="
                             f"{cfg.mesh_devices}] — plans only transfer "
                             f"between equal-shaped streams")
        # nothing to shrink: the measured width IS the worst case (and the
        # bounded no-carry exchange is the skew-proof one minus padding), so
        # skip the re-binning and take the jit-internal skew-proof path
        if (plan.routed_width >= plan.skewproof_width
                and plan.carried_lanes == 0):
            return _skewproof_stream()(table, ops, keys, vals)
        inner = _bounded_inner(plan.pair_capacity, plan.routed_width,
                               plan.routed_steps)
        return inner(table, ops, keys, vals)

    def bounded_stream(table, ops, keys, vals, plan=None):
        T, N = ops.shape
        if T == 0:
            return table, StepResults(
                found=jnp.zeros((0, N), jnp.bool_),
                value=jnp.zeros((0, N, cfg.val_words), jnp.uint32),
                ok=jnp.zeros((0, N), jnp.bool_),
                bucket=jnp.zeros((0, N), jnp.uint32))
        if plan is None:
            plan = make_plan(table, keys, ops)
        return dispatch(table, ops, keys, vals, plan)

    bounded_stream.router = "bounded"
    bounded_stream.cfg = cfg
    bounded_stream.slack = slack
    bounded_stream.measure = measure
    bounded_stream.plan = make_plan
    bounded_stream.dispatch = dispatch
    return bounded_stream


def make_distributed_bulk_build(mesh: Mesh, cfg: HashTableConfig,
                                axis: str = "ht", router: str | None = None,
                                backend: str | None = None):
    """Bucket-sharded bulk build (DESIGN.md §3.2): route records to their
    owner shards with the existing exchange, then run ONE local
    count-then-place sweep per partition.

    Returns ``f(table, keys, vals, live=None) -> (table, BulkBuildReport)``
    over ``[T, N(, W)]`` step tensors sharded over ``axis`` (``N = n_dev *
    n_local``, the stream layout; ``live`` masks padding records).  Requires
    ``cfg.shards == n_dev`` and an EMPTY table.  Program order is row-major
    ``(step, lane)``; both routers deliver an owner's records in program
    order, so each local sweep is byte-identical to the serialized-insert
    oracle over that partition — and unlike the query stream, the bounded
    router's FIFO carry-over cannot break bit-exactness here (the sweep sees
    all records at once; carry shifts only which routed ROW a record rides,
    never its rank in program order).  ``router`` overrides ``cfg.router``
    (``"skewproof"`` or ``"bounded"``); the bounded path measures each batch
    on the host and dispatches a jit specialized on the measured widths.
    Spill/placement flags ride the inverse exchange home, so the report
    keeps the caller's ``[T, N]`` record layout.
    """
    n_dev = mesh.shape[axis]
    cfg.validate_mesh(n_dev, axis)
    router = cfg.router if router is None else router
    # under replica_groups every record broadcasts to its owner's whole
    # group (mut=True for all lanes): each member runs the identical sweep
    # on the identical record sequence, so the partitions stay identical;
    # the serving copy carries the report home
    _shard_of = jnp.asarray(_engine.replica_layout(cfg)[0], jnp.int32)

    table_spec = XorHashTable(P(), _parts(axis), cfg)
    shmap = lambda body: jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(table_spec, P(None, axis), P(None, axis), P(None, axis)),
        out_specs=(table_spec, P(None, axis), P(None, axis), P(None, axis),
                   P(None, axis), P(None, axis), P()),
        check_vma=False,
    ))

    def _local_sweep(table, r_bkt, r_key, r_val, r_live, d):
        """One owner partition's count-then-place over the routed records,
        flattened row-major == program order."""
        Wk, Wv = cfg.key_words, cfg.val_words
        fb = r_bkt.reshape(-1)
        fk = r_key.reshape(-1, Wk)
        fv = r_val.reshape(-1, Wv)
        fl = r_live.reshape(-1)
        packed, placed, spilled, slot, first, max_load = \
            _engine.bulk_place_records(
                cfg, table.packed, fb, fk, fv, fl,
                bucket_base=_shard_of[d] * cfg.local_buckets,
                backend=backend)
        shape = r_bkt.shape
        return (XorHashTable(table.q_masks, packed, cfg),
                placed.reshape(shape), spilled.reshape(shape),
                slot.reshape(shape), first.reshape(shape),
                jax.lax.pmax(max_load, axis))

    @functools.lru_cache(maxsize=None)
    def _skewproof_build():
        def body(table, keys, vals, live):
            d = jax.lax.axis_index(axis)
            T, n = live.shape
            bucket = _h3(keys.reshape(T * n, cfg.key_words),
                         table.q_masks).reshape(T, n)
            if cfg.replicated:
                (r_key, r_val, r_bkt, r_live), tgt = \
                    _engine.route_stream_grouped(
                        cfg, axis, bucket, jnp.ones_like(live),
                        keys, vals, bucket, live)
            else:
                (r_key, r_val, r_bkt, r_live), tgt = _engine.route_stream(
                    cfg, axis, bucket, keys, vals, bucket, live)
            table, placed, spilled, slot, first, max_load = _local_sweep(
                table, r_bkt, r_key, r_val, r_live, d)
            p_l, s_l, sl_l, f_l = _engine.inverse_route(axis, tgt, placed,
                                                        spilled, slot, first)
            return table, p_l, s_l, sl_l, f_l, bucket, max_load

        return shmap(body)

    @functools.lru_cache(maxsize=None)
    def _bounded_build(q_cap: int, nr: int, tr: int):
        def body(table, keys, vals, live):
            d = jax.lax.axis_index(axis)
            T, n = live.shape
            bucket = _h3(keys.reshape(T * n, cfg.key_words),
                         table.q_masks).reshape(T, n)
            if cfg.replicated:
                routed, pe, carry = _engine.route_stream_grouped_bounded(
                    cfg, axis, bucket, jnp.ones_like(live),
                    keys, vals, bucket, live,
                    pair_capacity=q_cap, routed_width=nr, routed_steps=tr)
            else:
                routed, pe, carry = _engine.route_stream_bounded(
                    cfg, axis, bucket, keys, vals, bucket, live,
                    pair_capacity=q_cap, routed_width=nr, routed_steps=tr)
            r_key, r_val, r_bkt, r_live = routed
            # dead routed padding carries pe == D (zeros elsewhere too, but
            # the explicit live word is the single source of truth)
            table, placed, spilled, slot, first, max_load = _local_sweep(
                table, r_bkt, r_key, r_val, r_live & (pe < n_dev), d)
            p_l, s_l, sl_l, f_l = _engine.inverse_route_bounded(
                axis, carry, placed, spilled, slot, first)
            return table, p_l, s_l, sl_l, f_l, bucket, max_load

        return shmap(body)

    @jax.jit
    def _measure(keys, q_masks):
        T, N = keys.shape[:2]
        bucket = _h3(keys.reshape(T * N, cfg.key_words),
                     q_masks).reshape(T, N)
        owner = _engine.shard_owner(cfg, bucket)
        if cfg.replicated:      # every record is a broadcast "mutation"
            return _engine.route_load_pass_grouped(
                cfg, owner, jnp.ones((T, N), jnp.bool_))
        return _engine.route_load_pass(cfg, owner)

    def build(table, keys, vals, live=None):
        T, N = keys.shape[:2]
        if live is None:
            live = jnp.ones((T, N), jnp.bool_)
        if T == 0:
            z = jnp.zeros((0, N), jnp.int32)
            zb = jnp.zeros((0, N), jnp.bool_)
            return table, _engine.BulkBuildReport(
                bucket=z, slot=z, placed=zb, spilled=zb, first=zb,
                max_load=jnp.zeros((), jnp.int32))
        if router == "skewproof":
            fn = _skewproof_build()
        else:
            loads, pair = jax.device_get(_measure(keys, table.q_masks))
            plan = _engine.plan_bounded_route(cfg, loads=loads, pair=pair,
                                              n_local=N // n_dev)
            if plan.routed_width >= plan.skewproof_width:
                fn = _skewproof_build()
            else:
                fn = _bounded_build(plan.pair_capacity, plan.routed_width,
                                    plan.routed_steps)
        table, placed, spilled, slot, first, bucket, max_load = fn(
            table, keys, vals, live)
        report = _engine.BulkBuildReport(
            bucket=bucket.astype(jnp.int32), slot=slot, placed=placed,
            spilled=spilled, first=first, max_load=max_load)
        return table, report

    build.router = router
    build.cfg = cfg
    return build


def make_distributed_compact(mesh: Mesh, cfg: HashTableConfig,
                             axis: str = "ht", backend: str | None = None):
    """Shard-local compaction: every owner rewrites its own partition with
    the count-then-place sweep (records already live at their owners, so no
    exchange is needed).  Returns ``f(table) -> table`` — jitted end to
    end; same semantics per partition as ``engine.compact``."""
    n_dev = mesh.shape[axis]
    cfg.validate_mesh(n_dev, axis)
    table_spec = XorHashTable(P(), _parts(axis), cfg)

    def body(table):
        return _engine.compact(table, backend=backend)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(table_spec,),
                                 out_specs=table_spec, check_vma=False))


def make_distributed_reconfigure(mesh: Mesh, cfg: HashTableConfig,
                                 new_cfg: HashTableConfig, axis: str = "ht",
                                 backend: str | None = None):
    """Shard-local geometry migration: every owner re-places its own
    partition's records into the new ``(replicas, k)`` store shape (records
    stay at their owners — the bucket axis is untouched — so like
    :func:`make_distributed_compact` no exchange is needed).  Returns
    ``f(table) -> table`` holding ``new_cfg``-shaped partitions; same
    record-set contract per partition as ``engine.reconfigure``."""
    n_dev = mesh.shape[axis]
    cfg.validate_mesh(n_dev, axis)
    new_cfg.validate_mesh(n_dev, axis)
    in_spec = XorHashTable(P(), _parts(axis), cfg)
    out_spec = XorHashTable(P(), _parts(axis), new_cfg)

    def body(table):
        return _engine.reconfigure(table, new_cfg, backend=backend)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(in_spec,),
                                 out_specs=out_spec, check_vma=False))


class DistributedResize:
    """The sharded mesh's online-resize driver (built by
    :func:`make_distributed_resize`); same watermark contract as the
    single-domain ``engine`` seam, carried by the same
    ``engine.ResizeState`` value.

    The H3 rows are inserted at ``cfg.local_index_bits`` (the boundary
    between the in-partition bits and the owner-shard bits), so a record's
    OWNER NEVER CHANGES: routing is computed once from the predecessor hash,
    the successor partitions live on the same devices (and the same
    ``replica_groups``), and migration is embarrassingly shard-local —
    every shard walks its own local bucket range ``[w, w + n)`` in lockstep
    under ONE shared watermark.  Replica-group members migrate their
    identical partition copies with identical inputs, so the copies stay
    byte-identical through the resize.

    ``stream`` runs the SKEW-PROOF exchange for the duration of the resize
    window regardless of ``cfg.router``: the bounded router's measured
    widths are a latency optimization, and re-measuring against two moving
    tables per slab would cost more than the padding it saves — the serve
    loop already bypasses its plan cache while a resize is open.
    """

    def __init__(self, begin, stream, migrate):
        self.begin = begin      # (table, new_buckets, rng=None) -> ResizeState
        self.stream = stream    # (state, ops, keys, vals) -> (state, results)
        self.migrate = migrate  # (state, n_buckets) -> ResizeState

    @staticmethod
    def finish(state):
        """Close a completed resize: the successor table (sharded)."""
        return _engine.finish_resize(state)


def make_distributed_resize(mesh: Mesh, cfg: HashTableConfig,
                            new_buckets: int, axis: str = "ht",
                            fused: bool | None = None,
                            backend: str | None = None) -> DistributedResize:
    """Build the sharded online-resize driver (class docstring above;
    DESIGN.md §6).  ``new_buckets`` is the successor's GLOBAL bucket count
    (power of two above ``cfg.buckets``; the shard count is fixed, so the
    added index bits all land in the per-shard local range).  The stream and
    migrate entry points are jitted shard_maps with the watermark riding as
    a traced scalar — migration progress never recompiles."""
    n_dev = mesh.shape[axis]
    if cfg.shards == 1:
        raise ValueError(
            "the replicated mapping (cfg.shards == 1) resizes through the "
            "single-domain engine seam (engine.begin_resize) — "
            "make_distributed_resize drives bucket-sharded partitions")
    cfg.validate_mesh(n_dev, axis)
    if new_buckets & (new_buckets - 1) or new_buckets <= cfg.buckets:
        raise ValueError(f"new_buckets must be a power of two above "
                         f"buckets={cfg.buckets}, got {new_buckets}")
    new_cfg = dataclasses.replace(cfg, buckets=new_buckets)
    new_cfg.validate_mesh(n_dev, axis)
    lib = cfg.local_index_bits
    g = new_cfg.index_bits - cfg.index_bits
    bl_old = cfg.local_buckets
    Wk = cfg.key_words
    _shard_of = jnp.asarray(_engine.replica_layout(cfg)[0], jnp.int32)

    pred_spec = XorHashTable(P(), _parts(axis), cfg)
    succ_spec = XorHashTable(P(), _parts(axis), new_cfg)

    def begin(table: XorHashTable, rng: jax.Array | None = None):
        """Open the resize: allocate the empty sharded successor (extended
        H3 matrix replicated, partitions on the same devices) at
        watermark 0.  The masks are extended on the host — one small
        gather/put beats an n_dev-way SPMD launch for a [index_bits, Wk]
        matrix."""
        if rng is None:
            rng = jax.random.PRNGKey(new_buckets)
        qm = _engine.successor_masks(
            jnp.asarray(jax.device_get(table.q_masks)), cfg, new_cfg, rng)
        succ = XorHashTable(
            q_masks=jax.device_put(qm, NamedSharding(mesh, P())),
            packed=_empty_partitions(mesh, axis, new_cfg), cfg=new_cfg)
        return _engine.ResizeState(pred=table, succ=succ, watermark=0)

    def _local_stream(pred, succ, w, ops, keys, vals):
        d = jax.lax.axis_index(axis)
        T, n = ops.shape
        flat = keys.reshape(T * n, Wk)
        b_old = _h3(flat, pred.q_masks).reshape(T, n)
        extra = _h3(flat, succ.q_masks[lib:lib + g]).reshape(T, n)
        b_new = _engine.resize_buckets(b_old, extra, lib, g, bl_old)
        # route ONCE by the (stable) owner; both buckets ride as payload
        if cfg.replicated:
            mut = ops >= _engine.OP_INSERT
            (r_op, r_key, r_val, r_bo, r_bn), tgt = \
                _engine.route_stream_grouped(cfg, axis, b_old, mut,
                                             ops, keys, vals, b_old, b_new)
        else:
            (r_op, r_key, r_val, r_bo, r_bn), tgt = _engine.route_stream(
                cfg, axis, b_old, ops, keys, vals, b_old, b_new)
        pe = jnp.repeat(jnp.arange(n_dev, dtype=jnp.int32), n)
        mig = (r_bo & jnp.uint32(bl_old - 1)) < w
        # each side sees the other's lanes as dead NOP padding (routing
        # padding already rides as op 0 — the same contract)
        p_tab, f_p, ok_p, v_p = _engine.run_stream_local(
            cfg, pred.packed, pe, r_bo, jnp.where(mig, 0, r_op), r_key,
            r_val, bucket_base=_shard_of[d] * bl_old, fused=fused)
        s_tab, f_s, ok_s, v_s = _engine.run_stream_local(
            new_cfg, succ.packed, pe, r_bn, jnp.where(mig, r_op, 0), r_key,
            r_val, bucket_base=_shard_of[d] * new_cfg.local_buckets,
            fused=fused)
        found = jnp.where(mig, f_s, f_p)
        ok = jnp.where(mig, ok_s, ok_p)
        value = jnp.where(mig[..., None], v_s, v_p)
        f_l, ok_l, v_l = _engine.inverse_route(axis, tgt, found, ok, value)
        pred = XorHashTable(pred.q_masks, p_tab, cfg)
        succ = XorHashTable(succ.q_masks, s_tab, new_cfg)
        return pred, succ, StepResults(found=f_l, value=v_l, ok=ok_l,
                                       bucket=b_new)

    _stream_jit = jax.jit(jax.shard_map(
        _local_stream, mesh=mesh,
        in_specs=(pred_spec, succ_spec, P(), P(None, axis), P(None, axis),
                  P(None, axis)),
        out_specs=(pred_spec, succ_spec, P(None, axis)),
        check_vma=False,
    ))

    def stream(state, ops, keys, vals):
        if ops.ndim != 2 or ops.shape[1] != cfg.queries_per_step:
            raise ValueError(f"stream shape {ops.shape} != [T, p*qpp="
                             f"{cfg.queries_per_step}]")
        pred, succ, res = _stream_jit(
            state.pred, state.succ, jnp.uint32(state.watermark),
            ops, keys, vals)
        return dataclasses.replace(state, pred=pred, succ=succ), res

    @functools.lru_cache(maxsize=None)
    def _migrate_jit(n: int):
        def body(pred, succ, w):
            d = jax.lax.axis_index(axis)
            keys, vals, live, rows = _engine.slab_records(pred, w, n)
            b_old = (_shard_of[d].astype(jnp.uint32) << lib) | (w + rows)
            extra = _h3(keys, succ.q_masks[lib:lib + g])
            b_new = _engine.resize_buckets(b_old, extra, lib, g, bl_old)
            packed = _engine.bulk_place_records(
                new_cfg, succ.packed, b_new, keys, vals, live,
                bucket_base=_shard_of[d] * new_cfg.local_buckets,
                backend=backend)[0]
            return (_engine.zero_slab(pred, w, n),
                    XorHashTable(succ.q_masks, packed, new_cfg))

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(pred_spec, succ_spec, P()),
            out_specs=(pred_spec, succ_spec), check_vma=False))

    def migrate(state, n_buckets: int):
        """Every shard migrates its own local rows ``[w, w + n)`` — one
        lockstep watermark, no exchange (owners never change)."""
        w = state.watermark
        n = min(n_buckets, bl_old - w)
        if n <= 0:
            return state
        pred, succ = _migrate_jit(n)(state.pred, state.succ, jnp.uint32(w))
        return _engine.ResizeState(pred=pred, succ=succ, watermark=w + n)

    return DistributedResize(begin, stream, migrate)


def make_distributed_step(mesh: Mesh, cfg: HashTableConfig, axis: str = "ht"):
    """Per-step entry point — the ``T == 1`` special case of
    :func:`make_distributed_stream`.  Returns ``f(table, op, key, val) ->
    (table, results)`` with ``[N]``-shaped per-step tensors.
    """
    stream = make_distributed_stream(mesh, cfg, axis)

    def step_fn(table, op, key, val):
        table, res = stream(table, op[None], key[None], val[None])
        return table, jax.tree.map(lambda x: x[0], res)

    return step_fn
