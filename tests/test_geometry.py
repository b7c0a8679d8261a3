"""Op-mix-adaptive geometry planning (DESIGN.md §5): OpMix accounting,
plan_geometry's legal (k, replicas) lattice, k="auto" config resolution,
pack_trace lane-class properties, live-table migration via
engine.reconfigure (record-set round-trips on both backends + the sharded
mesh in a fake-device subprocess), and TableServer's slab-boundary replan."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH,
                        engine, init_table, pack_trace, reconfigure,
                        run_stream)
from repro.core.engine import extract_records
from repro.core.perfmodel import (MIX_DEFAULT, OpMix, as_mix,
                                  geometry_modeled_mops, plan_geometry)

REPO = os.path.dirname(os.path.dirname(__file__))


# --------------------------------------------------------------------------
# OpMix accounting
# --------------------------------------------------------------------------

def test_op_mix_normalizes_and_classifies():
    m = OpMix(search=2.0, insert=1.0, update=0.5, delete=0.5)
    assert abs(sum(m.as_tuple()) - 1.0) < 1e-12
    assert abs(m.search - 0.5) < 1e-12
    assert abs(m.nsq_fraction - 0.5) < 1e-12
    # all-zero degenerates to pure search (no NSQ demand)
    z = OpMix(search=0.0, insert=0.0, update=0.0, delete=0.0)
    assert z.search == 1.0 and z.nsq_fraction == 0.0
    with pytest.raises(ValueError):
        OpMix(search=-0.1, insert=1.1)


def test_op_mix_from_ops_counts_only_live_lanes():
    ops = np.array([OP_SEARCH, OP_SEARCH, OP_INSERT, OP_DELETE, 0, 0],
                   np.int32)
    m = OpMix.from_ops(ops)
    assert abs(m.search - 0.5) < 1e-12
    assert abs(m.insert - 0.25) < 1e-12
    assert abs(m.delete - 0.25) < 1e-12


def test_as_mix_accepts_float_tuple_none():
    assert as_mix(None) is MIX_DEFAULT
    assert abs(as_mix(0.1).nsq_fraction - 0.1) < 1e-12
    m = as_mix((0.9, 0.08, 0.0, 0.02))
    assert abs(m.search - 0.9) < 1e-12
    with pytest.raises(ValueError):
        as_mix(1.5)


# --------------------------------------------------------------------------
# plan_geometry: the legal lattice and the compact win
# --------------------------------------------------------------------------

def _cfg(**kw):
    base = dict(p=8, k=8, buckets=1 << 10, slots=4, key_words=2, val_words=2,
                replicate_reads=False, stagger_slots=True, queries_per_pe=8)
    base.update(kw)
    return HashTableConfig(**base)


def test_plan_geometry_read_mostly_picks_compact_k():
    cfg = _cfg()
    plan = plan_geometry(cfg, (0.9, 0.08, 0.0, 0.02))
    assert plan.k < cfg.k
    assert plan.table_bytes < plan.baseline_table_bytes
    # never trades away modeled throughput for the memory win
    assert plan.modeled_mops >= plan.baseline_mops * (1 - 1e-9)
    # the chosen k still covers the declared NSQ demand
    assert plan.k / cfg.p >= as_mix((0.9, 0.08, 0.0, 0.02)).nsq_fraction
    new = plan.apply(cfg)
    assert new.k == plan.k and new.table_bytes == plan.table_bytes


def test_plan_geometry_balanced_mix_keeps_coverage():
    plan = plan_geometry(_cfg(), 0.5)          # 50% NSQ -> k >= p/2
    assert plan.k >= 4
    assert plan.table_bytes <= plan.baseline_table_bytes


def test_plan_geometry_never_worse_than_current():
    for mix in (0.0, 0.25, 0.5, 1.0):
        plan = plan_geometry(_cfg(), mix)
        assert plan.modeled_mops >= plan.baseline_mops * (1 - 1e-9)
        assert plan.table_bytes <= plan.baseline_table_bytes


def test_plan_geometry_vmem_budget_discrete_win():
    # budget sized so the full-k replica is blocked but a compact one fits:
    # the planner must see the regime cliff and report the resident config
    cfg = _cfg(buckets=1 << 10)                # replica k=8: 655360 B
    budget = 100 * 1024
    plan = plan_geometry(cfg, (0.95, 0.05), vmem_budget=budget)
    assert plan.fits_vmem and plan.replica_bytes <= budget
    assert plan.bucket_tiles == 1
    full_mops = geometry_modeled_mops(cfg, (0.95, 0.05), vmem_budget=budget)
    assert plan.modeled_mops > full_mops


def test_plan_geometry_grouped_mesh_falls_back_gracefully():
    cfg = _cfg(p=8, k=8, shards=2, replica_groups=(2, 2))
    plan = plan_geometry(cfg, 0.5)             # must not crash on the 2-D mesh
    assert 1 <= plan.k <= cfg.p


# --------------------------------------------------------------------------
# k="auto" config resolution
# --------------------------------------------------------------------------

def test_k_auto_resolves_from_declared_mix():
    cfg = _cfg(k="auto", op_mix=(0.9, 0.08, 0.0, 0.02))
    assert isinstance(cfg.k, int) and cfg.k < cfg.p
    # same plan the planner would produce from the worst-case base
    plan = plan_geometry(_cfg(), (0.9, 0.08, 0.0, 0.02))
    assert cfg.k == plan.k


def test_k_auto_default_mix_is_balanced():
    cfg = _cfg(k="auto")                      # no declared mix -> 50/50
    assert cfg.k == plan_geometry(_cfg(), None).k


def test_k_auto_conflicts_with_replicate_reads():
    with pytest.raises(ValueError, match="replicate_reads"):
        _cfg(k="auto", replicate_reads=True)


def test_bad_op_mix_rejected():
    with pytest.raises(ValueError):
        _cfg(op_mix=(0.5, 0.5))               # must be the 4-tuple
    with pytest.raises(ValueError):
        _cfg(op_mix=(1.0, -0.5, 0.25, 0.25))


def test_replica_bytes_matches_kernel_accounting():
    from repro.kernels.ops import table_vmem_bytes
    cfg = _cfg(k=3)
    tab = init_table(cfg, jax.random.key(0))
    assert cfg.replica_bytes == 4 * sum(x[0].size for x in tab.stores())
    assert cfg.table_bytes == cfg.replicas * cfg.replica_bytes
    assert tab.memory_bytes == cfg.table_bytes
    # the kernels' VMEM accounting adds only the stored layout's padding
    assert cfg.replica_bytes <= table_vmem_bytes(tab.packed)


# --------------------------------------------------------------------------
# pack_trace lane-class properties
# --------------------------------------------------------------------------

def test_pack_trace_properties_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from conftest import TraceGen

    @hyp.given(n=st.integers(min_value=1, max_value=80),
               p=st.sampled_from([2, 4, 8]),
               k_off=st.integers(min_value=0, max_value=7),
               qpp=st.sampled_from([1, 2, 4]),
               seed=st.integers(min_value=0, max_value=2 ** 16))
    @hyp.settings(deadline=None, max_examples=60)
    def prop(n, p, k_off, qpp, seed):
        k = 1 + k_off % p
        cfg = HashTableConfig(p=p, k=k, buckets=1 << 8, slots=2, key_words=2,
                              val_words=2, queries_per_pe=qpp)
        gen = TraceGen(np.random.default_rng(seed))
        op, keys, vals = gen.mixed(n, key_words=2, val_words=2)
        op_s, kk_s, vv_s, place = pack_trace(op, keys, vals, cfg,
                                             return_placement=True)
        N = cfg.queries_per_step
        # 1) capacity: every step holds at most k*qpp NSQs, all on legal lanes
        nsq = np.isin(op_s, (OP_INSERT, OP_DELETE))
        assert nsq.sum(axis=1).max(initial=0) <= k * qpp
        lanes = np.nonzero(nsq)[1]
        assert np.all(lanes % p < k)
        # 2) program order: placements are strictly increasing per op class
        flat = place[:, 0].astype(np.int64) * N + place[:, 1]
        assert len(np.unique(flat)) == n        # no two queries share a lane
        for cls in (op == OP_SEARCH, np.isin(op, (OP_INSERT, OP_DELETE))):
            steps = place[cls, 0]
            assert np.all(np.diff(steps) >= 0)  # class order never reordered
        # live entries at their placements reproduce the input exactly
        np.testing.assert_array_equal(op_s.reshape(-1)[flat], op)
        np.testing.assert_array_equal(kk_s.reshape(-1, 2)[flat], keys)
        np.testing.assert_array_equal(vv_s.reshape(-1, 2)[flat], vals)
        # 3) repack fixed point: packing the packed trace (flattened in
        # program order) is deterministic and adds no steps
        op2, kk2, vv2, place2 = pack_trace(op, keys, vals, cfg,
                                           return_placement=True)
        np.testing.assert_array_equal(place, place2)
        op3, _, _, place3 = pack_trace(op_s.reshape(-1)[flat],
                                       kk_s.reshape(-1, 2)[flat],
                                       vv_s.reshape(-1, 2)[flat], cfg,
                                       return_placement=True)
        np.testing.assert_array_equal(place3, place)
        assert op3.shape[0] == op_s.shape[0]

    prop()


def test_pack_trace_custom_pe_map():
    # sharded mesh lane->PE mapping (origin device): pe = lane // n_local
    cfg = HashTableConfig(p=4, k=1, buckets=1 << 8, slots=2, key_words=2,
                          val_words=2, queries_per_pe=2)
    N = cfg.queries_per_step
    n_local = N // 4
    op = np.array([OP_INSERT] * 5 + [OP_SEARCH] * 3, np.int32)
    keys = np.tile(np.arange(1, 9, dtype=np.uint32)[:, None], (1, 2))
    vals = keys + 1
    _, _, _, place = pack_trace(op, keys, vals, cfg, return_placement=True,
                                pe_of_lane=lambda lane: lane // n_local)
    muts = place[np.isin(op, (OP_INSERT, OP_DELETE))]
    assert np.all(muts[:, 1] // n_local < cfg.k)


# --------------------------------------------------------------------------
# reconfigure: live-table migration round-trips
# --------------------------------------------------------------------------

def _record_set(table):
    keys, vals, live, _ = extract_records(table)
    keys, vals = np.asarray(keys), np.asarray(vals)
    live = np.asarray(live)
    return {tuple(np.concatenate([keys[i], vals[i]]).tolist())
            for i in np.nonzero(live)[0]}


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_reconfigure_round_trip(backend, trace_gen):
    cfg = HashTableConfig(p=8, k=8, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, replicate_reads=False,
                          stagger_slots=True, queries_per_pe=4,
                          backend=backend)
    table = init_table(cfg, jax.random.key(0))
    op, keys, vals = trace_gen.mixed(300, key_words=2, val_words=2,
                                     key_space=500)
    op_s, kk_s, vv_s = pack_trace(op, keys, vals, cfg)
    table, _ = run_stream(table, jnp.asarray(op_s), jnp.asarray(kk_s),
                          jnp.asarray(vv_s), backend=backend)
    before = _record_set(table)
    assert before                              # the trace inserted something

    compact = reconfigure(table, dataclasses.replace(cfg, k=2),
                          backend=backend)
    assert compact.stores()[0].shape[1] == 2
    assert _record_set(compact) == before
    # searches on the migrated table resolve every live record
    rec = sorted(before)
    skeys = np.array([r[:2] for r in rec], np.uint32)
    svals = np.array([r[2:] for r in rec], np.uint32)
    cfg2 = compact.cfg
    sop = np.full(len(rec), OP_SEARCH, np.int32)
    op_q, kk_q, vv_q, place = pack_trace(sop, skeys, svals * 0, cfg2,
                                         return_placement=True)
    _, res = run_stream(compact, jnp.asarray(op_q), jnp.asarray(kk_q),
                        jnp.asarray(vv_q), backend=backend)
    N = cfg2.queries_per_step
    flat = place[:, 0].astype(np.int64) * N + place[:, 1]
    assert bool(np.asarray(res.found).reshape(-1)[flat].all())
    np.testing.assert_array_equal(
        np.asarray(res.value).reshape(-1, 2)[flat], svals)

    back = reconfigure(compact, cfg, backend=backend)
    assert _record_set(back) == before


def test_reconfigure_to_replicated_and_back(trace_gen):
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=2, key_words=2,
                          val_words=2, replicate_reads=True,
                          stagger_slots=True, queries_per_pe=2)
    table = init_table(cfg, jax.random.key(1))
    op, keys, vals = trace_gen.mixed(100, key_words=2, val_words=2)
    op_s, kk_s, vv_s = pack_trace(op, keys, vals, cfg)
    table, _ = run_stream(table, jnp.asarray(op_s), jnp.asarray(kk_s),
                          jnp.asarray(vv_s))
    before = _record_set(table)
    compact = reconfigure(table, dataclasses.replace(
        cfg, k=1, replicate_reads=False))
    assert compact.stores()[0].shape[:2] == (1, 1)
    assert _record_set(compact) == before
    assert _record_set(reconfigure(compact, cfg)) == before


def test_reconfigure_capacity_round_trip(trace_gen):
    """Capacity deltas route through the migration path now (DESIGN.md §6):
    grow rehashes at the wider index, shrink back is the inverse — the
    record set survives both."""
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=2, key_words=2,
                          val_words=2)
    table = init_table(cfg, jax.random.key(0))
    op, keys, vals = trace_gen.mixed(200, key_words=2, val_words=2,
                                     key_space=400)
    op_s, kk_s, vv_s = pack_trace(op, keys, vals, cfg)
    table, _ = run_stream(table, jnp.asarray(op_s), jnp.asarray(kk_s),
                          jnp.asarray(vv_s))
    before = _record_set(table)
    assert before
    big = reconfigure(table, dataclasses.replace(cfg, buckets=1 << 9),
                      rng=jax.random.key(7))
    assert big.stores()[0].shape[2] == 1 << 9
    assert big.q_masks.shape[0] == big.cfg.index_bits
    assert _record_set(big) == before
    # searches resolve on the grown table
    rec = sorted(before)
    skeys = np.array([r[:2] for r in rec], np.uint32)
    svals = np.array([r[2:] for r in rec], np.uint32)
    sop = np.full(len(rec), OP_SEARCH, np.int32)
    op_q, kk_q, vv_q, place = pack_trace(sop, skeys, svals * 0, big.cfg,
                                         return_placement=True)
    _, res = run_stream(big, jnp.asarray(op_q), jnp.asarray(kk_q),
                        jnp.asarray(vv_q))
    N = big.cfg.queries_per_step
    flat = place[:, 0].astype(np.int64) * N + place[:, 1]
    assert bool(np.asarray(res.found).reshape(-1)[flat].all())
    np.testing.assert_array_equal(
        np.asarray(res.value).reshape(-1, 2)[flat], svals)
    # shrink back deletes the same index rows; record set unchanged
    back = reconfigure(big, cfg)
    assert _record_set(back) == before


def test_reconfigure_shrink_spill_raises():
    """A shrink that cannot hold every live record reports the spill count
    instead of dropping records."""
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 6, slots=2, key_words=2,
                          val_words=2)
    table = init_table(cfg, jax.random.key(2))
    n = 64
    keys = np.zeros((n, 2), np.uint32)
    keys[:, 0] = np.arange(1, n + 1)
    vals = np.ones((n, 2), np.uint32)
    op = np.full(n, 2, np.int32)            # OP_INSERT
    op_s, kk_s, vv_s = pack_trace(op, keys, vals, cfg)
    table, res = run_stream(table, jnp.asarray(op_s), jnp.asarray(kk_s),
                            jnp.asarray(vv_s))
    with pytest.raises(ValueError, match="drop"):
        reconfigure(table, dataclasses.replace(cfg, buckets=4, slots=1))


def test_reconfigure_rejects_frozen_fields(trace_gen):
    """Genuinely frozen fields (hash-input width, lane layout, mesh shape)
    still get the fix-it error — only capacity and geometry migrate."""
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=2, key_words=2,
                          val_words=2)
    table = init_table(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="key_words"):
        reconfigure(table, dataclasses.replace(cfg, key_words=4))


def test_reconfigure_sharded_capacity_raises():
    """Per-partition reconfigure cannot re-home records across shards —
    sharded capacity changes go through the online-resize seam."""
    cfg = HashTableConfig(p=4, k=2, buckets=1 << 8, slots=2, key_words=2,
                          val_words=2, shards=4, replicate_reads=False)
    local = HashTableConfig(p=4, k=2, buckets=1 << 8, slots=2, key_words=2,
                            val_words=2)
    table = init_table(local, jax.random.key(0))
    table = dataclasses.replace(table, cfg=cfg)
    with pytest.raises(ValueError, match="make_distributed_resize"):
        reconfigure(table, dataclasses.replace(cfg, buckets=1 << 9))


_SHARDED_RECONFIG = r"""
import dataclasses, sys
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, "tests")
from conftest import TraceGen
from repro.core import HashTableConfig, OP_SEARCH, pack_trace
from repro.core.distributed import (init_distributed_table,
                                    make_distributed_reconfigure,
                                    make_distributed_stream, make_ht_mesh)
from repro.core.engine import extract_records

cfg = HashTableConfig(p=8, k=8, buckets=1 << 9, slots=2, key_words=2,
                      val_words=2, queries_per_pe=4, shards=4,
                      replicate_reads=False, stagger_slots=True)
mesh = make_ht_mesh(4)
tab = init_distributed_table(cfg, jax.random.key(0), mesh)
stream = make_distributed_stream(mesh, cfg)
gen = TraceGen(np.random.default_rng(0))
op, keys, vals = gen.mixed(400, key_words=2, val_words=2, key_space=800)
n_local = cfg.queries_per_step // 4
op_s, kk_s, vv_s = pack_trace(op, keys, vals, cfg,
                              pe_of_lane=lambda lane: lane // n_local)
tab, _ = stream(tab, jnp.asarray(op_s), jnp.asarray(kk_s), jnp.asarray(vv_s))

def record_set(t):
    k, v, lv, _ = extract_records(t)
    k, v, lv = np.asarray(k), np.asarray(v), np.asarray(lv)
    return {tuple(np.concatenate([k[i], v[i]]).tolist())
            for i in np.nonzero(lv)[0]}

before = record_set(tab)
assert before, "empty table"
new_cfg = dataclasses.replace(cfg, k=2)
tab2 = make_distributed_reconfigure(mesh, cfg, new_cfg)(tab)
after = record_set(tab2)
assert after == before, (len(before), len(after))
# searches through the migrated sharded table resolve every record
rec = sorted(before)
skeys = np.array([r[:2] for r in rec], np.uint32)
svals = np.array([r[2:] for r in rec], np.uint32)
sop = np.full(len(rec), OP_SEARCH, np.int32)
oq, kq, vq, place = pack_trace(sop, skeys, svals * 0, new_cfg,
                               return_placement=True,
                               pe_of_lane=lambda lane: lane // n_local)
stream2 = make_distributed_stream(mesh, new_cfg)
_, res = stream2(tab2, jnp.asarray(oq), jnp.asarray(kq), jnp.asarray(vq))
N = new_cfg.queries_per_step
flat = place[:, 0].astype(np.int64) * N + place[:, 1]
assert bool(np.asarray(res.found).reshape(-1)[flat].all())
np.testing.assert_array_equal(np.asarray(res.value).reshape(-1, 2)[flat],
                              svals)
print("SHARDED_RECONFIG_OK", len(before))
"""


def test_sharded_reconfigure_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SHARDED_RECONFIG], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_RECONFIG_OK" in r.stdout


# --------------------------------------------------------------------------
# TableServer: slab-boundary replanning + migration
# --------------------------------------------------------------------------

def _serve_cfg(**kw):
    from repro.serving import ServeConfig
    return ServeConfig(**kw)


def test_table_server_migrates_read_mostly(trace_gen):
    """Migration invisibility: a replanning server must return bit-identical
    results to a frozen-geometry twin fed the same requests — inserts land
    before the search-heavy tail flips the served mix and triggers the
    migration, so the searches straddle at least one live reconfigure."""
    from repro.serving import TableServer
    cfg = HashTableConfig(p=8, k=8, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, backend="jnp", queries_per_pe=2)
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    n_ins = 40
    ikeys = np.tile(np.arange(1, n_ins + 1, dtype=np.uint32)[:, None], (1, 2))
    ivals = ikeys + 7

    def serve(replan):
        srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                          _serve_cfg(slab_steps=2, geometry_replan=replan,
                                     geometry_hysteresis=1.0,
                                     geometry_min_slabs=1))
        reqs = [srv.submit(np.full(n_ins, OP_INSERT, np.int32), ikeys, ivals)]
        # search-heavy tail drives the served mix read-mostly
        for _ in range(6):
            reqs.append(srv.submit(np.full(n_ins, OP_SEARCH, np.int32),
                                   ikeys, np.zeros_like(ivals)))
        srv.run()
        return srv, reqs

    srv_auto, reqs_auto = serve(True)
    srv_fixed, reqs_fixed = serve(False)
    assert srv_auto.migrations >= 1, srv_auto.stats()
    assert srv_auto.cfg.k < 8                # migrated into a compact layout
    assert srv_fixed.cfg.k == 8
    for ra, rf in zip(reqs_auto, reqs_fixed):
        np.testing.assert_array_equal(ra.found, rf.found)
        np.testing.assert_array_equal(ra.ok, rf.ok)
        np.testing.assert_array_equal(ra.value, rf.value)
    # the searches did find records (the tail isn't vacuously all-miss)
    assert any(bool(np.asarray(r.found).any()) for r in reqs_auto[1:])
    st = srv_auto.stats()
    assert st["migrations"] == srv_auto.migrations
    assert st["geometry"]["k"] == srv_auto.cfg.k
    assert 0.0 <= st["nsq_fraction"] <= 1.0
    assert abs(sum(st["op_mix"]) - 1.0) < 1e-9


def test_table_server_hysteresis_blocks_marginal_moves(trace_gen):
    from repro.serving import TableServer
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, backend="jnp", queries_per_pe=2)
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                      _serve_cfg(slab_steps=2, geometry_replan=True,
                                 geometry_hysteresis=1e9,
                                 geometry_min_slabs=1))
    op, keys, vals = trace_gen.mixed(60, key_words=2, val_words=2,
                                     mix=(0.95, 0.05, 0.0))
    srv.submit(op, keys, vals)
    srv.run()
    assert srv.migrations == 0               # margin never met
    assert srv.cfg.k == 4
    assert srv.geometry_plan is not None     # but the would-be plan is there
    assert srv.stats()["geometry"]["changed"] in (True, False)


def test_table_server_replan_off_by_flag(trace_gen):
    from repro.serving import TableServer
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, backend="jnp")
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                      _serve_cfg(slab_steps=2, geometry_replan=False))
    op, keys, vals = trace_gen.mixed(40, key_words=2, val_words=2)
    srv.submit(op, keys, vals)
    srv.run()
    assert srv.migrations == 0 and srv.geometry_plan is None
