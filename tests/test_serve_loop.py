"""Continuous-batching serve loop (DESIGN.md §4): host-side measurement
equivalence, slab packing, the LRU plan cache, and TableServer's bit-exact
agreement with the one-shot stream — plus the sharded conformance run in a
fake-device subprocess."""
import functools
import glob
import os
import re
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_NOP,
                        OP_SEARCH, engine, h3_hash, init_table, make_h3_params)
from repro.serving import (PlanCache, ServeConfig, SlabQueue, SlabRequest,
                           TableServer, measure_loads_host, op_mix_bucket)
from repro.serving.engine import StepReport
from repro.serving.serve_loop import h3_hash_host

REPO = os.path.dirname(os.path.dirname(__file__))


# --------------------------------------------------------------------------
# host-side measurement == device pass 1
# --------------------------------------------------------------------------

def test_h3_hash_host_matches_device(rng):
    qm = make_h3_params(jax.random.key(3), key_words=2, index_bits=10)
    keys = rng.integers(0, 1 << 32, size=(257, 2), dtype=np.uint32)
    dev = np.asarray(h3_hash(jnp.asarray(keys), qm))
    host = h3_hash_host(keys, np.asarray(jax.device_get(qm)))
    np.testing.assert_array_equal(dev, host)


def test_measure_loads_host_matches_route_load_pass(rng):
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 10, slots=2, key_words=2,
                          queries_per_pe=4, shards=4, router="bounded")
    qm = make_h3_params(jax.random.key(7), key_words=2,
                        index_bits=cfg.index_bits)
    T, N = 6, cfg.queries_per_step
    keys = rng.integers(1, 1 << 32, size=(T, N, 2), dtype=np.uint32)
    bucket = h3_hash(jnp.asarray(keys.reshape(T * N, 2)), qm)
    owner = engine.shard_owner(cfg, bucket).reshape(T, N)
    loads_d, pair_d = engine.route_load_pass(cfg, owner)
    loads_h, pair_h = measure_loads_host(cfg, np.asarray(jax.device_get(qm)),
                                         keys)
    np.testing.assert_array_equal(np.asarray(loads_d), loads_h)
    np.testing.assert_array_equal(np.asarray(pair_d), pair_h)


# --------------------------------------------------------------------------
# slab packing
# --------------------------------------------------------------------------

def _pack_all(queue):
    slabs = []
    while queue.pending_requests:
        slabs.append(queue.next_slab())
    return slabs


def _check_packing(requests, slabs, steps, lanes):
    """The packing invariant: concatenating the live lanes of every slab (in
    dispatch order) reproduces the submitted requests' lanes exactly — no
    drop, no reorder, no duplicate — and every non-live lane is a NOP."""
    flat_ops = np.concatenate([s.ops.reshape(-1) for s in slabs])
    flat_keys = np.concatenate([s.keys.reshape(s.ops.size, -1)
                                for s in slabs])
    flat_vals = np.concatenate([s.vals.reshape(s.ops.size, -1)
                                for s in slabs])
    live = np.zeros(len(flat_ops), bool)
    cursor = 0
    for s_i, slab in enumerate(slabs):
        assert slab.ops.shape == (steps, lanes)
        base = s_i * steps * lanes
        for req, r_off, f_off, cnt in slab.spans:
            lo = base + f_off
            np.testing.assert_array_equal(flat_ops[lo:lo + cnt],
                                          req.ops[r_off:r_off + cnt])
            np.testing.assert_array_equal(flat_keys[lo:lo + cnt],
                                          req.keys[r_off:r_off + cnt])
            np.testing.assert_array_equal(flat_vals[lo:lo + cnt],
                                          req.vals[r_off:r_off + cnt])
            live[lo:lo + cnt] = True
        assert slab.live == sum(cnt for *_, cnt in slab.spans)
    # arrival order: the live lanes, in slab order, ARE the requests' lanes
    # concatenated in submission order
    want_ops = np.concatenate([r.ops for r in requests])
    np.testing.assert_array_equal(flat_ops[live], want_ops)
    want_keys = np.concatenate([r.keys for r in requests])
    np.testing.assert_array_equal(flat_keys[live], want_keys)
    # padding is NOPs with zero keys (the dead-lane sentinel)
    assert (flat_ops[~live] == OP_NOP).all()
    assert (flat_keys[~live] == 0).all()


def test_slab_packing_roundtrip(rng, trace_gen):
    steps, lanes = 3, 4
    q = SlabQueue(steps, lanes, key_words=2, val_words=2)
    reqs = []
    for i, n in enumerate([5, 1, 17, 4, 12, 2, 9]):
        op, keys, vals = trace_gen.mixed(n, key_words=2, val_words=2)
        req = SlabRequest(rid=i, ops=op, keys=keys, vals=vals)
        q.submit(req)
        reqs.append(req)
    slabs = _pack_all(q)
    _check_packing(reqs, slabs, steps, lanes)
    assert q.pending_lanes == 0


def test_slab_queue_admission_cap(trace_gen):
    q = SlabQueue(2, 4, key_words=1, val_words=1, max_requests=2)
    for i in range(2):
        op, keys, vals = trace_gen.mixed(3)
        q.submit(SlabRequest(rid=i, ops=op, keys=keys, vals=vals))
    op, keys, vals = trace_gen.mixed(3)
    with pytest.raises(RuntimeError, match="admission queue full"):
        q.submit(SlabRequest(rid=9, ops=op, keys=keys, vals=vals))


def test_slab_packing_property_hypothesis():
    """Property form of the packing invariant over generated request-size
    mixes (sub-lane, lane-straddling, multi-slab requests)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from conftest import TraceGen

    @hyp.given(sizes=st.lists(st.integers(min_value=1, max_value=40),
                              min_size=1, max_size=12),
               steps=st.integers(min_value=1, max_value=4),
               lanes=st.sampled_from([2, 4, 8]),
               seed=st.integers(min_value=0, max_value=2 ** 16))
    @hyp.settings(deadline=None, max_examples=40)
    def prop(sizes, steps, lanes, seed):
        gen = TraceGen(np.random.default_rng(seed))
        q = SlabQueue(steps, lanes, key_words=2, val_words=2)
        reqs = []
        for i, n in enumerate(sizes):
            op, keys, vals = gen.mixed(n, key_words=2, val_words=2)
            req = SlabRequest(rid=i, ops=op, keys=keys, vals=vals)
            q.submit(req)
            reqs.append(req)
        _check_packing(reqs, _pack_all(q), steps, lanes)

    prop()


# --------------------------------------------------------------------------
# plan cache
# --------------------------------------------------------------------------

def _cache_cfg(n_local=16):
    return HashTableConfig(p=2, k=2, buckets=1 << 10, slots=2, key_words=2,
                           queries_per_pe=n_local, shards=2,
                           router="bounded")


def test_plan_cache_cold_then_warm():
    cfg = _cache_cfg()
    pc = PlanCache(cfg, plans=4)
    T, D, n = 4, 2, 16
    loads = np.full((T, D), n, np.int64)
    pair = np.full((D, D), T * n // D, np.int64)
    p1, hit1 = pc.lookup(loads, pair)
    p2, hit2 = pc.lookup(loads, pair)
    assert not hit1 and hit2
    assert p2 is p1, "a warm hit returns the cached frozen plan"
    assert pc.stats() == {"entries": 1, "hits": 1, "misses": 1,
                          "evictions": 0, "hit_rate": 0.5}


def test_plan_cache_coverage_miss_replans():
    """Same cache key (same measured-width bucket and mix), but the new
    batch's pair totals exceed the cached plan's FIFO capacity — the safety
    check must force a replan instead of silently dropping lanes."""
    cfg = _cache_cfg()
    T, D, n = 4, 2, 16
    loads = np.full((T, D), n, np.int64)            # max load 16 both times
    even = np.full((D, D), 32, np.int64)            # pair max 32
    skew = np.array([[48, 16], [16, 48]], np.int64)  # pair max 48
    pc = PlanCache(cfg, plans=4)
    p1, _ = pc.lookup(loads, even)
    p2, hit2 = pc.lookup(loads, skew)
    assert not hit2, "covers() must reject the capacity-exceeding batch"
    assert p2.pair_capacity >= 48 > p1.pair_capacity
    assert p2.covers(int(loads.max()), int(skew.max()))
    # the replacement plan covers the even batch too -> now a hit
    p3, hit3 = pc.lookup(loads, even)
    assert hit3 and p3 is p2


def test_plan_cache_eviction():
    cfg = _cache_cfg()
    pc = PlanCache(cfg, plans=2)
    D, n = 2, 16
    shapes = [2, 4, 8]                   # three distinct T -> three keys
    for T in shapes:
        pc.lookup(np.full((T, D), n, np.int64),
                  np.full((D, D), T * n // D, np.int64))
    assert len(pc) == 2 and pc.evictions == 1
    # T=2 (the LRU-oldest) was evicted: looking it up again misses
    _, hit = pc.lookup(np.full((2, D), n, np.int64),
                       np.full((D, D), 16, np.int64))
    assert not hit


def test_plan_cache_disabled():
    pc = PlanCache(_cache_cfg(), plans=0)
    loads = np.full((2, 2), 16, np.int64)
    pair = np.full((2, 2), 16, np.int64)
    _, h1 = pc.lookup(loads, pair)
    _, h2 = pc.lookup(loads, pair)
    assert not h1 and not h2 and len(pc) == 0


def test_op_mix_bucket():
    search = np.full(32, OP_SEARCH, np.int32)
    mutate = np.full(32, OP_INSERT, np.int32)
    assert op_mix_bucket(search) == 0
    assert op_mix_bucket(mutate) == 7
    assert op_mix_bucket(np.full(8, OP_NOP, np.int32)) == 0  # dead slab
    mixed = np.concatenate([search, mutate])
    assert 0 < op_mix_bucket(mixed) < 7


# --------------------------------------------------------------------------
# TableServer: bit-exact vs the one-shot stream
# --------------------------------------------------------------------------

def _oneshot_oracle(cfg, trace, backend):
    """The identical concatenated trace through one run_stream call."""
    N = cfg.queries_per_step
    tot = sum(len(op) for op, _, _ in trace)
    T = -(-tot // N)
    op = np.zeros(T * N, np.int32)
    kk = np.zeros((T * N, cfg.key_words), np.uint32)
    vv = np.zeros((T * N, cfg.val_words), np.uint32)
    off = 0
    for o, k, v in trace:
        op[off:off + len(o)] = o
        kk[off:off + len(o)] = k
        vv[off:off + len(o)] = v
        off += len(o)
    table = init_table(cfg, jax.random.key(0))
    _, res = engine.run_stream(table, jnp.asarray(op.reshape(T, N)),
                               jnp.asarray(kk.reshape(T, N, -1)),
                               jnp.asarray(vv.reshape(T, N, -1)),
                               backend=backend)
    found = np.asarray(res.found).reshape(-1)[:tot]
    ok = np.asarray(res.ok).reshape(-1)[:tot]
    value = np.asarray(res.value).reshape(T * N, -1)[:tot]
    return found, ok, value


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_table_server_bit_exact_vs_oneshot(backend, trace_gen):
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, replicate_reads=False,
                          stagger_slots=True, backend=backend)
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    # collision-heavy mixed trace: duplicate keys within and across slabs,
    # deletes racing inserts — the commit-order stimulus
    trace = [trace_gen.duplicate_heavy(n, key_words=2, key_space=32,
                                       val_words=2)
             for n in (7, 19, 3, 26, 11)]
    table = init_table(cfg, jax.random.key(0))
    # force the 2-deep window even on 1-CPU hosts: overlap correctness (the
    # table chaining through un-synced in-flight slabs) must be exercised
    srv = TableServer(cfg, table, stream,
                      ServeConfig(slab_steps=2, serve_double_buffer=True))
    assert srv.window == 2
    reqs = [srv.submit(op, keys, vals) for op, keys, vals in trace]
    finished = srv.run()
    assert sorted(r.rid for r in finished) == list(range(len(trace)))
    found, ok, value = _oneshot_oracle(cfg, trace, backend)
    off = 0
    for r in reqs:
        n = len(r.ops)
        np.testing.assert_array_equal(r.found, found[off:off + n])
        np.testing.assert_array_equal(r.ok, ok[off:off + n])
        np.testing.assert_array_equal(r.value, value[off:off + n])
        off += n


def test_table_server_single_buffer_same_results(trace_gen):
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, backend="jnp")
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    trace = [trace_gen.mixed(n, key_words=2, key_space=64, val_words=2)
             for n in (9, 14, 5)]
    out = []
    for dbl in (False, True):
        srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                          ServeConfig(slab_steps=2, serve_double_buffer=dbl))
        reqs = [srv.submit(*t) for t in trace]
        srv.run()
        out.append([(r.found.copy(), r.ok.copy(), r.value.copy())
                    for r in reqs])
    for (f1, o1, v1), (f2, o2, v2) in zip(*out):
        np.testing.assert_array_equal(f1, f2)
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(v1, v2)


def test_table_server_submit_after_run_raises(trace_gen):
    cfg = HashTableConfig(p=2, k=2, buckets=1 << 6, slots=2, backend="jnp")
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                      ServeConfig(slab_steps=1))
    op, keys, vals = trace_gen.mixed(3)
    srv.submit(op, keys, vals)
    srv.run()
    with pytest.raises(RuntimeError, match="submit before run"):
        srv.submit(op, keys, vals)


def test_step_report_quiescence(trace_gen):
    assert StepReport(finished=[], queued=0, occupied=0).quiescent
    assert not StepReport(finished=[], queued=1, occupied=0).quiescent
    assert not StepReport(finished=[], queued=0, occupied=2).quiescent
    cfg = HashTableConfig(p=2, k=2, buckets=1 << 6, slots=2, backend="jnp")
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    srv = TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                      ServeConfig(slab_steps=1, serve_double_buffer=True))
    op, keys, vals = trace_gen.mixed(2 * cfg.queries_per_step + 1)
    req = srv.submit(op, keys, vals)
    r1 = srv.step()                 # dispatches slab 1, nothing retires yet
    assert r1.queued == 1 and r1.occupied == 1 and not r1.quiescent
    reports = [r1]
    while not reports[-1].quiescent:
        reports.append(srv.step())
    assert req.done
    assert [r for rep in reports for r in rep.finished] == [req]
    # termination came from the report, not an extra empty sweep: the final
    # report is the one that retired the last slab
    assert reports[-1].finished or reports[-2].finished


# --------------------------------------------------------------------------
# spans and scopes (repro.tracing)
# --------------------------------------------------------------------------

def _small_server(k):
    cfg = HashTableConfig(p=4, k=k, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, backend="jnp")
    stream = jax.jit(engine.run_stream, static_argnames=("backend",))
    return TableServer(cfg, init_table(cfg, jax.random.key(0)), stream,
                       ServeConfig(slab_steps=2, serve_double_buffer=True))


def _recorded_spans(log_dir):
    """``(name, start, end, stats)`` of every ``kv.*`` host event."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("kv.")]


@pytest.mark.parametrize("k", [4, 2], ids=["plain", "classed"])
def test_traced_server_records_its_spans(k, tmp_path, trace_gen):
    """One ``kv.submit`` per request, one ``kv.pack``/``kv.dispatch``/
    ``kv.retire`` per slab, each inside a ``kv.step`` and tagged with its
    slab's number, and every ``kv.retire.wait`` inside its ``kv.retire``."""
    srv = _small_server(k)
    trace = [trace_gen.mixed(n, key_words=2, key_space=64, val_words=2)
             for n in (9, 14, 5, 1, 30)]
    tracing.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in trace:
            srv.submit(*t)
        srv.run()
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
    ev = _recorded_spans(str(tmp_path))

    def named(n):
        return [e for e in ev if e[0] == n]

    def inside(e, outer):
        return outer[1] <= e[1] and e[2] <= outer[2]

    assert len(named("kv.submit")) == len(trace)
    steps = named("kv.step")
    for n in ("kv.pack", "kv.dispatch", "kv.retire"):
        got = named(n)
        assert sorted(e[3]["slab"] for e in got) == list(range(srv.slabs))
        assert all(any(inside(e, s) for s in steps) for e in got)
    retires = named("kv.retire")
    waits = named("kv.retire.wait")
    assert len(waits) == len(retires)
    assert all(any(inside(w, r) and w[3] == r[3] for r in retires)
               for w in waits)
    # the geometry replan works from the second dispatch on; no resize
    migrate = named("kv.migrate")
    assert len(migrate) == srv.slabs - 1
    assert all(any(inside(e, s) for s in steps) for e in migrate)


def test_spans_off_construct_no_annotation(monkeypatch, trace_gen):
    class Refused:
        def __init__(self, *a, **kw):
            raise AssertionError("TraceAnnotation built with recording off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    assert not tracing.enabled
    srv = _small_server(2)
    req = srv.submit(*trace_gen.mixed(11, key_words=2, val_words=2))
    srv.run()
    assert req.done
    tracing.enable()
    try:        # the patched class is the one a recorded span would build
        with pytest.raises(AssertionError, match="recording off"):
            srv.step()
    finally:
        tracing.disable()


def test_served_stream_carries_each_scope():
    """The served stream keeps the seam's scopes around the kernel, and
    hands the stored table to it as it is: no op under a repack scope, and
    the donated table parameter aliased to the table output."""
    cfg = HashTableConfig(p=4, k=4, buckets=1 << 8, slots=4, key_words=2,
                          val_words=2, replicate_reads=False,
                          backend="pallas")
    stream = jax.jit(functools.partial(engine.run_stream, backend="pallas"),
                     donate_argnums=(0,))
    T, N = 2, cfg.queries_per_step
    hlo = stream.lower(init_table(cfg, jax.random.key(0)),
                       jnp.zeros((T, N), jnp.int32),
                       jnp.zeros((T, N, 2), jnp.uint32),
                       jnp.zeros((T, N, 2), jnp.uint32)).compile().as_text()
    for scope in ("kv.h3", "kv.prepass", "kv.unpermute", "xor_stream"):
        assert f"/{scope}/" in hlo, scope
    for scope in ("kv.table_pack", "kv.table_unpack"):
        assert scope not in hlo, scope
    # flat argument 1 (the stored table) is flat result 1: in place
    assert re.search(r"\{1\}: \(1, \{\}, (may|must)-alias\)", hlo), \
        hlo.splitlines()[0]


# --------------------------------------------------------------------------
# perf model
# --------------------------------------------------------------------------

def test_serve_loop_model_monotonicity():
    from repro.core.perfmodel import serve_loop_modeled, serve_plan_seconds
    cfg = HashTableConfig(p=8, k=8, buckets=1 << 12, slots=4, shards=4,
                          router="bounded")
    cold = serve_loop_modeled(cfg, 8, hit_rate=0.0, double_buffer=False)
    warm = serve_loop_modeled(cfg, 8, hit_rate=1.0, double_buffer=False)
    dbl = serve_loop_modeled(cfg, 8, hit_rate=1.0, double_buffer=True)
    padded = serve_loop_modeled(cfg, 8, hit_rate=1.0, pad_fraction=0.25,
                                double_buffer=True)
    assert warm["mops"] > cold["mops"], "hits amortize planning away"
    assert dbl["mops"] >= warm["mops"], "overlap can only help"
    assert padded["mops"] < dbl["mops"], "padding is pure throughput loss"
    for m in (cold, warm, dbl):
        assert m["p99_seconds"] > m["p50_seconds"]
    assert serve_plan_seconds(256, 1.0) < serve_plan_seconds(256, 0.5) \
        < serve_plan_seconds(256, 0.0)
    single_cfg = HashTableConfig(p=8, k=8, buckets=1 << 12, slots=4)
    assert serve_loop_modeled(single_cfg, 8)["mops"] > 0


# --------------------------------------------------------------------------
# sharded conformance (subprocess, fake devices)
# --------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import HashTableConfig
from repro.core.distributed import (init_distributed_table,
                                    make_distributed_stream, make_ht_mesh)
from repro.serving import ServeConfig, TableServer

import sys
sys.path.insert(0, "tests")
from conftest import TraceGen

D = 2
cfg = HashTableConfig(p=D, k=D, buckets=1 << 8, slots=2, key_words=2,
                      val_words=2, queries_per_pe=2, replicate_reads=False,
                      stagger_slots=True, shards=D, router="bounded")
mesh = make_ht_mesh(D)
stream = make_distributed_stream(mesh, cfg)
gen = TraceGen(np.random.default_rng(0))
trace = [gen.mixed(n, key_words=2, key_space=40, val_words=2)
         for n in (6, 13, 3, 9, 18, 5)]

# serve loop: forced 2-deep window, tiny plan cache so evictions fire
srv = TableServer(cfg, init_distributed_table(cfg, jax.random.key(0), mesh),
                  stream, ServeConfig(slab_steps=2, plan_cache_plans=2,
                                      serve_double_buffer=True))
reqs = [srv.submit(*t) for t in trace]
srv.run()
stats = srv.plan_cache.stats()
assert stats["hits"] + stats["misses"] == srv.slabs, stats

# one-shot bounded oracle: same concatenated trace, stock wrapper per call
N = cfg.queries_per_step
tot = sum(len(op) for op, _, _ in trace)
T = -(-tot // N)
op = np.zeros(T * N, np.int32)
kk = np.zeros((T * N, 2), np.uint32)
vv = np.zeros((T * N, 2), np.uint32)
off = 0
for o, k, v in trace:
    op[off:off + len(o)] = o; kk[off:off + len(o)] = k
    vv[off:off + len(o)] = v; off += len(o)
args = (jnp.asarray(op.reshape(T, N)), jnp.asarray(kk.reshape(T, N, 2)),
        jnp.asarray(vv.reshape(T, N, 2)))
_, res_b = stream(init_distributed_table(cfg, jax.random.key(0), mesh), *args)

# replicated oracle: same trace through the shards=1 mapping
import dataclasses
cfg_rep = dataclasses.replace(cfg, shards=1, router="skewproof")
rep = make_distributed_stream(mesh, cfg_rep)
_, res_r = rep(init_distributed_table(cfg_rep, jax.random.key(0)), *args)

for res in (res_b, res_r):
    found = np.asarray(res.found).reshape(-1)[:tot]
    ok = np.asarray(res.ok).reshape(-1)[:tot]
    value = np.asarray(res.value).reshape(T * N, -1)[:tot]
    off = 0
    for r in reqs:
        n = len(r.ops)
        np.testing.assert_array_equal(r.found, found[off:off + n])
        np.testing.assert_array_equal(r.ok, ok[off:off + n])
        np.testing.assert_array_equal(r.value, value[off:off + n])
        off += n
print("SERVE_CONFORMANCE_OK", stats["hits"], stats["evictions"])
"""


def test_sharded_serve_conformance():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SERVE_CONFORMANCE_OK" in r.stdout
