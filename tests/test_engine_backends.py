"""The pluggable query engine: jnp vs pallas backends must be bit-exact —
same StepResults AND same final table state — on randomized S/I/U/D traces,
for both replica layouts, with and without slot staggering.  Also covers
backend registry/resolution and the engine-integrated consistency checker."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH,
                        QueryBatch, apply_step, engine, init_table,
                        run_stream, schedule_queries)


def _run_backend(cfg, backend, ops, keys, vals, seed=0):
    cfg = dataclasses.replace(cfg, backend=backend)
    tab = init_table(cfg, jax.random.key(seed))
    tab, res = run_stream(tab, jnp.array(ops), jnp.array(keys),
                          jnp.array(vals))
    return tab, res


@pytest.mark.parametrize("replicate", [True, False])
@pytest.mark.parametrize("stagger", [False, True])
@pytest.mark.parametrize("kw", [1, 2])
def test_backends_bit_exact_on_random_trace(replicate, stagger, kw,
                                            trace_gen):
    cfg = HashTableConfig(p=4, k=2, buckets=128, slots=4, key_words=kw,
                          val_words=1, replicate_reads=replicate,
                          stagger_slots=stagger)
    op, keys, vals = trace_gen.mixed(96, kw)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab_j, res_j = _run_backend(cfg, "jnp", ops, kk, vv)
    tab_p, res_p = _run_backend(cfg, "pallas", ops, kk, vv)
    for name in ("found", "value", "ok", "bucket"):
        a = np.asarray(getattr(res_j, name))
        b = np.asarray(getattr(res_p, name))
        assert (a == b).all(), f"StepResults.{name} diverged"
    for name, a, b in zip(("keys", "vals", "valid"), tab_j.stores(),
                          tab_p.stores()):
        a, b = np.asarray(a), np.asarray(b)
        assert (a == b).all(), f"table.{name} diverged ({(a != b).sum()} words)"


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_engine_step_matches_apply_step(backend, trace_gen):
    """apply_step routes through the engine — engine.step is the same thing."""
    cfg = HashTableConfig(p=4, k=4, buckets=64, slots=4, backend=backend)
    op, keys, vals = trace_gen.mixed(16, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    tab_a, tab_b = tab, tab
    for t in range(ops.shape[0]):
        batch = QueryBatch(jnp.array(ops[t]), jnp.array(kk[t]),
                           jnp.array(vv[t]))
        tab_a, res_a = apply_step(tab_a, batch)
        tab_b, res_b = engine.step(tab_b, batch)
        assert (np.asarray(res_a.found) == np.asarray(res_b.found)).all()
        assert (np.asarray(res_a.value) == np.asarray(res_b.value)).all()
    assert (np.asarray(tab_a.packed) == np.asarray(tab_b.packed)).all()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_probe_commit_api(backend, rng):
    """The two-stage engine API: probe alone is read-only; probe+commit ==
    one apply_step."""
    cfg = HashTableConfig(p=4, k=4, buckets=64, slots=2, backend=backend)
    tab = init_table(cfg, jax.random.key(0))
    op = np.array([OP_INSERT, OP_INSERT, OP_SEARCH, 0], np.int32)
    keys = np.array([[3], [5], [3], [0]], np.uint32)
    vals = np.array([[30], [50], [0], [0]], np.uint32)
    batch = QueryBatch(jnp.array(op), jnp.array(keys), jnp.array(vals))
    pr = engine.probe(tab, batch)
    assert isinstance(pr, engine.ProbeResult)
    assert not np.asarray(pr.found).any()           # empty table, no commit
    tab2 = engine.commit(tab, pr, batch)
    # a second probe against the committed table finds the inserts
    pr2 = engine.probe(tab2, QueryBatch(
        jnp.full(4, OP_SEARCH, np.int32), jnp.array(keys), jnp.array(vals)))
    assert bool(np.asarray(pr2.found)[0]) and bool(np.asarray(pr2.found)[1])


def test_backend_registry_and_resolution():
    assert set(engine.available_backends()) >= {"jnp", "pallas"}
    with pytest.raises(ValueError):
        engine.get_backend("nope")
    with pytest.raises(ValueError):
        HashTableConfig(backend="nope")
    cfg = HashTableConfig(p=2, k=2, buckets=16, slots=2, backend="jnp")
    tab = init_table(cfg, jax.random.key(0))
    assert engine.resolve_backend(cfg, tab).name == "jnp"
    cfg_p = dataclasses.replace(cfg, backend="pallas")
    assert engine.resolve_backend(cfg_p, tab).name == "pallas"
    # auto: pallas only on TPU (this host is CPU -> jnp)
    cfg_a = dataclasses.replace(cfg, backend="auto")
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert engine.resolve_backend(cfg_a, tab).name == expect


def test_vmem_budget_auto_fallback(monkeypatch):
    """backend='pallas' silently degrades to jnp when a replica exceeds the
    VMEM table budget (HBM-resident regime)."""
    import repro.kernels.ops as kops
    cfg = HashTableConfig(p=2, k=2, buckets=16, slots=2, backend="pallas")
    tab = init_table(cfg, jax.random.key(0))
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES", 16)
    assert engine.resolve_backend(cfg, tab).name == "jnp"
    # and the step still runs correctly through the fallback
    batch = QueryBatch(jnp.array([OP_INSERT, OP_SEARCH], np.int32),
                       jnp.array([[7], [7]], np.uint32),
                       jnp.array([[9], [0]], np.uint32))
    tab2, _ = engine.step(tab, batch)
    _, res = engine.step(tab2, QueryBatch(
        jnp.full(2, OP_SEARCH, np.int32),
        jnp.array([[7], [7]], np.uint32), jnp.zeros((2, 1), jnp.uint32)))
    assert bool(np.asarray(res.found)[0])


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_duplicate_write_targets_last_wins(backend):
    """Beyond the paper's one-write-per-port-per-cycle regime (qpp > 1), two
    same-step writes from the SAME port to the SAME (bucket, slot) resolve
    last-wins in lane order — identically on every backend."""
    cfg = HashTableConfig(p=2, k=2, buckets=32, slots=2, queries_per_pe=2,
                          backend=backend)
    tab = init_table(cfg, jax.random.key(0))
    # lanes 0 and 2 both map to PE 0 / port 0; same key => same target row
    op = np.array([OP_INSERT, 0, OP_INSERT, 0], np.int32)
    keys = np.array([[9], [0], [9], [0]], np.uint32)
    vals = np.array([[111], [0], [222], [0]], np.uint32)
    tab, _ = apply_step(tab, QueryBatch(jnp.array(op), jnp.array(keys),
                                        jnp.array(vals)))
    _, res = apply_step(tab, QueryBatch(
        jnp.array([OP_SEARCH, 0, 0, 0], np.int32), jnp.array(keys),
        jnp.zeros_like(jnp.array(vals))))
    assert bool(np.asarray(res.found)[0])
    assert int(np.asarray(res.value)[0, 0]) == 222, "later lane must win"


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_consistency_engine_errors_backend_agnostic(backend, rng):
    """measure_engine_errors reports the same error count on any backend:
    one shared semantics, one relaxed-consistency window."""
    from repro.core.consistency import measure_engine_errors
    cfg = HashTableConfig(p=4, k=4, buckets=64, slots=4, queries_per_pe=2)
    n = 64
    trace = np.stack([
        rng.choice([OP_SEARCH, OP_INSERT, OP_DELETE], size=n, p=[.4, .4, .2]),
        rng.integers(1, 12, size=n),          # tiny key space: forced hazards
        rng.integers(1, 2 ** 31, size=n),
    ], axis=1).astype(np.int64)
    n_err, n_q = measure_engine_errors(trace, cfg, backend=backend)
    n_err_j, _ = measure_engine_errors(trace, cfg, backend="jnp")
    assert n_q == n
    assert n_err == n_err_j