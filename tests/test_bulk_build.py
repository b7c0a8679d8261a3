"""Bulk build + compaction (engine stage five, DESIGN.md §3.2) vs the
serialized-insert oracle — bit-exact table bytes and per-record reports on
both backends, including duplicate-heavy batches, bucket overflow (spill),
multi-pass placement, and the sharded builder under both routers at
``cfg.shards in {4, 8}`` (subprocess with 8 fake CPU devices).  The
hypothesis property (importorskip-guarded) checks the compaction contract:
bulk output is canonical (compact is the identity on it) and compaction of a
fragmented table preserves exactly the live record set."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import (
    OP_INSERT,
    HashTableConfig,
    XorHashTable,
    bulk_build,
    compact,
    init_table,
    run_stream,
)
from repro.core.engine import extract_records


def _cfg(**kw):
    base = dict(p=4, k=4, buckets=64, slots=4, replicate_reads=False,
                stagger_slots=True)
    base.update(kw)
    return HashTableConfig(**base)


def _records(rng, n, cfg, key_space=60):
    """Duplicate-heavy batch: ``n`` records over a small key pool, so both
    last-wins resolution and bucket overflow occur."""
    keys = np.zeros((n, cfg.key_words), np.uint32)
    keys[:, 0] = rng.integers(1, key_space, size=n)
    vals = rng.integers(1, 2 ** 32, size=(n, cfg.val_words), dtype=np.uint32)
    return keys, vals


def _serialized_oracle(cfg, rng_key, keys, vals):
    """Stream the records through the insert path ONE PER STEP on lane 0 —
    the layout bulk_build is defined to be byte-identical to."""
    tab = init_table(cfg, rng_key)
    n = keys.shape[0]
    N = cfg.queries_per_step
    ops = np.zeros((n, N), np.int32)
    ops[:, 0] = OP_INSERT
    K = np.zeros((n, N, cfg.key_words), np.uint32)
    K[:, 0] = keys
    V = np.zeros((n, N, cfg.val_words), np.uint32)
    V[:, 0] = vals
    tab2, res = run_stream(tab, jnp.array(ops), jnp.array(K), jnp.array(V),
                           backend="jnp")
    return tab2, np.asarray(res.ok)[:, 0]


def _assert_tables_equal(a, b, ctx=""):
    for nm, x, y in zip(("keys", "vals", "valid"), a.stores(), b.stores()):
        assert (np.asarray(x) == np.asarray(y)).all(), (ctx, nm)


def _first_occurrence(keys):
    seen, out = set(), np.zeros(len(keys), bool)
    for i, k in enumerate(map(tuple, keys)):
        out[i] = k not in seen
        seen.add(k)
    return out


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bulk_build_matches_serialized_insert_oracle(backend, rng, key):
    cfg = _cfg(buckets=8)                  # ~59 keys over 8x4 slots -> spill
    keys, vals = _records(rng, 300, cfg)
    oracle_tab, oracle_ok = _serialized_oracle(cfg, key, keys, vals)
    tab, rep = bulk_build(init_table(cfg, key), keys, vals, backend=backend)
    _assert_tables_equal(tab, oracle_tab, backend)
    assert (np.asarray(rep.placed) == oracle_ok).all()
    assert (np.asarray(rep.spilled) == ~oracle_ok).all()
    assert np.asarray(rep.spilled).any(), "stimulus must actually overflow"
    assert (np.asarray(rep.first) == _first_occurrence(keys)).all()
    assert int(rep.max_load) >= cfg.slots
    # spill_indices is the reported spill list (never a silent drop)
    assert (rep.spill_indices() == np.nonzero(~oracle_ok)[0]).all()


@pytest.mark.parametrize("chunk", [32, 128])
def test_bulk_build_pallas_multipass_bit_exact(chunk, rng, key):
    """Multi-group planes: the placement kernel streams its records through
    SMEM in ``chunk``-record grid steps and moves each touched 128-bucket
    group through VMEM once — byte-identical to the jnp scatter, and
    ``bulk_build`` end to end to the jnp backend."""
    from repro.core.engine import get_backend, plan_bulk_build
    from repro.core.hashing import h3_hash
    from repro.kernels.bulk_place import bulk_place_pallas
    cfg = _cfg(buckets=512)
    keys, vals = _records(rng, 300, cfg, key_space=400)
    ref, _ = bulk_build(init_table(cfg, key), keys, vals, backend="jnp")
    tab, _ = bulk_build(init_table(cfg, key), keys, vals, backend="pallas")
    _assert_tables_equal(tab, ref, chunk)
    t0 = init_table(cfg, key)
    plan = plan_bulk_build(jnp.array(keys), jnp.array(vals),
                           h3_hash(jnp.array(keys), t0.q_masks),
                           buckets=cfg.buckets, slots=cfg.slots)
    args = (plan["w_bucket"], plan["w_slot"], jnp.array(keys), plan["val_w"])
    geom = dict(buckets=cfg.buckets, slots=cfg.slots)
    want = get_backend("jnp").bulk_place(t0.packed[0], *args, **geom)
    got = bulk_place_pallas(*args, t0.packed[0], chunk=chunk, **geom)
    assert (np.asarray(got) == np.asarray(want)).all(), chunk


def test_bulk_build_empty_batch(key):
    cfg = _cfg()
    tab0 = init_table(cfg, key)
    tab, rep = bulk_build(tab0, np.zeros((0, cfg.key_words), np.uint32),
                          np.zeros((0, cfg.val_words), np.uint32))
    _assert_tables_equal(tab, tab0)
    assert rep.placed.shape == (0,) and int(rep.spill_count) == 0


@pytest.mark.parametrize("key_words", [1, 2])
def test_plan_host_and_xla_paths_bit_exact(key_words, rng):
    """plan_bulk_build has two implementations (numpy host pass via
    pure_callback, pure-XLA two-lexsort) picked by backend; they must agree
    field-for-field on dup-heavy batches with dead lanes.  key_words covers
    both host sort1 paths (packed-u64 fast path vs general lexsort)."""
    from repro.core.engine import plan_bulk_build
    n, B, S = 400, 8, 4
    keys = np.zeros((n, key_words), np.uint32)
    keys[:, 0] = rng.integers(1, 40, size=n)
    if key_words > 1:
        keys[:, 1] = rng.integers(0, 3, size=n)      # collisions in word 0
    vals = rng.integers(1, 2 ** 32, size=(n, 1), dtype=np.uint32)
    bucket = rng.integers(0, B, size=n).astype(np.int32)
    live = rng.random(n) > 0.1
    a = plan_bulk_build(jnp.array(keys), jnp.array(vals), jnp.array(bucket),
                        jnp.array(live), buckets=B, slots=S, host=True)
    b = plan_bulk_build(jnp.array(keys), jnp.array(vals), jnp.array(bucket),
                        jnp.array(live), buckets=B, slots=S, host=False)
    assert set(a) == set(b)
    for nm in a:
        x, y = np.asarray(a[nm]), np.asarray(b[nm])
        assert x.dtype == y.dtype, nm
        assert (x == y).all(), nm
    assert np.asarray(a["spilled"]).any(), "stimulus must actually overflow"


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_compact_is_canonical_and_preserves_records(backend, rng, key,
                                                    trace_gen):
    cfg = _cfg()
    # bulk output is already canonical: compact is the identity on it
    keys, vals = _records(rng, 200, cfg)
    tab, _ = bulk_build(init_table(cfg, key), keys, vals, backend=backend)
    _assert_tables_equal(compact(tab, backend=backend), tab, "fixed point")
    # fragment a table with a mixed S/I/U/D stream, then compact: the live
    # record set survives exactly and re-compaction is idempotent
    ops, k, v = map(jnp.array, trace_gen.stream_mixed(8, cfg.queries_per_step,
                                                      key_space=48))
    frag, _ = run_stream(init_table(cfg, key), ops, k, v, backend="jnp")
    dense = compact(frag, backend=backend)
    _assert_tables_equal(compact(dense, backend=backend), dense, "idempotent")

    def live_set(t):
        ks, vs, live, _ = map(np.asarray, extract_records(t))
        return {(tuple(a), tuple(b)) for a, b in zip(ks[live], vs[live])}

    assert live_set(dense) == live_set(frag)
    # densification: occupied slots are a prefix 0..count-1 of every bucket
    valid = np.asarray(dense.plaintext()[2])            # [B, S]
    counts = valid.sum(axis=1)
    assert all((valid[b, :c] == 1).all() and (valid[b, c:] == 0).all()
               for b, c in enumerate(counts))


SHARDED = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *

    for D in (4, 8):
        for router in ('skewproof', 'bounded'):
            cfg = HashTableConfig(p=D, k=max(D // 2, 1), buckets=32, slots=4,
                                  replicate_reads=False, stagger_slots=True,
                                  shards=D, router=router)
            mesh = make_ht_mesh(D)
            dtab = init_distributed_table(cfg, jax.random.key(1), mesh)
            T, nl = 16, 4
            N = D * nl
            rng = np.random.default_rng(D)
            keys = np.zeros((T, N, cfg.key_words), np.uint32)
            keys[:, :, 0] = rng.integers(1, 200, size=(T, N))  # dups + spill
            vals = rng.integers(1, 2 ** 32, size=(T, N, cfg.val_words),
                                dtype=np.uint32)
            build = make_distributed_bulk_build(mesh, cfg, router=router)
            dtab2, rep = build(dtab, jnp.array(keys), jnp.array(vals))
            # unsharded serialized-oracle reference with the SAME H3 params,
            # records flattened row-major == program order
            cfg_r = dataclasses.replace(cfg, shards=1)
            ref = init_table(cfg_r, jax.random.key(1))
            ref = XorHashTable(jnp.array(jax.device_get(dtab.q_masks)),
                               ref.packed, cfg_r)
            ref2, rrep = bulk_build(ref, keys.reshape(T * N, -1),
                                    vals.reshape(T * N, -1), backend='jnp')
            for nm, a, b in zip(('keys', 'vals', 'valid'), dtab2.stores(),
                                ref2.stores()):
                assert (np.asarray(a) == np.asarray(b)).all(), \\
                    (D, router, nm)
            for nm in ('placed', 'spilled', 'first', 'slot'):
                a = np.asarray(getattr(rep, nm)).reshape(T * N)
                b = np.asarray(getattr(rrep, nm))
                assert (a == b).all(), (D, router, nm)
            assert np.asarray(rep.spilled).any(), (D, router, 'no spill?')
            assert int(rep.max_load) == int(rrep.max_load), (D, router)
            # distributed compaction: bulk output is already canonical
            dcomp = make_distributed_compact(mesh, cfg)(dtab2)
            a, b = np.asarray(dcomp.packed), np.asarray(dtab2.packed)
            assert (a == b).all(), (D, router, 'compact')
    print('SHARDED_BULK_OK')
""")


def test_sharded_bulk_build_bit_exact_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "SHARDED_BULK_OK" in r.stdout, r.stdout + r.stderr


def test_bulk_build_compact_property():
    """Hypothesis: for ANY record batch, bulk output is canonical (compact
    == identity) and every placed record's key is resident with the
    last-wins value."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    cfg = _cfg(buckets=32, slots=2)
    tab0 = init_table(cfg, jax.random.key(7))

    @hyp.settings(max_examples=25, deadline=None,
                  suppress_health_check=[hyp.HealthCheck.too_slow])
    @hyp.given(st.lists(st.tuples(st.integers(1, 40),
                                  st.integers(1, 2 ** 32 - 1)),
                        min_size=0, max_size=64))
    def run(recs):
        keys = np.zeros((len(recs), cfg.key_words), np.uint32)
        vals = np.zeros((len(recs), cfg.val_words), np.uint32)
        for i, (k, v) in enumerate(recs):
            keys[i, 0], vals[i, 0] = k, v
        tab, rep = bulk_build(tab0, keys, vals, backend="jnp")
        _assert_tables_equal(compact(tab, backend="jnp"), tab)
        ks, vs, live, _ = map(np.asarray, extract_records(tab))
        resident = {tuple(a): tuple(b) for a, b in zip(ks[live], vs[live])}
        last = {}
        for k, v in zip(map(tuple, keys), map(tuple, vals)):
            last[k] = v
        placed = np.asarray(rep.placed)
        for i, k in enumerate(map(tuple, keys)):
            if placed[i]:
                assert resident[k] == last[k]
            else:
                assert k not in resident
        assert len(resident) == int(placed[
            np.asarray(rep.first)].sum() if len(recs) else 0)

    run()
