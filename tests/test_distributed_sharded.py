"""Bucket-sharded distributed stream (core.distributed, cfg.shards > 1) vs
the replicated scanned oracle — bit-exact on randomized S/I/U/D traces for
two shard counts, live-sharding capacity asserts, routing round-trip under
arbitrary key skew, and the sharded prefix cache.  Runs in a subprocess with
8 fake CPU devices so the main test session keeps its single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

BITEXACT = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    for D in (4, 8):
        cfg = HashTableConfig(p=D, k=max(D // 2, 1), buckets=256, slots=4,
                              replicate_reads=False, stagger_slots=True,
                              shards=D, backend='BACKEND')
        mesh = make_ht_mesh(D)
        tab_s = init_distributed_table(cfg, jax.random.key(1), mesh)
        # each device holds only buckets/shards of the table (live sharding)
        from repro.kernels.packed import packed_groups
        arr = tab_s.packed
        shp = arr.sharding.shard_shape(arr.shape)
        assert shp[1] == packed_groups(cfg.local_buckets), shp
        assert cfg.local_buckets == cfg.buckets // D
        assert len({s.device for s in arr.addressable_shards}) == D
        cfg_rep = dataclasses.replace(cfg, shards=1)
        tab_r = init_distributed_table(cfg_rep, jax.random.key(1))
        stream_s = make_distributed_stream(mesh, cfg)
        stream_r = make_distributed_stream(mesh, cfg_rep)
        T, nl = 6, 4
        N = D * nl
        # randomized S/I/U/D trace in a small key space (collisions, updates
        # and deletes of live keys all occur) — the shared conftest generator
        gen = TraceGen(np.random.default_rng(D))
        ops, keys, vals = map(jnp.array, gen.stream_mixed(T, N, key_space=48))
        ts, rs = stream_s(tab_s, ops, keys, vals)
        tr, rr = stream_r(tab_r, ops, keys, vals)
        for nm in ('found', 'value', 'ok', 'bucket'):
            a, b = np.asarray(getattr(rs, nm)), np.asarray(getattr(rr, nm))
            assert (a == b).all(), (D, nm)
        # the gathered sharded table == the replicated table, byte for byte
        for nm, a, b in zip(('keys', 'vals', 'valid'), ts.stores(),
                            tr.stores()):
            assert (np.asarray(a) == np.asarray(b)).all(), (D, nm)
        # T == 1 special case: the rewritten per-step entry agrees too
        step_s = make_distributed_step(mesh, cfg)
        step_r = make_distributed_step(mesh, cfg_rep)
        t1s = step_s(tab_s, ops[0], keys[0], vals[0])
        t1r = step_r(tab_r, ops[0], keys[0], vals[0])
        assert (np.asarray(t1s[1].found) == np.asarray(t1r[1].found)).all()
        assert (np.asarray(t1s[0].stores()[0])
                == np.asarray(t1r[0].stores()[0])).all()
    print('SHARDED_BITEXACT_OK')
""")

SKEW = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    D, nl = 8, 4
    N = D * nl
    cfg = HashTableConfig(p=D, k=4, buckets=512, slots=4,
                          replicate_reads=False, stagger_slots=True, shards=D)
    mesh = make_ht_mesh(D)
    tab = init_distributed_table(cfg, jax.random.key(0), mesh)
    stream = make_distributed_stream(mesh, cfg)
    # adversarial skew: every key owned by ONE shard (id 5) — the routing
    # capacity argument (n slots per destination per origin) must absorb it
    gen = TraceGen(np.random.default_rng(0))
    all_keys = gen.one_shard_keys(cfg, tab.q_masks, 5, N)
    vals = (all_keys + 17).astype(np.uint32)
    # step 0: EVERY lane inserts — only NSQ-capable origins (device < k) may
    # land theirs; step 1: every origin device searches the landed keys
    n_ins = cfg.k * nl
    srch = np.resize(all_keys[:n_ins], (N, 1))
    srch_vals = np.resize(vals[:n_ins], (N, 1))
    ops = jnp.array(np.stack([np.full(N, OP_INSERT, np.int32),
                              np.full(N, OP_SEARCH, np.int32)]))
    keys = jnp.array(np.stack([all_keys, srch]))
    vv = jnp.array(np.stack([vals, srch_vals]))
    tab2, res = stream(tab, ops, keys, vv)
    ok0 = np.asarray(res.ok)[0]
    assert ok0[:n_ins].all(), 'all-one-shard inserts must land'
    assert not ok0[n_ins:].any(), 'search-only origins reject NSQs'
    # results land on ORIGIN lanes: every lane of step 1 finds its key
    assert np.asarray(res.found)[1].all()
    assert (np.asarray(res.value)[1, :, 0] == srch_vals[:, 0]).all()
    # the whole population lives on shard 5's partition and nowhere else
    occupied = np.asarray(tab2.stores()[2]).sum(axis=(0, 1, 3))  # per bucket
    lb = cfg.local_buckets
    assert occupied[5 * lb:(5 + 1) * lb].sum() > 0
    assert occupied[:5 * lb].sum() == 0 and occupied[6 * lb:].sum() == 0
    # bit-exact against the replicated oracle under the same skew
    cfg_rep = dataclasses.replace(cfg, shards=1)
    tab_r = init_distributed_table(cfg_rep, jax.random.key(0))
    tr, rr = make_distributed_stream(mesh, cfg_rep)(tab_r, ops, keys, vv)
    assert (np.asarray(res.found) == np.asarray(rr.found)).all()
    assert (np.asarray(res.value) == np.asarray(rr.value)).all()
    assert (np.asarray(tab2.stores()[0]) == np.asarray(tr.stores()[0])).all()
    print('SHARDED_SKEW_OK')
""")

PREFIX_CACHE = textwrap.dedent("""
    import numpy as np
    from repro.serving.prefix_cache import PrefixCache

    pc = PrefixCache(num_pages=64, p=8, shards=4)
    assert pc.cfg.shards == 4
    from repro.kernels.packed import packed_groups
    tab = pc.table.packed
    assert (tab.sharding.shard_shape(tab.shape)[1]
            == packed_groups(pc.cfg.local_buckets))
    keys = np.arange(1, 25, dtype=np.uint64) * 0x9E3779B97F4A7C15
    pages = pc.admit_batch(keys)
    assert (pages >= 0).all() and len(set(pages.tolist())) == len(keys)
    hit, pg = pc.lookup_batch(keys)
    assert hit.all() and (pg == pages).all()
    miss, _ = pc.lookup_batch(keys + np.uint64(1))
    assert not miss.any()
    print('SHARDED_PREFIX_OK')
""")


def _run(script: str, token: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert token in r.stdout, r.stdout + r.stderr


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_stream_bit_exact_vs_replicated_8dev(backend):
    _run(BITEXACT.replace("BACKEND", backend), "SHARDED_BITEXACT_OK")


def test_sharded_routing_round_trip_under_skew_8dev():
    _run(SKEW, "SHARDED_SKEW_OK")


def test_sharded_prefix_cache_8dev():
    _run(PREFIX_CACHE, "SHARDED_PREFIX_OK")
