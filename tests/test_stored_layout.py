"""The table's one stored format (``XorHashTable.packed``, the kernels'
lane-dense ``[R, G, C, 128]`` layout of ``kernels/packed.py``).

Round trips: ``pack``/``unpack`` and ``from_stores``/``stores()`` are
identities, including bucket counts that do not fill a 128-bucket group,
1- and 2-word keys and values, one and ``p`` replicas, and a sharded
table's per-partition packing.  Equivalence: a bulk-built table served
through ``TableServer`` on the pallas backend (the served stream hands the
stored array to the kernel as it is) answers every request and leaves the
same table as the jnp backend over the same slabs.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.kernels.ops as kops
from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH,
                        XorHashTable, engine, init_table, memory_bytes)
from repro.core.hash_table import packed_shape
from repro.kernels import packed as pk
from repro.serving import ServeConfig, TableServer


def _stores(rng, R, k, B, S, kw, vw):
    draw = lambda *shape: jnp.asarray(
        rng.integers(0, 2 ** 32, size=shape, dtype=np.uint32))
    return draw(R, k, B, S, kw), draw(R, k, B, S, vw), draw(R, k, B, S)


@pytest.mark.parametrize("buckets", [1, 100, 300])
@pytest.mark.parametrize("kw,vw", [(1, 1), (2, 2)])
def test_pack_unpack_round_trip(buckets, kw, vw, rng):
    """One replica: ``unpack(pack(x)) == x`` and padding stays zero, at bucket
    counts that end inside a group."""
    k, S = 3, 2
    sk, sv, sb = (x[0] for x in _stores(rng, 1, k, buckets, S, kw, vw))
    tab = pk.pack(sk, sv, sb)
    W = kw + vw + 1
    assert tab.shape == (pk.packed_groups(buckets), pk.packed_rows(k, S, W),
                         pk.LANES)
    for a, b in zip(pk.unpack(tab, k, buckets, S, kw, vw), (sk, sv, sb)):
        assert (np.asarray(a) == np.asarray(b)).all()
    rows = np.asarray(tab).transpose(1, 0, 2).reshape(tab.shape[1], -1)
    assert not rows[k * S * W:].any() and not rows[:, buckets:].any()


@pytest.mark.parametrize("buckets,shards,replicate", [
    (64, 1, False), (512, 1, False), (64, 1, True), (512, 1, True),
    (64, 4, False), (512, 2, False)],
    ids=["64-R1", "512-R1", "64-Rp", "512-Rp", "64-4parts", "512-2parts"])
@pytest.mark.parametrize("kw,vw", [(1, 1), (2, 2)])
def test_stores_round_trip(buckets, shards, replicate, kw, vw, rng):
    """``from_stores``/``stores()`` are inverse at every geometry (one and
    ``p`` replicas; a sharded table, one replica per device, packs each
    partition on its own); the stored shape comes from the config alone;
    ``plaintext()`` is the XOR of the planes and ``memory_bytes`` the
    logical stores' bytes."""
    cfg = HashTableConfig(p=4, k=2, buckets=buckets, slots=2, key_words=kw,
                          val_words=vw, replicate_reads=replicate,
                          shards=shards)
    stores = _stores(rng, cfg.replicas, cfg.k, buckets, cfg.slots, kw, vw)
    qm = init_table(cfg, jax.random.key(0)).q_masks
    tab = XorHashTable.from_stores(qm, *stores, cfg)
    assert tab.packed.shape == packed_shape(cfg)
    assert tab.buckets == buckets
    for a, b in zip(tab.stores(), stores):
        assert (np.asarray(a) == np.asarray(b)).all()
    again = XorHashTable.from_stores(qm, *tab.stores(), cfg)
    assert (np.asarray(again.packed) == np.asarray(tab.packed)).all()
    for r in (0, cfg.replicas - 1):
        plain = tab.plaintext(r)
        want = [np.bitwise_xor.reduce(np.asarray(x[r]), axis=0)
                for x in stores]
        want[2] = want[2] & 1
        for a, b in zip(plain, want):
            assert (np.asarray(a) == b).all()
    assert tab.memory_bytes == memory_bytes(cfg)


def _records(rng, n, kw, vw):
    keys = np.zeros((n, kw), np.uint32)
    keys[:, 0] = rng.choice(np.arange(1, 1 << 24, dtype=np.uint32), n,
                            replace=False)
    vals = rng.integers(1, 2 ** 32, size=(n, vw), dtype=np.uint32)
    return keys, vals


def _requests(rng, keys, n_req, kw, vw):
    """Requests of 1-9 operations: searches and updates of loaded keys,
    fresh inserts, deletes and misses."""
    out = []
    for _ in range(n_req):
        m = int(rng.integers(1, 10))
        ops = rng.choice([OP_SEARCH, OP_INSERT, OP_DELETE], size=m,
                         p=[0.6, 0.3, 0.1]).astype(np.int32)
        q = keys[rng.integers(0, len(keys), size=m)].copy()
        fresh = rng.random(m) < 0.2
        q[fresh, 0] = rng.integers(1 << 24, 1 << 25, size=int(fresh.sum()))
        vals = rng.integers(1, 2 ** 32, size=(m, vw), dtype=np.uint32)
        out.append((ops, q, vals))
    return out


def _serve(cfg, backend, keys, vals, requests, steps):
    table = init_table(cfg, jax.random.key(3))
    table, _ = engine.bulk_build(table, jnp.asarray(keys), jnp.asarray(vals),
                                 backend=backend)
    stream = jax.jit(functools.partial(engine.run_stream, backend=backend),
                     donate_argnums=(0,))
    srv = TableServer(cfg, table, stream,
                      ServeConfig(slab_steps=steps, geometry_replan=False))
    reqs = [srv.submit(*r) for r in requests]
    srv.run()
    assert all(r.done for r in reqs)
    return srv, reqs


@pytest.mark.parametrize("passes", [1, 4], ids=["resident", "swept"])
def test_served_pallas_matches_jnp(passes, rng, monkeypatch):
    """Bulk build, then ``TableServer`` over the donated pallas stream (the
    benchmark's served path, interpret mode here) against the jnp backend on
    the same requests: every answer and the final table are identical."""
    cfg = HashTableConfig(p=16, k=4, buckets=512, slots=4, key_words=2,
                          val_words=2, replicate_reads=False,
                          stagger_slots=True)
    tab = init_table(cfg, jax.random.key(3))
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES",
                        kops.table_vmem_bytes(tab.packed) // passes)
    assert kops.stream_bucket_tiles(tab.packed) == passes
    keys, vals = _records(rng, cfg.buckets * cfg.slots // 2, 2, 2)
    requests = _requests(rng, keys, 120, 2, 2)
    srv_p, got = _serve(cfg, "pallas", keys, vals, requests, steps=4)
    srv_j, want = _serve(cfg, "jnp", keys, vals, requests, steps=4)
    assert srv_p.slabs == srv_j.slabs > 1
    for a, b in zip(got, want):
        for f in ("found", "ok", "value"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert any(r.found.any() for r in got)
    for a, b in zip(srv_p.table.plaintext(), srv_j.table.plaintext()):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(srv_p.table.packed),
                          np.asarray(srv_j.table.packed))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_partitioned_table_single_domain(backend, rng):
    """A bucket-sharded table used as one domain (outside shard_map), with
    partitions of 16 buckets that each pad a 128-bucket group: bulk build,
    compaction and the jnp per-step stream address every bucket's column
    of its own partition, so the logical view, the plaintext and the answers
    equal those of the unsharded table with the same H3 matrix."""
    base = dict(p=4, k=2, buckets=64, slots=4, key_words=1, val_words=1,
                replicate_reads=False)
    cfgs = [HashTableConfig(**base), HashTableConfig(**base, shards=4)]
    keys, vals = _records(rng, 96, 1, 1)
    tabs = []
    for cfg in cfgs:
        tab = init_table(cfg, jax.random.key(5))
        tab, rep = engine.bulk_build(tab, jnp.asarray(keys),
                                     jnp.asarray(vals), backend=backend)
        assert int(rep.spill_count) == 0
        tabs.append(engine.compact(tab, backend=backend))
    assert tabs[1].packed.shape[1] == 4 * tabs[0].packed.shape[1]
    n = cfgs[0].queries_per_step
    ops = rng.choice([OP_SEARCH, OP_INSERT, OP_DELETE], size=(6, n),
                     p=[0.5, 0.3, 0.2]).astype(np.int32)
    q = keys[rng.integers(0, len(keys), size=(6, n))]
    qv = rng.integers(1, 2 ** 32, size=(6, n, 1), dtype=np.uint32)
    outs = [engine.run_stream(t, jnp.asarray(ops), jnp.asarray(q),
                              jnp.asarray(qv), backend="jnp") for t in tabs]
    (t0, r0), (t1, r1) = outs
    assert np.asarray(r0.found).any()
    for f in ("found", "ok", "value"):
        assert np.array_equal(np.asarray(getattr(r0, f)),
                              np.asarray(getattr(r1, f))), f
    for before, after in zip(zip(*(t.stores() for t in tabs)),
                             zip(t0.stores(), t1.stores())):
        assert np.array_equal(np.asarray(before[0]), np.asarray(before[1]))
        assert np.array_equal(np.asarray(after[0]), np.asarray(after[1]))
    for a, b in zip(tabs[0].plaintext(), tabs[1].plaintext()):
        assert np.array_equal(np.asarray(a), np.asarray(b))
