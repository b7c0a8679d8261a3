"""The fused stream kernel (kernels/xor_stream.py, DESIGN.md §3.1) must be
bit-exact with the scanned per-step jnp oracle — same per-step StepResults
AND same final table — on long randomized S/I/U/D traces, for both replica
layouts, stagger on/off, and tables below/above the VMEM budget (the
multi-pass sweep).  Also covers the StreamBackend dispatch and the
replica_bytes / stream_bucket_tiles helpers."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.kernels.ops as kops
from repro.core import (HashTableConfig, OP_DELETE, OP_INSERT, OP_SEARCH,
                        QueryBatch, XorHashTable, apply_step, engine,
                        init_table, run_stream, schedule_queries)


def _assert_same(tab_a, res_a, tab_b, res_b, what=""):
    for name in ("found", "value", "ok", "bucket"):
        a = np.asarray(getattr(res_a, name))
        b = np.asarray(getattr(res_b, name))
        assert (a == b).all(), f"{what}: StepResults.{name} diverged"
    for name, a, b in zip(("keys", "vals", "valid"), tab_a.stores(),
                          tab_b.stores()):
        a, b = np.asarray(a), np.asarray(b)
        assert (a == b).all(), \
            f"{what}: table.{name} diverged ({(a != b).sum()} words)"


def _oracle_and_fused(cfg, ops, kk, vv, seed=0):
    tab = init_table(cfg, jax.random.key(seed))
    oj = run_stream(tab, jnp.array(ops), jnp.array(kk), jnp.array(vv),
                    backend="jnp", fused=False)
    of = run_stream(tab, jnp.array(ops), jnp.array(kk), jnp.array(vv),
                    fused=True)
    return oj, of


@pytest.mark.parametrize("replicate", [True, False])
@pytest.mark.parametrize("stagger", [False, True])
@pytest.mark.parametrize("kw", [1, 2])
def test_fused_stream_bit_exact_on_random_trace(replicate, stagger, kw,
                                                trace_gen):
    cfg = HashTableConfig(p=4, k=2, buckets=128, slots=4, key_words=kw,
                          val_words=1, replicate_reads=replicate,
                          stagger_slots=stagger)
    op, keys, vals = trace_gen.mixed(128, kw)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    (tab_j, res_j), (tab_f, res_f) = _oracle_and_fused(cfg, ops, kk, vv)
    _assert_same(tab_j, res_j, tab_f, res_f,
                 f"replicate={replicate} stagger={stagger} kw={kw}")


@pytest.mark.parametrize("buckets", [512, 1024])
@pytest.mark.parametrize("stagger", [False, True])
def test_fused_stream_bucket_blocked_bit_exact(stagger, buckets, trace_gen,
                                               monkeypatch):
    """Tables above the VMEM budget run the multi-pass sweep (the shrunken
    budget asks for 8 passes; the count is capped at the packed table's
    128-bucket groups: 4 passes at 512 buckets, 8 at 1024) and stay
    bit-exact (lane-order last-wins commit)."""
    cfg = HashTableConfig(p=4, k=2, buckets=buckets, slots=4,
                          replicate_reads=False, stagger_slots=stagger)
    op, keys, vals = trace_gen.mixed(128, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    rb = kops.table_vmem_bytes(tab.packed)
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES", rb // 7)
    assert kops.stream_bucket_tiles(tab.packed) == buckets // 128
    (tab_j, res_j), (tab_f, res_f) = _oracle_and_fused(cfg, ops, kk, vv)
    _assert_same(tab_j, res_j, tab_f, res_f,
                 f"blocked stagger={stagger} buckets={buckets}")


@pytest.mark.parametrize("passes", [1, 2])
def test_fused_stream_pass_count_follows_budget(passes, trace_gen,
                                                monkeypatch):
    """The sweep pass count is derived from the VMEM budget alone (the 4-
    and 8-pass counts are test_fused_stream_bucket_blocked_bit_exact's):
    the budget resolves to the count asked for, bit-exact with the
    oracle."""
    cfg = HashTableConfig(p=4, k=2, buckets=1024, slots=4,
                          replicate_reads=False, stagger_slots=True)
    op, keys, vals = trace_gen.mixed(64, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES",
                        kops.table_vmem_bytes(tab.packed) // passes)
    assert kops.stream_bucket_tiles(tab.packed) == passes
    (tab_j, res_j), (tab_f, res_f) = _oracle_and_fused(cfg, ops, kk, vv)
    _assert_same(tab_j, res_j, tab_f, res_f, f"passes={passes}")


def test_xor_stream_rejects_passes_that_split_a_group():
    """A sweep span is a whole number of 128-bucket groups."""
    from repro.kernels.xor_stream import xor_stream_pallas
    cfg = HashTableConfig(p=2, k=2, buckets=512, slots=2)
    tab = init_table(cfg, jax.random.key(0))
    z = jnp.zeros((1, 2), jnp.int32)
    zq = jnp.zeros((1, 2, 1), jnp.uint32)
    with pytest.raises(ValueError):
        xor_stream_pallas(z.astype(jnp.uint32), z[0], z[0] + 1, z, zq, zq,
                          tab.packed[0], k=cfg.k, buckets=cfg.buckets,
                          slots=cfg.slots, passes=3)


def test_fused_stream_matches_scanned_pallas(trace_gen):
    """Third seam stage vs second: fused stream == scanned Pallas kernels."""
    cfg = HashTableConfig(p=4, k=4, buckets=64, slots=4, stagger_slots=True,
                          backend="pallas")
    op, keys, vals = trace_gen.mixed(64, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    tab_s, res_s = run_stream(tab, jnp.array(ops), jnp.array(kk),
                              jnp.array(vv), fused=False)
    tab_f, res_f = run_stream(tab, jnp.array(ops), jnp.array(kk),
                              jnp.array(vv), fused=True)
    _assert_same(tab_s, res_s, tab_f, res_f, "scanned-pallas vs fused")


def test_fused_stream_duplicate_write_targets_last_wins():
    """qpp > 1: same-step writes from one port to one (bucket, slot) resolve
    last-wins in lane order — in the fused kernel exactly as in the oracle,
    across multiple steps of one stream."""
    cfg = HashTableConfig(p=2, k=2, buckets=32, slots=2, queries_per_pe=2)
    tab = init_table(cfg, jax.random.key(0))
    # step 0: lanes 0 and 2 (both PE 0) insert the same key; step 1: search.
    ops = np.array([[OP_INSERT, 0, OP_INSERT, 0],
                    [OP_SEARCH, 0, 0, 0]], np.int32)
    keys = np.array([[[9], [0], [9], [0]], [[9], [0], [0], [0]]], np.uint32)
    vals = np.array([[[111], [0], [222], [0]],
                     [[0], [0], [0], [0]]], np.uint32)
    tab_f, res_f = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), fused=True)
    assert bool(np.asarray(res_f.found)[1, 0])
    assert int(np.asarray(res_f.value)[1, 0, 0]) == 222, "later lane must win"
    tab_j, res_j = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), backend="jnp", fused=False)
    _assert_same(tab_j, res_j, tab_f, res_f, "duplicate targets")


# fused-path layout under test -> sweep passes (a shrunken VMEM budget
# forces the multi-pass sweep; the 512-bucket tables below hold four
# 128-bucket groups, so four passes really sweep four spans)
_LAYOUTS = {"resident_1pass": 1, "swept_2pass": 2, "swept_4pass": 4}
RACE_BUCKETS = 512


def _set_layout(layout, tab, monkeypatch):
    passes = _LAYOUTS[layout]
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES",
                        kops.table_vmem_bytes(tab.packed) // passes)
    assert kops.stream_bucket_tiles(tab.packed) == passes


def _keys_in_last_pass(tab, n):
    """``n`` one-word keys whose buckets lie in the table's top quarter —
    the last sweep pass at 2 and 4 passes — so in each step their lanes
    sort behind the NOP lanes (key 0 hashes to bucket 0, pass 0)."""
    from repro.core.hashing import h3_hash as h3
    cand = np.arange(1, 4096, dtype=np.uint32)
    b = np.asarray(h3(jnp.asarray(cand[:, None]), tab.q_masks))
    B = tab.buckets
    return [int(x) for x in cand[b >= 3 * B // 4][:n]]


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_fused_stream_cross_port_duplicate_bucket_slot(layout, monkeypatch):
    """Same-step writes from DIFFERENT ports to one (bucket, slot) must both
    land — the supersession key is (port, bucket, slot), matching the
    oracle's _scatter_records, NOT (bucket, slot) — bit-exact on every
    layout including the XOR-scrambled decode the collision produces."""
    cfg = HashTableConfig(p=2, k=2, buckets=RACE_BUCKETS,
                          slots=2)                           # stagger OFF
    tab = init_table(cfg, jax.random.key(0))
    (kx,) = _keys_in_last_pass(tab, 1)
    # step 0: PE 0 and PE 1 insert the same fresh key -> same bucket, same
    # argmax open slot, different write ports; step 1: search it.
    ops = np.array([[OP_INSERT, OP_INSERT], [OP_SEARCH, 0]], np.int32)
    keys = np.array([[[kx], [kx]], [[kx], [0]]], np.uint32)
    vals = np.array([[[111], [222]], [[0], [0]]], np.uint32)
    tab_j, res_j = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), backend="jnp", fused=False)
    _set_layout(layout, tab, monkeypatch)
    tab_f, res_f = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), fused=True)
    _assert_same(tab_j, res_j, tab_f, res_f, f"cross-port dup {layout}")


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_fused_stream_insert_delete_race(layout, monkeypatch):
    """Inserts racing deletes on one key in one step: cross-port (both
    encodings land in distinct partial stores) and same-port (the later
    lane supersedes the earlier), bit-exact with the oracle on every
    layout; the same-port race must resolve insert-wins in program order."""
    cfg = HashTableConfig(p=2, k=2, buckets=RACE_BUCKETS, slots=2,
                          queries_per_pe=2)
    tab = init_table(cfg, jax.random.key(0))                 # N=4, PE=lane%2
    k7, k8 = _keys_in_last_pass(tab, 2)
    ops = np.array([
        [OP_INSERT, 0, 0, 0],                  # key 7 in (port 0)
        [OP_DELETE, OP_INSERT, 0, 0],          # del 7 (port 0) || upd 7 (port 1)
        [OP_SEARCH, 0, 0, 0],                  # what does the oracle say?
        [OP_INSERT, 0, 0, 0],                  # key 8 in (port 0)
        [OP_DELETE, 0, OP_INSERT, 0],          # del 8 || ins 8: SAME port+slot
        [OP_SEARCH, 0, 0, 0],                  # insert (later lane) must win
    ], np.int32)
    keys = np.array([
        [[k7], [0], [0], [0]], [[k7], [k7], [0], [0]], [[k7], [0], [0], [0]],
        [[k8], [0], [0], [0]], [[k8], [0], [k8], [0]], [[k8], [0], [0], [0]],
    ], np.uint32)
    vals = np.array([
        [[50], [0], [0], [0]], [[0], [60], [0], [0]], [[0], [0], [0], [0]],
        [[70], [0], [0], [0]], [[0], [0], [999], [0]], [[0], [0], [0], [0]],
    ], np.uint32)
    tab_j, res_j = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), backend="jnp", fused=False)
    _set_layout(layout, tab, monkeypatch)
    tab_f, res_f = run_stream(tab, jnp.array(ops), jnp.array(keys),
                              jnp.array(vals), fused=True)
    _assert_same(tab_j, res_j, tab_f, res_f, f"ins/del race {layout}")
    # same-port same-slot race (step 4): the later insert supersedes the
    # delete, so step 5 must find key 8 with the raced value
    assert bool(np.asarray(res_f.found)[5, 0])
    assert int(np.asarray(res_f.value)[5, 0, 0]) == 999


def test_stream_backend_dispatch(trace_gen):
    """fused=None routes by backend: jnp -> scan, pallas -> fused kernel;
    all three entries agree with apply_step iterated by hand."""
    cfg = HashTableConfig(p=4, k=4, buckets=64, slots=4)
    op, keys, vals = trace_gen.mixed(32, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    outs = {}
    for label, kwargs in {
        "auto": {},
        "jnp": dict(backend="jnp"),
        "pallas-auto": dict(backend="pallas"),      # -> fused via dispatch
        "fused": dict(fused=True),
        "scanned": dict(fused=False),
    }.items():
        outs[label] = run_stream(tab, jnp.array(ops), jnp.array(kk),
                                 jnp.array(vv), **kwargs)
    # hand-rolled scan of apply_step as the reference
    ref = tab
    for t in range(ops.shape[0]):
        ref, _ = apply_step(ref, QueryBatch(jnp.array(ops[t]),
                                            jnp.array(kk[t]),
                                            jnp.array(vv[t])))
    base = np.asarray(ref.packed)
    for label, (tab_x, _) in outs.items():
        assert (np.asarray(tab_x.packed) == base).all(), label
    _assert_same(*outs["jnp"], *outs["pallas-auto"], what="jnp vs dispatch")


def test_stream_empty_and_shape_guard():
    cfg = HashTableConfig(p=2, k=2, buckets=16, slots=2)
    tab = init_table(cfg, jax.random.key(0))
    n = cfg.queries_per_step
    tab2, res = run_stream(tab, jnp.zeros((0, n), jnp.int32),
                           jnp.zeros((0, n, 1), jnp.uint32),
                           jnp.zeros((0, n, 1), jnp.uint32), fused=True)
    assert res.found.shape == (0, n)
    assert (np.asarray(tab2.packed) == np.asarray(tab.packed)).all()
    with pytest.raises(ValueError):
        run_stream(tab, jnp.zeros((1, n + 1), jnp.int32),
                   jnp.zeros((1, n + 1, 1), jnp.uint32),
                   jnp.zeros((1, n + 1, 1), jnp.uint32))


def test_replica_bytes_helper():
    cfg = HashTableConfig(p=4, k=2, buckets=64, slots=2, key_words=2,
                          val_words=1, replicate_reads=True)
    tab = init_table(cfg, jax.random.key(0))
    rb = 4 * sum(x[0].size for x in tab.stores())
    assert rb == tab.memory_bytes // cfg.replicas == cfg.replica_bytes
    # one replica's stored array == one replica of the replicated array
    vb = kops.table_vmem_bytes(tab.packed)
    assert kops.table_vmem_bytes(tab.packed[0]) == vb >= rb
    # helper is the engine's budget check too
    assert engine.resolve_backend(
        dataclasses.replace(cfg, backend="pallas"), tab).name == "pallas"


def test_stream_bucket_tiles_power_of_two(monkeypatch):
    cfg = HashTableConfig(p=2, k=2, buckets=1024, slots=2)
    tab = init_table(cfg, jax.random.key(0))
    assert kops.stream_bucket_tiles(tab.packed) == 1
    rb = kops.table_vmem_bytes(tab.packed)
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES", rb // 3)
    assert kops.stream_bucket_tiles(tab.packed) == 4
    monkeypatch.setattr(kops, "VMEM_TABLE_BUDGET_BYTES", 1)
    # capped at one 128-bucket group per pass
    assert kops.stream_bucket_tiles(tab.packed) == cfg.buckets // 128


def test_run_stream_local_partitions_merge_to_oracle(trace_gen, monkeypatch):
    """The shard-local stream (engine.run_stream_local): manually partition a
    table's bucket axis, run the SAME global-bucket stream against every
    partition with its bucket-base offset (fused kernel — one pass and a
    four-pass sweep — and scanned jnp), and merge —
    bit-exact with the unsharded oracle; out-of-partition lanes are inert
    (the pre-pass sentinel-sorts them past every pass window).  This
    is the single-device half of the sharded distributed path
    (routing/all_to_all is covered by tests/test_distributed_sharded.py)."""
    from repro.core.hashing import h3_hash as h3
    # 2048 buckets: each partition holds four 128-bucket groups, so the
    # shrunken budget really sweeps four passes
    cfg = HashTableConfig(p=4, k=2, buckets=2048, slots=4,
                          replicate_reads=False, stagger_slots=True)
    scfg = dataclasses.replace(cfg, shards=4)
    op, keys, vals = trace_gen.mixed(64, 1)
    ops, kk, vv = schedule_queries(op, keys, vals, cfg)
    tab = init_table(cfg, jax.random.key(0))
    otab, ores = run_stream(tab, jnp.array(ops), jnp.array(kk), jnp.array(vv),
                            backend="jnp", fused=False)
    T, N = ops.shape
    bucket = h3(jnp.array(kk).reshape(T * N, 1), tab.q_masks).reshape(T, N)
    pe = jnp.arange(N, dtype=jnp.int32) % cfg.p     # == the oracle's lane map
    Bl = scfg.local_buckets
    # (fused, shrink_budget): scanned jnp, one-pass fused, four-pass sweep
    combos = [(False, False), (True, False), (True, True)]
    stores = tab.stores()
    for fused, shrink in combos:
        label = f"fused={fused} shrink={shrink}"
        parts = []
        got_f = np.zeros((T, N), bool)
        got_ok = np.zeros((T, N), bool)
        got_v = np.zeros((T, N, 1), np.uint32)
        for s in range(scfg.shards):
            lo = s * Bl
            part = XorHashTable.from_stores(
                tab.q_masks, *(x[:, :, lo:lo + Bl] for x in stores), scfg)
            with monkeypatch.context() as m:
                if shrink:     # multi-pass: 4 sweep passes
                    rb = kops.table_vmem_bytes(part.packed)
                    m.setattr(kops, "VMEM_TABLE_BUDGET_BYTES", rb // 3)
                    assert kops.stream_bucket_tiles(part.packed) == 4
                packed, f, ok, val = engine.run_stream_local(
                    scfg, part.packed,
                    pe, bucket, jnp.array(ops), jnp.array(kk), jnp.array(vv),
                    bucket_base=lo, fused=fused)
            parts.append(dataclasses.replace(part, packed=packed).stores())
            # exactly one partition owns each lane; the rest stay False/0
            assert not (got_f & np.asarray(f)).any()
            got_f |= np.asarray(f)
            got_ok |= np.asarray(ok)
            got_v = np.maximum(got_v, np.asarray(val))
        assert (got_f == np.asarray(ores.found)).all(), label
        assert (got_ok == np.asarray(ores.ok)).all(), label
        assert (got_v == np.asarray(ores.value)).all(), label
        for nm, chunks, want in zip(("keys", "vals", "valid"), zip(*parts),
                                    otab.stores()):
            merged = np.concatenate([np.asarray(c) for c in chunks], axis=2)
            assert (merged == np.asarray(want)).all(), \
                f"{label}: {nm} diverged"


def test_shards_config_validation():
    cfg = HashTableConfig(buckets=64, shards=4)
    assert cfg.local_buckets == 16 and cfg.global_buckets == 64
    assert cfg.local_index_bits == 4 and cfg.index_bits == 6
    with pytest.raises(ValueError):
        HashTableConfig(buckets=64, shards=3)       # power of two
    with pytest.raises(ValueError):
        HashTableConfig(buckets=16, shards=32)      # shards <= buckets


def test_scatter_records_supersession_still_last_wins(rng):
    """The O(N log N) segment-max supersession mask must keep XLA-scatter
    duplicate resolution bit-identical to sequential last-wins, including
    interleaved dead lanes."""
    cfg = HashTableConfig(p=2, k=2, buckets=16, slots=2, queries_per_pe=4)
    tab = init_table(cfg, jax.random.key(0))
    n = cfg.queries_per_step
    # many duplicate targets: one hot key from both ports, plus dead lanes
    op = np.zeros(n, np.int32)
    op[0::2] = OP_INSERT
    keys = np.zeros((n, 1), np.uint32)
    keys[0::2, 0] = 7
    vals = np.arange(1, n + 1, dtype=np.uint32).reshape(n, 1)
    tab2, _ = apply_step(tab, QueryBatch(jnp.array(op), jnp.array(keys),
                                         jnp.array(vals)))
    _, res = apply_step(tab2, QueryBatch(
        jnp.array([OP_SEARCH] + [0] * (n - 1), np.int32),
        jnp.array(keys[:1].repeat(n, 0)), jnp.zeros((n, 1), jnp.uint32)))
    # port 0's last write lane is n-2 (lanes 0,2,..: even lanes, PE = lane%2)
    # all even lanes are PE 0 -> port 0, same key 7, same slot => last wins
    assert int(np.asarray(res.value)[0, 0]) == n - 1
