"""Differential conformance for the 2-D (shard x replica) mesh (DESIGN.md
§2.3): the grouped stream — searches round-robin fanned out across each
shard's replica group, mutations broadcast to every group member — is
bit-exact with the replicated ``cfg.shards == 1`` oracle at (shards,
replicas) ∈ {(2,2), (2,4), (4,2)} plus a load-aware non-uniform (6, 2)
split, on both the jnp and pallas backends, for mixed S/I/U/D traces,
zipf-skewed traces, and an adversarial all-reads-one-shard burst.  Beyond
the served results, every device's partition must equal the oracle's slice
for its shard — the replica-coherence invariant the mutation broadcast
exists for (all group members see ALL their shard's mutations in program
order, so last-wins resolves identically everywhere).  The grouped bulk
build and compaction are held to the same standard.  Runs in subprocesses
with 8 fake CPU devices, the tests/test_router_conformance.py convention."""
import os
import subprocess
import sys
import textwrap

import pytest

# (shards, replica_groups): uniform 2x2 / 2x4 / 4x2 + the non-uniform
# hot-shard split plan_replication produces for skewed loads
SHAPES = "[(2, (2, 2)), (2, (4, 4)), (4, (2, 2, 2, 2)), (2, (6, 2))]"

CONFORM = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    from repro.core import engine
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    for S, groups in SHAPES:
        cfg = HashTableConfig(p=sum(groups), k=4, buckets=256, slots=4,
                              replicate_reads=False, stagger_slots=True,
                              shards=S, replica_groups=groups,
                              backend='BACKEND', router='bounded',
                              routed_lane_tile=4)
        Dv = cfg.mesh_devices
        lb = cfg.local_buckets
        shard_of = engine.replica_layout(cfg)[0]
        mesh = make_ht_mesh(Dv)
        streams = {
            'bounded': (make_distributed_stream(mesh, cfg),
                        init_distributed_table(cfg, jax.random.key(1), mesh)),
            'skewproof': (make_distributed_stream(
                              mesh, cfg, router='skewproof'),
                          init_distributed_table(cfg, jax.random.key(1),
                                                 mesh)),
        }
        cfg_rep = dataclasses.replace(cfg, shards=1, replica_groups=None,
                                      router='skewproof')
        tab_rep = init_distributed_table(cfg_rep, jax.random.key(1))
        stream_rep = make_distributed_stream(mesh, cfg_rep)
        T, nl = 5, 4
        N = Dv * nl
        gen = TraceGen(np.random.default_rng(S * 10 + Dv))
        qm = streams['bounded'][1].q_masks
        # all-reads-one-shard burst: step 0 inserts its keys, the rest is a
        # pure search storm on the hot shard — the read-fan-out case
        hot = np.resize(gen.one_shard_keys(cfg, qm, 0, 2 * N), (T, N, 1))
        burst_ops = np.full((T, N), OP_SEARCH, np.int32)
        burst_ops[0] = OP_INSERT
        traces = {
            'mixed': gen.stream_mixed(T, N, key_space=48),
            'zipf': gen.stream_zipf(T, N),
            'burst': (burst_ops, hot.astype(np.uint32),
                      (hot + 5).astype(np.uint32).reshape(T, N, 1)),
        }
        for kind, (ops, keys, vals) in traces.items():
            ops, keys, vals = map(jnp.array, (ops, keys, vals))
            tr, rr = stream_rep(tab_rep, ops, keys, vals)
            for name, (stream, tab) in streams.items():
                ts, rs = stream(tab, ops, keys, vals)
                for nm in ('found', 'value', 'ok', 'bucket'):
                    a = np.asarray(getattr(rs, nm))
                    b = np.asarray(getattr(rr, nm))
                    assert (a == b).all(), (S, groups, kind, name, nm)
                # replica coherence: device d's partition == the oracle's
                # slice for shard_of[d], byte for byte
                for nm, a, b in zip(('keys', 'vals', 'valid'),
                                    ts.stores(), tr.stores()):
                    a, b = np.asarray(a), np.asarray(b)
                    for d in range(Dv):
                        s = shard_of[d]
                        assert (a[:, :, d * lb:(d + 1) * lb]
                                == b[:, :, s * lb:(s + 1) * lb]).all(), \\
                            (S, groups, kind, name, nm, d)
    print('REPLICA_CONFORM_OK')
""").replace("SHAPES", SHAPES)

BULK = textwrap.dedent("""
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    from repro.core import engine

    for S, groups in SHAPES:
        cfg = HashTableConfig(p=sum(groups), k=4, buckets=256, slots=4,
                              replicate_reads=False, stagger_slots=True,
                              shards=S, replica_groups=groups,
                              router='bounded', routed_lane_tile=4)
        Dv, lb = cfg.mesh_devices, cfg.local_buckets
        shard_of = engine.replica_layout(cfg)[0]
        mesh = make_ht_mesh(Dv)
        T, N = 4, Dv * cfg.queries_per_pe
        rng = np.random.default_rng(Dv)
        keys = np.zeros((T, N, cfg.key_words), np.uint32)
        keys[:, :, 0] = rng.integers(1, 4 * T * N, size=(T, N))  # dups too
        vals = rng.integers(1, 2 ** 32, size=(T, N, cfg.val_words),
                            dtype=np.uint32)
        build = make_distributed_bulk_build(mesh, cfg)
        dtab = init_distributed_table(cfg, jax.random.key(2), mesh)
        tab, rep = build(dtab, jnp.array(keys), jnp.array(vals))
        # unsharded serialized-insert oracle with the SAME H3 params
        cfg_r = dataclasses.replace(cfg, shards=1, replica_groups=None)
        ref = init_table(cfg_r, jax.random.key(2))
        ref = XorHashTable(jnp.array(jax.device_get(dtab.q_masks)),
                           ref.packed, cfg_r)
        ref2, rrep = engine.bulk_build(ref, keys.reshape(T * N, -1),
                                       vals.reshape(T * N, -1),
                                       backend='jnp')
        for nm in ('placed', 'spilled', 'slot', 'first'):
            a = np.asarray(getattr(rep, nm)).reshape(T * N)
            b = np.asarray(getattr(rrep, nm))
            assert (a == b).all(), (S, groups, nm)
        for nm, a, b in zip(('keys', 'vals', 'valid'), tab.stores(),
                            ref2.stores()):
            a, b = np.asarray(a), np.asarray(b)
            for d in range(Dv):
                s = shard_of[d]
                assert (a[:, :, d * lb:(d + 1) * lb]
                        == b[:, :, s * lb:(s + 1) * lb]).all(), \\
                    (S, groups, nm, d)
        # grouped compaction keeps every group member's partition identical
        compact = make_distributed_compact(mesh, cfg)
        tab2 = compact(tab)
        v = np.asarray(tab2.stores()[2])
        for s in range(S):
            o = cfg.group_offsets[s]
            ref = v[:, :, o * lb:(o + 1) * lb]
            for r in range(1, groups[s]):
                d = o + r
                assert (v[:, :, d * lb:(d + 1) * lb] == ref).all(), \\
                    (S, groups, s, r)
    print('REPLICA_BULK_OK')
""").replace("SHAPES", SHAPES)


def _run(script: str, token: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert token in r.stdout, r.stdout + r.stderr


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_replica_mesh_conformance_8dev(backend):
    _run(CONFORM.replace("BACKEND", backend), "REPLICA_CONFORM_OK")


def test_replica_bulk_build_and_compact_8dev():
    _run(BULK, "REPLICA_BULK_OK")


def test_replica_config_validation_fix_it_messages():
    """Satellite: inconsistent replica configs fail at construction (or at
    the single validate_mesh entry path) with actionable fix-it text."""
    from repro.core import HashTableConfig

    def cfg(**kw):
        base = dict(p=4, k=2, buckets=64, slots=2, replicate_reads=False,
                    shards=2)
        base.update(kw)
        return HashTableConfig(**base)

    # replica_groups conflicts with the on-chip replicate_reads layout
    with pytest.raises(ValueError, match="replicate_reads=False"):
        cfg(replicate_reads=True, replica_groups=(2, 2))
    # a shards=1 table is already fully replicated
    with pytest.raises(ValueError, match="shards > 1"):
        cfg(shards=1, replica_groups=(2,))
    # one degree per shard
    with pytest.raises(ValueError, match="one replica degree per shard"):
        cfg(replica_groups=(2, 2, 2))
    # every shard keeps at least one replica
    with pytest.raises(ValueError, match="degree >= 1"):
        cfg(replica_groups=(3, 0))
    # lists coerce to tuples; derived layout properties agree
    c = cfg(replica_groups=[3, 1])
    assert c.replica_groups == (3, 1)
    assert c.group_sizes == (3, 1) and c.group_offsets == (0, 3)
    assert c.mesh_devices == 4 and c.max_group == 3 and c.replicated
    # validate_mesh names the fix (make_ht_mesh(mesh_devices))
    with pytest.raises(ValueError, match=r"make_ht_mesh\(4\)"):
        c.validate_mesh(8)
    c.validate_mesh(4)                  # matching mesh passes
    # the late replicate_reads raise folded into the same entry path
    legacy = HashTableConfig(p=4, k=2, buckets=64, shards=4)
    with pytest.raises(ValueError, match="replicate_reads=False"):
        legacy.validate_mesh(4)
    # unreplicated 1-D configs still state the per-shard device need
    flat = cfg(shards=4)
    with pytest.raises(ValueError, match="one device per shard"):
        flat.validate_mesh(8)
