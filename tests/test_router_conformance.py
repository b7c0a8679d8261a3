"""Differential conformance for the capacity-bounded two-pass router
(DESIGN.md §2.2): bounded router == skew-proof router == the replicated
``cfg.shards == 1`` oracle, bit-exact — results AND final table bytes — on
random S/I/U/D traces (uniform and zipf-skewed) at D ∈ {2, 4, 8} on both the
jnp and pallas backends, plus the carry-over path forced by an adversarial
all-one-shard burst under a binding ``routed_slack`` cap, and the live-mesh
round-trip invariant (``inverse_route ∘ route_stream == id`` for both
routers, including all-keys-one-shard skew).  Runs in subprocesses with 8
fake CPU devices, the tests/test_distributed_sharded.py convention."""
import os
import subprocess
import sys
import textwrap

import pytest

CONFORM = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    from repro.core import engine
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    for D in (2, 4, 8):
        cfg = HashTableConfig(p=D, k=max(D // 2, 1), buckets=256, slots=4,
                              replicate_reads=False, stagger_slots=True,
                              shards=D, backend='BACKEND', router='bounded',
                              routed_lane_tile=4)
        mesh = make_ht_mesh(D)
        streams = {
            'bounded': (make_distributed_stream(mesh, cfg),
                        init_distributed_table(cfg, jax.random.key(1), mesh)),
            'skewproof': (make_distributed_stream(
                              mesh, cfg, router='skewproof'),
                          init_distributed_table(cfg, jax.random.key(1),
                                                 mesh)),
        }
        cfg_rep = dataclasses.replace(cfg, shards=1)
        tab_rep = init_distributed_table(cfg_rep, jax.random.key(1))
        stream_rep = make_distributed_stream(mesh, cfg_rep)
        T, nl = 6, 4
        N = D * nl
        gen = TraceGen(np.random.default_rng(D))
        for kind in ('mixed', 'zipf'):
            make = gen.stream_mixed if kind == 'mixed' else gen.stream_zipf
            kw = dict(key_space=48) if kind == 'mixed' else dict()
            ops, keys, vals = map(jnp.array, make(T, N, **kw))
            tr, rr = stream_rep(tab_rep, ops, keys, vals)
            for name, (stream, tab) in streams.items():
                ts, rs = stream(tab, ops, keys, vals)
                for nm in ('found', 'value', 'ok', 'bucket'):
                    a = np.asarray(getattr(rs, nm))
                    b = np.asarray(getattr(rr, nm))
                    assert (a == b).all(), (D, kind, name, nm)
                for nm, a, b in zip(('keys', 'vals', 'valid'),
                                    ts.stores(), tr.stores()):
                    assert (np.asarray(a) == np.asarray(b)).all(), \\
                        (D, kind, name, nm)
            # the bounded plan really shrank the routed width on this trace
            bucket = h3_hash(keys.reshape(T * N, 1),
                             streams['bounded'][1].q_masks).reshape(T, N)
            plan = engine.plan_bounded_route(
                cfg, engine.shard_owner(cfg, bucket))
            assert plan.routed_width <= plan.skewproof_width
            assert plan.carried_lanes == 0      # auto mode never carries
        # mesh-committed query tensors (the stream's advertised sharded
        # layout) must take the bounded path too — the measurement pass may
        # not pin them to one device (regression: incompatible-devices)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, 'ht'))
        s_ops, s_keys, s_vals = (jax.device_put(x, sh)
                                 for x in (ops, keys, vals))
        tab2 = init_distributed_table(cfg, jax.random.key(1), mesh)
        _, rs2 = streams['bounded'][0](tab2, s_ops, s_keys, s_vals)
        for nm in ('found', 'value', 'ok'):
            a = np.asarray(getattr(rs2, nm))
            b = np.asarray(getattr(rr, nm))
            assert (a == b).all(), (D, 'sharded-input', nm)
    print('ROUTER_CONFORM_OK')
""")

CARRY = textwrap.dedent("""
    import dataclasses
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import *
    from repro.core.distributed import *
    from repro.core import engine
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    D, nl = 4, 4
    N = D * nl
    cfg = HashTableConfig(p=D, k=D, buckets=256, slots=4,
                          replicate_reads=False, stagger_slots=True,
                          shards=D, router='bounded', routed_lane_tile=4)
    mesh = make_ht_mesh(D)
    tab = init_distributed_table(cfg, jax.random.key(0), mesh)
    gen = TraceGen(np.random.default_rng(7))
    # steps 0-1: uniform inserts of distinct keys; step 2: an adversarial
    # all-ONE-shard search burst (load N, far above the cap); steps 3-5:
    # uniform searches of the inserted keys.  No writes after step 1, so the
    # carried burst lanes probe exactly the state the oracle's do — the
    # bit-exact carry regime the DESIGN.md §2.2 contract names.
    cand = np.arange(1, 4 * N + 1, dtype=np.uint32)
    ik = gen.rng.permutation(cand)[:2 * N].reshape(2, N, 1)
    iowner = np.asarray(engine.shard_owner(
        cfg, h3_hash(jnp.array(ik.reshape(2 * N, 1)), tab.q_masks)))
    burst = np.resize(ik.reshape(2 * N, 1)[iowner == 2], (N, 1))
    ops = np.full((6, N), OP_SEARCH, np.int32)
    ops[0] = OP_INSERT; ops[1] = OP_INSERT
    keys = np.stack([ik[0], ik[1], burst, ik[0], ik[1], ik[0]])
    vals = (keys + 13).astype(np.uint32)
    ops, keys, vals = jnp.array(ops), jnp.array(keys.astype(np.uint32)), \\
        jnp.array(vals)
    bkt = h3_hash(keys.reshape(6 * N, 1), tab.q_masks).reshape(6, N)
    ow = np.asarray(engine.shard_owner(cfg, bkt))
    loads = np.stack([np.bincount(ow[t], minlength=D) for t in range(6)])
    cap = int(loads[[0, 1, 3, 4, 5]].max())   # >= every non-burst step load
    plan = engine.plan_bounded_route(cfg, ow, slack=cap)
    assert plan.carried_lanes > 0, 'the burst must force carry-over'
    assert plan.routed_steps > 6, 'carry must add drain rows'
    stream_b = make_distributed_stream(mesh, cfg, routed_slack=cap)
    cfg_rep = dataclasses.replace(cfg, shards=1, router='skewproof')
    tab_r = init_distributed_table(cfg_rep, jax.random.key(0))
    tb, rb = stream_b(tab, ops, keys, vals)
    tr, rr = make_distributed_stream(mesh, cfg_rep)(tab_r, ops, keys, vals)
    for nm in ('found', 'value', 'ok', 'bucket'):
        a, b = np.asarray(getattr(rb, nm)), np.asarray(getattr(rr, nm))
        assert (a == b).all(), nm
    for nm, a, b in zip(('keys', 'vals', 'valid'), tb.stores(), tr.stores()):
        assert (np.asarray(a) == np.asarray(b)).all(), nm
    assert np.asarray(rb.found)[2].all(), 'carried burst searches must hit'
    print('ROUTER_CARRY_OK')
""")

ROUNDTRIP = textwrap.dedent("""
    import sys
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import *
    from repro.core.distributed import *
    from repro.core import engine
    sys.path.insert(0, "tests")
    from conftest import TraceGen

    D, nl, T = 8, 4, 5
    N = D * nl
    cfg = HashTableConfig(p=D, k=D, buckets=512, replicate_reads=False,
                          shards=D, routed_lane_tile=4)
    mesh = make_ht_mesh(D)
    tab = init_distributed_table(cfg, jax.random.key(0), mesh)
    gen = TraceGen(np.random.default_rng(3))
    traces = {
        'uniform': gen.stream_mixed(T, N, key_space=1 << 13),
        'one_shard': (np.full((T, N), OP_SEARCH, np.int32),
                      np.resize(gen.one_shard_keys(cfg, tab.q_masks, 6,
                                                   T * N // 2),
                                (T, N, 1)),
                      np.ones((T, N, 1), np.uint32)),
    }
    for kind, (ops, keys, vals) in traces.items():
        ops, keys, vals = map(jnp.array, (ops, keys, vals))
        bucket_g = h3_hash(keys.reshape(T * N, 1), tab.q_masks).reshape(T, N)
        plan = engine.plan_bounded_route(
            cfg, engine.shard_owner(cfg, bucket_g))

        def skew_rt(ops, keys, vals):
            Tl, n = ops.shape
            bucket = h3_hash(keys.reshape(Tl * n, 1),
                             tab.q_masks).reshape(Tl, n)
            routed, tgt = engine.route_stream(cfg, 'ht', bucket,
                                              ops, keys, vals)
            return tuple(engine.inverse_route('ht', tgt, *routed))

        def bounded_rt(ops, keys, vals):
            Tl, n = ops.shape
            bucket = h3_hash(keys.reshape(Tl * n, 1),
                             tab.q_masks).reshape(Tl, n)
            routed, pe, carry = engine.route_stream_bounded(
                cfg, 'ht', bucket, ops, keys, vals,
                pair_capacity=plan.pair_capacity,
                routed_width=plan.routed_width,
                routed_steps=plan.routed_steps)
            return tuple(engine.inverse_route_bounded('ht', carry, *routed))

        for name, fn in (('skewproof', skew_rt), ('bounded', bounded_rt)):
            rt = jax.shard_map(fn, mesh=mesh,
                               in_specs=(P(None, 'ht'),) * 3,
                               out_specs=(P(None, 'ht'),) * 3,
                               check_vma=False)
            o2, k2, v2 = rt(ops, keys, vals)
            assert (np.asarray(o2) == np.asarray(ops)).all(), (kind, name)
            assert (np.asarray(k2) == np.asarray(keys)).all(), (kind, name)
            assert (np.asarray(v2) == np.asarray(vals)).all(), (kind, name)
    print('ROUTER_ROUNDTRIP_OK')
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
def test_bounded_router_conformance_8dev(backend):
    _run(CONFORM.replace("BACKEND", backend), "ROUTER_CONFORM_OK")


def test_bounded_router_carry_over_bit_exact_8dev():
    _run(CARRY, "ROUTER_CARRY_OK")


def test_router_round_trip_identity_on_mesh_8dev():
    _run(ROUNDTRIP, "ROUTER_ROUNDTRIP_OK")
