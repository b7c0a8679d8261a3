"""Distributed scaling: bucket-sharded stream (bounded vs skew-proof router)
vs replicated per-step MOPS.

Sweeps shard count D over a fake-device mesh and times, on identical
stimulus (``bench_group`` paired round-robin, drift-immune):

  sharded_bounded   make_distributed_stream with cfg.shards == D and the
                    capacity-bounded two-pass router (DESIGN.md §2.2): the
                    host load pass shrinks the routed width to the measured
                    max per-(step, owner) load, so each owner streams
                    ``[T', Nr]`` lanes instead of ``[T, D*n_local]``
  sharded_skewproof the PR 3 router: fixed ``D*n_local`` routed lanes per
                    owner per step (data-agnostic worst case) — the A/B
                    baseline the ROADMAP item was sized against
  replicated_step   make_distributed_step with cfg.shards == 1 — the
                    superseded design: T dispatches, each probing the FULL
                    replicated table and all-gathering mutation records

Each sharded row records **routed-lane occupancy** (the skew-proof
capacity's utilisation, which sized the router item) and the **achieved
bounded-router shapes**: routed width vs the skew-proof ``D*n_local``,
owner rows ``T'``, and the overflow/carry rate (always 0 in auto mode —
carry only fires under a static ``routed_slack`` cap).  Off-TPU the local
streams run the scanned jnp path on all sides (interpret-mode Pallas is a
correctness harness, not a fast path — same policy as BENCH_stream.json);
the comparison stays apples-to-apples.

A second paired A/B (the ``replication_ab`` JSON section, DESIGN.md §2.3)
pits the 2-D (shard x replica) mesh against the unreplicated 1-D mesh on
the SAME 8 devices and the SAME search-heavy hot-shard zipf stream: flat =
8 shards x 1 replica (bounded router), replicated = 2 shards whose replica
degrees come from ``engine.plan_replication`` on the measured per-shard
loads.  Replicating the hot shard splits its search traffic round-robin
across the group, so the bounded router's measured max per-(step, dest)
load — and with it the routed width every per-device term scales with —
shrinks; the mutation broadcast (every insert/delete ships one copy per
group member) is priced into the same measurement, which is why the mix is
search-heavy.  Per-group replica occupancy stats record how evenly the
fan-out lands.

Emits ``BENCH_distributed.json`` (full mode; ``--smoke`` is the CI harness
check; ``--bounded`` / ``--skewproof`` pin a single sharded column — CI runs
the pair as an A/B; ``--replicated`` runs only the replication A/B and
updates that section in place).  The measurement re-execs in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the conftest
convention) so the driver process keeps its single-device view.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

SHARDS = (2, 4, 8)
T_FULL, NL_FULL, BUCKETS_FULL, ITERS = 16, 8, 1 << 13, 9
T_SMOKE, NL_SMOKE, BUCKETS_SMOKE = 2, 2, 1 << 8

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _routed_occupancy(cfg, q_masks, keys_j):
    """Per-owner routed-lane load vs the skew-proof capacity, from the
    stimulus alone (deterministic, no timing)."""
    import numpy as np

    from repro.core.engine import shard_owner
    from repro.core.hashing import h3_hash

    T, N = keys_j.shape[:2]
    bucket = h3_hash(keys_j.reshape(T * N, cfg.key_words), q_masks)
    owner = np.asarray(shard_owner(cfg, bucket)).reshape(T, N)
    D = cfg.shards
    loads = np.zeros((T, D), np.int64)          # real lanes routed per owner
    for t in range(T):
        loads[t] = np.bincount(owner[t], minlength=D)
    capacity = N                                # n_local per origin x D origins
    return owner, {
        "capacity_per_owner": int(capacity),
        "mean_owner_load": float(loads.mean()),
        "max_owner_load": int(loads.max()),
        "mean_occupancy": float(loads.mean() / capacity),
        "max_occupancy": float(loads.max() / capacity),
        "router_shrink_potential": float(capacity / max(loads.max(), 1)),
    }


def _zipf_hot_stream(cfg, q_masks, T, N, nsq_fraction, zipf_a, seed=3):
    """Search-heavy stream whose bucket traffic is zipf-hot by owner shard.

    Random keys are pooled by owner under ``cfg`` (the flat 1-D sharding),
    then lanes draw their owner from a zipf(a) distribution over shards —
    shard 0 hottest — and take a pool key.  Because owners are contiguous
    bucket ranges, the same keys are hot-shard-skewed under ANY coarser
    sharding of the same bucket space (the replicated side's 2 shards)."""
    import numpy as np

    from repro.core.engine import OP_DELETE, OP_INSERT, OP_SEARCH, shard_owner
    from repro.core.hashing import h3_hash

    rng = np.random.default_rng(seed)
    D = cfg.shards
    pool_n = 8 * T * N
    pool = rng.integers(1, np.iinfo(np.uint32).max, dtype=np.uint32,
                        size=(pool_n, cfg.key_words))
    bucket = np.asarray(h3_hash(pool, q_masks))
    owner = np.asarray(shard_owner(cfg, bucket))
    by_owner = [pool[owner == s] for s in range(D)]
    probs = 1.0 / np.arange(1, D + 1) ** zipf_a
    probs /= probs.sum()
    lane_shard = rng.choice(D, size=T * N, p=probs)
    keys = np.empty((T * N, cfg.key_words), np.uint32)
    cursor = np.zeros(D, np.int64)
    for i, s in enumerate(lane_shard):
        keys[i] = by_owner[s][cursor[s] % len(by_owner[s])]
        cursor[s] += 1
    mut = rng.random(T * N) < nsq_fraction
    ops = np.where(mut, np.where(rng.random(T * N) < 0.5, OP_INSERT,
                                 OP_DELETE), OP_SEARCH).astype(np.int32)
    vals = rng.integers(0, np.iinfo(np.uint32).max, dtype=np.uint32,
                        size=(T * N, cfg.val_words))
    return (ops.reshape(T, N), keys.reshape(T, N, cfg.key_words),
            vals.reshape(T, N, cfg.val_words))


def _replication_ab(smoke: bool) -> dict:
    """Paired flat-1-D vs load-aware-replicated A/B on 8 devices."""
    import dataclasses as _dc

    import jax
    import numpy as np

    from benchmarks.common import bench_group, row
    from repro.core import HashTableConfig
    from repro.core.distributed import (init_distributed_table,
                                        make_distributed_stream, make_ht_mesh)
    from repro.core.engine import (plan_bounded_route, plan_replication,
                                   shard_owner)
    from repro.core.hashing import h3_hash
    from repro.serving.serve_loop import measure_loads_host

    n_dev = 8
    T, nl, buckets, iters = ((T_SMOKE, NL_SMOKE, BUCKETS_SMOKE, 1) if smoke
                             else (T_FULL, NL_FULL, BUCKETS_FULL, ITERS))
    nsq, zipf_a = 0.06, 1.6           # search-heavy, hot shard 0
    N = n_dev * nl
    mesh = make_ht_mesh(n_dev)
    cfg_flat = HashTableConfig(p=n_dev, k=n_dev, buckets=buckets, slots=2,
                               queries_per_pe=nl, replicate_reads=False,
                               stagger_slots=True, shards=n_dev,
                               router="bounded")
    tab_flat = init_distributed_table(cfg_flat, jax.random.key(0), mesh)
    qm_host = np.asarray(jax.device_get(tab_flat.q_masks))
    ops, keys, vals = _zipf_hot_stream(cfg_flat, tab_flat.q_masks, T, N,
                                       nsq, zipf_a)

    # plan the replica degrees from the measured 2-shard owner skew
    cfg2 = _dc.replace(cfg_flat, shards=2)
    bucket = h3_hash(keys.reshape(T * N, cfg2.key_words), tab_flat.q_masks)
    owner2 = np.asarray(shard_owner(cfg2, bucket))
    shard_loads = np.bincount(owner2, minlength=2)
    degrees = plan_replication(cfg2, shard_loads, n_dev)
    cfg_rep = _dc.replace(cfg_flat, shards=2, replica_groups=degrees)
    tab_rep = init_distributed_table(cfg_rep, jax.random.key(0), mesh)

    import jax.numpy as jnp
    ops_j, keys_j, vals_j = jnp.asarray(ops), jnp.asarray(keys), \
        jnp.asarray(vals)
    stream_flat = make_distributed_stream(mesh, cfg_flat, router="bounded")
    stream_rep = make_distributed_stream(mesh, cfg_rep, router="bounded")
    us = bench_group({
        "flat": lambda: stream_flat(tab_flat, ops_j, keys_j, vals_j)[1].found,
        "replicated":
            lambda: stream_rep(tab_rep, ops_j, keys_j, vals_j)[1].found,
    }, iters=iters)
    mops = {name: T * N / t for name, t in us.items()}

    def plan_shapes(plan):
        return {"routed_width": plan.routed_width,
                "skewproof_width": plan.skewproof_width,
                "width_ratio": plan.width_ratio,
                "routed_steps": plan.routed_steps,
                "carry_rate": plan.carry_rate}

    owner_flat = np.asarray(shard_owner(cfg_flat, bucket)).reshape(T, N)
    plan_flat = plan_bounded_route(cfg_flat, owner_flat)
    loads_g, pair_g = measure_loads_host(cfg_rep, qm_host, keys, ops)
    plan_rep = plan_bounded_route(cfg_rep, loads=loads_g, pair=pair_g,
                                  n_local=nl)
    # per-group replica occupancy: how evenly the round-robin fan-out +
    # mutation broadcast land across each shard's group members
    occupancy = []
    for s in range(2):
        o = cfg_rep.group_offsets[s]
        g = loads_g[:, o:o + degrees[s]]
        occupancy.append({
            "shard": s, "degree": int(degrees[s]),
            "shard_load_fraction": float(shard_loads[s] / shard_loads.sum()),
            "mean_member_load": float(g.mean()),
            "max_member_load": int(g.max()),
            "member_balance": float(g.max() / max(g.mean(), 1e-9)),
        })
    ab = {
        "n_devices": n_dev, "steps": T, "n_local": nl, "iters": iters,
        "nsq_fraction": nsq, "zipf_a": zipf_a,
        "stat": "paired best-of-N (bench_group round-robin)",
        "flat": {"shards": n_dev, "mops": mops["flat"],
                 "bounded_router": plan_shapes(plan_flat)},
        "replicated": {"shards": 2, "replica_groups": list(degrees),
                       "mops": mops["replicated"],
                       "bounded_router": plan_shapes(plan_rep),
                       "group_occupancy": occupancy},
        "replicated_over_flat": mops["replicated"] / mops["flat"],
        "plan": {"shard_loads": [int(x) for x in shard_loads],
                 "degrees": list(degrees)},
    }
    row("distributed_replication_ab", 0.0,
        f"replicated_MOPS={mops['replicated']:.3f};"
        f"flat_MOPS={mops['flat']:.3f};"
        f"replicated_over_flat={ab['replicated_over_flat']:.2f};"
        f"groups={list(degrees)};"
        f"width={plan_rep.routed_width}vs{plan_flat.routed_width}")
    return ab


def _sweep(smoke: bool, routers) -> None:
    import jax

    from benchmarks.common import bench_group, mixed_stream, row
    from repro.core import HashTableConfig
    from repro.core.distributed import (init_distributed_table,
                                        make_distributed_step,
                                        make_distributed_stream, make_ht_mesh)
    from repro.core.engine import plan_bounded_route

    shards = SHARDS[:1] if smoke else SHARDS
    T, nl, buckets, iters = ((T_SMOKE, NL_SMOKE, BUCKETS_SMOKE, 1) if smoke
                             else (T_FULL, NL_FULL, BUCKETS_FULL, ITERS))
    results = {"host_backend": jax.default_backend(),
               "interpret_mode": jax.default_backend() != "tpu",
               "steps": T, "n_local": nl, "buckets": buckets, "iters": iters,
               "routers": list(routers),
               "stat": "paired best-of-N (bench_group round-robin)",
               "notes": "bounded rows include the per-call two-pass "
                        "measurement (~0.3ms host pass + sync); it pays "
                        "once the measured width shrink beats that — at "
                        "D=2 the uniform max load already fills the "
                        "skew-proof width (width_ratio 1.0, the wrapper "
                        "falls back to the skew-proof exchange), so the "
                        "bounded column there is pure measurement "
                        "overhead, while the shrink grows with D",
               "rows": []}
    for D in shards:
        cfg = HashTableConfig(p=D, k=D, buckets=buckets, slots=2,
                              queries_per_pe=nl, replicate_reads=False,
                              stagger_slots=True, shards=D)
        cfg_rep = dataclasses.replace(cfg, shards=1)
        mesh = make_ht_mesh(D)
        tab_sh = init_distributed_table(cfg, jax.random.key(0), mesh)
        tab_rep = init_distributed_table(cfg_rep, jax.random.key(0))
        step = make_distributed_step(mesh, cfg_rep)
        N = D * nl
        ops_j, keys_j, vals_j = mixed_stream(cfg, T)

        fns = {}
        for router in routers:
            stream = make_distributed_stream(mesh, cfg, router=router)

            def run_sharded(stream=stream):
                _, res = stream(tab_sh, ops_j, keys_j, vals_j)
                return res.found

            fns[f"sharded_{router}"] = run_sharded

        def run_replicated():
            tab, res = tab_rep, None
            for t in range(T):
                tab, res = step(tab, ops_j[t], keys_j[t], vals_j[t])
            return res.found          # chains through every step's table

        fns["replicated_step"] = run_replicated
        us = bench_group(fns, iters=iters)
        mops = {name: T * N / t for name, t in us.items()}
        owner, occ = _routed_occupancy(cfg, tab_sh.q_masks, keys_j)
        plan = plan_bounded_route(cfg, owner)
        out_row = {
            "shards": D,
            "mops_replicated_step": mops["replicated_step"],
            "routed_occupancy": occ,
            "bounded_router": {
                "routed_width": plan.routed_width,
                "skewproof_width": plan.skewproof_width,
                "width_ratio": plan.width_ratio,
                "routed_steps": plan.routed_steps,
                "pair_capacity": plan.pair_capacity,
                "carried_lanes": plan.carried_lanes,
                "carry_rate": plan.carry_rate,
            },
        }
        for router in routers:
            out_row[f"mops_sharded_{router}"] = mops[f"sharded_{router}"]
            out_row[f"sharded_{router}_over_replicated"] = (
                mops[f"sharded_{router}"] / mops["replicated_step"])
        if len(routers) == 2:
            out_row["bounded_over_skewproof"] = (
                mops["sharded_bounded"] / mops["sharded_skewproof"])
        results["rows"].append(out_row)
        sharded_cols = ";".join(
            f"{r}_MOPS={mops[f'sharded_{r}']:.3f}" for r in routers)
        row(f"distributed_throughput_D{D}", 0.0,
            f"{sharded_cols};"
            f"replicated_MOPS={mops['replicated_step']:.3f};"
            f"routed_width={plan.routed_width}/{plan.skewproof_width};"
            f"carry_rate={plan.carry_rate:.3f};"
            f"max_occupancy={occ['max_occupancy']:.3f};"
            f"router_shrink={occ['router_shrink_potential']:.1f}x")
    if len(routers) == 2:           # full A/B run: append the 2-D section
        results["replication_ab"] = _replication_ab(smoke)
    if smoke:
        print("smoke OK")
        return
    out = os.path.join(_ROOT, "BENCH_distributed.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


def _replicated_only(smoke: bool) -> None:
    """``--replicated``: run just the 2-D A/B and update its JSON section."""
    ab = _replication_ab(smoke)
    if smoke:
        print("smoke OK")
        return
    out = os.path.join(_ROOT, "BENCH_distributed.json")
    results = json.load(open(out)) if os.path.exists(out) else {}
    results["replication_ab"] = ab
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out} (replication_ab)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 iter, no JSON — CI harness check")
    ap.add_argument("--bounded", action="store_true",
                    help="pin the sharded column to the bounded router only")
    ap.add_argument("--skewproof", action="store_true",
                    help="pin the sharded column to the skew-proof router "
                         "only")
    ap.add_argument("--replicated", action="store_true",
                    help="run only the 2-D (shard x replica) mesh A/B and "
                         "update the replication_ab JSON section in place")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.bounded and args.skewproof:
        ap.error("--bounded and --skewproof are mutually exclusive "
                 "(omit both for the A/B pair)")
    if args.replicated and (args.bounded or args.skewproof):
        ap.error("--replicated is its own A/B; drop --bounded/--skewproof")
    routers = (("bounded",) if args.bounded else
               ("skewproof",) if args.skewproof else
               ("bounded", "skewproof"))
    if args.child:
        if args.replicated:
            _replicated_only(args.smoke)
        else:
            _sweep(args.smoke, routers)
        return
    # a device mesh needs >1 device; fork with forced fake devices so the
    # driver (benchmarks/run.py) keeps its real single-device view
    from benchmarks.common import run_fake_device_child
    r = run_fake_device_child(
        __file__, [f"--{flag}" for flag in ("smoke", "bounded", "skewproof",
                                            "replicated")
                   if getattr(args, flag)])
    if r.returncode:
        raise RuntimeError(f"distributed_throughput child failed "
                           f"(exit {r.returncode})")


if __name__ == "__main__":
    main()
