"""Fig 7: throughput vs supported non-search-queries-per-cycle ratio (k/p),
plus the memory saved by search-only PEs (the paper's workload
customization).

``geometry_ab`` is the planner's paired experiment (DESIGN.md §5): at each
search fraction the worst-case fixed geometry (k=p, every PE a write port)
races the ``perfmodel.plan_geometry`` choice for the measured mix, both under
the same bench-local VMEM budget.  The auto table is produced by migrating
the live fixed table through ``engine.reconfigure`` — the same path
``TableServer`` uses online — so the A/B also certifies the migration.
Emits ``BENCH_nsq.json`` (full mode; ``--smoke`` is the CI harness check).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bench, bench_group, row
from repro.core import (HashTableConfig, OP_INSERT, OP_SEARCH, bulk_build,
                        engine, init_table, memory_bytes, pack_trace,
                        run_stream)
from repro.core.perfmodel import plan_geometry, _planner_bucket_tiles
from repro.kernels import ops as kops

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

P = 8
QPP = 64
STEPS = 16

# geometry_ab shapes: sized so the FIXED worst-case replica (k=p) overflows
# the bench-local VMEM budget (blocked regime, bucket-axis tiling) while the
# planned compact replica fits resident — the discrete regime win the
# planner's budget term models.
AB_QPP = 8
AB_STEPS = 8
AB_BUCKETS = 1 << 13
AB_BUDGET = 1 << 20            # 1 MiB: fixed k=8 replica is 3 MiB -> tiles=4
AB_FRACTIONS = (0.5, 0.9, 0.99)

# keys every geometry_ab entry must carry — checked before the JSON is
# written so a refactor can't silently drop the paired columns
AB_ROW_KEYS = ("search_fraction", "fixed", "auto", "auto_over_fixed",
               "crossed_to_resident")
AB_SIDE_KEYS = ("k", "replica_bytes", "bucket_tiles", "vmem_regime", "mops")


def _ab_trace(frac: float, n_queries: int, rng):
    """Flat trace with EXACTLY ``round((1-frac) * n)`` NSQs at random
    positions.  Each side of the pair packs it for its own geometry via
    ``pack_trace`` — the compact side pays its longer schedule honestly
    (the planner's packing-stretch term), and MOPS counts live queries."""
    ops = np.full(n_queries, OP_SEARCH, np.int32)
    n_nsq = int(round((1.0 - frac) * n_queries))
    ops[rng.choice(n_queries, size=n_nsq, replace=False)] = OP_INSERT
    keys = rng.integers(1, 2 ** 32, size=(n_queries, 1), dtype=np.uint32)
    vals = keys + 1
    return ops, keys, vals


def geometry_ab(smoke: bool) -> dict:
    steps = 2 if smoke else AB_STEPS
    buckets = (1 << 8) if smoke else AB_BUCKETS
    budget = (1 << 14) if smoke else AB_BUDGET
    iters = 1 if smoke else 9
    cfg_fixed = HashTableConfig(p=P, k=P, buckets=buckets, slots=4,
                                replicate_reads=False, stagger_slots=True,
                                queries_per_pe=AB_QPP)
    N = cfg_fixed.queries_per_step
    ab = {"p": P, "queries_per_pe": AB_QPP, "steps": steps,
          "buckets": buckets, "vmem_budget_bytes": budget, "iters": iters,
          "stat": "paired best-of-N (bench_group round-robin)",
          "notes": "auto table produced by engine.reconfigure from the live "
                   "fixed table (the TableServer migration path); both sides "
                   "run the fused stream under the same bench-local VMEM "
                   "budget, so the regime column is the planner's discrete "
                   "blocked->resident win.  One flat trace per fraction, "
                   "packed per side by pack_trace — a compact k that can't "
                   "absorb the NSQ rate pays its longer schedule "
                   "(packed_steps), and mops counts live queries per us",
          "rows": []}
    rng = np.random.default_rng(0)
    for frac in AB_FRACTIONS:
        plan = plan_geometry(cfg_fixed, (frac, 1.0 - frac),
                             vmem_budget=budget)
        cfg_auto = plan.apply(cfg_fixed)
        tiles_fixed = _planner_bucket_tiles(cfg_fixed.replica_bytes,
                                            buckets, budget)
        tiles_auto = _planner_bucket_tiles(cfg_auto.replica_bytes,
                                           buckets, budget)
        n_q = steps * N
        ops, keys, vals = _ab_trace(frac, n_q, rng)
        tab_fixed = init_table(cfg_fixed, jax.random.key(0))
        # prepopulate with the stream's keys so search lanes measure hits,
        # then MIGRATE the live table into the planned geometry
        tab_fixed, _ = bulk_build(tab_fixed, jnp.array(keys),
                                  jnp.array(vals))
        tab_auto = engine.reconfigure(tab_fixed, cfg_auto)

        def make_fn(tab, cfg):
            op_s, kk_s, vv_s = pack_trace(ops, keys, vals, cfg)
            args = (jnp.array(op_s), jnp.array(kk_s), jnp.array(vv_s))
            fn = jax.jit(lambda t: run_stream(t, *args, fused=True))
            return op_s.shape[0], (lambda: fn(tab)[1].found)

        steps_fixed, fn_fixed = make_fn(tab_fixed, cfg_fixed)
        steps_auto, fn_auto = make_fn(tab_auto, cfg_auto)
        # the kernels size their sweep passes from the budget when traced
        # (in the warmup), so both sides trace under the bench-local one
        saved = kops.VMEM_TABLE_BUDGET_BYTES
        kops.VMEM_TABLE_BUDGET_BYTES = budget
        try:
            us = bench_group({"fixed": fn_fixed, "auto": fn_auto},
                             iters=iters, warmup=1)
        finally:
            kops.VMEM_TABLE_BUDGET_BYTES = saved
        mops = {name: n_q / t for name, t in us.items()}
        regime = lambda tiles: "resident" if tiles == 1 else "blocked"
        out = {
            "search_fraction": frac,
            "fixed": {"k": cfg_fixed.k,
                      "replica_bytes": cfg_fixed.replica_bytes,
                      "bucket_tiles": tiles_fixed,
                      "vmem_regime": regime(tiles_fixed),
                      "packed_steps": steps_fixed,
                      "mops": mops["fixed"]},
            "auto": {"k": cfg_auto.k,
                     "replica_bytes": cfg_auto.replica_bytes,
                     "bucket_tiles": tiles_auto,
                     "vmem_regime": regime(tiles_auto),
                     "packed_steps": steps_auto,
                     "mops": mops["auto"]},
            "planned_modeled_mops": plan.modeled_mops,
            "planned_improvement": plan.improvement,
            "memory_saving": plan.memory_saving,
            "auto_over_fixed": mops["auto"] / mops["fixed"],
            "crossed_to_resident": tiles_fixed > 1 and tiles_auto == 1,
        }
        ab["rows"].append(out)
        row(f"fig7_geometry_ab_f{frac}", 0.0,
            f"auto_k={cfg_auto.k};fixed_k={cfg_fixed.k};"
            f"auto_MOPS={mops['auto']:.3f};fixed_MOPS={mops['fixed']:.3f};"
            f"auto_over_fixed={out['auto_over_fixed']:.2f};"
            f"replica_bytes={cfg_auto.replica_bytes}vs"
            f"{cfg_fixed.replica_bytes};"
            f"regime={regime(tiles_auto)}vs{regime(tiles_fixed)}")
    _check_ab_schema(ab)
    return ab


def _check_ab_schema(ab: dict) -> None:
    """Refuse to emit a geometry_ab section missing the paired columns."""
    if not ab.get("rows"):
        raise AssertionError("geometry_ab: no rows")
    for r in ab["rows"]:
        missing = [k for k in AB_ROW_KEYS if k not in r]
        for side in ("fixed", "auto"):
            missing += [f"{side}.{k}" for k in AB_SIDE_KEYS
                        if k not in r.get(side, {})]
        if missing:
            raise AssertionError(f"geometry_ab row missing {missing}")


def k_sweep(smoke: bool) -> list:
    rows = []
    steps = 2 if smoke else STEPS
    buckets = (1 << 8) if smoke else (1 << 14)
    for k in (1, P) if smoke else (1, 2, 4, 8):
        cfg = HashTableConfig(p=P, k=k, buckets=buckets, slots=4,
                              replicate_reads=False, stagger_slots=True,
                              queries_per_pe=QPP)
        tab = init_table(cfg, jax.random.key(0))
        rng = np.random.default_rng(0)
        N = cfg.queries_per_step
        # NSQ fraction == the supported ratio; NSQs on lanes with pe < k
        ops = np.full((steps, N), OP_SEARCH, np.int32)
        lanes = np.arange(N) % P
        ops[:, lanes < k] = OP_INSERT
        keys = rng.integers(1, 2 ** 32, size=(steps, N, 1), dtype=np.uint32)
        vals = keys + 1
        # bulk-prepopulate with the stream's keys (one count-then-place
        # sweep) so the search-lane majority measures the hit path
        tab, _ = bulk_build(tab, jnp.array(keys.reshape(-1, 1)),
                            jnp.array(vals.reshape(-1, 1)))
        fn = jax.jit(lambda t: run_stream(t, jnp.array(ops), jnp.array(keys),
                                          jnp.array(vals)))
        us = bench(lambda: fn(tab), iters=1 if smoke else 3, warmup=1)
        mops = steps * N / us
        mem = memory_bytes(cfg) / 1e6
        full = memory_bytes(dataclasses.replace(cfg, k=P)) / 1e6
        row(f"fig7_nsq_p{P}_k{k}", 0.0,
            f"ratio={k}/{P};measured_cpu_MOPS={mops:.2f};mem_MB={mem:.1f};"
            f"saving_vs_full={100 * (1 - mem / full):.0f}%")
        rows.append({"k": k, "p": P, "ratio": k / P, "mops": mops,
                     "mem_mb": mem, "saving_vs_full": 1 - mem / full})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 iter, no JSON — CI harness check")
    args = ap.parse_args()
    results = {"host_backend": jax.default_backend(),
               "interpret_mode": jax.default_backend() != "tpu",
               "rows": k_sweep(args.smoke),
               "geometry_ab": geometry_ab(args.smoke)}
    if args.smoke:
        print("smoke OK")
        return
    out = os.path.join(_ROOT, "BENCH_nsq.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
