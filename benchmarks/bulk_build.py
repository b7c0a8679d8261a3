"""Bulk build vs streamed insert: count-then-place table construction A/B.

Times, on identical record sets (``bench_group`` paired round-robin,
drift-immune), building a table of ``n`` records three ways:

  bulk      ``engine.bulk_build`` (DESIGN.md §3.2): hash all keys, resolve
            intra-batch duplicates in-plan, histogram-rank per bucket, ONE
            placement pass.  Called EAGERLY — the count-then-place plan is
            sort-bound and runs as a host numpy pass off-TPU (engine
            ``plan_bulk_build``), with the placement stage internally jitted.
  streamed  one ``step`` dispatch per packed INSERT step (every lane an
            insert) — the construction loop every table population ran
            before the bulk seam existed (dedup, prefix_cache): records
            arrive a step at a time, so each step is its own dispatch.
            This is the acceptance pair: bulk_over_streamed.
  scan      ``run_stream`` over all ``n / N`` steps in ONE lax.scan program
            — the fastest streamed construction, but it needs every record
            ahead of time as a [T, N] tensor, which makes it a batch
            construction path too; reported as the honest second yardstick
            (bulk_over_scan).

Key sets sweep the duplicate spectrum: ``uniform`` (distinct random keys),
``zipf`` (skewed popularity — a hot head of repeated keys), and ``dup``
(small key pool, duplicate-heavy — the plan's last-wins pass does most of the
work).  Off-TPU every candidate runs the jnp engine path (interpret-mode
Pallas is a correctness harness, not a fast path — the BENCH_stream.json
policy), so the A/B stays apples-to-apples.

A sharded row (``--sharded``, included in full mode) re-execs in a subprocess
with 8 fake CPU devices (the conftest convention) and times
``make_distributed_bulk_build`` against the distributed INSERT stream at
``cfg.shards == 8`` (the shard_map trace keeps the plan on the XLA path, so
this row also covers the non-host plan).

Emits ``BENCH_bulk.json`` (full mode; ``--smoke`` is the CI harness check).
benchmarks/roofline.py reports measured-vs-modeled per row from
``perfmodel.bulk_build_modeled_mops``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os

import numpy as np

NS_FULL = (4096, 16384, 65536)
NS_SMOKE = (256,)
ITERS = 5          # paired best-of-N rounds (bench_group): drift-immune
SHARDED_ITERS = 2  # the distributed per-step loop is seconds per call
P = 8
TABLE = dict(buckets=1 << 13, slots=4, replicate_reads=False,
             stagger_slots=True)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_keys(kind: str, n: int, key_words: int, seed: int = 0):
    """Record sets across the duplicate spectrum (uint32 [n, Wk] / [n, 1])."""
    rng = np.random.default_rng(seed)
    keys = np.zeros((n, key_words), np.uint32)
    if kind == "uniform":
        keys[:, 0] = rng.integers(1, 2 ** 32, size=n, dtype=np.uint32)
    elif kind == "zipf":
        keys[:, 0] = (rng.zipf(1.3, size=n) % (2 ** 20 - 1)) + 1
    elif kind == "dup":
        keys[:, 0] = rng.integers(1, max(n // 8, 2), size=n)
    else:
        raise ValueError(kind)
    vals = rng.integers(1, 2 ** 32, size=(n, 1), dtype=np.uint32)
    return keys, vals


def run_single(n: int, kind: str, iters: int):
    """bulk vs streamed vs scanned construction of the same n-record table."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import bench_group
    from repro.core import OP_INSERT, HashTableConfig, init_table, run_stream
    from repro.core.engine import QueryBatch, bulk_build, step

    cfg = HashTableConfig(p=P, k=P, queries_per_pe=8, backend="jnp", **TABLE)
    tab = init_table(cfg, jax.random.key(0))
    keys, vals = make_keys(kind, n, cfg.key_words)
    N = cfg.queries_per_step
    T = -(-n // N)
    ops_t = np.zeros((T * N,), np.int32)
    ops_t[:n] = OP_INSERT                      # pad lanes are NOPs
    kk_t = np.zeros((T * N, cfg.key_words), np.uint32)
    kk_t[:n] = keys
    vv_t = np.zeros((T * N, cfg.val_words), np.uint32)
    vv_t[:n] = vals
    ops_j = jnp.array(ops_t.reshape(T, N))
    keys_j = jnp.array(kk_t.reshape(T, N, cfg.key_words))
    vals_j = jnp.array(vv_t.reshape(T, N, cfg.val_words))
    keys_f, vals_f = jnp.array(keys), jnp.array(vals)

    jscan = jax.jit(run_stream, static_argnames=("backend", "fused"))
    jstep = jax.jit(step, static_argnames=("backend",))

    def streamed():
        tb = tab
        for i in range(T):
            tb, _ = jstep(tb, QueryBatch(ops_j[i], keys_j[i], vals_j[i]))
        return tb

    us = bench_group({
        "bulk": functools.partial(bulk_build, tab, keys_f, vals_f),
        "streamed": streamed,
        "scan": functools.partial(jscan, tab, ops_j, keys_j, vals_j),
    }, iters=iters, warmup=2)
    # sanity: identical resident key sets (order-free — packed streamed steps
    # insert N records at once, so slot ranks may differ from the serialized
    # order bulk reproduces; bit-exactness vs the serialized oracle is
    # tests/test_bulk_build's job)
    tb, report = jax.block_until_ready(bulk_build(tab, keys_f, vals_f))
    return {
        "n": n, "keyset": kind, "steps": T,
        "distinct_keys": int(len(np.unique(keys[:, 0]))),
        "spilled": int(report.spill_count),
        "max_load": int(report.max_load),
        "mops_bulk": n / us["bulk"],
        "mops_streamed": n / us["streamed"],
        "mops_scan": n / us["scan"],
        "bulk_over_streamed": us["streamed"] / us["bulk"],
        "bulk_over_scan": us["scan"] / us["bulk"],
    }


def run_sharded(n: int, iters: int):
    """Distributed bulk build vs the distributed INSERT stream at
    shards == 8: streamed = one shard_map dispatch per step (records arrive
    a step at a time), scan = all steps in one routed program."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import bench_group
    from repro.core import OP_INSERT, HashTableConfig
    from repro.core.distributed import (init_distributed_table,
                                        make_distributed_bulk_build,
                                        make_distributed_stream, make_ht_mesh)

    D = 8
    cfg = HashTableConfig(p=D, k=D, queries_per_pe=8, shards=D, **TABLE)
    mesh = make_ht_mesh(D)
    tab = init_distributed_table(cfg, jax.random.key(0), mesh)
    keys, vals = make_keys("uniform", n, cfg.key_words)
    N = cfg.queries_per_step
    T = -(-n // N)
    kk = np.zeros((T * N, cfg.key_words), np.uint32); kk[:n] = keys
    vv = np.zeros((T * N, cfg.val_words), np.uint32); vv[:n] = vals
    lv = np.zeros(T * N, bool); lv[:n] = True
    ops = np.where(lv, OP_INSERT, 0).astype(np.int32)
    keys_j = jnp.array(kk.reshape(T, N, cfg.key_words))
    vals_j = jnp.array(vv.reshape(T, N, cfg.val_words))
    live_j = jnp.array(lv.reshape(T, N))
    ops_j = jnp.array(ops.reshape(T, N))

    build = make_distributed_bulk_build(mesh, cfg)
    stream = make_distributed_stream(mesh, cfg)

    def streamed():
        tb = tab
        for i in range(T):
            tb, _ = stream(tb, ops_j[i:i + 1], keys_j[i:i + 1],
                           vals_j[i:i + 1])
        return tb

    us = bench_group({
        "bulk": functools.partial(build, tab, keys_j, vals_j, live_j),
        "streamed": streamed,
        "scan": functools.partial(stream, tab, ops_j, keys_j, vals_j),
    }, iters=iters, warmup=1)
    return {
        "n": n, "keyset": "uniform", "shards": D, "steps": T,
        "mops_bulk": n / us["bulk"],
        "mops_streamed": n / us["streamed"],
        "mops_scan": n / us["scan"],
        "bulk_over_streamed": us["streamed"] / us["bulk"],
        "bulk_over_scan": us["scan"] / us["bulk"],
    }


def _emit(rec, label):
    from benchmarks.common import row
    row(f"bulk_build_{label}", 0.0,
        f"bulk_MOPS={rec['mops_bulk']:.3f};"
        f"streamed_MOPS={rec['mops_streamed']:.3f};"
        f"scan_MOPS={rec['mops_scan']:.3f};"
        f"bulk_over_streamed={rec['bulk_over_streamed']:.2f};"
        f"bulk_over_scan={rec['bulk_over_scan']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 iter, no JSON — CI harness check")
    ap.add_argument("--sharded", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="include the shards=8 subprocess row (default: "
                         "full mode yes, smoke no)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    iters = 1 if args.smoke else ITERS

    if args.child:
        # inside the 8-fake-device subprocess: emit the sharded rows as JSON
        ns = NS_SMOKE if args.smoke else NS_FULL[-1:]
        it = 1 if args.smoke else SHARDED_ITERS
        print(json.dumps([run_sharded(n, it) for n in ns]))
        return

    import jax
    results = {"host_backend": jax.default_backend(),
               "interpret_mode": jax.default_backend() != "tpu",
               "p": P, "iters": iters, "table": TABLE,
               "stat": "paired best-of-N (bench_group round-robin)",
               "notes": "every candidate on the jnp engine path off-TPU "
                        "(interpret-mode Pallas is a correctness harness); "
                        "streamed = one dispatch per packed INSERT step (the "
                        "pre-bulk construction loop, records arrive a step "
                        "at a time) — the acceptance pair; scan = all steps "
                        "in one lax.scan program (needs the full record set "
                        "upfront, i.e. itself a batch construction path)",
               "rows": [], "sharded_rows": []}
    ns = NS_SMOKE if args.smoke else NS_FULL
    for kind in ("uniform", "zipf", "dup"):
        for n in ns:
            rec = run_single(n, kind, iters)
            results["rows"].append(rec)
            _emit(rec, f"{kind}_n{n}")

    sharded = (not args.smoke) if args.sharded is None else args.sharded
    if sharded:
        from benchmarks.common import run_fake_device_child
        r = run_fake_device_child(__file__,
                                  ["--smoke"] if args.smoke else [],
                                  capture=True)
        if r.returncode:
            raise RuntimeError(f"bulk_build sharded child failed "
                               f"(exit {r.returncode}):\n{r.stderr}")
        results["sharded_rows"] = json.loads(r.stdout.strip().splitlines()[-1])
        for rec in results["sharded_rows"]:
            _emit(rec, f"sharded{rec['shards']}_n{rec['n']}")

    if args.smoke:
        print("smoke OK")
        return
    out = os.path.join(_ROOT, "BENCH_bulk.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
