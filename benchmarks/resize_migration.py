"""Grow-under-load vs stop-the-world resize (DESIGN.md §6): insert-heavy
traffic through a ``TableServer`` whose ``GrowthPolicy`` trips mid-serve.

Three modes serve the IDENTICAL request sequence against a fresh table each
measured iteration (paired best-of-N, the ``bench_group`` discipline
inlined because each run owns a stateful server):

  born_big        reference: table born at the capacity the grown runs end
                  at — no resize window, no migration pauses
  grow_online     online resize: the migration interleaves with serving,
                  ``migrate_buckets_per_slab`` predecessor buckets between
                  consecutive dispatches (the watermark walk)
  stop_the_world  the rebuild baseline: the same resize seam with the slab
                  sized to the whole table, so the dispatch after the
                  trigger stalls behind the entire migration — the classic
                  pause a streaming table cannot afford

Arrivals are open-loop: request i arrives at ``i * dt`` regardless of how
the server is doing, so a migration stall is priced the way a stream sees
it — every arrival that lands during the pause queues behind it, and the
headline metric, p99 submit->retire request latency, charges the pause
times its depth.  (A closed-loop/step-time view structurally hides the
stop-the-world stall: one giant step out of hundreds escapes the step
p99 while online's many small bumps all land in it.)  The per-``step()``
wall-time distribution, MOPS over live lanes, and the perfmodel per-slab
pause (``resize_migration_seconds``) ride along for the roofline
cross-check.

Full mode emits ``BENCH_resize.json`` (figure resize_migration);
``--smoke`` shrinks everything to the CI harness check and never writes.
"""
from __future__ import annotations

import argparse
import json
import os
import time

BUCKETS_FULL, SLOTS_FULL, QPP_FULL, SLAB_ONLINE_FULL = 1 << 13, 8, 16, 2048
REQS_FULL, LANES_FULL, KEYS_FULL, ITERS_FULL = 280, 128, 1 << 21, 3
DT_FULL_MS, SLAB_STEPS_FULL = 8.0, 8
BUCKETS_SMOKE, SLOTS_SMOKE, QPP_SMOKE, SLAB_ONLINE_SMOKE = 1 << 6, 4, 2, 16
REQS_SMOKE, LANES_SMOKE, KEYS_SMOKE = 12, 12, 1 << 10
DT_SMOKE_MS, SLAB_STEPS_SMOKE = 3.0, 4

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trace(cfg, requests, lanes, key_space, seed=0):
    import numpy as np
    from repro.core import OP_DELETE, OP_INSERT, OP_SEARCH
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(requests):
        ops = rng.choice([OP_SEARCH, OP_INSERT, OP_DELETE], size=lanes,
                         p=[0.25, 0.65, 0.10]).astype(np.int32)
        keys = np.zeros((lanes, cfg.key_words), np.uint32)
        keys[:, 0] = rng.integers(1, key_space, size=lanes)
        vals = rng.integers(1, 2 ** 32, size=(lanes, cfg.val_words),
                            dtype=np.uint32)
        out.append((ops, keys, vals))
    return out


def _serve_once(cfg, scfg, trace, seed_rng, stream, dt_s):
    """One fresh-table pass under open-loop paced arrivals: request i
    arrives at ``i * dt_s``; the loop submits everything whose arrival time
    has passed, steps the server while it has work, and sleeps to the next
    arrival otherwise.  Open-loop is what makes a migration stall visible
    at the request level — every arrival that lands during a pause queues
    behind it, so the request p99 prices the pause times its depth.

    Latency is measured from the SCHEDULED arrival (``i * dt_s``), not from
    ``submit`` — the loop is single-threaded, so arrivals that come due
    while a step is stalled can only be submitted after it returns, and
    clocking from submit would silently forgive exactly the stall being
    measured (coordinated omission).

    Returns (elapsed_s, request latencies, busy-step wall times, srv)."""
    import jax
    from repro.core.hash_table import init_table
    from repro.serving import TableServer

    table = init_table(cfg, jax.random.key(0))
    jax.block_until_ready(table.packed)
    srv = TableServer(cfg, table, stream, scfg, rng=seed_rng)
    reqs, steps = [], []
    i, report = 0, None
    t0 = time.perf_counter()
    while i < len(trace) or report is None or not report.quiescent:
        now = time.perf_counter() - t0
        while i < len(trace) and i * dt_s <= now:
            ops, keys, vals = trace[i]
            reqs.append(srv.submit(ops, keys, vals))
            i += 1
        if (not srv._queue.pending_requests and not srv._inflight
                and i < len(trace)):
            # idle until the next arrival — stepping now would look
            # quiescent and drain any open resize in one giant stall
            time.sleep(max(0.0, i * dt_s - now))
            continue
        busy = srv._queue.pending_requests
        ts = time.perf_counter()
        report = srv.step()
        if busy:
            steps.append(time.perf_counter() - ts)
    elapsed = time.perf_counter() - t0
    srv._closed = True
    # retire wall time = submit stamp + submit->retire latency; subtract the
    # scheduled arrival to put pre-submit queueing back on the clock
    lats = [(r.submit_s + r.latency_s) - (t0 + j * dt_s)
            for j, r in enumerate(reqs)]
    return elapsed, lats, steps, srv


def _sweep(smoke: bool) -> None:
    import dataclasses

    import numpy as np
    import jax

    from benchmarks.common import row
    from repro.core import HashTableConfig
    from repro.core import engine as eng
    from repro.core.config import GrowthPolicy
    from repro.core.perfmodel import (resize_migration_seconds,
                                      resize_total_seconds)
    from repro.serving import ServeConfig

    buckets, slots, qpp, slab_online = (
        (BUCKETS_SMOKE, SLOTS_SMOKE, QPP_SMOKE, SLAB_ONLINE_SMOKE) if smoke
        else (BUCKETS_FULL, SLOTS_FULL, QPP_FULL, SLAB_ONLINE_FULL))
    requests, lanes, key_space = (
        (REQS_SMOKE, LANES_SMOKE, KEYS_SMOKE) if smoke
        else (REQS_FULL, LANES_FULL, KEYS_FULL))
    iters = 1 if smoke else ITERS_FULL
    dt_s = (DT_SMOKE_MS if smoke else DT_FULL_MS) * 1e-3
    # slab wider than one request: batching headroom is what lets the serve
    # loop absorb a migration pause — a backlogged dispatch coalesces
    # several queued requests into one slab, so the queue drains even while
    # in-window steps run slow.  With slab == request size the service rate
    # is capped at the arrival rate and ANY incremental scheme accumulates
    # its whole window overhead into the tail.
    slab_steps = SLAB_STEPS_SMOKE if smoke else SLAB_STEPS_FULL
    # jnp backend: the metric is the serve loop's pause structure, not
    # kernel throughput — interpret-mode pallas dispatch would bury the
    # migration pause under per-step overhead
    cfg = HashTableConfig(p=4, k=4, buckets=buckets, slots=slots,
                          queries_per_pe=qpp, key_words=2, val_words=1,
                          backend="jnp")
    trace = _trace(cfg, requests, lanes, key_space)
    # trigger/target and the trace volume are sized together so exactly ONE
    # doubling trips mid-stream and its migration completes while the queue
    # is still busy — a resize still open at quiescence would drain in one
    # final step and pollute the pause distribution
    pol = GrowthPolicy(grow_load_factor=0.2, grow_target_occupancy=0.1,
                       migrate_buckets_per_slab=slab_online)
    pol_stw = dataclasses.replace(
        pol, migrate_buckets_per_slab=max(cfg.buckets * 16, 1 << 20))
    grow_rng = jax.random.PRNGKey(0x9e512e)
    # one jitted stream shared by every run: plain eng.run_stream retraces
    # per call, which would bury the migration pause under dispatch cost.
    # The table arg is donated — the server rebinds its table every dispatch
    # and never reads the stale one, and without donation every step pays a
    # full-table copy that saturates the loop once any backlog forms
    stream = jax.jit(eng.run_stream, donate_argnums=(0,))

    # warmup pass discovers the capacity the grown runs end at (the policy
    # is deterministic in the trace) and compiles the resize kernels
    _, _, _, warm = _serve_once(cfg, ServeConfig(slab_steps=slab_steps, growth=pol,
                                                 geometry_replan=False),
                                trace, grow_rng, stream, dt_s)
    assert warm.resizes >= 1, "trace never tripped the growth trigger"
    big = dataclasses.replace(cfg, buckets=warm.cfg.buckets)

    def run_mode(m):
        if m == "born_big":
            return _serve_once(big, ServeConfig(slab_steps=slab_steps,
                                                geometry_replan=False),
                               trace, None, stream, dt_s)
        growth = pol if m == "grow_online" else pol_stw
        return _serve_once(cfg, ServeConfig(slab_steps=slab_steps, growth=growth,
                                            geometry_replan=False),
                           trace, grow_rng, stream, dt_s)

    modes = ("born_big", "grow_online", "stop_the_world")
    for m in modes:                              # compile every mode's path
        run_mode(m)
    best = {m: (float("inf"),) * 2 + (None,) * 3 for m in modes}
    for _ in range(iters):
        for m in modes:
            elapsed, lats, steps, srv = run_mode(m)
            # best by request p99, the headline — elapsed is pinned by the
            # arrival pacing, so it cannot rank runs
            score = float(np.percentile(np.asarray(lats), 99))
            if score < best[m][0]:
                best[m] = (score, elapsed, lats, steps, srv)

    results = {"figure": "resize_migration",
               "host_backend": jax.default_backend(),
               "interpret_mode": jax.default_backend() != "tpu",
               "mode": "smoke" if smoke else "full",
               "table": dict(p=cfg.p, k=cfg.k, buckets=cfg.buckets,
                             slots=cfg.slots, queries_per_pe=qpp),
               "grown_buckets": big.buckets,
               "policy": dict(grow_load_factor=pol.grow_load_factor,
                              grow_target_occupancy=pol.grow_target_occupancy,
                              migrate_buckets_per_slab=slab_online),
               "requests": requests, "lanes_per_request": lanes,
               "key_space": key_space, "iters": iters,
               "arrival_dt_ms": dt_s * 1e3,
               "stat": "paired best-of-N (by request p99), open-loop "
                       "arrivals, fresh table per run",
               "rows": []}
    for m in modes:
        _, elapsed, lats, steps, srv = best[m]
        la, st = np.asarray(lats), np.asarray(steps)
        results["rows"].append({
            "mode": m,
            "mops": srv.live_lanes / elapsed / 1e6,
            "elapsed_s": elapsed,
            "req_p50_ms": float(np.percentile(la, 50) * 1e3),
            "req_p99_ms": float(np.percentile(la, 99) * 1e3),
            "req_max_ms": float(la.max() * 1e3),
            "busy_steps": len(steps),
            "step_p50_ms": float(np.percentile(st, 50) * 1e3),
            "step_max_ms": float(st.max() * 1e3),
            "resizes": srv.resizes,
            "final_buckets": srv.cfg.buckets,
        })
    by = {r["mode"]: r for r in results["rows"]}
    results["derived"] = {
        # the headline: the tail a client sees while the table doubles
        # under it, online watermark walk vs the rebuild stall
        "online_over_stw_p99": (by["grow_online"]["req_p99_ms"]
                                / by["stop_the_world"]["req_p99_ms"]),
        "online_over_stw_stall": (by["grow_online"]["step_max_ms"]
                                  / by["stop_the_world"]["step_max_ms"]),
        "online_over_born_big_p99": (by["grow_online"]["req_p99_ms"]
                                     / by["born_big"]["req_p99_ms"]),
        "model_slab_pause_ms": resize_migration_seconds(
            cfg, buckets_per_slab=slab_online) * 1e3,
        "model_total_migration_ms": resize_total_seconds(
            cfg, buckets_per_slab=slab_online) * 1e3,
    }
    for r in results["rows"]:
        row(f"resize_migration_{r['mode']}", r["elapsed_s"] * 1e6,
            f"MOPS={r['mops']:.3f};req_p50_ms={r['req_p50_ms']:.3f};"
            f"req_p99_ms={r['req_p99_ms']:.3f};"
            f"req_max_ms={r['req_max_ms']:.3f};"
            f"step_max_ms={r['step_max_ms']:.3f};"
            f"resizes={r['resizes']};buckets={r['final_buckets']}")
    row("resize_migration_derived", 0.0,
        f"online_over_stw_p99="
        f"{results['derived']['online_over_stw_p99']:.3f};"
        f"online_over_stw_stall="
        f"{results['derived']['online_over_stw_stall']:.3f};"
        f"online_over_born_big_p99="
        f"{results['derived']['online_over_born_big_p99']:.3f}")
    if smoke:
        # sibling contract: smoke never touches the committed full-mode JSON
        print("smoke OK")
        return
    out = os.path.join(_ROOT, "BENCH_resize.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes — CI harness check, no JSON written")
    args = ap.parse_args()
    _sweep(args.smoke)


if __name__ == "__main__":
    main()
