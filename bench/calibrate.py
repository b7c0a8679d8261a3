#!/usr/bin/env python3
"""Runs on the chip that set the benchmark's numbers; the benchmark's own
runs never make them.

    python3 bench/calibrate.py sweep --workload ycsb_b.resident.open \\
        --seed 7 --seconds 10 --rates 100000,200000,300000
    python3 bench/calibrate.py control --workload ycsb_b.resident \\
        --seeds 11,12,13 --seconds 10
    python3 bench/calibrate.py program --workload ycsb_b.resident \\
        --seeds 14,15,16 --seconds 10

``sweep`` serves an open-loop cell at each offered rate (a fresh table per
rate, one process) and prints, per rate, the latency percentiles, the
completed rate and the backlog (requests arrived but not answered) at the
middle and at the end of the window: the knee is the highest rate whose
backlog does not grow.

``program`` runs a cell on several seeds in one process and prints, per
seed, the numbers the check compares (the lower readings).  ``control``
does the same with the control in the served stream's place:
``StaleReads`` answers each slab from the table as it stood one slab
earlier, so updates acknowledged in the previous slab are not yet visible,
breaking the configurations' stated visibility guarantee.  Every run of the control must read ``correct``
false.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


class StaleReads:
    """The control: the next table is the served stream's, but each slab's
    answers come from the table one slab older."""

    def __init__(self, stream):
        self.stream = stream
        self.prev = None

    def __call__(self, table, ops, keys, vals):
        new_table, _ = self.stream(table, ops, keys, vals)
        _, res = self.stream(table if self.prev is None else self.prev,
                             ops, keys, vals)
        self.prev = table
        return new_table, res


def backlog(cell, win, at_s: float) -> int:
    """Requests arrived by ``at_s`` into the window and not yet answered."""
    subs = win.submitted
    arrived = cell.requests.arrival_s(np.arange(len(subs))) <= at_s
    done = np.fromiter((s.done and s.done_s - win.t0 <= at_s for s in subs),
                       bool, len(subs))
    return int((arrived & ~done).sum())


def sweep(spec, args, devices) -> None:
    import kvbench
    entry = kvbench.cell_entry(spec, args.workload)
    config = kvbench.load_config(spec, entry["config"])
    base = kvbench.load_traffic(entry["traffic"])
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dataclasses.replace(base, ops_per_s=rate)
        cell = kvbench.build(config, traffic, args.seed)
        win = kvbench.run_window(cell, args.seconds)
        e2e = kvbench.end_to_end(cell, win, 0.0)
        mid, end = (backlog(cell, win, args.seconds / 2),
                    backlog(cell, win, args.seconds))
        lat = win.lateness_s
        print(json.dumps({
            "rate": rate, "p50_ms": e2e.get("p50_ms"),
            "p99_ms": e2e.get("p99_ms"),
            "completed_ops_per_s": e2e["ops_per_s"],
            "backlog_mid": mid, "backlog_end": end,
            "step_max_ms": win.step_max_s * 1e3,
            "pad_fraction": win.counters["pad_lanes"] / max(
                1, win.counters["pad_lanes"] + win.counters["live_lanes"]),
            "late_p99_ms": float(np.percentile(lat, 99) * 1e3)}), flush=True)
        del cell, win


def control(spec, args, devices) -> None:
    import kvbench
    wrap = StaleReads if args.mode == "control" else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = kvbench.run_cell(spec, args.workload, seed, args.seconds, False,
                             t_start=time.perf_counter(), devices=devices,
                             wrap_stream=wrap,
                             donate=wrap is None)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": {k: v["value"]
                                     for k, v in r["checks"].items()}}),
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("sweep", "control", "program"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(REPO, "src"), BENCH]
    import kvbench
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("bench/calibrate.py: no TPU found", file=sys.stderr)
        return 1
    spec = kvbench.load_spec()
    (sweep if args.mode == "sweep" else control)(spec, args, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
