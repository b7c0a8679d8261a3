"""Fused-stream scaling: MOPS vs stream length T.

The fused xor_stream kernel amortizes one kernel launch over the whole
``[T, N]`` stream while the scanned path dispatches probe+commit per step —
so the fused/scanned ratio should GROW with T (the FPGA pipeline analogy:
longer bursts keep the PE array full).  The default ``fused`` column is the
VMEM-resident kernel (one sweep pass: its T steps run inside ONE grid
iteration; tables past the VMEM budget sweep in passes, DESIGN.md §3.1 —
chip_smoke.py drives that regime at its real size).  The table is
bulk-prepopulated with the stream's key set (``engine.bulk_build``) so
search lanes exercise the hit path, not the empty-table miss path.  Emits
``BENCH_stream.json`` (full mode only; ``--smoke`` is the CI harness
check).
"""
from __future__ import annotations

import argparse
import functools
import json
import os

import jax

from benchmarks.common import bench_group, mixed_stream, row
from repro.core import HashTableConfig, bulk_build, init_table, run_stream

P = 8
QPP = 8
TS = (2, 8, 32)
ITERS = 9          # paired best-of-N rounds (bench_group): drift-immune


# table geometry, recorded in BENCH_stream.json so roofline.py models the
# config that was actually measured
TABLE = dict(buckets=1 << 12, slots=4, replicate_reads=False,
             stagger_slots=True)


def run_t(steps: int, qpp: int = QPP, iters: int = ITERS):
    """scanned vs fused on identical stimulus, timed round-robin
    (drift-immune paired comparison)."""
    cfg = HashTableConfig(p=P, k=P, queries_per_pe=qpp, backend="pallas",
                          **TABLE)
    tab = init_table(cfg, jax.random.key(0))
    N = cfg.queries_per_step
    ops_j, keys_j, vals_j = mixed_stream(cfg, steps)
    # bulk-prepopulate with the stream's own key set (engine.bulk_build, one
    # count-then-place sweep) so the timed stream probes a WARM table — the
    # empty-table variant measured only the miss path for every search lane
    tab, _ = bulk_build(tab, keys_j.reshape(-1, cfg.key_words),
                        vals_j.reshape(-1, cfg.val_words))
    jfn = jax.jit(run_stream, static_argnames=("backend", "fused"))

    fns = {
        "scanned": functools.partial(jfn, tab, ops_j, keys_j, vals_j,
                                     fused=False),
        # the default unblocked kernel: one sweep pass, all T steps inside
        # ONE grid iteration
        "fused": functools.partial(jfn, tab, ops_j, keys_j, vals_j,
                                   fused=True),
    }
    us = bench_group(fns, iters=iters, warmup=2)
    return {name: steps * N / t for name, t in us.items()}   # MOPS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 iter, no JSON — CI harness check")
    args = ap.parse_args()
    ts, qpp, iters = ((2,), 2, 1) if args.smoke else (TS, QPP, ITERS)

    results = {"host_backend": jax.default_backend(),
               "interpret_mode": jax.default_backend() != "tpu",
               "p": P, "qpp": qpp, "iters": iters, "table": TABLE,
               "stat": "paired best-of-N (bench_group round-robin)",
               "rows": []}
    for steps in ts:
        mops = run_t(steps, qpp=qpp, iters=iters)
        scanned, fused = mops["scanned"], mops["fused"]
        rec = {"steps": steps, "mops_scanned": scanned, "mops_fused": fused,
               "fused_over_scanned": fused / scanned}
        derived = (f"scanned_MOPS={scanned:.2f};fused_MOPS={fused:.2f};"
                   f"fused_over_scanned={fused / scanned:.3f}")
        results["rows"].append(rec)
        row(f"stream_throughput_T{steps}", 0.0, derived)
    if args.smoke:
        print("smoke OK")
        return
    out = os.path.normpath(os.path.join(os.path.dirname(__file__) or ".",
                                        "..", "BENCH_stream.json"))
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
