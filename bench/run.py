#!/usr/bin/env python3
"""Run one cell of the table benchmark once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are in
``BENCHMARK.json`` at the root of the checkout (``bench/kvbench.py`` says
what a run does).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the plain reference beside its limit.  The same numbers close
standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for, or the checkout lacks the program, or the
cell's ``chips`` differ from the devices its configuration lays the table
over (``HashTableConfig.mesh_devices``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench/run.py: no repro package under {src}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [src, BENCH]
    import kvbench
    spec = kvbench.load_spec()
    entry = kvbench.cell_entry(spec, args.workload)
    try:
        kvbench.cell_config(spec, args.workload)
    except ValueError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/run.py: no TPU found (JAX platform "
              f"{devices[0].platform!r}); the benchmark runs only on the "
              f"chip", file=sys.stderr)
        return 1
    if len(devices) < entry["chips"]:
        print(f"bench/run.py: {args.workload} needs {entry['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    result = kvbench.run_cell(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=t_start,
                              devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
