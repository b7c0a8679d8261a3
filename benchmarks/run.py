"""Benchmark driver: one module per paper table/figure + the roofline
collector.  Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    # some benchmark mains parse argv (e.g. --smoke); the driver runs them
    # all in full mode, and a stray driver arg must not SystemExit the sweep
    sys.argv = sys.argv[:1]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (backend_compare, bulk_build,
                            distributed_throughput,
                            fig4_memory, fig5_throughput, fig6_capacity,
                            fig7_nsq_ratio, fig10_latency, ht_hillclimb,
                            resize_migration, serve_latency,
                            stream_throughput, table12_resources, table3_sota)
    from benchmarks import roofline
    mods = [("fig4", fig4_memory), ("fig5", fig5_throughput),
            ("fig6", fig6_capacity), ("fig7", fig7_nsq_ratio),
            ("table12", table12_resources), ("table3", table3_sota),
            ("fig10", fig10_latency), ("ht_hillclimb", ht_hillclimb),
            ("backend_compare", backend_compare),
            ("stream_throughput", stream_throughput),
            ("distributed_throughput", distributed_throughput),
            ("serve_latency", serve_latency),
            ("bulk_build", bulk_build),
            ("resize_migration", resize_migration),
            ("roofline", roofline)]
    failures = 0
    for name, mod in mods:
        try:
            mod.main()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR")
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
