"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig9 fig11 # subset

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import header
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "async_aipm": "benchmarks.bench_async_aipm",
    "cascade": "benchmarks.bench_cascade",
    "fig8": "benchmarks.bench_throughput",
    "fig9": "benchmarks.bench_vs_pipeline",
    "fig10": "benchmarks.bench_optimizer",
    "fig11": "benchmarks.bench_index_recall",
    "fig12": "benchmarks.bench_index_perf",
    "index_knn": "benchmarks.bench_index_perf",
    "pq_knn": "benchmarks.bench_pq_knn",
    "sharded": "benchmarks.bench_sharded",
    "failover": "benchmarks.bench_failover",
    "overload": "benchmarks.bench_overload",
    "kernels": "benchmarks.bench_kernels",
    "roofline": "benchmarks.roofline",
}


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    enable_compile_cache()
    header()
    failures = []
    ran = set()
    for key in wanted:
        mod_name = SUITES.get(key)
        if mod_name is None:
            print(f"unknown suite {key!r}; known: {sorted(SUITES)}")
            continue
        if mod_name in ran:     # aliases (fig12 / index_knn) run once
            continue
        ran.add(mod_name)
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
        except Exception:  # noqa: BLE001
            failures.append(key)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
