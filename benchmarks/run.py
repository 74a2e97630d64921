"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [table2|cache|precompute|kernels]

Emits CSV blocks per suite; table2_reproduction is the paper's §5
experiment (its assertions enforce the paper's qualitative claims).
Roofline terms for the dry-run grid are produced by
``repro.launch.dryrun`` (see EXPERIMENTS.md §Roofline), not here —
they need the 512-device placeholder env.
"""
from __future__ import annotations

import sys
import time

from repro.caching import use_persistent_compile_cache

from . import cache_micro, kernels_bench, plan_bench, precompute_bench, \
    table2_reproduction

SUITES = {
    "table2": table2_reproduction.main,
    "cache": cache_micro.main,
    "precompute": precompute_bench.main,
    # plan_bench.main argparses its argv; the orchestrator passes none
    "plan": lambda: plan_bench.main([]),
    "kernels": kernels_bench.main,
}


def main(argv=None) -> None:
    use_persistent_compile_cache()
    args = argv if argv is not None else sys.argv[1:]
    names = args or list(SUITES)
    for name in names:
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        SUITES[name]()
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
