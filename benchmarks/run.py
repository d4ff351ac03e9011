"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Each module also asserts the
paper's qualitative claims (DO ~3x workload cut, memory ~1/3 of edge list,
weak-scaling flatness, comm-model bounds), so this doubles as the
reproduction gate.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (comm_model, memory_model, msbfs_throughput,
                            options_ablation, strong_scaling, th_perf,
                            th_sweep, weak_scaling)

    suites = [
        ("th_sweep (Fig 5)", th_sweep.run),
        ("memory_model (Table I)", memory_model.run),
        ("th_perf (Fig 6)", th_perf.run),
        ("options_ablation (Fig 8)", options_ablation.run),
        ("weak_scaling (Fig 9)", weak_scaling.run),
        ("strong_scaling (Fig 11)", strong_scaling.run),
        ("comm_model (Sec V)", comm_model.run),
        ("msbfs_throughput (serve)", msbfs_throughput.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            fn()
            print(f"# {name}: OK ({time.time()-t0:.1f}s)")
        except AssertionError as e:
            failures += 1
            print(f"# {name}: CLAIM FAILED: {e}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name}: ERROR: {type(e).__name__}: {e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
