"""Benchmark entry point: one section per paper table/figure plus the
dry-run roofline table.  Prints ``name,us_per_call,derived`` CSV.

``--smoke`` runs only the machine-readable sections (runtime + adapt,
reduced step counts) — the mode the CI benchmark job uses; the emitted
BENCH_*.json are then validated by scripts/check_bench_schema.py
(verify.sh --smoke chains the two).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# runnable both as `python -m benchmarks.run` and `python benchmarks/run.py`,
# with or without PYTHONPATH=src exported
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)


def _sections(smoke: bool):
    from benchmarks import adapt_bench, elastic_bench, obs_bench, runtime_bench

    runtime = (
        "runtime (fused DeftRuntime + solver, BENCH_runtime.json)",
        runtime_bench.run,
    )
    adapt = (
        "adapt (static vs adaptive replan, BENCH_adapt.json)",
        adapt_bench.run,
    )
    elastic = (
        "elastic (fault detection + scale-down repack, BENCH_elastic.json)",
        elastic_bench.run,
    )
    obs = (
        "obs (attribution closure + tracing overhead, BENCH_obs.json)",
        obs_bench.run,
    )
    if smoke:
        return [runtime, adapt, elastic, obs]

    from benchmarks import (
        fig10_time_to_solution,
        fig14_scalability,
        fig15_bandwidth,
        fig16_partition_size,
        roofline,
        table1_coverage_rates,
        table2_bucket_times,
        table4_multilink,
    )

    return [
        ("table1 (coverage rates)", table1_coverage_rates.run),
        ("table2 (bucket times)", table2_bucket_times.run),
        ("table4 (multi-link)", table4_multilink.run),
        ("fig10 (time-to-solution)", fig10_time_to_solution.run),
        ("fig14 (scalability)", fig14_scalability.run),
        ("fig15 (bandwidth)", fig15_bandwidth.run),
        ("fig16 (partition size)", fig16_partition_size.run),
        ("roofline (dry-run)", roofline.run),
        runtime,
        adapt,
        elastic,
        obs,
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="BENCH-emitting sections only, reduced steps "
                         "(the CI benchmark job; verify.sh --smoke "
                         "schema-checks the output)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ.setdefault("BENCH_RUNTIME_STEPS", "6")
        os.environ.setdefault("BENCH_ADAPT_STEPS", "120")
        os.environ.setdefault("BENCH_OBS_STEPS", "20")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    t0 = time.time()
    failures = 0
    for name, fn in _sections(args.smoke):
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception as e:  # keep the harness going; fail at the end
            failures += 1
            print(f"{name},0,ERROR {type(e).__name__}: {e}")

    print(f"# benchmarks done in {time.time() - t0:.1f}s, "
          f"{failures} section failures")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
