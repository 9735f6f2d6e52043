# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run [--full]``.

Quick mode (default) uses miniature scenes so the whole suite finishes on a
single CPU core; ``--full`` runs the paper-scale sweeps.
"""

import argparse
import sys
import time

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (e.g. table6,fig17)")
    args = ap.parse_args()

    from benchmarks import (
        bench_paged,
        bench_serve,
        bench_sessions,
        bench_slam_fps,
        bench_sparse,
        bench_wsu,
        fig14_pruning_ablation,
        fig17_breakdown,
        kernel_bench,
        roofline_table,
        table6_quality,
        table7_splatam,
    )

    suites = {
        "table6": table6_quality.run,
        "table7": table7_splatam.run,
        "fig14": fig14_pruning_ablation.run,
        "fig17": fig17_breakdown.run,
        "kernel": kernel_bench.run,
        "roofline": roofline_table.run,
        "slam_fps": bench_slam_fps.run,
        # after slam_fps: wsu + sparse + sessions + serve amend the
        # BENCH_slam.json it (re)writes
        "wsu": bench_wsu.run,
        "sparse": bench_sparse.run,
        "paged": bench_paged.run,
        "sessions": bench_sessions.run,
        "serve": bench_serve.run,
        "serve_v2": bench_serve.run_v2,
    }
    chosen = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in chosen:
        suites[name](quick=not args.full)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
