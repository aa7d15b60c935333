"""Benchmark harness entrypoint — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--skip-scaling`` avoids
the subprocess-based strong-scaling benchmark (used under pytest).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-scaling", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark subset")
    args = ap.parse_args()

    from benchmarks import (
        bench_fig3_split_techniques,
        bench_fig4_baselines,
        bench_fig5_phase_split,
        bench_fig6_scaling,
        bench_fig7_gve_vs_gsl,
        bench_roofline,
        bench_stale_exchange,
        bench_table1_datasets,
    )

    benches = {
        "table1": bench_table1_datasets.run,
        "fig3": bench_fig3_split_techniques.run,
        "fig4": bench_fig4_baselines.run,
        "fig5": bench_fig5_phase_split.run,
        "fig7": bench_fig7_gve_vs_gsl.run,
        "roofline": bench_roofline.run,
    }
    if not args.skip_scaling:
        benches["fig6"] = bench_fig6_scaling.run
        benches["stale"] = bench_stale_exchange.run
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    t0 = time.time()
    print("bench,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name},0.0,ERROR={e!r}", file=sys.stderr)
    print(f"# total {time.time() - t0:.1f}s; failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
