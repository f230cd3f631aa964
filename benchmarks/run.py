"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # quick suite
    PYTHONPATH=src python -m benchmarks.run --full       # paper-scale sweep
    PYTHONPATH=src python -m benchmarks.run --only table2,fig9
    PYTHONPATH=src python -m benchmarks.run --suite kernels   # kernel bench
    PYTHONPATH=src python -m benchmarks.run --suite serving --smoke  # CI
    PYTHONPATH=src python -m benchmarks.run --suite serving --smoke --chaos

Prints ``name,value,unit`` CSV lines and writes results/benchmarks.json.
``--smoke`` runs tiny shapes with 1 rep — CI's per-PR artifact pass; only
suites that implement it (kernels, serving) accept the flag.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 rep (CI artifact pass)")
    ap.add_argument("--chaos", action="store_true",
                    help="serving suite: append degraded-mode chaos rows "
                         "(fault injection, overload) to BENCH_serving.json")
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys (table2,fig2,...)")
    ap.add_argument("--suite", default=None,
                    help="named group: paper (default) | kernels | serving")
    args = ap.parse_args(argv)
    if args.smoke and args.full:
        ap.error("--smoke and --full are mutually exclusive")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig2_scaling, fig9_quadrature, kernel_bench,
                            roofline_report, serving_bench,
                            table2_poly_approx, table3_synthetic,
                            table4_extreme, table5_slayformer)
    suites = {
        "table2": table2_poly_approx,
        "fig2": fig2_scaling,
        "fig9": fig9_quadrature,
        "table3": table3_synthetic,
        "table4": table4_extreme,
        "table5": table5_slayformer,
        "roofline": roofline_report,
        "kernels": kernel_bench,
        "serving": serving_bench,
    }
    # The kernel/serving benches are opt-in (their own suite groups); the
    # default / "paper" group runs everything else.
    groups = {"paper": set(suites) - {"kernels", "serving"},
              "kernels": {"kernels"}, "serving": {"serving"}}
    only = set(args.only.split(",")) if args.only else None
    if args.suite:
        if args.suite not in groups:
            ap.error(f"unknown --suite {args.suite!r} "
                     f"(choose from {sorted(groups)})")
        only = groups[args.suite] if only is None else only & groups[args.suite]
        if not only:
            ap.error(f"--only {args.only!r} selects nothing inside "
                     f"--suite {args.suite!r}")
    elif only is None:
        only = groups["paper"]
    all_results = []
    for key, mod in suites.items():
        if only and key not in only:
            continue
        t0 = time.monotonic()
        print(f"# --- {key} ({mod.__name__}) ---", flush=True)
        kwargs = {"quick": not args.full}
        sig = inspect.signature(mod.run).parameters
        if "smoke" in sig:
            kwargs["smoke"] = args.smoke
        elif args.smoke:
            print(f"# {key}: no --smoke support, skipping", flush=True)
            continue
        if "chaos" in sig:
            kwargs["chaos"] = args.chaos
        elif args.chaos:
            print(f"# {key}: no --chaos support, skipping", flush=True)
            continue
        try:
            results = mod.run(**kwargs)
        except Exception as e:  # noqa: BLE001 — report per-suite failures
            print(f"{key}/SUITE_FAILED,{type(e).__name__},{e}",
                  file=sys.stderr)
            raise
        for r in results:
            print(r.csv(), flush=True)
            all_results.append({"name": r.name, "value": r.value,
                                "unit": r.unit, **r.extra})
        print(f"# {key} done in {time.monotonic() - t0:.1f}s", flush=True)

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(all_results, f, indent=1)
    print(f"# wrote results/benchmarks.json ({len(all_results)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
