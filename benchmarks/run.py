"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # quick suite
    PYTHONPATH=src python -m benchmarks.run --full       # paper-scale sweep
    PYTHONPATH=src python -m benchmarks.run --only table2,fig9

Prints ``name,value,unit`` CSV lines and writes results/benchmarks.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys (table2,fig2,...)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig2_scaling, fig9_quadrature, table2_poly_approx,
                            table3_synthetic, table4_extreme,
                            table5_slayformer)
    suites = {
        "table2": table2_poly_approx,
        "fig2": fig2_scaling,
        "fig9": fig9_quadrature,
        "table3": table3_synthetic,
        "table4": table4_extreme,
        "table5": table5_slayformer,
    }
    only = set(args.only.split(",")) if args.only else None
    all_results = []
    for key, mod in suites.items():
        if only and key not in only:
            continue
        t0 = time.monotonic()
        print(f"# --- {key} ({mod.__name__}) ---", flush=True)
        try:
            results = mod.run(quick=not args.full)
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
