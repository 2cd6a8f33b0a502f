"""Benchmark harness entry point — one module per paper table/figure
(deliverable (d)). Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig10,fig12,...]
"""

from __future__ import annotations

import argparse
import sys
import traceback

SUITES = {
    "fig10": ("benchmarks.bench_fig10_applicability",
              "Fig 10: generated vs hand-written relative runtime"),
    "fig12": ("benchmarks.bench_fig12_blocksize",
              "Fig 12: throughput vs block (vector) size"),
    "loc": ("benchmarks.bench_extensibility_loc",
            "§5.3: extensibility LOC accounting"),
    "adaptive": ("benchmarks.bench_adaptive_selection",
                 "§4.2: benchmark-driven adaptive variant selection"),
    "prim": ("benchmarks.bench_primitive_microbench",
             "primitive-level zero-overhead check"),
    "roofline": ("benchmarks.roofline_report",
                 "dry-run roofline summary (reads experiments/dryrun)"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(args.only.split(",")) if args.only else set(SUITES)
    failures = []
    for key, (module, desc) in SUITES.items():
        if key not in want:
            continue
        print(f"# --- {key}: {desc}")
        try:
            import importlib

            mod = importlib.import_module(module)
            mod.run()
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}")
        sys.exit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()
