"""Benchmark driver — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from . import (fig1_label_distortion, table1_components, table2_overhead,
                   table3_decompress, table4_stream, table5_fixloop,
                   table6_entropy, table7_preserve, fig7_fixed_bound,
                   fig8_fixed_bitrate, fig9_scaling, fig11_convergence)
    modules = {
        "fig1": fig1_label_distortion,
        "table1": table1_components,
        "table2": table2_overhead,
        "table3": table3_decompress,
        "table4": table4_stream,
        "table5": table5_fixloop,   # also writes BENCH_fixloop.json
        "table6": table6_entropy,   # also writes BENCH_entropy.json
        "table7": table7_preserve,  # also writes BENCH_preserve.json
        "fig7": fig7_fixed_bound,
        "fig8": fig8_fixed_bitrate,
        "fig9": fig9_scaling,
        "fig11": fig11_convergence,
    }
    selected = (args.only.split(",") if args.only else list(modules))
    print("name,us_per_call,derived")
    failures = []
    for key in selected:
        mod = modules[key]
        t0 = time.time()
        try:
            mod.run(quick=quick)
            print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark modules failed: {failures}")


if __name__ == "__main__":
    main()
