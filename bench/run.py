#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload nyx512-write --seed 7 --seconds 45 \\
        --trace 0

from the root of a checkout. The cell (``BENCHMARK.json``) names its
configuration and traffic mix; fields are made on the device from
``--seed``; every shape the window uses runs once in set-up; then the
traffic runs for ``--seconds``, and a sample of the answers is checked
against ``bench/reference.py``. ``--trace 1`` runs the same window under
the profiler and reports the cell's per-layer metrics instead of its
end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and ``checks`` last: each number compared with its
limit). The last lines of standard error repeat the checks. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits with code 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    sys.path.insert(0, str(ROOT))
    try:
        from bench import harness
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               log=lambda s: print(s, flush=True))
    except Exception as exc:            # noqa: BLE001 — no result line
        if type(exc).__name__ == "NoChip":
            print(f"bench: {exc}", file=sys.stderr)
            return 3
        traceback.print_exc()
        return 1
    for name, c in out["checks"].items():
        lim = (f"<= {c['limit']!r}" if "limit" in c
               else f">= {c['limit_min']!r}")
        print(f"check {name} {c['value']!r} {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
