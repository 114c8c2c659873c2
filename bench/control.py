#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size, on the chip.

    python3 bench/control.py --workload nyx512-write --seeds 1 2 3

For each seed it makes the cell's fields as a run does and judges, by
``bench/reference.py``, the control's answer for each: the field stored
in bfloat16, one precision below the configuration's float32. Each line
printed is one reading: the control must fail the limits that sound runs
of the program meet (``label_violations`` <= 0, ``err_over_xi`` <=
1 + tolerance). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import numpy as np

    from bench import fields, harness, reference as ref
    cell, config, traffic = harness.cell_spec(harness.load_benchmark(),
                                              args.workload)
    harness.device_info(int(cell["chips"]), require_tpu=True)
    for seed in args.seeds:
        pool = fields.field_pool(config["recipe"], config["shape"],
                                 int(traffic["pool"]), seed,
                                 config.get("content_seed"))
        for i, f in enumerate(pool[:2]):
            xi = float(config["xi_rel"]) * float(np.ptp(f))
            t0 = time.perf_counter()
            res = ref.Original(f, xi).compare(ref.to_bfloat16(f))
            print(json.dumps(dict(workload=args.workload, seed=seed,
                                  field=i, control="bfloat16",
                                  seconds=time.perf_counter() - t0, **res)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
