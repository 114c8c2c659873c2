#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell: where does it saturate?

    python3 bench/sweep.py --workload cesm-atm-service --seed 5 \\
        --seconds 20 --rates 2 4 6 8 10 12

One process sets the cell up once and offers each rate in turn for
``--seconds``. Each line printed is one rate: the rate completed, the
median and 95th-percentile latency from the due time, how late the
generator sent, and the mean latency of the last quarter of the requests
over that of the first (a backlog that grows through the window reads
well above 1). The highest rate whose backlog does not grow is the knee;
the cell's traffic file holds its rate, fixed, below it.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import numpy as np

    import jax

    from bench import arith, fields, harness, lookup
    cell, config, traffic = harness.cell_spec(harness.load_benchmark(),
                                              args.workload)
    harness.device_info(int(cell["chips"]), require_tpu=True)
    program = harness._program()
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    counter = harness.CompileCounter()
    pool = fields.field_pool(config["recipe"], config["shape"],
                             int(traffic["pool"]), args.seed,
                             config.get("content_seed"))
    xis = [float(config["xi_rel"]) * float(np.ptp(f)) for f in pool]
    entry, items = harness.set_up(traffic, program, pool, xis, counter)
    loop = lookup.find("loops", traffic["loop"])
    rng = np.random.default_rng(args.seed)
    for rate in args.rates:
        t = dict(traffic, rate_per_s=rate, drain_s=30)
        t0 = time.perf_counter()
        reqs = loop.run(entry, t["op"], items, args.seconds, rng, t, 0,
                        jax.profiler.TraceAnnotation)
        lat = arith.due_latencies_ms([r.due for r in reqs],
                                     [r.done if r.ok else None for r in reqs])
        done = [r.done for r in reqs if r.ok]
        q = max(len(lat) // 4, 1)
        first, last = np.mean(lat[:q]), np.mean(lat[-q:])
        print(json.dumps(dict(
            rate=rate, due=len(reqs), done=len(done),
            completed_per_s=len(done) / (max(done) - t0) if done else 0.0,
            p50_ms=arith.percentile(lat, 50), p95_ms=arith.percentile(lat, 95),
            gen_late_p95_ms=arith.percentile(
                [(r.sent - r.due) * 1e3 for r in reqs], 95),
            backlog_growth=float(last / first) if first > 0 else None)),
            flush=True)
    print(json.dumps(dict(occupancy=entry.stats()["compress"]
                          ["batch_occupancy"])), flush=True)
    entry.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
