"""``closed``: one client, back to back, until the window has passed;
the call in flight at the close runs to its end and counts."""
import time
from typing import Any, Dict, List

import numpy as np

from bench.load import Request, _fill, reservoir


def run(entry, op: str, items: List, seconds: float,
        rng: np.random.Generator, traffic: Dict[str, Any], sample: int,
        span) -> List[Request]:
    """Requests cycle through ``items`` in an order drawn from ``rng``;
    ``sample`` answers are kept by reservoir sampling from ``rng``."""
    order = rng.permutation(len(items))
    reqs: List[Request] = []
    kept: List[Request] = []
    t_close = time.perf_counter() + seconds
    k = 0
    while time.perf_counter() < t_close:
        i = int(order[k % len(order)])
        req = Request(i=k, item=i, due=time.perf_counter())
        req.sent = req.due
        try:
            with span("bench.call"):
                out = entry.call(op, items[i])
            req.done = time.perf_counter()
            req.ok = True
            _fill(req, op, items[i], out)
        except Exception as exc:        # noqa: BLE001 — a failed request
            req.done, req.error = None, repr(exc)
            out = None
        reqs.append(req)
        reservoir(kept, req, out, sample, rng)
        k += 1
    return reqs
