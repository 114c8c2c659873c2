"""``steps``: a closed loop over steps. One client submits a step of
``traffic["step"]`` requests at once, waits for every answer, then
submits the next step; the step in flight at the close runs to its end
and counts, as a closed loop's call does."""
import threading
import time
from typing import Any, Dict, List

import numpy as np

from bench.load import Request, _fill, reservoir


def run(entry, op: str, items: List, seconds: float,
        rng: np.random.Generator, traffic: Dict[str, Any], sample: int,
        span) -> List[Request]:
    """A step is pool members ``0..step-1`` in an order drawn from
    ``rng``; each request is timed from its submission to its answer's
    arrival. ``sample`` answers are kept by reservoir sampling from
    ``rng``."""
    if not hasattr(entry, "submit"):
        raise TypeError(f"the steps loop submits a whole step at once; "
                        f"the entry {type(entry).__name__} has no submit")
    step = int(traffic["step"])
    if not 1 <= step <= len(items):
        raise ValueError(f"a step of {step} needs that many pool members; "
                         f"the pool holds {len(items)}")
    reqs: List[Request] = []
    kept: List[Request] = []
    arrived = threading.Semaphore(0)

    def on_done(req: Request, _fut) -> None:
        req.done = time.perf_counter()
        arrived.release()

    t_close = time.perf_counter() + seconds
    while time.perf_counter() < t_close:
        flight = []
        for m in rng.permutation(step):
            req = Request(i=len(reqs), item=int(m), due=time.perf_counter())
            req.sent = req.due
            reqs.append(req)
            try:
                with span("bench.submit"):
                    fut = entry.submit(op, items[req.item])
            except Exception as exc:    # noqa: BLE001 — a refused request
                req.error = repr(exc)
                flight.append((req, None))
                continue
            fut.add_done_callback(lambda f, req=req: on_done(req, f))
            flight.append((req, fut))
        with span("bench.wait"):
            for _req, fut in flight:
                if fut is not None:
                    arrived.acquire()
        for req, fut in flight:
            out = None
            if fut is not None:
                try:
                    out = fut.result()
                except Exception as exc:  # noqa: BLE001 — a failed request
                    req.done, req.error = None, repr(exc)
                else:
                    req.ok = True
                    _fill(req, op, items[req.item], out)
            reservoir(kept, req, out, sample, rng)
    return reqs
