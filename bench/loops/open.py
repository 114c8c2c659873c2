"""``open``: requests fall due on the fixed schedule of
``load.open_schedule``, whether or not earlier ones have returned."""
import threading
import time
from typing import Any, Dict, List

import numpy as np

from bench.load import Request, _fill, item_order, open_schedule


def run(entry, op: str, items: List, seconds: float,
        rng: np.random.Generator, traffic: Dict[str, Any], sample: int,
        span) -> List[Request]:
    """Each request is sent at its due time (or as soon after as the
    generator can), and waited for until ``drain_s`` past the window's
    close. ``rng`` draws which item each carries, then which ``sample``
    answers are kept."""
    offsets = open_schedule(traffic, seconds)
    which = item_order(len(items), len(offsets), rng)
    keep = set(rng.choice(len(offsets), min(sample, len(offsets)),
                          replace=False).tolist()) if len(offsets) else set()
    reqs = [Request(i=k, item=int(which[k]), due=0.0)
            for k in range(len(offsets))]
    lock = threading.Lock()
    futs = []
    t0 = time.perf_counter()

    def on_done(req: Request, item, fut) -> None:
        t = time.perf_counter()
        try:
            out = fut.result()
        except Exception as exc:        # noqa: BLE001 — a failed request
            with lock:
                req.error = repr(exc)
            return
        with lock:
            req.done, req.ok = t, True
            _fill(req, op, item, out)
            if req.i in keep:
                req.answer = out

    for req, off in zip(reqs, offsets):
        req.due = t0 + float(off)
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        req.sent = time.perf_counter()
        item = items[req.item]
        try:
            with span("bench.submit"):
                fut = entry.submit(op, item)
        except Exception as exc:        # noqa: BLE001 — a refused request
            req.error = repr(exc)
            continue
        fut.add_done_callback(
            lambda f, req=req, item=item: on_done(req, item, f))
        futs.append(fut)
    deadline = t0 + seconds + float(traffic.get("drain_s", 60))
    for fut in futs:
        try:
            fut.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except Exception:               # noqa: BLE001 — judged per request
            pass
    with lock:
        for req in reqs:
            if not req.ok and not req.error:
                req.error = "no answer by the end of the drain"
                req.done = None
    return reqs
