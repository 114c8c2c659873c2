"""The one load generator: drives the program as a traffic file says.

A traffic file (``bench/traffic/<mix>.json``) is data only:

``op``
    ``compress`` (fields in, artifacts out) or ``decompress`` (the
    artifact of a field in, the field out).
``entry``
    ``one_shot`` calls ``compress_preserving_mss`` /
    ``decompress_preserving_mss``; ``service`` submits to one
    ``CompressionService`` with its default configuration.
``loop``
    ``closed``: one client, the next request once the last returned;
    ``open``: requests fall due on a fixed schedule at ``rate_per_s``,
    whether or not earlier ones have returned.
``pool``
    How many distinct fields the seed makes; requests cycle through them
    in an order drawn from the seed.
``check_sample``
    How many answers, drawn from the seed, the reference checks.
``rate_per_s`` (open loop)
    A window of ``s`` seconds offers ``round(rate_per_s * s)`` requests.
    Their gaps are as many quantiles (midpoints) of the exponential
    distribution at that rate.
``schedule_seed`` (open loop)
    The one order of those gaps, the same for every run: the arrivals
    are a fixed trace, and the run's seed draws the fields and which
    field each arrival carries. With a few dozen requests in a window,
    an order drawn anew per seed moved the 95th percentile by a third
    from seed to seed (two runs of one seed agreed within a few
    percent), so it is not left to the seed.
``drain_s``
    How long after the window closes the generator waits for answers
    still due; one that has not come by then counts as failed.

Each request is timed on the host's clock from when it was due (for a
closed loop, when it was sent) to when its answer arrived.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    """One request of a run and what came of it."""
    i: int
    item: int                     # pool index
    due: float
    sent: float = math.nan
    done: Optional[float] = None  # None: never answered
    ok: bool = False
    error: str = ""
    bytes_in: int = 0
    bytes_out: int = 0
    values: int = 0
    nbytes: int = 0               # compressed bytes (compress only)
    stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    answer: Any = None            # kept only for sampled requests


def _art_stats(art) -> Dict[str, float]:
    return dict(t_base=float(art.t_base), t_transform=float(art.t_transform),
                t_fix=float(art.t_fix), fix_iters=float(art.fix_iters),
                edit_ratio=float(art.edit_ratio))


class OneShot:
    """The program's one-shot entry points."""

    def __init__(self, program):
        self.p = program

    def call(self, op: str, item):
        if op == "compress":
            f, xi = item
            return self.p.compress_preserving_mss(f, xi)
        return self.p.decompress_preserving_mss(item)

    def close(self) -> None:
        pass


class Service:
    """One ``CompressionService`` with its default configuration."""

    def __init__(self, program):
        self.svc = program.CompressionService()

    def submit(self, op: str, item):
        if op == "compress":
            f, xi = item
            return self.svc.submit_compress(f, xi)
        return self.svc.submit_decompress(item)

    def call(self, op: str, item):
        return self.submit(op, item).result()

    def stats(self) -> Dict[str, Any]:
        return self.svc.stats()

    def close(self) -> None:
        self.svc.close()


ENTRIES = {"one_shot": OneShot, "service": Service}


def _fill(req: Request, op: str, item, out) -> None:
    if op == "compress":
        f = item[0]
        req.bytes_in, req.values = f.nbytes, f.size
        req.nbytes = int(out.nbytes)
        req.stats = _art_stats(out)
    else:
        req.bytes_in = int(item.nbytes)
        req.bytes_out, req.values = out.nbytes, out.size


def open_schedule(traffic: Dict[str, Any], seconds: float) -> np.ndarray:
    """Due offsets (s, from the window's start) of an open loop: the
    first request at 0, then the exponential quantile gaps in the order
    that ``schedule_seed`` draws."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    u = (np.arange(n) + 0.5) / n
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    gaps = order.permutation(-np.log1p(-u) / rate)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def item_order(n_pool: int, n_req: int, rng: np.random.Generator
               ) -> np.ndarray:
    """Pool index of each request: every field equally often, in an
    order drawn from the seed."""
    reps = -(-n_req // n_pool) if n_req else 0
    return rng.permutation(np.tile(np.arange(n_pool), reps))[:n_req] \
        if reps else np.zeros(0, int)


def closed_loop(entry, op: str, items: List, seconds: float,
                rng: np.random.Generator, sample: int,
                span=None) -> List[Request]:
    """One client, back to back, until ``seconds`` have passed; the call
    in flight at the close runs to its end and counts. Keeps the answers
    of ``sample`` requests chosen by reservoir sampling from ``rng``."""
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
            if span is not None:
                with span("bench.call"):
                    out = entry.call(op, items[i])
            else:
                out = entry.call(op, items[i])
            req.done = time.perf_counter()
            req.ok = True
            _fill(req, op, items[i], out)
        except Exception as exc:        # noqa: BLE001 — a failed request
            req.done, req.error = None, repr(exc)
            out = None
        reqs.append(req)
        # reservoir sampling keeps each answer with equal chance
        if len(kept) < sample:
            req.answer = out
            kept.append(req)
        else:
            j = int(rng.integers(0, k + 1))
            if j < sample:
                kept[j].answer = None
                req.answer = out
                kept[j] = req
        k += 1
    return reqs


def open_loop(entry, op: str, items: List, seconds: float,
              rng: np.random.Generator, traffic: Dict[str, Any],
              sample: int, span=None) -> List[Request]:
    """Requests due on the schedule of ``open_schedule``; each is sent
    at its due time (or as soon after as the generator can), and waited
    for until ``drain_s`` past the window's close."""
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
            if span is not None:
                with span("bench.submit"):
                    fut = entry.submit(op, item)
            else:
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
