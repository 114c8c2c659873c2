"""The load generator's shared parts: a traffic file, the request
record, and the helpers its loops share.

A traffic file (``bench/traffic/<mix>.json``) is data only:

``op``
    ``compress`` (fields in, artifacts out) or ``decompress`` (the
    artifact of a field in, the field out).
``entry``
    The way into the program, ``bench/entries/<entry>.py``:
    ``one_shot`` calls ``compress_preserving_mss`` /
    ``decompress_preserving_mss``; ``service`` submits to one
    ``CompressionService`` with its default configuration.
``loop``
    The traffic's shape, ``bench/loops/<loop>.py``:
    ``closed``: one client, the next request once the last returned;
    ``open``: requests fall due on a fixed schedule at ``rate_per_s``,
    whether or not earlier ones have returned;
    ``steps``: one client sends ``step`` requests at once, waits for
    all of them, and sends the next step.
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
``drain_s`` (open loop)
    How long after the window closes the generator waits for answers
    still due; one that has not come by then counts as failed.
``step`` (steps loop)
    Requests in one step: pool members ``0..step-1``, in an order drawn
    from the seed for each step. The service also warms a whole step.

Each request is timed on the host's clock from when it was due (for a
closed or steps loop, when it was sent) to when its answer arrived.
"""
from __future__ import annotations

import dataclasses
import math
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


def reservoir(kept: List[Request], req: Request, out, sample: int,
              rng: np.random.Generator) -> None:
    """Keep the answer of ``req``, the ``req.i``-th request, in ``kept``
    by reservoir sampling: each answer with equal chance, ``sample`` at
    most, one draw from ``rng`` once ``kept`` is full."""
    if len(kept) < sample:
        req.answer = out
        kept.append(req)
        return
    j = int(rng.integers(0, req.i + 1))
    if j < sample:
        kept[j].answer = None
        req.answer = out
        kept[j] = req
