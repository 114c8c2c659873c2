"""The benchmark's arithmetic, kept apart from the program it measures.

Everything here is a pure function of numbers the harness recorded:
the whole-request rate, the due-time percentile of an open loop, the
least bytes a transform must move, the peak of a device, and the spread
of repeated runs. Each is tested in ``bench/tests/test_bench_arith.py``.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Dict, Iterable, Optional, Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """The published peaks of one chip, by JAX's ``device_kind``. A kind
    that the table does not list is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def transform_least_bytes(shape: Sequence[int], itemsize: int = 4,
                          code_itemsize: int = 4) -> int:
    """Least HBM bytes of the quantize + Lorenzo transform of one field:
    the field read once and its residual codes written once. The count
    depends on the shape alone, whatever implements the transform."""
    n = math.prod(int(s) for s in shape)
    return n * (itemsize + code_itemsize)


def roofline_pct(least_bytes: float, device_s: float,
                 hbm_bytes_per_s: float) -> Optional[float]:
    """Share of the memory roofline, in percent: the time the least bytes
    need at peak bandwidth over the device time measured. None when no
    device time was seen."""
    if device_s <= 0 or least_bytes <= 0:
        return None
    return 100.0 * (least_bytes / hbm_bytes_per_s) / device_s


def whole_request_rate(units: Iterable[float], window_start: float,
                       last_done: float) -> Optional[float]:
    """Units per second over whole requests: everything the window
    completed, the request in flight at its close included, over the
    time from the window's start to the last completion."""
    total = float(sum(units))
    span = last_done - window_start
    if span <= 0 or total <= 0:
        return None
    return total / span


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q < 100) by the nearest-rank rule:
    the smallest value with at least q% of the values at or below it.
    Infinite values (requests that never finished) sort last."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def due_latencies_ms(due: Sequence[float], done: Sequence[Optional[float]]
                     ) -> list:
    """Completion minus due time, in ms, for each request of an open
    loop; a request that failed or never finished (``done`` None) reads
    as infinitely late."""
    return [math.inf if d is None else (d - t) * 1e3
            for t, d in zip(due, done)]


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as a share: the spread the
    bounds of ``BENCHMARK.json`` are set from (``statistics.quantiles``
    with n=4, the exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med) if med else math.inf
