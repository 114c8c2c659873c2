"""The program's own spans (``msz.*``) in a traced run's window.

The program marks its host-side phases with ``jax.profiler``
annotations named ``msz.<phase>``: the pulls (``msz.wait``, the host
blocked on the device; ``msz.d2h``, the transfer), the pushes
(``msz.h2d``), the host coding (``msz.encode.residuals``,
``msz.encode.edits``), the device path's precondition scan
(``msz.precheck``) and the stream scheduler's waits
(``msz.stream.empty``, ``msz.stream.linger``). On one thread they never
nest, so their seconds add up.

This reads the host planes of the newest ``.xplane.pb`` under
``harness.TRACE_DIR / "<workload>-*"``, once per file, and sums each
span name's count and seconds clipped to ``bench.window``. It does not
go through the device's op lines. A program without such spans, or a
run without a trace, reads as nothing (None), never as zero.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Dict, Optional, Tuple

from bench import harness
from bench.trace import WINDOW_SPAN

PREFIX = "msz."


def newest_xplane(workload: str) -> Optional[str]:
    """The newest ``.xplane.pb`` of ``workload``'s traced runs, if any."""
    pattern = os.path.join(str(harness.TRACE_DIR), f"{workload}-*", "**",
                           "*.xplane.pb")
    paths = glob.glob(pattern, recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=4)
def read_xplane(path: str) -> Optional[Dict[str, Tuple[int, float]]]:
    """{span name: (count, seconds)} of the ``msz.*`` spans on the host
    planes of one trace, clipped to the window span; None when the trace
    has no window span."""
    from jax.profiler import ProfileData
    win = None
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    if win is None:
        return None
    out: Dict[str, Tuple[int, float]] = {}
    for name, s, e in spans:
        if e < win[0] or s > win[1]:
            continue
        n, secs = out.get(name, (0, 0.0))
        out[name] = (n + 1, secs + (min(e, win[1]) - max(s, win[0])) * 1e-9)
    return out


def for_run(run) -> Optional[Dict[str, Tuple[int, float]]]:
    """The spans of ``run``'s window, or None without a trace."""
    path = newest_xplane(run.workload)
    return read_xplane(path) if path else None


def ms_per_field(run, name: str) -> Optional[float]:
    """Milliseconds of span ``name`` in the window per field answered
    there; None when the trace holds no such span."""
    spans = for_run(run)
    if not spans or name not in spans or not run.done:
        return None
    return 1e3 * spans[name][1] / len(run.done)
