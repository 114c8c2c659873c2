"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` alone. A device plane is one
whose name starts with ``/device:`` and that is not a host plane; its
``XLA Ops`` line holds one event per operation the device ran, and its
``XLA Modules`` line one event per program (``jit_<function>(<id>)``).
Host planes (``/host:``) hold the benchmark's own spans
(``bench.window`` around the measured window) and the runtime's.

What comes out, for the window that ``bench.window`` marks:

* busy seconds: the union of the device-op intervals, averaged over the
  devices traced; the idle share is one minus busy over the window;
* device seconds per program, by the program's name without its id;
* the idle gaps, each named by the host event that overlaps it most
  (the benchmark's own ``bench.*`` spans only where no other does).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``."""
    return _ID_SUFFIX.sub("", event_name.strip())


def union_seconds(intervals: np.ndarray) -> float:
    """Length of the union of [start, end) intervals (ns), in seconds."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after every earlier end;
    # a run ends at the running maximum of its last interval
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return float(np.sum(ends[last] - iv[first, 0])) * 1e-9


def gaps(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle gaps [start, end) (ns) of ``intervals`` inside [lo, hi]."""
    if len(intervals) == 0:
        return np.array([[lo, hi]])
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    g_start = np.concatenate([[lo], ends])
    g_end = np.concatenate([iv[:, 0], [hi]])
    keep = g_end > g_start
    return np.stack([g_start[keep], g_end[keep]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


@dataclass
class TraceSummary:
    """What one traced window holds."""
    window_s: float
    busy_s: float                      # mean over the devices traced
    devices: int
    program_s: Dict[str, float] = field(default_factory=dict)
    idle_by_host: Dict[str, float] = field(default_factory=dict)
    line_names: Dict[str, List[str]] = field(default_factory=dict)

    def program_seconds(self, *names: str) -> float:
        """Device seconds of every program whose name contains one of
        ``names``."""
        return sum(s for p, s in self.program_s.items()
                   if any(n in p for n in names))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The programs that held the device longest, and the idle time
        by what the host was doing."""
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> np.ndarray:
    return np.array([(e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events], dtype=np.float64).reshape(-1, 2)


def _intervals(events: List[Tuple[str, float, float]]):
    return ([n for n, _, _ in events],
            np.array([(s, e) for _, s, e in events]).reshape(-1, 2))


def _most_overlap(events, gs: float, ge: float) -> Optional[str]:
    """Name of the event that overlaps [gs, ge) most, if any does."""
    names, iv = events
    if not names:
        return None
    ov = np.minimum(iv[:, 1], ge) - np.maximum(iv[:, 0], gs)
    k = int(np.argmax(ov))
    return names[k] if ov[k] > 0 else None


def summarize(path: str, window_span: str = WINDOW_SPAN,
              gap_names: int = 400, min_host_ns: float = 1e4
              ) -> Optional[TraceSummary]:
    """Reduce one ``.xplane.pb``. None when the trace has no device plane
    or no ``window_span`` (nothing to read)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    dev = [p for p in planes if p.name.startswith("/device:")
           and not p.name.startswith("/device:CUSTOM")]
    line_names = {p.name: [ln.name for ln in p.lines] for p in planes}

    win = None
    host_events: List[Tuple[str, float, float]] = []
    bench_spans: List[Tuple[str, float, float]] = []
    for p in host:
        for ln in p.lines:
            for e in ln.events:
                if e.name == window_span:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.duration_ns >= min_host_ns:
                    (bench_spans if e.name.startswith("bench.")
                     else host_events).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if win is None:
        return None
    lo, hi = win
    window_s = (hi - lo) * 1e-9

    busy, program_s, all_ops = [], {}, []
    for p in dev:
        lines = {ln.name: ln for ln in p.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        iv = _clip(_events(ops), lo, hi)
        if len(iv) == 0:
            continue
        busy.append(union_seconds(iv))
        all_ops.append(iv)
        mods = lines.get(MODULES_LINE)
        for e in (mods.events if mods is not None else ()):
            d = min(e.start_ns + e.duration_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                k = program_name(e.name)
                program_s[k] = program_s.get(k, 0.0) + d * 1e-9
    if not busy:
        return None

    idle: Dict[str, float] = {}
    g = gaps(np.concatenate(all_ops), lo, hi)
    order = np.argsort(g[:, 0] - g[:, 1])           # longest first
    named = 0.0
    host_iv, bench_iv = _intervals(host_events), _intervals(bench_spans)
    for gs, ge in g[order[:gap_names]]:
        name = (_most_overlap(host_iv, gs, ge)
                or _most_overlap(bench_iv, gs, ge) or "(no host event)")
        idle[name] = idle.get(name, 0.0) + float(ge - gs) * 1e-9
        named += float(ge - gs) * 1e-9
    rest = float(np.sum(g[:, 1] - g[:, 0])) * 1e-9 - named
    if rest > 0:
        idle["(shorter gaps)"] = rest
    return TraceSummary(window_s=window_s, busy_s=float(np.mean(busy)),
                        devices=len(busy), program_s=program_s,
                        idle_by_host=idle, line_names=line_names)
