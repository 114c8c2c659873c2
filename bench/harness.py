"""One run of one cell: set-up, the measured window, metrics, the check.

Everything that belongs to one configuration, traffic mix, recipe,
loop, entry or metric is found by name, as a file of its own
(``bench/lookup.py``): ``BENCHMARK.json`` names the cell's configuration
file and traffic mix (``bench/traffic/<mix>.json``); the configuration
names its field recipe or recipes (``bench/recipes/<name>.py``); the
traffic names its loop (``bench/loops/<loop>.py``) and its entry into
the program (``bench/entries/<entry>.py``); and each metric is read by
``bench/metrics/<name>.py``, or, for a name with a dot such as
``device_idle_pct.write``, by ``bench/metrics/<part before the dot>.py``
when no file of the full name exists. A reader is a module with
``read(run) -> float | None``; None means it found nothing to read, and
the metric is left out of the result line. A new cell adds such files;
it edits none.

``run_cell`` is the whole run; ``bench/run.py`` is its command line.
Tests call ``run_cell`` with ``require_tpu=False`` and ``overrides`` to
rehearse a cell on the CPU at a tiny shape.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import json
import math
import sys
import time
import types
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import numpy as np

from bench import lookup

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"

#: one event per program lowered, per program compiled or read from the
#: persistent cache, and per persistent-cache hit
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric reader may read about one run."""
    workload: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seconds: float
    setup_s: float
    window_start: float
    reqs: list
    device: Dict[str, Any]
    peaks: Optional[Dict[str, float]] = None
    trace: Any = None                  # trace.TraceSummary or None
    stats_before: Optional[Dict[str, Any]] = None
    stats_after: Optional[Dict[str, Any]] = None

    @property
    def done(self) -> list:
        """Requests answered, in request order."""
        return [r for r in self.reqs if r.ok and r.done is not None]


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: Dict[str, Any], workload: str, root: Path = ROOT):
    """(cell, configuration, traffic) of ``workload``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(bench: Dict[str, Any], workload: str, trace: bool) -> list:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of the metric's reader module (full name first, then the
    part before the first dot)."""
    return lookup.find("metrics", name, name.split(".")[0],
                       base=root / "bench").read


def _merge(base: Dict[str, Any], over: Optional[Dict[str, Any]]):
    out = copy.deepcopy(base)
    out.update(over or {})
    return out


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX runs on {d.platform!r}, not on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 where the
    backend keeps no such count)."""
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class CompileCounter:
    """Counts programs lowered while it is armed (``n``; cache hits
    included: each is a new program in the process), and of those the
    ones read from the persistent cache; the rest the backend compiled."""

    def __init__(self):
        from jax import monitoring
        self.reset()
        self.armed = False
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def reset(self) -> None:
        self.n = self.backend = self.hits = 0
        self.names: Dict[str, int] = {}

    def summary(self) -> str:
        return json.dumps({"lowered": self.n, "cache_hits": self.hits,
                           "compiled": self.backend - self.hits,
                           "lowered_by_name": self.names})

    def _on_duration(self, name: str, _secs: float, fun_name: str = "?",
                     **_kw) -> None:
        if self.armed and name == _LOWER_EVENT:
            self.n += 1
            self.names[fun_name] = self.names.get(fun_name, 0) + 1
        elif self.armed and name == _BACKEND_EVENT:
            self.backend += 1

    def _on_event(self, name: str, **_kw) -> None:
        if self.armed and name == _HIT_EVENT:
            self.hits += 1


def _program():
    """The program's entry points (imported only after the device check,
    with ``src`` on the path)."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compress import (compress_preserving_mss,
                                decompress_preserving_mss)
    from repro.serve import CompressionService
    return types.SimpleNamespace(
        compress_preserving_mss=compress_preserving_mss,
        decompress_preserving_mss=decompress_preserving_mss,
        CompressionService=CompressionService)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def set_up(traffic: Dict[str, Any], program, fields: list, xis: list,
           counter: CompileCounter, root: Path = ROOT):
    """(entry, items) ready for the window: the entry has warmed every
    shape the window will use."""
    entry = lookup.find("entries", traffic["entry"],
                        base=root / "bench").start(program)
    op = traffic["op"]
    comp = [(f, xi) for f, xi in zip(fields, xis)]
    if op == "compress":
        items = comp
    else:                    # the artifacts the readers read
        items = [program.compress_preserving_mss(f, xi) for f, xi in comp]
    counter.armed = True
    entry.warm(op, items, traffic, counter)
    counter.armed = False
    return entry, items


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else 1e300


def check(run: Run, fields: list, xis: list, items: list,
          config: Dict[str, Any], log=print) -> Dict[str, Dict[str, float]]:
    """Every number compared, with its limit. Answers sampled in the
    window are judged by ``bench.reference`` against the originals."""
    from bench import reference as ref
    tol = float(config["guarantees"]["err_rel_tolerance"])
    sampled = [r for r in run.reqs if r.answer is not None]
    failed = sum(1 for r in run.reqs if not (r.ok and r.done is not None))
    labels, errs, decode_mm = [], [], []
    originals: Dict[int, Any] = {}
    t_ref = {"originals": 0.0, "decode": 0.0, "compare": 0.0}
    for r in sampled:
        t0 = time.perf_counter()
        if r.item not in originals:
            originals[r.item] = ref.Original(fields[r.item], xis[r.item])
        orig = originals[r.item]
        t1 = time.perf_counter()
        t_ref["originals"] += t1 - t0
        if run.traffic["op"] == "compress":
            try:
                g = ref.decode(r.answer.base_payload, r.answer.edit_payload)
            except (ValueError, KeyError, IndexError) as exc:
                print(f"# reference refuses request {r.i}: {exc!r}",
                      file=sys.stderr)
                labels.append(math.inf)
                errs.append(math.inf)
                continue
        else:
            art = items[r.item]
            g = np.asarray(r.answer)
            try:
                g_ref = ref.decode(art.base_payload, art.edit_payload)
                decode_mm.append(ref.bitwise_mismatch(g, g_ref))
            except (ValueError, KeyError, IndexError) as exc:
                print(f"# reference refuses the artifact: {exc!r}",
                      file=sys.stderr)
                decode_mm.append(math.inf)
        t2 = time.perf_counter()
        res = orig.compare(g)
        t_ref["decode"] += t2 - t1
        t_ref["compare"] += time.perf_counter() - t2
        labels.append(res["label_violations"])
        errs.append(res["err_over_xi"])
        r.answer = None
    log("# reference seconds " + json.dumps(t_ref))
    checks = {
        "checked": {"value": float(len(sampled)), "limit_min": 1.0},
        "failed": {"value": float(failed), "limit": 0.0},
        "label_violations": {"value": _finite(max(labels, default=math.inf)),
                           "limit": 0.0},
        "err_over_xi": {"value": _finite(max(errs, default=math.inf)),
                        "limit": 1.0 + tol},
    }
    if run.traffic["op"] == "decompress":
        checks["decode_mismatch"] = {
            "value": _finite(max(decode_mm, default=math.inf)), "limit": 0.0}
    return checks


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    ok = True
    for c in checks.values():
        v = c["value"]
        if "limit" in c:
            ok &= bool(v <= c["limit"])
        if "limit_min" in c:
            ok &= bool(v >= c["limit_min"])
    return ok


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             overrides: Optional[Dict[str, Dict[str, Any]]] = None,
             program=None, root: Path = ROOT, trace_dir: Optional[Path] = None,
             log=print) -> Dict[str, Any]:
    """Run one cell and return the result line's object. Tests only:
    ``overrides`` replaces keys of the configuration and traffic, and
    ``program`` stands in for the program's entry points."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark(root)
    cell, config, traffic = cell_spec(bench, workload, root)
    overrides = overrides or {}
    config = _merge(config, overrides.get("config"))
    traffic = _merge(traffic, overrides.get("traffic"))

    device = device_info(int(cell["chips"]), require_tpu)
    import jax
    program = program or _program()
    if device["platform"] == "tpu":
        from repro.launch.compile_cache import configure_compile_cache
        log(f"# cache {configure_compile_cache()}")
        # every program goes to the persistent cache, however fast it
        # compiled, so that a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import arith, fields as fieldgen

    counter = CompileCounter()
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    fields = fieldgen.field_pool(config["recipe"], config["shape"],
                                 int(traffic["pool"]), seed,
                                 config.get("content_seed"),
                                 base=root / "bench")
    xis = [float(config["xi_rel"]) * float(np.ptp(f)) for f in fields]
    loop = lookup.find("loops", traffic["loop"], base=root / "bench")
    entry, items = set_up(traffic, program, fields, xis, counter, root)
    log(f"# warm-up programs {counter.summary()}")
    stats_before = entry.stats() if hasattr(entry, "stats") else None

    span = jax.profiler.TraceAnnotation
    log_dir = None
    if trace:
        log_dir = (trace_dir or TRACE_DIR) / f"{workload}-{seed}"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    sample = int(traffic.get("check_sample", 1))
    counter.reset()
    counter.armed = True
    window_start = time.perf_counter()
    setup_s = window_start - t_start
    with span("bench.window"):
        reqs = loop.run(entry, traffic["op"], items, seconds, rng, traffic,
                        sample, span)
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    stats_after = entry.stats() if hasattr(entry, "stats") else None
    entry.close()
    log(f"# compiles_in_window {counter.summary()}")
    if reqs:
        lat = arith.due_latencies_ms([r.due for r in reqs],
                                     [r.done if r.ok else None for r in reqs])
        log("# latency_ms " + json.dumps(
            {f"p{q}": arith.percentile(lat, q) for q in (50, 75, 90, 95)}))

    peak = memory_peak_bytes()
    run = Run(workload=workload, cell=cell, config=config, traffic=traffic,
              seconds=seconds, setup_s=setup_s, window_start=window_start,
              reqs=reqs, device=device, stats_before=stats_before,
              stats_after=stats_after)
    if trace:
        from bench import trace as tr
        run.trace = tr.summarize(tr.find_xplane(str(log_dir)))
        if device["platform"] == "tpu":
            run.peaks = arith.peaks_for(device["kind"])
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        v = reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    if trace and run.trace is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)

    del entry
    gc.collect()
    checks = check(run, fields, xis, items, config, log)
    out = {"correct": passed(checks),
           "attempted": len(reqs),
           "failed": int(checks["failed"]["value"]),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        out["breakdown"] = run.trace.breakdown()
        log("# trace lines " + json.dumps(run.trace.line_names))
        log("# trace programs " + json.dumps(
            sorted(run.trace.program_s.items(), key=lambda kv: -kv[1])[:25]))
    out["checks"] = checks
    return out
