"""Profiler spans of the program's host-side phases, and the stream's
queue-wait counter.

The program marks each host phase with a ``jax.profiler`` annotation
named ``msz.<phase>``. The contract under test: every span shows up in a
trace of a device-path compress and of a two-request stream batch; on
any one thread no two spans overlap (each is a leaf phase, so an idle
gap of the device is never claimed by a parent and a child at once);
span names are fixed strings, with any per-call data in the event's
stats rather than the name; and tracing changes no byte of an artifact.
"""
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.compress import CompressStream, compress_preserving_mss
from repro.data import synthetic_field

SHAPE = (8, 8, 8)
SOLO_SPANS = {"msz.precheck", "msz.h2d", "msz.wait", "msz.d2h",
              "msz.encode.residuals", "msz.encode.edits"}
STREAM_SPANS = SOLO_SPANS | {"msz.stream.empty", "msz.stream.linger"}


def _fields(n):
    fields = [synthetic_field("nyx", shape=SHAPE, seed=s).astype(np.float32)
              for s in range(n)]
    return fields, [1e-3 * float(np.ptp(f)) for f in fields]


def _record(log_dir, fn):
    """Run ``fn`` under the profiler inside a ``bench.window`` span, as
    the benchmark traces a run; return the host events per thread as
    ``{line: [(name, start_ns, end_ns, stats)]}`` and ``fn``'s result."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.window"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            lines[(plane.name, k)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats) if e.name.startswith("msz.") else {})
                for e in line.events
                if e.name.startswith("msz.") or e.name == "bench.window"]
    return lines, out


def _msz(lines):
    return {k: [e for e in evs if e[0].startswith("msz.")]
            for k, evs in lines.items()}


@pytest.fixture(scope="module")
def solo(tmp_path_factory):
    (f,), (xi,) = _fields(1)
    compress_preserving_mss(f, xi)              # compile outside the trace
    lines, art = _record(tmp_path_factory.mktemp("solo"),
                         lambda: compress_preserving_mss(f, xi))
    return lines, art, compress_preserving_mss(f, xi)


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    fields, xis = _fields(2)

    def two_request_batch():
        cs = CompressStream(window=4, max_batch=4, linger_ms=200.0)
        time.sleep(0.05)                        # the scheduler waits empty
        futs = [cs.submit(f, xi) for f, xi in zip(fields, xis)]
        arts = [fut.result(timeout=120) for fut in futs]
        st = cs.stats()
        cs.close()
        return arts, st

    lines, (arts, st) = _record(tmp_path_factory.mktemp("stream"),
                                two_request_batch)
    return lines, arts, st


def test_device_path_compress_shows_every_host_span(solo):
    lines, art, _ = solo
    assert art.path == "device"
    names = {e[0] for evs in _msz(lines).values() for e in evs}
    assert names == SOLO_SPANS


def test_stream_batch_shows_every_span(stream):
    lines, arts, st = stream
    assert st["batches"] == 1 and st["mean_batch"] == 2.0
    assert all(a.path == "device" for a in arts)
    names = {e[0] for evs in _msz(lines).values() for e in evs}
    assert names == STREAM_SPANS


@pytest.mark.parametrize("which", ["solo", "stream"])
def test_no_two_spans_overlap_on_one_thread(which, request):
    lines = request.getfixturevalue(which)[0]
    checked = 0
    for key, evs in _msz(lines).items():
        evs = sorted(evs, key=lambda e: e[1])
        for prev, nxt in zip(evs, evs[1:]):
            assert nxt[1] >= prev[2], (key, prev[:3], nxt[:3])
            checked += 1
    assert checked > 0


@pytest.mark.parametrize("which", ["solo", "stream"])
def test_span_names_carry_no_suffix(which, request):
    lines = request.getfixturevalue(which)[0]
    evs = [e for v in _msz(lines).values() for e in v]
    assert evs and all(e[0] in STREAM_SPANS for e in evs)
    # per-call data rides as event stats, not in the name
    moves = [e for e in evs if e[0] in ("msz.d2h", "msz.h2d")]
    assert moves and all(e[3].get("nbytes", -1) >= 0 for e in moves)


def test_every_span_lies_in_the_window(solo):
    lines, _, _ = solo
    win = [e for evs in lines.values() for e in evs
           if e[0] == "bench.window"]
    assert len(win) == 1
    lo, hi = win[0][1], win[0][2]
    for evs in _msz(lines).values():
        assert all(lo <= e[1] and e[2] <= hi for e in evs)


def test_tracing_changes_no_byte(solo):
    _, traced, untraced = solo
    assert traced.base_payload == untraced.base_payload
    assert traced.edit_payload == untraced.edit_payload


def test_queue_wait_counts_time_from_submit_to_pop():
    fields, xis = _fields(1)
    cs = CompressStream(window=2, start=False)
    st0 = cs.stats()
    assert st0["queue_wait_s"] == 0.0 and st0["dispatched"] == 0
    fut = cs.submit(fields[0], xis[0])
    time.sleep(0.2)
    cs.start()
    fut.result(timeout=120)
    st = cs.stats()
    cs.close()
    assert st["dispatched"] == 1
    assert st["queue_wait_s"] >= 0.2


def test_queue_wait_counts_every_popped_request():
    fields, xis = _fields(2)
    cs = CompressStream(window=4, max_batch=1, start=False)
    futs = [cs.submit(f, xi) for f, xi in zip(fields, xis)]
    time.sleep(0.1)
    cs.start()
    for fut in futs:
        fut.result(timeout=120)
    st = cs.stats()
    cs.close()
    assert st["batches"] == 2 and st["dispatched"] == 2
    # both waited the sleep; the second also waited out the first batch
    assert st["queue_wait_s"] >= 0.2
