"""The program-span reader (``bench/spans.py``) and the four metrics that
read the program's spans and the service's queue-wait counter, on
traces recorded on the CPU."""
import types

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import harness, spans
from bench.tests.test_bench_cells import SEED, TINY

SPAN_METRICS = {"host_deflate_ms.write": "msz.encode.residuals",
                "host_edit_coding_ms.write": "msz.encode.edits",
                "d2h_ms.write": "msz.d2h"}


def _quiet(_line):
    pass


def _trace_only_the_window(log_dir):
    """A trace of a program that marks no span of its own."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def traced_write(tmp_path_factory):
    """The write cell run traced on the CPU at a tiny shape, its trace
    where the benchmark keeps traces."""
    trace_dir = tmp_path_factory.mktemp("bench_trace")
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "TRACE_DIR", trace_dir)
    try:
        out = harness.run_cell("nyx512-write", SEED, 0.5, True,
                               require_tpu=False,
                               overrides=TINY["nyx512-write"], log=_quiet)
    finally:
        mp.undo()
    return trace_dir, out


def _run(workload, done=1):
    return types.SimpleNamespace(workload=workload, done=[object()] * done)


def test_the_traced_write_line_carries_the_span_metrics(traced_write):
    _, out = traced_write
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    for name in SPAN_METRICS:
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms", name
    # the spans split the outside view of host coding, which also holds
    # the waits on the device and the code around the spans
    assert "host_encode_ms.write" in m


def test_spans_are_counted_and_clipped_to_the_window(traced_write,
                                                     monkeypatch):
    trace_dir, _ = traced_write
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    path = spans.newest_xplane("nyx512-write")
    assert path is not None and path.startswith(str(trace_dir))
    got = spans.read_xplane(path)
    for name in ("msz.precheck", "msz.h2d", "msz.wait", "msz.d2h",
                 "msz.encode.residuals", "msz.encode.edits"):
        n, secs = got[name]
        assert n >= 1 and secs >= 0.0, name
    assert all(k.startswith("msz.") for k in got)
    # one field compressed in a half-second window: one of each coding
    assert got["msz.encode.residuals"][0] == got["msz.encode.edits"][0]


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_reads_its_span_per_field(name, traced_write,
                                              monkeypatch):
    trace_dir, _ = traced_write
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    got = spans.read_xplane(spans.newest_xplane("nyx512-write"))
    for done in (1, 2):
        v = harness.reader(name)(_run("nyx512-write", done))
        assert v == pytest.approx(1e3 * got[SPAN_METRICS[name]][1] / done)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metric_reads_nothing_without_spans(name, tmp_path,
                                                 monkeypatch):
    """A program that writes no ``msz.*`` span (the parent of the change
    that added them) and a run with no trace both read as None."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    read = harness.reader(name)
    assert read(_run("nyx512-write")) is None
    _trace_only_the_window(tmp_path / "nyx512-write-1")
    assert spans.for_run(_run("nyx512-write")) == {}
    assert read(_run("nyx512-write")) is None
    assert read(_run("nyx512-write", done=0)) is None


def _service_run(before, after, trace=object()):
    return types.SimpleNamespace(stats_before={"compress": before},
                                 stats_after={"compress": after},
                                 trace=trace)


def test_queue_wait_reads_the_mean_wait_of_the_window():
    read = harness.reader("queue_wait_ms.service")
    v = read(_service_run({"queue_wait_s": 1.0, "dispatched": 4},
                          {"queue_wait_s": 7.0, "dispatched": 7}))
    assert v == pytest.approx(2000.0)


@pytest.mark.parametrize("case", ["no_counter", "nothing_popped",
                                  "no_trace", "no_stats"])
def test_queue_wait_reads_nothing(case):
    read = harness.reader("queue_wait_ms.service")
    before = {"queue_wait_s": 1.0, "dispatched": 4}
    run = {
        "no_counter": _service_run({"batches": 1}, {"batches": 2}),
        "nothing_popped": _service_run(before, dict(before)),
        "no_trace": _service_run(before, {"queue_wait_s": 2.0,
                                          "dispatched": 5}, trace=None),
        "no_stats": types.SimpleNamespace(stats_before=None,
                                          stats_after=None, trace=object()),
    }[case]
    assert read(run) is None
