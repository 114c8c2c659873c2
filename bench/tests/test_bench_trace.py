"""Trace reduction: interval arithmetic, and a trace recorded on a v5e."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_nyx64_write.xplane.pb"


def test_union_of_overlapping_and_nested_intervals():
    iv = np.array([[0, 10], [2, 5], [8, 12], [20, 25], [21, 22]], float)
    assert trace.union_seconds(iv) == pytest.approx(17e-9)
    assert trace.union_seconds(np.zeros((0, 2))) == 0.0


def test_gaps_inside_a_window():
    iv = np.array([[5, 10], [8, 12], [20, 25]], float)
    g = trace.gaps(iv, 0.0, 30.0)
    assert g.tolist() == [[0, 5], [12, 20], [25, 30]]
    assert trace.gaps(np.zeros((0, 2)), 1.0, 4.0).tolist() == [[1.0, 4.0]]


def test_program_name_drops_the_id():
    assert trace.program_name("jit__fused_fix_impl(123)") == \
        "jit__fused_fix_impl"
    assert trace.program_name("jit_wrapped") == "jit_wrapped"


def test_recorded_v5e_trace():
    """A 64^3 write run traced on one v5e (``bench/run.py``'s trace
    options): one device plane whose ``XLA Modules`` line names the
    programs, and the benchmark's window span on the host."""
    t = trace.summarize(str(FIXTURE))
    assert t is not None and t.devices == 1
    assert t.window_s == pytest.approx(1.0830843570000002)
    assert t.busy_s == pytest.approx(0.436190022)
    assert 0 < t.busy_s < t.window_s
    # the fix loop, the Lorenzo transform (an eager pallas_call), and the
    # program's own labels of the original
    for name in ("jit__fused_fix_impl", "jit_wrapped",
                 "jit_labels_from_codes", "jit_sz_inverse"):
        assert t.program_s[name] > 0, name
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit_labels_from_codes"
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle <= t.window_s - t.busy_s + 1e-9


def test_a_trace_without_the_window_span_reads_nothing(tmp_path):
    assert trace.summarize(str(FIXTURE), window_span="no.such.span") is None
