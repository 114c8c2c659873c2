"""The benchmark's arithmetic: least bytes, peaks, rates, percentiles."""
import json
import math
import statistics

import pytest

from bench import arith


def test_transform_least_bytes_reads_field_and_writes_codes_once():
    assert arith.transform_least_bytes((512, 512, 512)) == 8 * 512 ** 3
    assert arith.transform_least_bytes((1800, 3600)) == 8 * 1800 * 3600
    assert arith.transform_least_bytes((4, 4), itemsize=8) == 16 * 12


def test_roofline_pct():
    # 819 bytes in one microsecond at 819 GB/s is the whole roofline
    assert arith.roofline_pct(819e3, 1e-6, 819e9) == pytest.approx(100.0)
    assert arith.roofline_pct(819e3, 2e-6, 819e9) == pytest.approx(50.0)
    assert arith.roofline_pct(1.0, 0.0, 819e9) is None


def test_peaks_lookup_and_unknown_kind(tmp_path):
    v5e = arith.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        arith.peaks_for("TPU v4")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"devices": {"x": {"hbm_bytes_per_s": 1.0}}}))
    assert arith.peaks_for("x", p) == {"hbm_bytes_per_s": 1.0}
    with pytest.raises(KeyError):
        arith.peaks_for("cpu", p)


def test_whole_request_rate_counts_the_request_in_flight():
    # three fields of 536.870912 MB; the window started at 10 s, the
    # third (in flight at the close) finished at 40 s
    mb = 512 ** 3 * 4 / 1e6
    assert arith.whole_request_rate([mb] * 3, 10.0, 40.0) == \
        pytest.approx(3 * mb / 30.0)
    assert arith.whole_request_rate([], 0.0, 1.0) is None
    assert arith.whole_request_rate([1.0], 5.0, 5.0) is None


def test_due_time_percentile():
    due = [0.0, 1.0, 2.0, 3.0]
    done = [0.1, 1.5, None, 3.2]
    lat = arith.due_latencies_ms(due, done)
    assert lat[0] == pytest.approx(100.0)
    assert lat[1] == pytest.approx(500.0)
    assert math.isinf(lat[2])
    # nearest rank: the 95th of four values is the largest
    assert math.isinf(arith.percentile(lat, 95))
    assert arith.percentile(lat, 50) == pytest.approx(200.0)
    xs = list(range(1, 101))
    assert arith.percentile(xs, 95) == 95
    assert arith.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        arith.percentile([], 95)


def test_spread_is_the_python_quartile_distance_over_the_median():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert arith.spread(xs) == pytest.approx((q3 - q1) / med)
