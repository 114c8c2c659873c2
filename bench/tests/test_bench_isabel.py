"""The ``isabel-stream`` cell rehearsed on the CPU at a tiny shape, its
two recipes held to what they promise, and the ``fix_iters`` reader."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import fields, harness, lookup
from bench.tests.test_bench_cells import SEED

ROOT = Path(__file__).resolve().parents[2]
CELL = "isabel-stream"
TINY = {"config": {"shape": [6, 20, 20]}}
fix_iters = lookup.find("metrics", "fix_iters")


def _quiet(_line):
    pass


def test_isabel_stream_runs_end_to_end_on_cpu():
    out = harness.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                           overrides=TINY, log=_quiet)
    bench = harness.load_benchmark()
    names = {m["name"] for m in harness.metrics_for(bench, CELL, False)}
    assert names == {"compress_MBps", "bits_per_value", "setup_s"}
    assert set(out["metrics"]) == names
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] % 13 == 0
    assert out["checks"]["checked"]["value"] == 4
    assert out["checks"]["label_violations"]["value"] == 0
    assert out["checks"]["err_over_xi"]["value"] <= 1.000001


def test_traced_isabel_run_reads_the_fix_passes_on_cpu(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    out = harness.run_cell(CELL, SEED + 1, 0.5, True, require_tpu=False,
                           overrides=TINY, trace_dir=tmp_path, log=_quiet)
    # the CPU trace has no device plane: the program counter and the
    # host spans are read, the device metrics are not
    assert set(out["metrics"]) == {
        "fix_iters.steps", "host_deflate_ms.steps",
        "host_edit_coding_ms.steps", "d2h_ms.steps"}
    assert out["metrics"]["fix_iters.steps"]["value"] >= 1.0
    for name in ("host_deflate_ms.steps", "host_edit_coding_ms.steps",
                 "d2h_ms.steps"):
        assert out["metrics"][name]["value"] > 0.0, name
    assert out["correct"] is True, out["checks"]


def test_the_configuration_names_thirteen_variables_in_order():
    cfg = json.loads((ROOT / "bench/configs/isabel.json").read_text())
    hydro = {"CLOUDf", "PRECIPf", "QCLOUDf", "QGRAUPf", "QICEf", "QRAINf",
             "QSNOWf"}
    assert len(cfg["variables"]) == len(cfg["recipe"]) == 13
    for var, recipe in zip(cfg["variables"], cfg["recipe"]):
        want = ("isabel_hydrometeor" if var in hydro else
                "isabel_vortex" if var in ("Uf", "Vf") else "climate_like")
        assert recipe == want, var
    assert cfg["shape"] == [100, 500, 500]
    assert cfg["reduced"] == []


@pytest.mark.parametrize("shape", [(6, 20, 20), (24, 40)])
@pytest.mark.parametrize("seed", [SEED, 7])
def test_hydrometeor_members_are_mostly_exact_zeros(shape, seed):
    pool = fields.field_pool("isabel_hydrometeor", shape, 3, seed)
    for f in pool:
        zeros = float(np.mean(f == 0))
        assert f.dtype == np.float32 and f.min() == 0.0 and f.max() > 0.0
        # the quantile cut leaves the drawn share, up to one value
        assert 0.75 - 1.0 / f.size <= zeros <= 0.90 + 1.0 / f.size


@pytest.mark.parametrize("shape", [(6, 20, 20), (24, 40)])
def test_vortex_members_are_signed_without_zeros(shape):
    for f in fields.field_pool("isabel_vortex", shape, 2, SEED):
        assert f.min() < 0.0 < f.max()
        assert not np.any(f == 0)
        assert 20.0 < np.abs(f).max() < 200.0       # m/s, V_MAX 60


def _run(before, after):
    return types.SimpleNamespace(
        stats_before=None if before is None else {"compress": before},
        stats_after=None if after is None else {"compress": after})


def test_fix_iters_reads_mean_passes_per_member():
    run = _run({"dispatched": 13, "fix_iters": 400},
               {"dispatched": 39, "fix_iters": 1700})
    assert fix_iters.read(run) == pytest.approx(50.0)


@pytest.mark.parametrize("before,after", [
    (None, None),
    ({"dispatched": 13}, {"dispatched": 26}),          # no counter
    ({"dispatched": 13, "fix_iters": 9}, {"dispatched": 13, "fix_iters": 9}),
])
def test_fix_iters_reads_nothing_without_the_counters(before, after):
    assert fix_iters.read(_run(before, after)) is None
