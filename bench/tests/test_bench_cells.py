"""Every cell, rehearsed end to end on the CPU at a tiny shape through
``harness.run_cell``'s test seam, and the command line's refusals."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
#: the read cell's entries, which BENCHMARK.json leaves out until the
#: cell is measured on the chip; its files are under bench/
READ = json.loads((Path(__file__).parent / "read_cell.json").read_text())
SEED = 2 ** 40 + 3          # beyond 32 bits, as the driver's seeds are

TINY = {
    "nyx512-write": {"config": {"shape": [12, 12, 12]}},
    "nyx512-read": {"config": {"shape": [12, 12, 12]}},
    "cesm-atm-service": {"config": {"shape": [16, 32]},
                         "traffic": {"pool": 2, "rate_per_s": 6.0,
                                     "check_sample": 4}},
}


def _quiet(_line):
    pass


def root_for(workload, tmp_path):
    """The checkout's root, or for the read cell a root whose
    BENCHMARK.json adds it, with bench/ linked."""
    if workload != READ["workload"]["name"]:
        return ROOT
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(READ["workload"])
    bench["end_to_end"].append(READ["end_to_end"])
    bench["per_layer"].extend(READ["per_layer"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    return tmp_path


def _assert_result_line(out, names):
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) == set(names)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in line["checks"].values():
        assert "value" in c and ("limit" in c or "limit_min" in c)
    return line


@pytest.mark.parametrize("workload", sorted(TINY))
def test_cell_runs_end_to_end_on_cpu(workload, tmp_path):
    root = root_for(workload, tmp_path)
    out = harness.run_cell(workload, SEED, 1.0, False, require_tpu=False,
                           overrides=TINY[workload], root=root, log=_quiet)
    bench = harness.load_benchmark(root)
    names = [m["name"] for m in harness.metrics_for(bench, workload, False)]
    line = _assert_result_line(out, names)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["checks"]["label_violations"]["value"] == 0
    assert line["checks"]["err_over_xi"]["value"] <= 1.000001
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_what_it_can_read_on_cpu(tmp_path):
    w = "cesm-atm-service"
    out = harness.run_cell(w, SEED + 1, 1.0, True, require_tpu=False,
                           overrides=TINY[w], trace_dir=tmp_path, log=_quiet)
    # the CPU trace has no device plane: the device-trace metrics are
    # left out, the counters and the generator's clock are not
    line = _assert_result_line(out, ["batch_occupancy.service",
                                     "gen_late_p95_ms.service"])
    assert line["correct"] is True
    assert "busy_s" not in line["device"]


def test_a_new_cell_runs_from_added_files_alone(tmp_path):
    """A later PR adds configurations, traffic mixes, a field recipe and
    a metric reader as files, and entries in BENCHMARK.json; no existing
    file changes. One added cell is a closed one-shot loop; the other
    sends steps to the service, over a list of two recipes, one of them
    added: mostly exact zeros, as a hydrometeor field is."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny2d.json").write_text(json.dumps({
        "name": "tiny2d", "shape": [16, 24], "dtype": "float32",
        "recipe": "climate_like", "xi_rel": 0.002,
        "guarantees": {"err_rel_tolerance": 1e-6}, "reduced": []}))
    (tmp_path / "bench/traffic/pairs.json").write_text(json.dumps({
        "op": "compress", "entry": "one_shot", "loop": "closed",
        "pool": 3, "check_sample": 2}))
    (tmp_path / "bench/metrics/edit_pct.py").write_text(
        "def read(run):\n"
        "    xs = [r.stats['edit_ratio'] for r in run.done]\n"
        "    return 100.0 * sum(xs) / len(xs) if xs else None\n")
    (tmp_path / "bench/recipes/plateau.py").write_text(
        "import jax.numpy as jnp\n"
        "from bench.fields import _spectral\n\n\n"
        "def make(key, shape):\n"
        "    return jnp.maximum(_spectral(key, shape, -3.0) - 1.0, 0.0)\n")
    (tmp_path / "bench/configs/tiny3d.json").write_text(json.dumps({
        "name": "tiny3d", "shape": [6, 10, 12], "dtype": "float32",
        "recipe": ["climate_like", "plateau"], "xi_rel": 0.002,
        "guarantees": {"err_rel_tolerance": 1e-6}, "reduced": []}))
    (tmp_path / "bench/traffic/stream.json").write_text(json.dumps({
        "op": "compress", "entry": "service", "loop": "steps",
        "pool": 3, "step": 3, "check_sample": 3}))
    for name in ("tiny2d", "tiny3d"):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2d.pairs", "config": "tiny2d",
                               "traffic": "pairs", "chips": 1, "why": "t"})
    bench["workloads"].append({"name": "tiny3d.stream", "config": "tiny3d",
                               "traffic": "stream", "chips": 1, "why": "t"})
    for m in bench["end_to_end"]:
        if m["name"] in ("compress_MBps", "bits_per_value"):
            m["workloads"].append("tiny3d.stream")
    bench["per_layer"].append({"name": "edit_pct.tiny", "unit": "%",
                               "better": "lower", "source": "program_span",
                               "layer": "t", "moves": "setup_s",
                               "workloads": ["tiny2d.pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run_cell("tiny2d.pairs", SEED, 0.5, True,
                           require_tpu=False, root=tmp_path,
                           trace_dir=tmp_path / "trace", log=_quiet)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"edit_pct.tiny"}
    out = harness.run_cell("tiny3d.stream", SEED, 0.5, False,
                           require_tpu=False, root=tmp_path, log=_quiet)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] % 3 == 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"compress_MBps", "bits_per_value",
                                   "setup_s"}
    for path in (ROOT / "bench").rglob("*"):
        rel = path.relative_to(ROOT / "bench")
        if path.is_file() and rel.parts[0] not in ("tests", "__pycache__") \
                and path.suffix != ".pyc":
            assert (tmp_path / "bench" / rel).read_bytes() \
                == path.read_bytes(), rel


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nyx512-write",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, text=True, capture_output=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_the_command_refuses_a_cpu():
    proc = _cli(ROOT)
    _no_result(proc)
    assert proc.returncode == 3
    assert "not on a TPU" in proc.stderr


def test_the_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_cli(tmp_path))


def test_importing_the_benchmark_loads_no_libtpu():
    code = ("import bench.harness, bench.run, bench.trace, bench.reference,"
            " bench.fields, bench.load, bench.arith\n"
            "print('libtpu' in open('/proc/self/maps').read())")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          text=True, capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "False"
