"""A run with the timed path broken underneath must come out not
correct. Each test drives the rest of a run (no look for a chip) with one
fault planted in the program, at a tiny shape on the CPU:

* a fix step that returns its state unchanged (the fix loop hands back
  the base field, so no edits are made);
* half of a service batch left out: members past the first half of
  each batch get the first member's artifact;
* an answer altered where it is produced: the edit stream emptied on the
  write path, one decoded value changed on the read path.

The cells run on one chip each, so there is no exchange between chips
to leave out.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_cells import SEED, TINY, root_for


def _quiet(_line):
    pass


def _run(workload, program=None, traffic=None, seconds=1.0, root=None):
    over = {k: dict(v) for k, v in TINY[workload].items()}
    over.setdefault("traffic", {}).update(traffic or {})
    return harness.run_cell(workload, SEED + 7, seconds, False,
                            require_tpu=False, overrides=over,
                            program=program, log=_quiet,
                            **({"root": root} if root else {}))


def test_fix_step_returning_its_state_unchanged(monkeypatch):
    from repro.core import fixes

    def unchanged(g0, topo, max_iters=512, backend="auto", mesh=None):
        return g0, jnp.int32(1), jnp.bool_(True)

    monkeypatch.setattr(fixes, "fused_fix", unchanged)
    out = _run("nyx512-write")
    assert out["correct"] is False
    assert out["checks"]["label_violations"]["value"] > 0


def test_half_of_each_service_batch_left_out(monkeypatch):
    from repro.compress import pipeline
    real = pipeline._encode_batch_member

    def first_half_only(db, i, evd):
        half = max(1, (len(db.fields) + 1) // 2)
        return real(db, 0 if i >= half else i, evd)

    monkeypatch.setattr(pipeline, "_encode_batch_member", first_half_only)
    # offered far above what the CPU serves, so that batches fill up
    out = _run("cesm-atm-service", seconds=0.5,
               traffic={"rate_per_s": 40.0, "check_sample": 64,
                        "drain_s": 240})
    assert out["correct"] is False
    assert out["checks"]["label_violations"]["value"] > 0


def test_write_answer_altered_where_it_is_produced():
    from repro.compress import codec

    p = harness._program()
    real = p.compress_preserving_mss

    def no_edits(f, xi):
        art = real(f, xi)
        return dataclasses.replace(art, edit_payload=codec.encode_edits(
            np.zeros(0, np.int64), np.zeros(0, np.float32)))

    p.compress_preserving_mss = no_edits
    out = _run("nyx512-write", program=p)
    assert out["correct"] is False
    assert out["checks"]["label_violations"]["value"] > 0


def test_read_answer_altered_where_it_is_produced(tmp_path):
    p = harness._program()
    real = p.decompress_preserving_mss

    def one_value_off(art):
        g = real(art).copy()
        g.reshape(-1)[g.size // 2] += np.float32(1e-3)
        return g

    p.decompress_preserving_mss = one_value_off
    out = _run("nyx512-read", program=p,
               root=root_for("nyx512-read", tmp_path))
    assert out["correct"] is False
    assert out["checks"]["decode_mismatch"]["value"] == 1


@pytest.mark.parametrize("workload", ["nyx512-write", "cesm-atm-service"])
def test_a_sound_run_of_the_same_cell_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True, out["checks"]
