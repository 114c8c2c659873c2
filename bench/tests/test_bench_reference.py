"""The reference against the program at small sizes, and the control.

The reference shares no code with the program; here it must agree with
it where both are right (decoding, labels against the program's own
brute-force oracle), and the control (the field stored in bfloat16, one
precision below the configuration's float32) must fail the comparison.
"""
import numpy as np
import pytest

from bench import fields, reference as ref


def _field(recipe, shape, seed):
    return fields.field_pool(recipe, shape, 1, seed)[0]


@pytest.mark.parametrize("recipe,shape", [("nyx_like", (10, 12, 14)),
                                          ("climate_like", (24, 40))])
def test_reference_decodes_what_the_program_decodes(recipe, shape):
    from repro.compress import (compress_preserving_mss,
                                decompress_preserving_mss)
    f = _field(recipe, shape, 5)
    xi = 1e-3 * float(np.ptp(f))
    art = compress_preserving_mss(f, xi)
    g = ref.decode(art.base_payload, art.edit_payload)
    assert ref.bitwise_mismatch(g, decompress_preserving_mss(art)) == 0
    res = ref.Original(f, xi).compare(g)
    assert res["label_violations"] == 0
    assert res["err_over_xi"] <= 1.0 + 1e-6


@pytest.mark.parametrize("shape", [(5, 6, 7), (9, 11)])
def test_reference_labels_match_the_brute_force_oracle(shape):
    from repro.core.ref import mss_labels_ref
    rng = np.random.default_rng(3)
    # few distinct values: ties everywhere, so the index tie-break counts
    f = rng.integers(0, 4, size=shape).astype(np.float32)
    M, m = mss_labels_ref(f)
    mx, mn = ref.labels(f)
    assert np.array_equal(np.asarray(mx), M.reshape(-1))
    assert np.array_equal(np.asarray(mn), m.reshape(-1))


def test_reference_refuses_bytes_it_cannot_read():
    with pytest.raises(ref.BadArtifact):
        ref.decode_base(b"XXXX" + bytes(40))
    with pytest.raises(ValueError):
        ref.decode_edits(b"MSE1" + bytes(30), 10)


@pytest.mark.parametrize("recipe,shape", [("nyx_like", (16, 16, 16)),
                                          ("climate_like", (36, 72))])
def test_the_bfloat16_control_is_not_correct(recipe, shape):
    for seed in (1, 2, 3):
        f = _field(recipe, shape, seed)
        xi = 1e-3 * float(np.ptp(f))
        res = ref.Original(f, xi).compare(ref.to_bfloat16(f))
        assert res["label_violations"] > 0      # the limit is 0


def test_the_base_field_without_edits_is_not_correct():
    from repro.compress import compress_preserving_mss
    f = _field("nyx_like", (16, 16, 16), 4)
    xi = 1e-3 * float(np.ptp(f))
    art = compress_preserving_mss(f, xi)
    res = ref.Original(f, xi).compare(ref.decode_base(art.base_payload))
    assert res["label_violations"] > 0
    assert res["err_over_xi"] <= 1.0 + 1e-6


@pytest.mark.parametrize("shape", [(6, 7, 8), (12, 13)])
def test_local_violations_are_zero_exactly_when_labels_agree(shape):
    rng = np.random.default_rng(11)
    f = rng.normal(size=shape).astype(np.float32)
    orig = ref.Original(f, 1.0)
    mf, nf = (np.asarray(x) for x in ref.labels(f))
    for scale in (0.0, 1e-3, 1e-2, 0.1, 0.5):
        g = (f + scale * rng.normal(size=shape)).astype(np.float32)
        mg, ng = (np.asarray(x) for x in ref.labels(g))
        wrong = int(np.sum((mf != mg) | (nf != ng)))
        got = orig.compare(g)["label_violations"]
        assert (wrong == 0) == (got == 0), (scale, wrong, got)


def test_a_content_seed_fixes_the_values_and_the_seed_shifts_them():
    base = fields.field_pool("nyx_like", (6, 7, 8), 2, 11)
    for seed in (1, 2 ** 40 + 5):
        moved = fields.field_pool("nyx_like", (6, 7, 8), 2, seed,
                                  content_seed=11)
        for b, m in zip(base, moved):
            shifts = [(z, y, x) for z in range(6) for y in range(7)
                      for x in range(8)
                      if np.array_equal(np.roll(b, (z, y, x), (0, 1, 2)), m)]
            assert len(shifts) == 1
    again = fields.field_pool("nyx_like", (6, 7, 8), 2, 2, content_seed=11)
    assert all(np.array_equal(a, b) for a, b in zip(
        again, fields.field_pool("nyx_like", (6, 7, 8), 2, 2,
                                 content_seed=11)))
