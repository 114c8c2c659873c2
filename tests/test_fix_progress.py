"""The fix loop's progress rule and stop condition (DESIGN.md §2, "The edit
makes progress in float32" and "Why the loop ends"):

* the halving edit goes to the bound exactly where the halving would
  round back to ``g``, and is today's value everywhere else;
* a field whose loop stalled on the old rule converges;
* fields of exact-zero plateaus converge with every backend, bitwise
  alike, labels equal to the oracle's;
* artifacts of the benchmark's field kinds are byte-identical to those
  the loop made before the rule (constants from that commit).

Sharded cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
on a 1-device host they skip.
"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import (compress_preserving_mss,
                            compress_preserving_mss_batch, szlike)
from repro.compress.preserve import (PreservingCodec, compress_host,
                                     register_preserving_codec)
from repro.core import derive_edits, field_topology, fused_fix
from repro.core.backend import ReferenceBackend, _pull, halve_toward_lower
from repro.core.fixes import NOT_CONVERGED, fused_fix_batch
from repro.core.ref import verify_preservation_ref
from repro.data.fields import climate_like, nyx_like
from repro.launch.mesh import make_data_mesh

N_AVAIL = len(jax.devices())


def _old_rule(g, lower):
    return np.maximum((g + lower) * np.float32(0.5), lower)


# ---------------------------------------------------------------------------
# the edit
# ---------------------------------------------------------------------------

def test_the_stalling_pair_goes_to_its_bound():
    lower, g = np.float32(1.0000001), np.float32(1.0000002)
    assert _old_rule(g, lower) == g                  # the old rule stalls
    new = np.asarray(halve_toward_lower(jnp.float32(g), jnp.float32(lower)))
    assert new == lower and new.dtype == np.float32


def _pairs(n=200_000, seed=0):
    """Random float32 pairs g >= lower: half at every scale, half one to
    four ulps apart (where the stall lives)."""
    rng = np.random.default_rng(seed)
    lower = (rng.standard_normal(n) * 10.0 ** rng.integers(-30, 30, n)
             ).astype(np.float32)
    wide = (lower + np.abs(rng.standard_normal(n)).astype(np.float32)
            * np.abs(lower)).astype(np.float32)
    near = lower.copy()
    for _ in range(4):
        step = rng.random(n) < 0.6
        near = np.where(step, np.nextafter(near, np.float32(np.inf)), near)
    g = np.where(rng.random(n) < 0.5, wide, near).astype(np.float32)
    keep = np.isfinite(g) & (g >= lower)
    return g[keep], lower[keep]


def test_the_edit_never_rises_never_passes_its_bound_and_else_is_unchanged():
    g, lower = _pairs()
    new = np.asarray(halve_toward_lower(jnp.asarray(g), jnp.asarray(lower)))
    old = _old_rule(g, lower)
    assert np.all(new <= g) and np.all(new >= lower)
    stall = old == g
    assert stall.sum() > 1000 and (~stall).sum() > 1000
    np.testing.assert_array_equal(new[stall], lower[stall])
    # away from the stall: today's formula, to the bit
    np.testing.assert_array_equal(new[~stall].view(np.uint32),
                                  old[~stall].view(np.uint32))
    # and the edit moves every value above its bound
    assert np.all(new[g > lower] < g[g > lower])


# ---------------------------------------------------------------------------
# a field the old rule stalled on
# ---------------------------------------------------------------------------

XI = 0.5


def _stall_field():
    """Two equal maxima of f side by side; in f_hat the lower-index one
    sits one ulp above its bound, the higher-index one at its bound, so
    the old halving leaves the false maximum where it is."""
    f = np.zeros((4, 5), np.float32)
    f[1, 1] = f[1, 2] = np.float32(1.5000001)
    fh = f.copy()
    fh[1, 1] = np.float32(1.0000002)          # its bound is 1.0000001
    fh[1, 2] = np.float32(1.0000001)
    return f, fh


@dataclasses.dataclass(frozen=True)
class _OldRuleBackend(ReferenceBackend):
    """The reference stencils with the edit as it was: halving alone."""
    name: str = "old_rule"

    def fix_pass(self, g, topo, masks):
        target = ((masks.self_edit != 0)
                  | _pull(masks.demote_src != 0, masks.up_c_g)
                  | _pull(masks.promote_src != 0, masks.dn_c_f))
        old = jnp.maximum((g + topo.lower) * jnp.asarray(0.5, g.dtype),
                          topo.lower)
        g2 = jnp.where(target, old, g)
        return g2, masks.n_violations, jnp.sum(g2 != g, dtype=jnp.int32)


register_preserving_codec(PreservingCodec(
    name="stall_fixture", compress=lambda f, xi: b"STL0",
    decompress=lambda payload: _stall_field()[1], magics=(b"STL0",)))


def test_a_field_the_old_rule_stalled_on_raises_there_and_converges_now():
    f, fh = _stall_field()
    topo = field_topology(jnp.asarray(f), XI)
    g, iters, ok = fused_fix(jnp.asarray(fh), topo, backend=_OldRuleBackend())
    # the old rule moves nothing on a pass with violations: the loop
    # stops there at once instead of spinning to a cap
    assert not bool(ok) and int(iters) == 1
    with pytest.raises(RuntimeError, match="did not converge"):
        compress_host("stall_fixture", f, XI, backend=_OldRuleBackend())
    assert NOT_CONVERGED.startswith("MSz fix loops did not converge")
    for be in ("reference", "pallas", "pallas_worklist"):
        res = derive_edits(f, fh, XI, backend=be)
        assert res.converged and res.iters == 2, be
        v = verify_preservation_ref(f, res.g, XI)
        assert v["mss_preserved"] and v["bound_ok"], be
    art = compress_host("stall_fixture", f, XI)
    assert art.fix_iters == 2


# ---------------------------------------------------------------------------
# exact-zero plateaus through every backend
# ---------------------------------------------------------------------------

def _plateau(shape, zeros, seed):
    """max(|k|^-3 field - cut, 0) with ``zeros`` of it exact zeros, and
    its szlike reconstruction at ``xi = 1e-2 x range``, whose plateaus
    are ties decided by index."""
    rng = np.random.default_rng(seed)
    white = rng.standard_normal(shape)
    k2 = sum(np.fft.fftfreq(n).reshape((-1,) + (1,) * (len(shape) - 1 - ax))
             ** 2 for ax, n in enumerate(shape))
    amp = np.where(k2 > 0, np.maximum(k2, 1e-12) ** (-3.0 / 4.0), 0.0)
    v = np.fft.ifftn(np.fft.fftn(white) * amp).real
    f = np.maximum(v - np.quantile(v, zeros), 0.0).astype(np.float32)
    xi = float(np.float32(1e-2 * np.ptp(f)))
    return f, szlike.sz_decompress(szlike.sz_compress(f, xi)), xi


PLATEAUS = [((24, 40), 0.5), ((24, 40), 0.85), ((10, 12, 16), 0.6),
            ((10, 12, 16), 0.9)]


@functools.lru_cache(maxsize=None)
def _reference_run(shape, zeros):
    f, fh, xi = _plateau(shape, zeros, seed=len(shape) * 10 + int(zeros * 10))
    topo = field_topology(jnp.asarray(f), xi)
    g, it, ok = fused_fix(jnp.asarray(fh), topo, backend="reference")
    return f, fh, xi, np.asarray(g), int(it), bool(ok)


@pytest.mark.parametrize("shape,zeros", PLATEAUS)
def test_plateau_fields_converge_to_the_oracles_labels(shape, zeros):
    f, fh, xi, g, it, ok = _reference_run(shape, zeros)
    assert 0.5 <= np.mean(f == 0) <= 0.9 + 1e-3
    assert ok and it > 1
    v = verify_preservation_ref(f, g, xi)
    assert v["mss_preserved"] and v["bound_ok"], v


@pytest.mark.parametrize("backend", ["pallas", "pallas_worklist", "sharded2",
                                     "sharded4"])
@pytest.mark.parametrize("shape,zeros", PLATEAUS)
def test_plateau_fields_are_bitwise_alike_across_backends(shape, zeros,
                                                          backend):
    f, fh, xi, g_ref, it_ref, _ = _reference_run(shape, zeros)
    mesh = None
    if backend.startswith("sharded"):
        n_dev = int(backend[len("sharded"):])
        if N_AVAIL < n_dev:
            pytest.skip(f"needs {n_dev} devices, have {N_AVAIL} (run under "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        mesh, backend = make_data_mesh(n_dev), "sharded"
    topo = field_topology(jnp.asarray(f), xi)
    g, it, ok = fused_fix(jnp.asarray(fh), topo, backend=backend, mesh=mesh)
    assert bool(ok) and int(it) == it_ref
    np.testing.assert_array_equal(np.asarray(g).view(np.uint32),
                                  g_ref.view(np.uint32))


def test_a_batch_of_plateau_fields_matches_its_solo_runs():
    runs = [_reference_run((24, 40), z) for z in (0.5, 0.85)]
    topos = [field_topology(jnp.asarray(r[0]), r[2]) for r in runs]
    topo_b = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *topos)
    fh_b = jnp.asarray(np.stack([r[1] for r in runs]))
    for batching in ("fused", "compact"):
        g, it, ok = fused_fix_batch(fh_b, topo_b, backend="reference",
                                    batching=batching, compact_every=3)
        assert np.all(np.asarray(ok))
        for i, r in enumerate(runs):
            np.testing.assert_array_equal(np.asarray(g)[i], r[3])
            assert int(np.asarray(it)[i]) == r[4]


# ---------------------------------------------------------------------------
# byte identity on the benchmark's field kinds
# ---------------------------------------------------------------------------

def _sha(art):
    return hashlib.sha256(art.base_payload + art.edit_payload).hexdigest()


#: (sha256 of base + edit payload, fix_iters), from the commit before the
#: progress rule and the stop condition
SOLO = {
    ("nyx_like", (12, 12, 12), 1):
        ("1adc27c2eba4edfec9be6b402df0fb9b02e99c91fd047fb653e315ede8f81137", 3),
    ("nyx_like", (64, 8, 8), 2):
        ("a7d1b2c80b78e75111c63e300f7005d8732d31c14e8422778413879a1d1180d2", 5),
    ("climate_like", (16, 32), 3):
        ("86d88d2ac04e58e39f74dff0178c4deb8783046ce95005799c8c7eb25c68010f", 2),
    ("climate_like", (40, 64), 4):
        ("a84d4ab3b708628e366680aaa4c5a465998ab5eb269cffd35a7298cade8b2170", 2),
}
BATCH = [
    ("2e2d6497d1cf0a5b29a2ac11f93d5f5c4afd9528ffe9c8074d1632ecd51fda5d", 2),
    ("180eafbf92ccd9e99fafe3bfd1ad96d0127391a26fe39c90beddf3872d5af963", 2),
    ("6b77ffd816bccdc3666654f8cb94dbb1d5e999b7966f3ad3527a02fb7846e514", 2),
]
MAKERS = {"nyx_like": nyx_like, "climate_like": climate_like}


@pytest.mark.parametrize("key", sorted(SOLO, key=str),
                         ids=lambda k: f"{k[0]}-{'x'.join(map(str, k[1]))}")
def test_artifacts_of_todays_field_kinds_are_byte_identical(key):
    name, shape, seed = key
    f = MAKERS[name](shape, seed).astype(np.float32)
    art = compress_preserving_mss(f, 1e-3 * float(np.ptp(f)))
    assert (_sha(art), art.fix_iters) == SOLO[key]


def test_batched_artifacts_of_climate_slices_are_byte_identical():
    fs = [climate_like((16, 32), s).astype(np.float32) for s in (5, 6, 7)]
    arts = compress_preserving_mss_batch(
        fs, [1e-3 * float(np.ptp(f)) for f in fs])
    assert [(_sha(a), a.fix_iters) for a in arts] == BATCH
