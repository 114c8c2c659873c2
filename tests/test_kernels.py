"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (field_topology, mss_labels, self_code, steepest_dirs)
from repro.kernels import ref as kref
from repro.kernels.extrema import extrema_masks_pallas
from repro.kernels.fixpass import fix_pass_pallas
from repro.kernels.lorenzo import lorenzo_quant_pallas

SHAPES_3D = [(4, 5, 6), (6, 8, 8), (3, 16, 16), (8, 4, 12)]
SHAPES_2D = [(5, 7), (9, 11), (4, 16)]
SHAPES = SHAPES_3D + SHAPES_2D


def _setup(shape, seed=0, xi=0.3, dtype=np.float32):
    rng = np.random.default_rng(seed)
    f = rng.normal(size=shape).astype(dtype)
    g = (f + rng.uniform(-xi, xi, size=shape)).astype(dtype)
    Mf, mf = mss_labels(jnp.asarray(f))
    upf, dnf = steepest_dirs(jnp.asarray(f))
    sc = self_code(len(shape))
    return (jnp.asarray(f), jnp.asarray(g), Mf, mf,
            (upf == sc), (dnf == sc), upf, dnf)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("seed", [0, 7])
def test_extrema_kernel_matches_ref(shape, seed):
    f, g, Mf, mf, maxf, minf, _, dnf = _setup(shape, seed)
    got = extrema_masks_pallas(g, Mf, mf, maxf.astype(jnp.int32),
                               minf.astype(jnp.int32), interpret=True)
    want = kref.extrema_masks_ref(g, Mf, mf, maxf, minf)
    for a, b, name in zip(got, want,
                          ["up_c", "dn_c", "self", "demote", "promote"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"mismatch in {name}")


@pytest.mark.parametrize("shape", [SHAPES_3D[0], SHAPES_2D[0]])
def test_extrema_kernel_dtype_sweep(shape):
    # f32 and f64 fields must classify identically for integer outputs
    for dtype in (np.float32, np.float64):
        f, g, Mf, mf, maxf, minf, _, dnf = _setup(shape, 3, dtype=dtype)
        got = extrema_masks_pallas(g, Mf, mf, maxf.astype(jnp.int32),
                                   minf.astype(jnp.int32), interpret=True)
        want = kref.extrema_masks_ref(g, Mf, mf, maxf, minf)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


@pytest.mark.parametrize("shape", SHAPES_3D + SHAPES_2D[:1])
@pytest.mark.parametrize("seed", [1, 11])
def test_fixpass_kernel_matches_ref(shape, seed):
    f, g, Mf, mf, maxf, minf, upf, dnf = _setup(shape, seed)
    xi = 0.3
    lower = f - xi
    up_c, dn_c, selfe, dem, pro = kref.extrema_masks_ref(g, Mf, mf, maxf, minf)
    g2k, violk, tgtk = fix_pass_pallas(g, lower, selfe, dem, pro, up_c, dnf,
                                       interpret=True)
    g2r, violr = kref.fix_pass_ref(g, lower, selfe, dem, pro, up_c, dnf)
    np.testing.assert_array_equal(np.asarray(g2k), np.asarray(g2r))
    assert int(jnp.sum(violk)) == int(violr)
    # per-slab target counts (the dirty-slab bitmap input): one count per
    # slab, consistent with where the pass actually edited g
    assert tgtk.shape == (g.shape[0],)
    edited = np.any(np.asarray(g2k) != np.asarray(g),
                    axis=tuple(range(1, g.ndim)))
    assert np.all((np.asarray(tgtk) > 0) >= edited)


@pytest.mark.parametrize("shape", SHAPES_3D)
@pytest.mark.parametrize("step", [0.01, 0.2])
def test_lorenzo_kernel_matches_ref(shape, step):
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    got = lorenzo_quant_pallas(f, step, interpret=True)
    want = kref.lorenzo_quant_ref(f, step)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_kernel_placement_matches_full():
    """Global-coordinate tiling: running the extrema kernel on an interior
    z-tile (slab_lo/n_slabs_total) must reproduce the full-field outputs on
    every slab whose 1-slab halo lies inside the tile."""
    shape = (9, 5, 6)
    f, g, Mf, mf, maxf, minf, _, dnf = _setup(shape, 4)
    full = extrema_masks_pallas(g, Mf, mf, maxf.astype(jnp.int32),
                                minf.astype(jnp.int32), interpret=True)
    a, b = 2, 8                     # tile [2, 8); interior slabs [3, 7)
    tile = extrema_masks_pallas(
        g[a:b], Mf[a:b], mf[a:b],
        maxf[a:b].astype(jnp.int32), minf[a:b].astype(jnp.int32),
        interpret=True, slab_lo=a, n_slabs_total=shape[0])
    for got, want, name in zip(tile, full,
                               ["up_c", "dn_c", "self", "demote", "promote"]):
        np.testing.assert_array_equal(
            np.asarray(got)[1:-1], np.asarray(want)[a + 1:b - 1],
            err_msg=f"tiled mismatch in {name}")


def test_kernel_fix_loop_end_to_end():
    """Drive the fused fix loop entirely through the Pallas kernels and
    check it reaches the same fixpoint as the reference-backend driver."""
    from repro.core import derive_edits
    shape = (5, 6, 7)
    rng = np.random.default_rng(2)
    f = rng.normal(size=shape).astype(np.float32)
    xi = 0.25
    fh = (f + rng.uniform(-xi, xi, size=shape) * 0.99).astype(np.float32)
    Mf, mf = mss_labels(jnp.asarray(f))
    upf, dnf = steepest_dirs(jnp.asarray(f))
    sc = self_code(len(shape))
    maxf, minf = (upf == sc).astype(jnp.int32), (dnf == sc).astype(jnp.int32)
    lower = jnp.asarray(f) - xi

    g = jnp.asarray(fh)
    for _ in range(200):
        up_c, dn_c, selfe, dem, pro = extrema_masks_pallas(
            g, Mf, mf, maxf, minf, interpret=True)
        g2, viol, _ = fix_pass_pallas(g, lower, selfe, dem, pro, up_c, dnf,
                                      interpret=True)
        if int(jnp.sum(viol)) == 0:
            break
        g = g2
    res = derive_edits(f, fh, xi, mode="fused", backend="reference")
    np.testing.assert_allclose(np.asarray(g), res.g, rtol=0, atol=0)


@pytest.mark.parametrize("chunk", [1, 3, 4])
@pytest.mark.parametrize("ascending", [True, False])
def test_steepest_dirs_chunked_matches_whole_field(chunk, ascending):
    """Z-chunked direction codes (the large-field path of
    grid.steepest_dirs) equal the whole-field stencil bit for bit, ties
    and domain edges included."""
    from repro.core import grid
    rng = np.random.default_rng(chunk)
    f = jnp.asarray(rng.integers(0, 4, size=(10, 6, 7)).astype(np.float32))
    lin = grid.linear_index(f.shape)
    want = grid._codes(f, lin, ascending=ascending)
    got = grid._codes_chunked(f, lin, chunk, ascending=ascending)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# 2D fields taller than one row block (2048 rows up to 128 columns,
# 1024 up to 256): block seams, the 8-row halo blocks, and a partial
# last block
SHAPES_2D_BLOCKED = [(2100, 9), (2059, 130)]


@pytest.mark.parametrize("shape", SHAPES_2D_BLOCKED)
def test_extrema_kernel_row_blocks_match_ref(shape):
    f, g, Mf, mf, maxf, minf, _, dnf = _setup(shape, 5)
    got = extrema_masks_pallas(g, Mf, mf, maxf.astype(jnp.int32),
                               minf.astype(jnp.int32), interpret=True)
    want = kref.extrema_masks_ref(g, Mf, mf, maxf, minf)
    for a, b, name in zip(got, want,
                          ["up_c", "dn_c", "self", "demote", "promote"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"mismatch in {name}")


@pytest.mark.parametrize("shape", SHAPES_2D_BLOCKED)
def test_fixpass_kernel_row_blocks_match_ref(shape):
    f, g, Mf, mf, maxf, minf, upf, dnf = _setup(shape, 6)
    lower = f - 0.3
    up_c, dn_c, selfe, dem, pro = kref.extrema_masks_ref(g, Mf, mf, maxf, minf)
    g2k, violk, tgtk = fix_pass_pallas(g, lower, selfe, dem, pro, up_c, dnf,
                                       interpret=True)
    g2r, violr = kref.fix_pass_ref(g, lower, selfe, dem, pro, up_c, dnf)
    np.testing.assert_array_equal(np.asarray(g2k), np.asarray(g2r))
    # per-row counts: sources are the masks' row sums, targets the rows
    # the pass edited
    np.testing.assert_array_equal(
        np.asarray(violk),
        np.asarray(selfe + dem + pro).sum(axis=1))
    assert int(jnp.sum(violk)) == int(violr)
    edited = np.asarray(g2k) != np.asarray(g)
    np.testing.assert_array_equal(np.asarray(tgtk) > 0, edited.any(axis=1))


@pytest.mark.parametrize("shape", SHAPES_2D_BLOCKED + SHAPES_2D)
@pytest.mark.parametrize("step", [0.01, 0.2])
def test_lorenzo_kernel_2d_matches_ref(shape, step):
    rng = np.random.default_rng(8)
    f = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    got = lorenzo_quant_pallas(f, step, interpret=True)
    want = kref.lorenzo_quant_ref(f, step)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_2d_row_placement_matches_full():
    """A 2D row tile placed with slab_lo/n_slabs_total, cut into row
    blocks, reproduces the full field on rows whose halo is inside."""
    shape = (2100, 13)
    f, g, Mf, mf, maxf, minf, _, dnf = _setup(shape, 9)
    full = extrema_masks_pallas(g, Mf, mf, maxf.astype(jnp.int32),
                                minf.astype(jnp.int32), interpret=True)
    a, b = 3, 2097
    tile = extrema_masks_pallas(
        g[a:b], Mf[a:b], mf[a:b],
        maxf[a:b].astype(jnp.int32), minf[a:b].astype(jnp.int32),
        interpret=True, slab_lo=a, n_slabs_total=shape[0])
    for got, want, name in zip(tile, full,
                               ["up_c", "dn_c", "self", "demote", "promote"]):
        np.testing.assert_array_equal(
            np.asarray(got)[1:-1], np.asarray(want)[a + 1:b - 1],
            err_msg=f"tiled mismatch in {name}")


def test_vmem_request_covers_blocks_and_refuses_oversized_planes():
    """Every stencil kernel states a scoped-VMEM limit that holds its
    double-buffered blocks and temporaries; a plane past the chip's
    VMEM raises instead of switching backends."""
    from repro.kernels import extrema as kx
    lay = kx.slab_layout((4, 512, 512))
    nbytes = lay.block_bytes(4, halo=3, center=7)
    temps = kx.TEMP_PLANES["extrema", 3] * lay.plane_bytes(4)
    limit = kx.vmem_params(nbytes, temps, "extrema").vmem_limit_bytes
    assert 2 * nbytes + temps <= limit <= kx.VMEM_REQUEST_CAP
    big = kx.slab_layout((4, 2048, 2048))
    with pytest.raises(ValueError, match="too large"):
        kx.vmem_params(big.block_bytes(4, halo=3, center=7),
                       kx.TEMP_PLANES["extrema", 3] * big.plane_bytes(4),
                       "extrema")
    # the lowered kernel refuses the oversized plane before Mosaic sees it
    g = jax.ShapeDtypeStruct((2, 2048, 2048), jnp.float32)
    i = jax.ShapeDtypeStruct((2, 2048, 2048), jnp.int32)
    with pytest.raises(ValueError, match="too large for the slab kernels"):
        jax.eval_shape(lambda g, M, m, a, b: extrema_masks_pallas(
            g, M, m, a, b, interpret=False), g, i, i, i, i)
    # 2D fields never hit the limit: rows per block shrink with width
    assert kx.slab_layout((1800, 3600)).P % 8 == 0
    assert kx.slab_layout((5, 7)).P == 5
