"""Slim-overlap patching + overlap-average fusion (Sec. IV-I)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.patching import (extract_patches, extract_patches_loop,
                                 fuse_patches_average,
                                 fuse_patches_average_loop, get_geometry,
                                 grid_starts, overlap_mac_overhead)


@settings(max_examples=30, deadline=None)
@given(st.integers(33, 200), st.integers(8, 48), st.integers(0, 6))
def test_grid_covers_every_pixel(size, patch, overlap):
    if overlap >= patch or patch > size:
        return
    starts = grid_starts(size, patch, overlap)
    covered = np.zeros(size, bool)
    for s in starts:
        covered[s:s + patch] = True
        assert s + patch <= size
    assert covered.all()


def test_extract_fuse_identity():
    """overlap+average of the identity model reconstructs the frame exactly."""
    img = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (64, 64, 3)).astype(np.float32))
    patches, pos = extract_patches(img, patch=32, overlap=2)
    out = fuse_patches_average(patches, pos, 1, (64, 64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(img), atol=1e-6)


def test_fuse_averages_disagreeing_patches():
    img = jnp.zeros((34, 32, 1))                 # tiles only along y: 2 patches
    patches, pos = extract_patches(img, patch=32, overlap=30)
    assert patches.shape[0] == 2
    patches = patches.at[0].set(0.0).at[1].set(1.0)
    out = fuse_patches_average(patches, pos, 1, (34, 32))
    # overlapping band (rows 2..31) must average to 0.5
    assert abs(float(out[17, 10, 0]) - 0.5) < 1e-6
    assert abs(float(out[0, 10, 0]) - 0.0) < 1e-6      # only patch 0
    assert abs(float(out[33, 10, 0]) - 1.0) < 1e-6     # only patch 1


# -- vectorized paths vs the seed loop oracles -------------------------------

SWEEP = [  # (h, w, patch, overlap, scale) incl. odd frame sizes
    (64, 64, 32, 2, 4), (62, 62, 32, 2, 2), (47, 53, 16, 3, 2),
    (34, 32, 32, 30, 1), (33, 95, 32, 2, 4), (40, 40, 8, 0, 2),
]


@pytest.mark.parametrize("h,w,patch,overlap,scale", SWEEP)
def test_vectorized_extract_matches_loop(h, w, patch, overlap, scale):
    img = jnp.asarray(np.random.default_rng(1).uniform(
        0, 1, (h, w, 3)).astype(np.float32))
    pv, posv = extract_patches(img, patch, overlap)
    pl, posl = extract_patches_loop(img, patch, overlap)
    assert np.array_equal(posv, posl)
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(pl))


@pytest.mark.parametrize("h,w,patch,overlap,scale", SWEEP)
def test_geometry_extract_matches_loop(h, w, patch, overlap, scale):
    """The serving path's `PatchGeometry.extract` is bit-exact against the
    seed's per-patch slices, in the same raster order."""
    img = jnp.asarray(np.random.default_rng(1).uniform(
        0, 1, (h, w, 3)).astype(np.float32))
    g = get_geometry(h, w, patch, overlap, scale)
    pl, posl = extract_patches_loop(img, patch, overlap)
    assert np.array_equal(g.pos, posl)
    np.testing.assert_array_equal(np.asarray(g.extract(img)), np.asarray(pl))


CELL_GEOMETRIES = [(1080, 1920, 4), (2160, 3840, 2)]   # the benchmark's cells


@pytest.mark.parametrize("h,w,scale", CELL_GEOMETRIES)
def test_cell_geometry_extract_matches_numpy_slices(h, w, scale):
    """At the cells' frame sizes, a few patches (first, interior, the
    clamped last row and column, the last) equal plain numpy slices."""
    img = np.random.default_rng(4).uniform(0, 1, (h, w, 3)).astype(np.float32)
    g = get_geometry(h, w, 32, 2, scale)
    n_y, n_x = g.grid_yx
    patches = np.asarray(g.extract(jnp.asarray(img)))
    assert patches.shape == (n_y * n_x, 32, 32, 3)
    assert g.pos[-1].tolist() == [h - 32, w - 32]   # clamped, not stride-30
    picks = [0, 1, n_x - 1, n_x * (n_y // 2) + n_x // 2,
             (n_y - 1) * n_x, (n_y - 1) * n_x + 5, 7 * n_x + n_x - 1,
             n_y * n_x - 1]
    for i in picks:
        y, x = g.pos[i]
        np.testing.assert_array_equal(patches[i], img[y:y + 32, x:x + 32])


@pytest.mark.parametrize("h,w", [(64, 94), (20, 50)])     # no pad; pad
def test_geometry_extract_under_vmap(h, w):
    """`jax.vmap(geometry.extract)`, as the multi-stream fused graph calls
    it, extracts each frame of the batch exactly."""
    frames = jnp.asarray(np.random.default_rng(5).uniform(
        0, 1, (3, h, w, 3)).astype(np.float32))
    g = get_geometry(h, w, 32, 2, 2)
    out = np.asarray(jax.jit(jax.vmap(g.extract))(frames))
    assert out.shape == (3, g.n, 32, 32, 3)
    for k in range(3):
        np.testing.assert_array_equal(out[k], np.asarray(g.extract(frames[k])))
        np.testing.assert_array_equal(
            out[k], np.asarray(extract_patches(frames[k], 32, 2)[0]))


@pytest.mark.parametrize("h,w,patch,overlap,scale", SWEEP)
def test_vectorized_fuse_matches_loop(h, w, patch, overlap, scale):
    g = get_geometry(h, w, patch, overlap, scale)
    ps = patch * scale
    sr = jnp.asarray(np.random.default_rng(2).uniform(
        0, 1, (g.n, ps, ps, 3)).astype(np.float32))
    ref = fuse_patches_average_loop(sr, g.pos, scale, (h * scale, w * scale))
    np.testing.assert_allclose(np.asarray(g.fuse_average(sr)),
                               np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fuse_patches_average(sr, g.pos, scale,
                                        (h * scale, w * scale))),
        np.asarray(ref), atol=1e-5)


def _numpy_overlap_average(sr, g):
    """Overlap-and-average in numpy, apart from the program: each column of
    patches slice-added along y into a zero strip, the strips slice-added
    along x into a zero plane (the program's order, so the comparison can
    be exact), then divided by the count of patches over each pixel."""
    n_y, n_x = g.grid_yx
    s, ps = g.scale, g.patch * g.scale
    hh, wh = g.hw[0] * s, g.hw[1] * s
    ys = np.unique(g.pos[:, 0]) * s
    xs = np.unique(g.pos[:, 1]) * s
    plane = np.zeros((hh, wh, sr.shape[-1]), np.float32)
    cnt = np.zeros((hh, wh, 1), np.float32)
    for j, x in enumerate(xs):
        strip = np.zeros((hh, ps, sr.shape[-1]), np.float32)
        for i, y in enumerate(ys):
            strip[y:y + ps] += sr[i * n_x + j]
            cnt[y:y + ps, x:x + ps] += 1
        plane[:, x:x + ps] += strip
    return plane / cnt


@pytest.mark.parametrize("h,w,scale", CELL_GEOMETRIES)
def test_cell_geometry_fuse_matches_numpy_bit_exact(h, w, scale):
    """At the cells' frame sizes the slice fold gives, bit for bit, the
    overlap-average of plain numpy slice-adds."""
    g = get_geometry(h, w, 32, 2, scale)
    assert g.fold == "slices"
    ps = 32 * scale
    sr = np.random.default_rng(6).uniform(
        0, 1, (g.n, ps, ps, 3)).astype(np.float32)
    got = np.asarray(g.fuse_average(jnp.asarray(sr)))
    np.testing.assert_array_equal(got, _numpy_overlap_average(sr, g))


FOLDS = list(zip(SWEEP, ["slices", "scatter", "slices", "scatter",
                         "scatter", "slices"])) + [
    ((63, 63, 32, 2, 2), "scatter"),    # pixel 31 lies in three patches
    ((40, 40, 32, 2, 2), "scatter"),    # under two patches a side
]


@pytest.mark.parametrize("geometry,fold", FOLDS)
def test_fold_choice_and_result(geometry, fold):
    """The geometry picks its fold once; either fold matches the seed loop,
    and the standalone `fuse_patches_average` takes the same fold."""
    h, w, patch, overlap, scale = geometry
    g = get_geometry(h, w, patch, overlap, scale)
    assert g.fold == fold
    ps = patch * scale
    sr = jnp.asarray(np.random.default_rng(7).uniform(
        0, 1, (g.n, ps, ps, 3)).astype(np.float32))
    got = np.asarray(g.fuse_average(sr))
    ref = fuse_patches_average_loop(sr, g.pos, scale, (h * scale, w * scale))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=0, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(fuse_patches_average(sr, g.pos, scale,
                                        (h * scale, w * scale))), got)


@pytest.mark.parametrize("h,w", [(64, 94), (60, 90)])     # slices; scatter
def test_geometry_fuse_under_vmap(h, w):
    """`jax.vmap(geometry.fuse_average)`, as the multi-stream fused graph
    calls it, fuses each frame of the batch as a lone call does."""
    g = get_geometry(h, w, 32, 2, 2)
    sr = jnp.asarray(np.random.default_rng(8).uniform(
        0, 1, (3, g.n, 64, 64, 3)).astype(np.float32))
    out = np.asarray(jax.jit(jax.vmap(g.fuse_average))(sr))
    assert out.shape == (3, 2 * h, 2 * w, 3)
    for k in range(3):
        np.testing.assert_array_equal(out[k], np.asarray(g.fuse_average(sr[k])))


def test_fuse_average_arbitrary_positions():
    """Non-cartesian position lists take the flat-scatter fallback."""
    pos = np.array([(0, 0), (2, 5)], dtype=np.int64)   # not a product grid
    sr = jnp.ones((2, 8, 8, 1))
    out = fuse_patches_average(sr, pos, 1, (10, 13))
    ref = fuse_patches_average_loop(sr, pos, 1, (10, 13))
    covered = ~np.isnan(np.asarray(ref))
    np.testing.assert_allclose(np.asarray(out)[covered],
                               np.asarray(ref)[covered], atol=1e-6)


def test_small_frame_reflect_pad():
    """Frames smaller than the patch are reflect-padded, then cropped back
    (the seed crashed in lax.dynamic_slice)."""
    img = jnp.asarray(np.random.default_rng(3).uniform(
        0, 1, (20, 24, 3)).astype(np.float32))
    patches, pos = extract_patches(img, patch=32, overlap=2)
    assert patches.shape == (1, 32, 32, 3) and pos.tolist() == [[0, 0]]
    # identity model round-trip still reconstructs the original exactly
    out = fuse_patches_average(patches, pos, 1, (20, 24))
    np.testing.assert_allclose(np.asarray(out), np.asarray(img), atol=1e-6)
    g = get_geometry(20, 24, 32, 2, 2)
    fused = g.fuse_average(jnp.repeat(jnp.repeat(g.extract(img), 2, 1), 2, 2))
    assert fused.shape == (40, 48, 3)


def test_geometry_cache_hits():
    a = get_geometry(64, 64, 32, 2, 4)
    assert get_geometry(64, 64, 32, 2, 4) is a     # LRU: zero per-frame setup
    assert get_geometry(64, 64, 32, 2, 2) is not a


def test_paper_mac_overhead_114_percent():
    # Table IV: 8-px HR overlap (2-px LR at x4) -> 114% MACs
    assert abs(overlap_mac_overhead(32, 2) - 1.138) < 0.01


def test_positions_scale_to_hr():
    img = jnp.zeros((62, 62, 3))
    patches, pos = extract_patches(img, patch=32, overlap=2)
    assert patches.shape[0] == len(pos) == 4
    assert pos[-1].tolist() == [30, 30]
