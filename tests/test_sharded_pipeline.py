"""Sharded patch-stream execution: shard/unshard equivalence + degradation.

The data-parallel path (ExecutionPlan.shards > 1 -> shard_map over the 1-D
patch mesh) must be numerically indistinguishable from the single-device
path for every geometry, including frames whose patch count does not divide
the shard count. The multi-device cases need ``jax.device_count() >= 2`` and
skip on one device; ``bench/tests/test_bench_shard4.py`` runs this file in a
subprocess under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so
they run with the tier-1 suite. On a single-device host the remaining cases
exercise the transparent degrade path.
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import ExecutionPlan, SREngine
from repro.core.patching import shard_slices
from repro.core.pipeline import edge_selective_sr, sharded_forward
from repro.data.synthetic import degrade, random_image
from repro.launch.mesh import make_patch_mesh
from repro.models.essr import ESSRConfig, init_essr

MULTI = jax.device_count() >= 2
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


# -- shard_slices ------------------------------------------------------------

def test_shard_slices_cover_and_balance():
    for n, shards in [(10, 4), (9, 2), (8, 8), (3, 5), (0, 2), (7, 1)]:
        sl = shard_slices(n, shards)
        assert len(sl) == shards
        idx = np.concatenate([np.arange(n)[s] for s in sl])
        assert idx.tolist() == list(range(n))          # exact cover, in order
        sizes = [len(np.arange(n)[s]) for s in sl]
        assert max(sizes) - min(sizes) <= 1            # balanced
    with pytest.raises(ValueError):
        shard_slices(4, 0)


# -- mesh helpers ------------------------------------------------------------

def test_make_patch_mesh_validates():
    m = make_patch_mesh(1)
    assert m.axis_names == ("shard",) and m.size == 1
    with pytest.raises(ValueError):
        make_patch_mesh(0)
    with pytest.raises(ValueError):
        make_patch_mesh(jax.device_count() + 1)


def test_patch_batch_spec_requires_1d_mesh():
    from repro.distributed.sharding import patch_batch_spec
    assert patch_batch_spec(make_patch_mesh(1)) == \
        jax.sharding.PartitionSpec("shard")
    if jax.device_count() >= 4:
        bad = jax.make_mesh((2, 2), ("a", "b"))
        with pytest.raises(ValueError):
            patch_batch_spec(bad)


# -- equivalence: sharded vs single-device -----------------------------------

CFG = ESSRConfig(scale=2)


def _frame(seed, h, w, scale=2):
    return degrade(jnp.asarray(random_image(seed, h * scale, w * scale)),
                   scale)


@needs_devices
def test_sharded_forward_matches_single_device():
    """Raw per-subnet batch forward, padded non-divisible batch included."""
    params = init_essr(jax.random.PRNGKey(0), CFG)
    mesh = make_patch_mesh(min(4, jax.device_count()))
    for n in (4, 7):                    # 7 does not divide the mesh size
        patches = jax.random.uniform(jax.random.PRNGKey(n), (n, 32, 32, 3))
        for width in (0, 27, 54):
            got = sharded_forward(params, patches, CFG, width, mesh=mesh)
            from repro.core.pipeline import resolve_backend
            want = resolve_backend("ref")(params, patches, CFG, width)
            assert got.shape == (n, 64, 64, 3)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5)


@needs_devices
@pytest.mark.parametrize("patch,overlap,scale,hw", [
    (32, 2, 2, (64, 64)),     # 9 patches: does not divide 2 or 4 shards
    (32, 2, 2, (64, 96)),     # 12 patches
    (16, 4, 2, (48, 40)),     # non-default patch/overlap
    (32, 2, 4, (64, 64)),     # paper scale
])
def test_sharded_pipeline_allclose(patch, overlap, scale, hw):
    """Full edge-selective pipeline through the mesh == single device,
    threshold routing included, across patch/overlap/scale sweeps."""
    cfg = ESSRConfig(scale=scale)
    params = init_essr(jax.random.PRNGKey(1), cfg)
    # half smooth gradient / half noise: exercises all three routing classes
    # (random_image's stroke generator rejects sub-32px edge tiles)
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, hw[0]), jnp.linspace(0, 1, hw[1]),
                          indexing="ij")
    smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
    noise = jax.random.uniform(jax.random.PRNGKey(2), (hw[0], hw[1], 3))
    frame = jnp.where((yy < 0.5)[..., None], smooth, noise)
    mesh = make_patch_mesh(min(4, jax.device_count()))
    kw = dict(patch=patch, overlap=overlap)
    want = edge_selective_sr(params, frame, cfg, **kw)
    got = edge_selective_sr(params, frame, cfg, mesh=mesh, **kw)
    assert got.ids.tolist() == want.ids.tolist()
    np.testing.assert_allclose(np.asarray(got.image), np.asarray(want.image),
                               atol=1e-5)


@needs_devices
def test_engine_shards_allclose_and_surfaces_fields():
    """Acceptance criterion: ExecutionPlan(shards=4) frames are allclose to
    the single-device path; streamed FrameResults carry per-shard fields."""
    single = SREngine.from_config(CFG, seed=3)
    shard4 = SREngine.from_config(CFG, seed=3, plan=ExecutionPlan(shards=4))
    frame = _frame(9, 64, 64)
    r1, r4 = single.upscale(frame), shard4.upscale(frame)
    assert r4.shards == 4
    np.testing.assert_allclose(np.asarray(r1.image), np.asarray(r4.image),
                               atol=1e-5)
    res = shard4.serve(frame)
    assert len(res.shard_counts) == 4
    assert len(res.shard_thresholds) == 4
    assert res.shard_deadline_missed == (False,) * 4     # no deadline set
    assert sum(sum(c) for c in res.shard_counts) == res.n_patches
    s = shard4.summary()
    assert s["shards"] == 4 and s["shard_deadline_misses"] == [0, 0, 0, 0]


def test_engine_degrades_transparently_on_few_devices():
    """shards > device_count keeps per-shard routing control but dispatches
    on the devices that exist — numerics identical, a warning tells the
    operator."""
    want_warn = jax.device_count() < 8
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = SREngine.from_config(CFG, seed=5, plan=ExecutionPlan(shards=8))
    assert any("device" in str(x.message) for x in w) == want_warn
    frame = _frame(11, 64, 64)
    r = eng.upscale(frame)
    ref = SREngine.from_config(CFG, seed=5).upscale(frame)
    np.testing.assert_allclose(np.asarray(r.image), np.asarray(ref.image),
                               atol=1e-5)
    res = eng.serve(frame)                 # 9 patches over 8 logical shards
    assert len(res.shard_counts) == 8      # routing stays 8-way sharded
    assert res.n_patches == 9


def test_straggler_demotion_drops_next_frame_c54_through_engine():
    """Engine-level satellite criterion: with an impossible deadline the
    overloaded shard is demoted and its next-frame C54 count drops."""
    from repro.core.adaptive import SwitchingConfig
    # top strip = noise (C54 demand), bottom strip = flat (cheap): shard 0
    # owns the heavy raster rows
    noise = jax.random.uniform(jax.random.PRNGKey(0), (32, 64, 3))
    flat = jnp.full((32, 64, 3), 0.5)
    frame = jnp.concatenate([noise, flat], axis=0)
    eng = SREngine.from_config(
        CFG, seed=0, plan=ExecutionPlan(shards=3), deadline_s=1e-9,
        switching=SwitchingConfig(c54_per_sec_budget=10 ** 9,
                                  frame_high=10 ** 6, frame_low=0))
    first = eng.serve(frame)
    assert first.deadline_missed
    assert any(first.shard_deadline_missed)
    heavy = int(np.argmax([c[2] for c in first.shard_counts]))
    assert first.shard_deadline_missed[heavy]
    t_first = first.shard_thresholds[heavy]
    second = eng.serve(frame)
    assert second.shard_thresholds[heavy] > t_first     # keeps rising
    # demotion holds or shrinks the straggler's C54 share, never grows it
    assert second.shard_counts[heavy][2] <= first.shard_counts[heavy][2]
    # run until the demotions bite: C54 must eventually drop strictly
    for _ in range(30):
        cur = eng.serve(frame)
        if cur.shard_counts[heavy][2] < first.shard_counts[heavy][2]:
            break
    else:
        pytest.fail("straggler demotion never reduced the shard's C54 count")
