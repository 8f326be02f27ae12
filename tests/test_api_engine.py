"""SREngine facade, ExecutionPlan, bucket padding, and deprecation shims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionPlan, FrameResult, SREngine
from repro.core import subnet_policy as sp
from repro.core.adaptive import AdaptiveSwitcher, SwitchingConfig
from repro.core.pipeline import (DEFAULT_BUCKETS, _bucket, edge_selective_sr,
                                 sr_all_patches)
from repro.data.synthetic import degrade, random_image
from repro.models.essr import ESSRConfig, init_essr


CFG = ESSRConfig(scale=2)


@pytest.fixture(scope="module")
def engine():
    return SREngine.from_config(CFG, plan=ExecutionPlan(t1=8, t2=40))


@pytest.fixture(scope="module")
def lr_frame():
    hr = jnp.asarray(random_image(3, 128, 128))
    return degrade(hr, 2)          # 64x64 LR -> 9 patches at patch=32/overlap=2


# -- ExecutionPlan -----------------------------------------------------------

def test_plan_validation():
    with pytest.raises(ValueError):
        ExecutionPlan(subnet_policy="nope")
    with pytest.raises(ValueError):
        ExecutionPlan(patch=16, overlap=16)
    with pytest.raises(ValueError):
        ExecutionPlan(t1=40, t2=8)
    with pytest.raises(ValueError):
        ExecutionPlan(buckets=())
    with pytest.raises(ValueError):
        ExecutionPlan(buckets=(128, 8))


def test_plan_validation_error_format():
    """Every rule — per-field and cross-field — raises the one error shape:
    field name, got-value, allowed set."""
    cases = [
        (dict(subnet_policy="nope"), "ExecutionPlan.subnet_policy='nope'"),
        (dict(patch=16, overlap=16), "ExecutionPlan.overlap=16"),
        (dict(t1=40, t2=8), "ExecutionPlan.t2=8"),
        (dict(buckets=()), "ExecutionPlan.buckets=()"),
        (dict(inflight=2), "ExecutionPlan.inflight=2"),
        (dict(streams=2), "ExecutionPlan.streams=2"),
        (dict(streams=0, dispatch="fused"), "ExecutionPlan.streams=0"),
        (dict(streams=2, dispatch="fused", subnet_policy="all_c54"),
         "ExecutionPlan.streams=2"),
        (dict(streams=2, dispatch="fused", stream_shares=(1.0,)),
         "ExecutionPlan.stream_shares=(1.0,)"),
        (dict(stream_shares=(0.0,)), "ExecutionPlan.stream_shares=(0.0,)"),
    ]
    for kwargs, prefix in cases:
        with pytest.raises(ValueError, match=r"allowed ") as ei:
            ExecutionPlan(**kwargs)
        assert str(ei.value).startswith(prefix), (kwargs, str(ei.value))


def test_plan_capacity_coercion_chains_cause():
    """The capacity coercion failure is chained (`raise ... from e`) so the
    non-int-iterable root cause survives — the former bare re-raise hid it."""
    with pytest.raises(ValueError) as ei:
        ExecutionPlan(capacity=("a", "b", "c"))   # iterable, non-int entries
    assert "ExecutionPlan.capacity=" in str(ei.value)
    assert isinstance(ei.value.__cause__, ValueError)
    with pytest.raises(ValueError) as ei:
        ExecutionPlan(capacity=object())          # not iterable at all
    assert isinstance(ei.value.__cause__, TypeError)
    with pytest.raises(ValueError):
        ExecutionPlan(capacity=(0, -1, 4))        # int but out of bounds


def test_plan_streams_normalizes_shares():
    p = ExecutionPlan(streams=2, dispatch="fused", stream_shares=[3, 1])
    assert p.stream_shares == (3.0, 1.0)          # tuple-coerced, hashable
    assert ExecutionPlan(streams=4, dispatch="fused").stream_shares is None


def test_plan_replace_and_decide():
    p = ExecutionPlan(t1=8, t2=40)
    assert p.replace(t1=0, t2=0).thresholds == (0, 0)
    scores = np.array([0.0, 10.0, 100.0])
    assert p.decide(scores).tolist() == [sp.BILINEAR, sp.C27, sp.C54]
    assert p.replace(t1=200, t2=201).decide(scores).tolist() == [0, 0, 0]
    forced = p.replace(subnet_policy="all_c27").decide(scores)
    assert forced.tolist() == [sp.C27] * 3


# -- bucket padding path -----------------------------------------------------

def test_bucket_schedule():
    assert _bucket(1) == 8 and _bucket(8) == 8 and _bucket(9) == 16
    assert _bucket(5000, DEFAULT_BUCKETS) == 8192          # ceil to multiple
    assert _bucket(3, (4, 16)) == 4 and _bucket(5, (4, 16)) == 16


def test_bucket_padding_writes_only_real_indices(engine, lr_frame):
    """Padding a subnet batch duplicates patch 0; those duplicate outputs must
    never land in other patches' slots of the fused frame."""
    n = 9
    ids = np.zeros(n, dtype=np.int64)
    ids[0] = sp.C54            # batch of 1 -> padded to bucket 8 with patch 0
    mixed = engine.upscale(lr_frame, ids_override=ids)
    all_bilinear = engine.upscale(lr_frame,
                                  ids_override=np.zeros(n, dtype=np.int64))
    assert mixed.counts == (8, 0, 1)
    # HR region covered only by patches 1.. (LR y,x >= 34) must be identical
    np.testing.assert_allclose(np.asarray(mixed.image[68:, 68:]),
                               np.asarray(all_bilinear.image[68:, 68:]),
                               atol=1e-6)
    # patch 0's exclusive region (LR y,x < 30) must reflect the C54 forward
    assert float(jnp.abs(mixed.image[:60, :60]
                         - all_bilinear.image[:60, :60]).max()) > 1e-4


def test_pipeline_matches_seed_loop_reference(engine, lr_frame):
    """The device-resident gather/scatter pipeline is allclose-identical to
    the seed per-patch loop pipeline, routing included."""
    new = edge_selective_sr(engine.params, lr_frame, engine.cfg)
    old = edge_selective_sr(engine.params, lr_frame, engine.cfg,
                            use_loop_reference=True)
    assert new.ids.tolist() == old.ids.tolist()
    np.testing.assert_allclose(np.asarray(new.image), np.asarray(old.image),
                               rtol=1e-6, atol=1e-5)


# -- upscale modes + ids_override round-trip ---------------------------------

def test_ids_override_roundtrip(engine, lr_frame):
    ids = np.array([0, 1, 2, 0, 1, 2, 0, 1, 2], dtype=np.int64)
    res = engine.upscale(lr_frame, ids_override=ids)
    assert res.ids.tolist() == ids.tolist()
    assert res.counts == sp.subnet_counts(ids)
    ref = edge_selective_sr(engine.params, lr_frame, engine.cfg,
                            ids_override=ids)
    np.testing.assert_allclose(np.asarray(res.image), np.asarray(ref.image),
                               atol=1e-6)
    assert res.mac_saving == ref.mac_saving


def test_modes_and_result_shape(engine, lr_frame):
    r = engine.upscale(lr_frame)
    assert isinstance(r, FrameResult)
    assert r.image.shape == (128, 128, 3) and r.mode == "edge_select"
    assert r.n_patches == 9 and r.scores is not None and r.latency_s > 0
    w = engine.reference(lr_frame)
    assert w.image.shape == (128, 128, 3) and w.mode == "whole"
    assert w.backend == "ref"        # sr_whole always runs the pure-JAX path
    a = engine.upscale(lr_frame, mode="all_patches", width=CFG.channels)
    assert a.counts == (0, 0, 9)
    with pytest.raises(ValueError):
        engine.upscale(lr_frame, mode="nope")
    with pytest.raises(ValueError):
        engine.upscale(lr_frame, mode="all_patches", width=13)
    with pytest.raises(ValueError):
        engine.reference(lr_frame, width=13)
    with pytest.raises(ValueError):
        engine.upscale(lr_frame, width=27)       # width needs all_patches/whole
    with pytest.raises(ValueError):
        engine.upscale(lr_frame, mode="whole",
                       ids_override=np.zeros(9, dtype=np.int64))
    forced = engine.upscale(lr_frame,
                            plan=engine.plan.replace(subnet_policy="all_c27"))
    assert forced.counts == (0, 9, 0) and forced.scores is None
    assert forced.mode == "all_patches"   # labeled as what actually ran


def test_sr_all_patches_width_validation(engine, lr_frame):
    with pytest.raises(ValueError):
        sr_all_patches(engine.params, lr_frame, CFG, width=13)
    img = sr_all_patches(engine.params, lr_frame, CFG, width=CFG.channels // 2)
    assert img.shape == (128, 128, 3)


def test_backend_selected_once(lr_frame):
    with pytest.raises(ValueError):
        SREngine.from_config(CFG, backend="typo")
    ref = SREngine.from_config(CFG, seed=1)
    pal = SREngine.from_config(CFG, seed=1, backend="pallas")
    r, p = ref.upscale(lr_frame), pal.upscale(lr_frame)
    # honest labeling: on a CPU host the auto interpret policy falls back to
    # the Pallas interpreter, and the result says so
    assert (r.backend, p.backend) == ("ref", "pallas-interpret")
    np.testing.assert_allclose(np.asarray(r.image), np.asarray(p.image),
                               atol=1e-5)
    # forcing interpret=True pins the same label; ref never relabels
    forced = SREngine.from_config(
        CFG, seed=1, backend="pallas",
        plan=ExecutionPlan(interpret=True))
    assert forced.backend_label == "pallas-interpret"
    assert forced.upscale(lr_frame).backend == "pallas-interpret"
    assert ref.backend_label == "ref"


# -- streaming ---------------------------------------------------------------

def test_stream_and_summary(lr_frame):
    eng = SREngine.from_config(
        CFG, switching=SwitchingConfig(c54_per_sec_budget=3, frame_high=2,
                                       frame_low=1, fps=2))
    out = list(eng.stream([lr_frame] * 3))
    assert len(out) == 3 and all(isinstance(r, FrameResult) for r in out)
    s = eng.summary()
    assert s["frames"] == 3 and s["backend"] == "ref"
    assert abs(sum(s["subnet_share"].values()) - 1.0) < 1e-3
    forced = SREngine.from_config(
        CFG, plan=ExecutionPlan(subnet_policy="all_c27"))
    with pytest.raises(ValueError):      # streaming is adaptive-only
        forced.serve(lr_frame)


def test_from_checkpoint_falls_back_to_init(tmp_path):
    eng = SREngine.from_checkpoint(cfg=CFG, bench_cache=str(tmp_path))
    assert eng.upscale(jnp.zeros((40, 40, 3))).image.shape == (80, 80, 3)


def test_from_checkpoint_missing_ema_warns(tmp_path):
    """prefer='ema' against a checkpoint written without an 'ema' tree must
    warn and serve 'params' — not crash, not silently mis-restore."""
    from repro.ckpt.checkpoint import CheckpointManager
    params = init_essr(jax.random.PRNGKey(7), CFG)
    cm = CheckpointManager(str(tmp_path))
    cm.save(5, {"params": params}, blocking=True)
    with pytest.warns(UserWarning, match="no 'ema' tree"):
        eng = SREngine.from_checkpoint(str(tmp_path), cfg=CFG, prefer="ema")
    np.testing.assert_array_equal(
        np.asarray(eng.params["first"]["pw"]),
        np.asarray(params["first"]["pw"]))
    # a checkpoint WITH an ema tree restores it without warning
    ema = jax.tree_util.tree_map(lambda a: a * 0.5, params)
    cm2 = CheckpointManager(str(tmp_path / "full"))
    cm2.save(9, {"params": params, "ema": ema}, blocking=True)
    eng2 = SREngine.from_checkpoint(str(tmp_path / "full"), cfg=CFG,
                                    prefer="ema")
    np.testing.assert_array_equal(
        np.asarray(eng2.params["first"]["pw"]),
        np.asarray(ema["first"]["pw"]))
    # an ema-only checkpoint with prefer='params' serves the ema tree
    cm3 = CheckpointManager(str(tmp_path / "emaonly"))
    cm3.save(2, {"ema": ema}, blocking=True)
    with pytest.warns(UserWarning, match="no 'params' tree"):
        eng3 = SREngine.from_checkpoint(str(tmp_path / "emaonly"), cfg=CFG,
                                        prefer="params")
    np.testing.assert_array_equal(
        np.asarray(eng3.params["first"]["pw"]),
        np.asarray(ema["first"]["pw"]))


def test_upscale_sub_patch_frame(engine):
    """Frames smaller than the patch reflect-pad through the pipeline (the
    seed crashed in lax.dynamic_slice)."""
    r = engine.upscale(jnp.zeros((20, 24, 3)))
    assert r.image.shape == (40, 48, 3) and r.n_patches == 1


def test_plan_interpret_and_geometry():
    with pytest.raises(ValueError):
        ExecutionPlan(interpret="yes")
    p = ExecutionPlan()
    assert p.interpret is None and p.replace(interpret=True).interpret is True
    g = p.geometry(64, 64, 2)
    assert g is p.geometry(64, 64, 2)      # cached: zero per-frame setup
    assert g.n == 9 and g.scale == 2


# -- deprecation shims -------------------------------------------------------

def test_frame_server_alias_raises_with_migration_path():
    """The retired shim fails loudly and names the replacements — stale call
    sites must not silently fork serving behavior."""
    from repro.runtime.serving import FrameServer
    params = init_essr(jax.random.PRNGKey(0), CFG)
    with pytest.raises(RuntimeError, match=r"serve_streams"):
        FrameServer(params, CFG)
    with pytest.raises(RuntimeError, match=r"SREngine"):
        FrameServer()


def test_switching_config_not_shared():
    a, b = AdaptiveSwitcher(), AdaptiveSwitcher()
    assert a.cfg is not b.cfg
    params = init_essr(jax.random.PRNGKey(0), CFG)
    e1, e2 = SREngine(params, CFG), SREngine(params, CFG)
    assert e1.switcher.cfg is not e2.switcher.cfg
