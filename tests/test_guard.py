"""Serving resilience (`repro.runtime.guard` + the `plan.on_poison` /
`plan.faults` / `plan.max_retries` / `plan.quarantine_ticks` /
`plan.watchdog_s` knobs).

Contract under test (docs/api.md "Resilience & fault injection"):

  * poison-frame matrix: NaN / Inf / out-of-range / wrong-dtype inputs
    across backends x quant modes x fusion levels follow the documented
    ``on_poison`` policy — "raise" raises `PoisonFrameError`, "sanitize"
    and "bilinear" always serve a finite frame, "off" disables verdicts;
  * the health verdict is computed in-graph (fused dispatch stays a single
    device program; no host sync added — tier-1 ESSR1xx audits hold);
  * injected faults are deterministic: identical seeded `FaultPlan` runs
    produce identical degradation ledgers and identical outputs;
  * the degradation ladder steps fusion -> backend -> quant in documented
    order, retries are bounded, and serving labels stay honest;
  * per-tenant isolation: a poisoned or crashing stream never perturbs
    healthy tenants (bit-equal vs a no-fault run with pinned capacity);
    quarantined streams re-admit after ``plan.quarantine_ticks``;
  * corrupted QuantPack caches and truncated checkpoint manifests warn and
    fall back instead of crashing engine construction.
"""
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import ExecutionPlan, SREngine
from repro.core.adaptive import SwitchingConfig
from repro.models.essr import ESSRConfig, init_essr
from repro.runtime.guard import (FaultInjector, FaultPlan,
                                 PoisonFrameError, build_ladder)

CFG = ESSRConfig(scale=2)
HW = 64                                      # 64x64 LR -> 9 patches


def _stable_switching():
    return SwitchingConfig(frame_high=10 ** 9, frame_low=0)


def _clean_frame(seed: int = 0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.random((HW, HW, 3), np.float32))


def _poison(frame, kind: str):
    """Hand-poisoned frame (independent of the injector, so the matrix
    exercises the verdict, not the harness)."""
    f = np.array(frame)
    if kind == "dtype":
        return (f * 255).astype(np.uint8)
    bad = {"nan": np.nan, "inf": np.inf, "range": 3.0e6}[kind]
    f[4:12, 4:12, :] = bad
    return jnp.asarray(f)


_ENGINES = {}


def _engine(backend="ref", quant=None, fusion="layer", on_poison="raise",
            **plan_kw):
    """Engines are cached per configuration: construction (PTQ calibration
    for quant modes) dominates the matrix's runtime otherwise."""
    key = (backend, quant, fusion, on_poison, tuple(sorted(plan_kw.items())))
    if key not in _ENGINES:
        plan = ExecutionPlan(dispatch="fused", quant=quant, fusion=fusion,
                             on_poison=on_poison, **plan_kw)
        _ENGINES[key] = SREngine.from_config(
            CFG, seed=1, backend=backend, plan=plan,
            switching=_stable_switching())
    return _ENGINES[key]


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------

def test_faultplan_validation():
    with pytest.raises(ValueError, match="FaultPlan.poison_rate"):
        FaultPlan(poison_rate=1.5)
    with pytest.raises(ValueError, match="FaultPlan.poison_kinds"):
        FaultPlan(poison_kinds=("gamma-ray",))
    with pytest.raises(ValueError, match="FaultPlan.delay_s"):
        FaultPlan(delay_rate=0.5, delay_s=-1.0)
    fp = FaultPlan(seed=3, poison_rate=0.5, poison_kinds=["nan", "inf"],
                   target_streams=[1])
    assert fp.poison_kinds == ("nan", "inf")       # normalized to tuples
    assert fp.target_streams == (1,)


def test_plan_resilience_knob_validation():
    with pytest.raises(ValueError, match="ExecutionPlan.on_poison"):
        ExecutionPlan(on_poison="panic")
    with pytest.raises(ValueError, match="ExecutionPlan.faults"):
        ExecutionPlan(faults="chaos")
    with pytest.raises(ValueError, match="ExecutionPlan.max_retries"):
        ExecutionPlan(max_retries=-1)
    with pytest.raises(ValueError, match="ExecutionPlan.watchdog_s"):
        ExecutionPlan(watchdog_s=0.0)
    with pytest.raises(ValueError, match="watchdog"):
        ExecutionPlan(dispatch="host", watchdog_s=1.0)   # cross rule


# ---------------------------------------------------------------------------
# poison-frame matrix
# ---------------------------------------------------------------------------

#: (backend, quant, fusion) serving points the matrix sweeps. The first is
#: the cheap reference point swept against every kind x policy; the others
#: confirm the verdict rides inside the quantized / grouped / pallas
#: executables too.
MATRIX_POINTS = [("ref", None, "layer"),
                 ("pallas", "int8", "group"),
                 ("ref", "fxp10", "layer")]


@pytest.mark.parametrize("kind", ["nan", "inf", "range", "dtype"])
@pytest.mark.parametrize("backend,quant,fusion", MATRIX_POINTS)
def test_poison_raise_policy(backend, quant, fusion, kind):
    eng = _engine(backend, quant, fusion, "raise")
    with pytest.raises(PoisonFrameError):
        eng.upscale(_poison(_clean_frame(), kind))
    # the engine is not wedged: the next clean frame serves normally
    r = eng.upscale(_clean_frame())
    assert r.health == (0, 0, 0)
    assert np.isfinite(np.asarray(r.image)).all()


@pytest.mark.parametrize("kind", ["nan", "inf", "range", "dtype"])
@pytest.mark.parametrize("policy", ["sanitize", "bilinear"])
@pytest.mark.parametrize("backend,quant,fusion", MATRIX_POINTS)
def test_poison_recovery_policies(backend, quant, fusion, policy, kind):
    eng = _engine(backend, quant, fusion, policy)
    r = eng.upscale(_poison(_clean_frame(), kind))
    img = np.asarray(r.image)
    assert np.isfinite(img).all(), f"{policy} must serve a finite frame"
    assert r.health is not None
    if kind == "dtype":
        # integer input is normalized on ingest; the normalized frame is
        # clean, so the verdict is all-zero but the frame still serves
        assert r.health == (0, 0, 0)
    else:
        assert any(r.health), f"verdict missed the {kind} poisoning"
        if policy == "bilinear":
            assert not np.asarray(r.ids).any(), \
                "bilinear policy must demote every patch to the dense floor"


def test_poison_off_disables_verdicts():
    eng = _engine("ref", None, "layer", "off")
    r = eng.upscale(_poison(_clean_frame(), "range"))
    assert r.health is None and r.degraded == ()


def test_sanitize_bit_equal_on_clean_frames():
    """The sanitize path is a no-op on healthy input: verdict-on serving
    must not perturb clean frames (the guarded-vs-unguarded bench band
    rests on this)."""
    frame = _clean_frame(5)
    a = _engine("ref", None, "layer", "off").upscale(frame)
    b = _engine("ref", None, "layer", "sanitize").upscale(frame)
    assert (np.asarray(a.image).tobytes() == np.asarray(b.image).tobytes())
    assert b.health == (0, 0, 0)


# ---------------------------------------------------------------------------
# fault-injection determinism
# ---------------------------------------------------------------------------

def test_injector_deterministic_across_instances():
    fp = FaultPlan(seed=11, poison_rate=0.5, poison_kinds=("nan", "range"))
    a, b = FaultInjector(fp), FaultInjector(fp)
    frame = np.array(_clean_frame(2))
    for idx in range(8):
        fa = np.asarray(a.poison_frame(frame, 0, idx))
        fb = np.asarray(b.poison_frame(frame, 0, idx))
        assert fa.tobytes() == fb.tobytes()
    # a different seed moves the corruption
    c = FaultInjector(FaultPlan(seed=12, poison_rate=0.5,
                                poison_kinds=("nan", "range")))
    assert any(
        np.asarray(c.poison_frame(frame, 0, i)).tobytes()
        != np.asarray(a.poison_frame(frame, 0, i)).tobytes()
        for i in range(8))


def test_degradation_ladder_order():
    steps = [v.step for v in build_ladder("pallas", False, True, "group")]
    # the first rung is the as-planned serving point (empty step label)
    assert steps == ["", "fusion:group->layer", "backend:->ref",
                     "quant:->fp32"]
    # the floor plan has no rungs below it
    assert [v.step for v in build_ladder("ref", False, False, "layer")] \
        == [""]
    # no rung ever serves through the Pallas interpreter: compiled pallas
    # steps straight to ref
    for interp in (None, False, True):
        assert [v.step for v in build_ladder("pallas", interp, False,
                                             "layer")] == ["", "backend:->ref"]


def test_injected_backend_failures_degrade_deterministically():
    fp = FaultPlan(seed=4, backend_failure_rate=1.0)

    def run():
        eng = SREngine.from_config(
            CFG, seed=1, backend="pallas",
            plan=ExecutionPlan(dispatch="fused", quant="int8",
                               fusion="group", faults=fp),
            switching=_stable_switching())
        outs = [eng.upscale(_clean_frame(i)) for i in range(4)]
        return eng, outs

    eng1, outs1 = run()
    s1 = eng1.summary()["degradations"]
    assert s1["by_kind"].get("degrade", 0) >= 1
    # every frame served despite the failures, each labeled for what ran
    assert all(np.isfinite(np.asarray(o.image)).all() for o in outs1)
    assert outs1[0].degraded != ()
    eng2, outs2 = run()
    s2 = eng2.summary()["degradations"]
    assert s1["by_kind"] == s2["by_kind"]
    assert s1["by_step"] == s2["by_step"]
    assert [o.degraded for o in outs1] == [o.degraded for o in outs2]
    assert [o.backend for o in outs1] == [o.backend for o in outs2]


def test_watchdog_records_ladder_step():
    # a pallas/group plan has rungs for the watchdog to step down; an
    # impossible 1ns budget fires it on every frame
    eng = SREngine.from_config(
        CFG, seed=1, backend="pallas",
        plan=ExecutionPlan(dispatch="fused", fusion="group",
                           watchdog_s=1e-9),
        switching=_stable_switching())
    outs = list(eng.stream([_clean_frame(i) for i in range(3)]))
    assert len(outs) == 3
    assert any(o.degraded for o in outs)
    assert eng.summary()["degradations"]["by_kind"].get("watchdog", 0) >= 1
    # at the floor the watchdog keeps recording but has nothing to step
    eng2 = SREngine.from_config(
        CFG, seed=1, plan=ExecutionPlan(dispatch="fused", watchdog_s=1e-9),
        switching=_stable_switching())
    list(eng2.stream([_clean_frame(i) for i in range(2)]))
    assert eng2.summary()["degradations"]["by_kind"].get("watchdog", 0) >= 1


# ---------------------------------------------------------------------------
# per-tenant isolation (StreamMultiplexer)
# ---------------------------------------------------------------------------

def _mux_engine(params, faults, on_poison="raise", qt=1):
    plan = ExecutionPlan(dispatch="fused", streams=3, capacity=(0, 9, 9),
                         on_poison=on_poison, faults=faults,
                         quarantine_ticks=qt)
    return SREngine(params, CFG, plan=plan, switching=_stable_switching())


@pytest.fixture(scope="module")
def params():
    return init_essr(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def tenant_frames():
    return [[_clean_frame(100 * s + i) for i in range(4)] for s in range(3)]


def test_mux_poison_isolation_bit_equal(params, tenant_frames):
    """One tenant's poisoned frames must not perturb the others by a single
    bit (pinned capacity keeps the shared pool fault-independent), and the
    quarantine cycle must be deterministic across identical runs."""
    fp = FaultPlan(seed=7, poison_rate=1.0, poison_kinds=("nan",),
                   target_streams=(1,))
    base = list(_mux_engine(params, None).serve_streams(tenant_frames))
    eng1 = _mux_engine(params, fp)
    outs1 = list(eng1.serve_streams(tenant_frames))
    assert all(o.stream_id != 1 for o in outs1), \
        "poisoned tenant's results must be suppressed under raise"
    by_base, by_fault = {}, {}
    for o in base:
        by_base.setdefault(o.stream_id, []).append(np.asarray(o.image))
    for o in outs1:
        by_fault.setdefault(o.stream_id, []).append(np.asarray(o.image))
    for sid in (0, 2):
        assert len(by_fault[sid]) == len(by_base[sid]) == 4
        for a, b in zip(by_base[sid], by_fault[sid]):
            assert a.tobytes() == b.tobytes(), f"tenant {sid} perturbed"
    kinds = eng1.summary()["degradations"]["by_kind"]
    assert kinds.get("quarantine", 0) >= 1 and kinds.get("readmit", 0) >= 1
    eng2 = _mux_engine(params, fp)
    outs2 = list(eng2.serve_streams(tenant_frames))
    assert [o.stream_id for o in outs1] == [o.stream_id for o in outs2]
    assert kinds == eng2.summary()["degradations"]["by_kind"]


def test_mux_quarantine_zero_retires_permanently(params, tenant_frames):
    fp = FaultPlan(seed=7, poison_rate=1.0, poison_kinds=("inf",),
                   target_streams=(1,))
    eng = _mux_engine(params, fp, qt=0)
    outs = list(eng.serve_streams(tenant_frames))
    assert all(o.stream_id != 1 for o in outs)
    kinds = eng.summary()["degradations"]["by_kind"]
    # retired on the FIRST poison verdict: no re-admission, one poison event
    assert kinds.get("retire", 0) == 1 and kinds.get("poison", 0) == 1
    assert "readmit" not in kinds


def test_mux_sanitize_serves_every_tenant(params, tenant_frames):
    fp = FaultPlan(seed=7, poison_rate=1.0, poison_kinds=("nan",),
                   target_streams=(1,))
    eng = _mux_engine(params, fp, on_poison="sanitize")
    outs = list(eng.serve_streams(tenant_frames))
    assert sorted({o.stream_id for o in outs}) == [0, 1, 2]
    for o in outs:
        assert np.isfinite(np.asarray(o.image)).all()
        if o.stream_id == 1:
            assert o.health is not None and o.health[0] > 0


def test_mux_iterator_crash_retires_only_that_stream(params, tenant_frames):
    class Boom:
        def __init__(self, frames):
            self.frames = frames

        def __iter__(self):
            yield self.frames[0]
            raise RuntimeError("tenant iterator died")

    eng = _mux_engine(params, None)
    streams = [tenant_frames[0], Boom(tenant_frames[1]), tenant_frames[2]]
    outs = list(eng.serve_streams(streams))
    ids = [o.stream_id for o in outs]
    assert ids.count(1) == 1, "stream 1 serves its one good frame"
    assert ids.count(0) == 4 and ids.count(2) == 4, \
        "healthy tenants serve every frame"
    kinds = eng.summary()["degradations"]["by_kind"]
    assert kinds.get("retire", 0) == 1


def test_solo_stream_iterator_exception_recorded():
    def frames():
        yield _clean_frame(0)
        yield _clean_frame(1)
        raise ValueError("camera unplugged")

    eng = _engine("ref", None, "layer", "raise")
    n_before = len(eng.guard.events)
    outs = list(eng.stream(frames()))
    assert len(outs) == 2
    retires = [e for e in eng.guard.events[n_before:]
               if e["kind"] == "retire"]
    assert len(retires) == 1 and "camera unplugged" in retires[0]["reason"]


# ---------------------------------------------------------------------------
# persisted-state integrity (QuantPack cache, checkpoint manifest)
# ---------------------------------------------------------------------------

def test_quant_pack_corruption_warns_and_recalibrates(tmp_path):
    from repro.quant.pams import (build_quant_pack, load_quant_pack,
                                  params_fingerprint, save_quant_pack)
    p = init_essr(jax.random.PRNGKey(0), CFG)
    x = jnp.stack([_clean_frame(i)[:32, :32] for i in range(2)])
    pack = build_quant_pack(p, CFG, "int8", x)
    fp = params_fingerprint(p)
    path = str(tmp_path / "alphas.json")
    save_quant_pack(path, pack, fp)
    assert load_quant_pack(path, fp) == pack       # round trip intact
    # truncation fails the integrity checksum -> warn + recalibrate
    with open(path) as f:
        body = f.read()
    with open(path, "w") as f:
        f.write(body[: len(body) // 2])
    with pytest.warns(UserWarning, match="corrupted"):
        assert load_quant_pack(path, fp) is None
    # injector-corrupted payload (not even JSON) -> same fallback
    save_quant_pack(path, pack, fp)
    FaultInjector.corrupt_file(path)
    with pytest.warns(UserWarning, match="corrupted"):
        assert load_quant_pack(path, fp) is None
    # a bit-flip inside otherwise-valid JSON is caught by the checksum
    save_quant_pack(path, pack, fp)
    with open(path) as f:
        tampered = f.read().replace('"bits": 8', '"bits": 7')
    with open(path, "w") as f:
        f.write(tampered)
    with pytest.warns(UserWarning, match="corrupted"):
        assert load_quant_pack(path, fp) is None
    # quiet recalibration cases stay quiet: missing file, stale fingerprint,
    # and a legacy pack written before checksums were recorded
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert load_quant_pack(str(tmp_path / "missing.json"), fp) is None
        save_quant_pack(path, pack, fp)
        assert load_quant_pack(path, "0" * 16) is None
        with open(path) as f:
            legacy = json.load(f)
        del legacy["checksum"]
        with open(path, "w") as f:
            json.dump(legacy, f)
        assert load_quant_pack(path, fp) is None


def test_truncated_checkpoint_manifest_warns_and_serves(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager
    p = init_essr(jax.random.PRNGKey(7), CFG)
    cm = CheckpointManager(str(tmp_path))
    cm.save(5, {"params": p, "ema": p}, blocking=True)
    manifest = tmp_path / "step_5" / "manifest.bin"
    blob = manifest.read_bytes()
    manifest.write_bytes(blob[: len(blob) // 2])
    with pytest.warns(UserWarning):
        eng = SREngine.from_checkpoint(str(tmp_path), cfg=CFG,
                                       bench_cache=None)
    # construction survived; the engine serves (fresh init fallback)
    r = eng.upscale(_clean_frame())
    assert np.isfinite(np.asarray(r.image)).all()
