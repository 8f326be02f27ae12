"""The four-chip cell's path on four virtual CPU devices, and the readers of
its two metrics on synthetic reductions.

The tier-1 suite sees one CPU device, so the four-device checks run in one
subprocess started with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``:
this file run as a script. At a tiny size, on seeded weights, it serves
``SREngine`` with ``shards=4``, compares its frames with the plain
reference and serves one frame with ``mode="whole"``, runs the real harness on a four-chip tiny cell, and runs it again
with a fault planted in the program that drops three of the four shards'
outputs; then it runs the multi-device cases of
``tests/test_sharded_pipeline.py``. It prints what each found as one JSON
line.
"""
import json
import os
import subprocess
import sys
import time

import pytest

import tinyroot
import harness
import phase_trace as pt

SEED = 2**33 + 17
CHIPS = 4
CHILD_TIMEOUT_S = 300
ENGINE_HW = (120, 150)      # LR frame of the engine's check: 4 x 5 patches
MS = 1e6        # ns


# -- the readers, on synthetic reductions ----------------------------------

def _chips(busy):
    return {"trace": {"chips": [{"device": i, "busy_s": b}
                                for i, b in enumerate(busy)],
                      "window_s": 1.0},
            "chips": CHIPS}


@pytest.mark.parametrize("busy,want", [
    ([0.5, 0.5, 0.5, 0.5], 1.0),
    ([0.8, 0.0, 0.0, 0.0], 4.0),
    ([0.8], 4.0),                   # three chips with no operation at all
    ([0.6, 0.2, 0.2, 0.2], 2.0)])
def test_chip_busy_imbalance(busy, want):
    read = harness.load_reader(tinyroot.REPO, "chip_busy_imbalance")
    assert read(_chips(busy)) == pytest.approx(want)


def test_chip_busy_imbalance_is_silent_without_chips():
    read = harness.load_reader(tinyroot.REPO, "chip_busy_imbalance")
    assert read(_chips([])) is None


def _sharded_frame(shard_phases=True):
    """One sharded frame of 100 ms on four chips: the frame's chip (0)
    extracts 0-10, every chip scatters 10-12 and runs its C54 lane 12-40,
    chip 0 waits in the gather 40-44 and the others 40-42, and chip 0
    fuses 44-60."""
    host = [("bench.traced", 0, 100 * MS), ("essr.serve", 0, 100 * MS)]
    mods = [(0, "jit_essr_extract(1)", 0, 10 * MS),
            (0, "jit_essr_fuse(4)", 44 * MS, 16 * MS)]
    scatter, gather = (("jit_essr_shard_scatter(2)", "jit_essr_shard_gather(3)")
                       if shard_phases else ("jit__identity(2)", "jit_x(3)"))
    for dev in range(CHIPS):
        mods += [(dev, scatter, 10 * MS, 2 * MS),
                 (dev, "jit_essr_c54(5)", 12 * MS, 28 * MS),
                 (dev, gather, 40 * MS, (4 if dev == 0 else 2) * MS)]
    return mods, mods, host


def _ctx(red, frames=1):
    return {"trace": {}, "frames": frames, "chips": CHIPS,
            "window_s": red["window_s"]}


@pytest.mark.parametrize("phases", [pt.PHASES, pt.PHASES[:-1] + r"|shard_\w+)"],
                         ids=["unnamed", "named"])
def test_cross_chip_reads_only_the_shard_phases(phases, monkeypatch):
    """Scatter 2 ms on every chip, gather 4 ms on chip 0 and 2 on the
    others: (4 * 2 + 4 + 3 * 2) / 4 chips = 4.5 ms a frame, whether
    `phase_trace` names the two modules as phases or keeps them unnamed."""
    import re
    monkeypatch.setattr(pt, "PHASE_MODULE",
                        re.compile(rf"^jit_({phases})(\(.*)?$"))
    red = pt.reduce_events(*_sharded_frame())
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: red)
    read = harness.load_reader(tinyroot.REPO, "cross_chip_ms_per_frame")
    assert read(_ctx(red)) == pytest.approx(4.5)
    assert read(_ctx(red, frames=2)) == pytest.approx(2.25)


def test_cross_chip_is_silent_where_no_shard_phase_is_named(monkeypatch):
    red = pt.reduce_events(*_sharded_frame(shard_phases=False))
    assert red["named"]                       # extract, c54 and fuse are
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: red)
    read = harness.load_reader(tinyroot.REPO, "cross_chip_ms_per_frame")
    assert read(_ctx(red)) is None
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: None)
    assert read(_ctx(red)) is None


# -- four virtual devices, in a subprocess ---------------------------------

@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    out["seconds"] = time.perf_counter() - t0
    return out


def test_engine_on_four_devices_matches_the_reference(four_devices):
    eng = four_devices["engine"]
    assert eng["devices"] == CHIPS and eng["mesh"] == CHIPS
    assert eng["backend"] == "pallas-interpret"
    assert eng["image_on"] == ["frame's device"]
    # every lane, each with more patches than one shard's block of its
    # bucket: bilinear, C27 and C54 over two frames
    assert all(n > 2 * 2 for n in eng["routing"])
    checks = eng["checks"]
    assert checks["frames_compared"]["value"] >= 1
    assert checks["route_mismatch"]["value"] == 0
    assert checks["image_err"]["value"] <= checks["image_err"]["limit"]


def test_whole_frame_mode_stays_on_the_frames_device(four_devices):
    eng = four_devices["engine"]
    assert eng["whole_on"] == "frame's device"
    assert eng["whole_err"] == 0.0


def test_harness_on_a_four_chip_cell_is_correct(four_devices):
    res = four_devices["harness"]
    assert res["chips"] == CHIPS
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_three_shards_dropped_fail_the_check(four_devices):
    res = four_devices["dropped"]
    assert not res["correct"], res["checks"]
    assert res["checks"]["image_err"]["value"] > \
        res["checks"]["image_err"]["limit"]


def test_sharded_pipeline_cases_run_on_four_devices(four_devices):
    res = four_devices["pipeline_tests"]
    assert res["rc"] == 0, res
    assert res["outcomes"].get("passed", 0) >= 11
    assert "skipped" not in res["outcomes"] and "failed" not in res["outcomes"]


def _child() -> dict:
    """What the four-device tests read; runs with four CPU devices."""
    import tempfile
    import pathlib
    import jax
    import numpy as np
    import frames
    import reference
    from repro.core import pipeline

    assert jax.device_count() == CHIPS, jax.devices()
    tmp = pathlib.Path(tempfile.mkdtemp())
    # the cell's traffic as the real cell's: every patch C54, so the one
    # lane takes the whole frame and every shard holds some of it
    root = tinyroot.make(tmp, shares={"smooth": 0, "texture": 0, "edges": 1})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        if cell["name"] == tinyroot.TINY_CELL:
            cell["chips"] = CHIPS
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    path = root / "bench" / "configs" / "tiny.json"
    config = json.loads(path.read_text())
    config["plan"]["shards"] = CHIPS
    path.write_text(json.dumps(config))

    # the engine, frame by frame, against the plain reference, on frames of
    # 20 patches that route to every lane, each lane's bucket over all four
    # shards
    spec = harness.load_cell(root, tinyroot.TINY_CELL)
    plan = config["plan"]
    params = reference.init_weights(SEED, config["model"])
    pool = frames.make_pool(SEED, ENGINE_HW,
                            int(plan["patch"]) - int(plan["overlap"]),
                            {"smooth": 1, "texture": 1, "edges": 1}, 2)
    engine = harness.build_engine(config, params)
    sample, routing, on = {}, [0, 0, 0], set()
    for j, r in enumerate(engine.stream(pool)):
        sample[j] = {"j": j, "pool": j, "image": r.image,
                     "ids": np.asarray(r.ids)}
        routing = [a + int(b) for a, b in zip(routing, r.counts)]
        on.add("frame's device" if r.image.devices() == pool[j].devices()
               else str(r.image.sharding))
    # mode="whole" serves the frame on its own device with the weights as
    # given, whatever the mesh
    whole = engine.upscale(pool[0], mode="whole")
    one = pipeline.sr_whole(params, pool[0], engine.cfg)
    out = {"engine": {
        "devices": jax.device_count(), "mesh": int(engine.mesh.size),
        "backend": engine.backend_label, "routing": routing,
        "image_on": sorted(on),
        "whole_on": ("frame's device"
                     if whole.image.devices() == pool[0].devices()
                     else str(whole.image.sharding)),
        "whole_err": float(np.max(np.abs(np.asarray(whole.image)
                                         - np.asarray(one)))),
        "checks": harness.check(sample, pool, params, config,
                                config["limits"])}}

    # the real harness on the four-chip tiny cell, with the seams of
    # test_bench_check.py
    harness.check_devices = lambda chips: jax.devices()[:chips]
    harness.device_peaks = lambda peaks, kind: peaks["TPU v5 lite"]
    harness.enable_compile_cache = lambda root: "off"
    harness.planned_label = lambda config: f"{config['backend']}-interpret"

    def run():
        res = harness.run_cell(root, tinyroot.TINY_CELL, SEED, 1.0, False,
                               time.perf_counter())
        return {"chips": spec["cell"]["chips"], "correct": res["correct"],
                "failed": res["failed"], "checks": res["checks"]}
    out["harness"] = run()

    # planted: only the first shard's outputs come back from the gather
    lane = pipeline.sharded_lane

    def first_shard_only(params, patches, cfg, width, **kw):
        got = lane(params, patches, cfg, width, **kw)
        m = -(-int(got.shape[0]) // CHIPS)
        return got.at[m:].set(0.0)
    pipeline.sharded_lane = first_shard_only
    out["dropped"] = run()
    pipeline.sharded_lane = lane

    # the multi-device cases of the program's own sharded-path tests, which
    # skip on one device
    class Outcomes:
        def __init__(self):
            self.counts = {}

        def pytest_runtest_logreport(self, report):
            if report.when == "call" or report.outcome != "passed":
                self.counts[report.outcome] = \
                    self.counts.get(report.outcome, 0) + 1
    seen = Outcomes()
    rc = pytest.main(["-q", "-p", "no:cacheprovider",
                      str(tinyroot.REPO / "tests" / "test_sharded_pipeline.py")],
                     plugins=[seen])
    out["pipeline_tests"] = {"rc": int(rc), "outcomes": seen.counts}
    return out


if __name__ == "__main__":
    print(json.dumps(_child(), default=float))
