"""The correctness check: it passes the program on the CPU, and fails the
control (the reference one precision below fp32 at HIGHEST) and each fault
planted in the timed path; a frame served off the planned path counts as
failed."""
import dataclasses
import time

import numpy as np
import pytest

import tinyroot
import control
import harness

SEED = 2**33 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench"))


def _on_cpu(monkeypatch):
    """Let the harness run on the CPU: any device, the v5e's peaks, no
    persistent cache, frames labelled by the Pallas interpreter."""
    import jax
    monkeypatch.setattr(harness, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks",
                        lambda peaks, kind: peaks["TPU v5 lite"])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    monkeypatch.setattr(harness, "planned_label",
                        lambda config: f"{config['backend']}-interpret")


def _run(root, monkeypatch, wrap=None, seconds=1.0):
    _on_cpu(monkeypatch)
    if wrap is not None:
        build = harness.build_engine

        def faulty(config, params):
            engine = build(config, params)
            stream, calls = engine.stream, []

            def planted(frames):        # the first stream is warm-up's
                calls.append(1)
                return stream(frames) if len(calls) == 1 else wrap(stream(frames))
            engine.stream = planted
            return engine
        monkeypatch.setattr(harness, "build_engine", faulty)
    return harness.run_cell(root, tinyroot.TINY_CELL, SEED, seconds, False,
                            time.perf_counter())


def _each(change):
    """A stream wrapper that changes every served frame after warm-up's."""
    def wrap(results):
        prev = None
        for r in results:
            out = change(r, prev)
            prev = r
            yield out
    return wrap


def _answer_altered(r, prev):
    return dataclasses.replace(r, image=r.image.at[5:9, 5:9].add(0.05))


def _half_batch_left_out(r, prev):
    h = r.image.shape[0]
    return dataclasses.replace(r, image=r.image.at[h // 2:].set(0.0))


def _exchange_left_out(r, prev):
    # as if only the first of four shards' patches came back
    h = r.image.shape[0]
    return dataclasses.replace(r, image=r.image.at[h // 4:].set(0.0))


def _state_unchanged(r, prev):
    return r if prev is None else dataclasses.replace(r, image=prev.image)


def _route_altered(r, prev):
    ids = np.array(r.ids)
    ids[0] = (ids[0] + 1) % 3
    return dataclasses.replace(r, ids=ids)


def test_program_is_correct(root, monkeypatch):
    res = _run(root, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["frames_compared"]["value"] == harness.SAMPLE_FRAMES
    assert res["checks"]["route_mismatch"]["value"] == 0
    assert set(res["metrics"]) == {"fps", "frame_ms_p90", "setup_s"}
    assert res["attempted"] >= res["metrics"]["fps"]["value"] * 1.0


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out,
                                   _exchange_left_out, _state_unchanged,
                                   _route_altered])
def test_each_fault_fails_the_check(root, monkeypatch, fault):
    res = _run(root, monkeypatch, _each(fault))
    assert not res["correct"], (fault.__name__, res["checks"])


def test_control_fails_the_check(root):
    for seed in (3, 2**33):
        checks = control.control_checks(root, tinyroot.TINY_CELL, seed)
        assert not harness.verdict(checks), checks
        assert checks["image_err"]["value"] > 3 * 1e-7


def test_off_path_frames_count_as_failed(root, monkeypatch):
    res = _run(root, monkeypatch,
               _each(lambda r, prev: dataclasses.replace(r, backend="ref")))
    assert res["failed"] == res["attempted"] > 0


def test_raising_frames_count_as_failed(root, monkeypatch):
    def wrap(results):
        for i, r in enumerate(results):
            if i % 2:
                raise RuntimeError("planted")
            yield r
    res = _run(root, monkeypatch, wrap)
    assert res["failed"] > 0
    assert res["correct"]
