"""The split of a trace by the program's names, on small synthetic event
lists, the readers of the metrics built on it, and a traced harness run
on the CPU."""
import json
import time

import pytest

import tinyroot
import harness
import phase_trace as pt

MS = 1e6        # ns
CELLS = ["x4_1080p_edges", "x2_2160p_mixed", "x4_1080p_smooth"]
NEW = ["extract_ms_per_frame", "route_ms_per_frame",
       "lane_dispatch_ms_per_frame", "bilinear_ms_per_frame",
       "subnet_lane_ms_per_frame", "fuse_ms_per_frame",
       "unnamed_ms_per_frame", "executables_per_frame", "host_ms_per_frame",
       "host_waits_per_frame", "program_idle_ms_per_frame"]


@pytest.mark.parametrize("module,phase", [
    ("jit_essr_extract(4635585172558814308)", "essr_extract"),
    ("jit_essr_c54(12)", "essr_c54"),
    ("jit_essr_lane_scatter", "essr_lane_scatter"),
    ("jit__take(1)", None),
    ("jit_essr_fused_frame(7)", "essr_fused_frame"),
    ("jit_essr_forward_megakernel(3)", None),    # a kernel's own jit
    ("jit_scatter(9)", None),
    ("", None)])
def test_phase_of_reads_the_module_name(module, phase):
    assert pt.phase_of(module) == phase


def _frame():
    """One host-dispatch frame of 100 ms in a 120-ms window, on one chip.

    Host: serve 0-100 ms holds health (0-10, its wait 5-10), extract 10-20,
    route 20-40 (its wait 25-35), a lane 40-70, fuse 70-80 and the image
    wait 80-100. Device: health 2-5, extract 12-22, edge score 22-25, a
    C54 lane 45-75 with one op of an unnamed module 75-78, fuse 78-90."""
    host = [("bench.traced", 0, 120 * MS),
            ("essr.serve", 0, 100 * MS),
            ("essr.health", 0, 10 * MS), ("essr.wait.health", 5 * MS, 5 * MS),
            ("PjitFunction(essr_health)", 1 * MS, 1 * MS),
            ("essr.extract", 10 * MS, 10 * MS),
            ("PjitFunction(essr_extract)", 11 * MS, 1 * MS),
            ("PjitFunction(essr_extract)", 11.2 * MS, 0.5 * MS),   # nested
            ("essr.route", 20 * MS, 20 * MS),
            ("PjitFunction(essr_edge_score)", 21 * MS, 1 * MS),
            ("essr.wait.scores", 25 * MS, 10 * MS),
            ("essr.lane", 40 * MS, 30 * MS),
            ("PjitFunction(essr_c54)", 41 * MS, 2 * MS),
            ("essr.fuse", 70 * MS, 10 * MS),
            ("PjitFunction(essr_fuse)", 71 * MS, 1 * MS),
            ("essr.wait.image", 80 * MS, 20 * MS)]
    modules = [(0, "jit_essr_health(1)", 2 * MS, 3 * MS),
               (0, "jit_essr_extract(2)", 12 * MS, 10 * MS),
               (0, "jit_essr_edge_score(3)", 22 * MS, 3 * MS),
               (0, "jit_essr_c54(4)", 45 * MS, 30 * MS),
               (0, "jit_scatter(5)", 75 * MS, 3 * MS),
               (0, "jit_essr_fuse(6)", 78 * MS, 12 * MS),
               (0, "jit_essr_fuse(6)", -20 * MS, 1 * MS)]  # before the window
    ops = [(0, "jit_essr_health(1)", 2 * MS, 3 * MS),
           (0, "jit_essr_extract(2)", 12 * MS, 4 * MS),
           (0, "jit_essr_extract(2)", 16 * MS, 6 * MS),
           (0, "jit_essr_edge_score(3)", 22 * MS, 3 * MS),
           (0, "jit_essr_c54(4)", 45 * MS, 25 * MS),
           (0, "jit_essr_c54(4)", 70 * MS, 5 * MS),
           (0, "jit_scatter(5)", 75 * MS, 3 * MS),
           (0, "jit_essr_fuse(6)", 78 * MS, 12 * MS),
           (0, "jit_essr_fuse(6)", 130 * MS, 1 * MS)]
    return ops, modules, host


def test_device_time_splits_by_phase_module():
    red = pt.reduce_events(*_frame())
    (chip,) = red["chips"]
    assert red["window_s"] == pytest.approx(0.120)
    assert red["named"]
    want = {"essr_health": 3, "essr_extract": 10, "essr_edge_score": 3,
            "essr_c54": 30, "essr_fuse": 12}
    assert chip["phase_s"] == {k: pytest.approx(v * 1e-3)
                               for k, v in want.items()}
    # the unnamed remainder, by module
    assert chip["unnamed_s"] == pytest.approx(0.003)
    assert chip["unnamed_modules"] == [["jit_scatter", pytest.approx(0.003)]]
    # executions that start in the window; the early fuse is outside
    assert chip["executables"] == 6


def test_host_self_time_is_frame_span_minus_waits():
    prog = pt.reduce_events(*_frame())["program"]
    assert prog["frame_spans"] == 1
    assert prog["frame_s"] == pytest.approx(0.100)
    assert prog["waits"] == 3
    assert prog["wait_s"] == pytest.approx(0.035)
    assert prog["by_wait"]["essr.wait.image"] == [1, pytest.approx(0.020)]


def test_idle_goes_to_the_innermost_program_span():
    red = pt.reduce_events(*_frame())
    (chip,) = red["chips"]
    # device busy: 2-5, 12-25, 45-90 -> idle 0-2, 5-12, 25-45, 90-120 of
    # the window
    idle = chip["idle_by_span"]
    assert idle["essr.health"] == pytest.approx(0.002)       # 0-2
    assert idle["essr.wait.health"] == pytest.approx(0.005)  # 5-10
    assert idle["essr.extract"] == pytest.approx(0.002)      # 10-12
    assert idle["essr.route"] == pytest.approx(0.005)        # 35-40
    assert idle["essr.wait.scores"] == pytest.approx(0.010)  # 25-35
    assert idle["essr.lane"] == pytest.approx(0.005)         # 40-45
    assert idle["essr.wait.image"] == pytest.approx(0.010)   # 90-100
    assert idle[pt.NO_SPAN] == pytest.approx(0.020)          # 100-120
    assert sum(idle.values()) == pytest.approx(0.059)
    assert red["idle_by_span"] == idle
    # idle inside the frame's top span only
    assert chip["program_idle_s"] == pytest.approx(0.039)


def test_clock_pairs_executions_with_their_calls():
    # the fuse run before the window has no call in the trace: left out
    clock = pt.reduce_events(*_frame())["clock"]
    assert clock["pairs"] == 5 and clock["share"] == 1.0
    assert clock["early"] == {}
    assert clock["run_after_call_us"] == {"min": 1000.0, "p01": 1000.0,
                                          "p50": 1000.0}
    host = [("bench.traced", 0, 100 * MS), ("essr.serve", 10 * MS, 40 * MS),
            ("PjitFunction(essr_extract)", 11 * MS, 1 * MS),
            ("PjitFunction(essr_extract)", 11.2 * MS, 0.5 * MS),   # nested
            ("PjitFunction(essr_fuse)", 20 * MS, 1 * MS)]

    def clock(extract_ms, fuse_ms):
        modules = [(0, "jit_essr_extract(1)", extract_ms * MS, MS),
                   (0, "jit_essr_fuse(2)", fuse_ms * MS, MS)]
        return pt.reduce_events([], modules, host)["clock"]
    assert clock(12, 25)["share"] == 1.0
    # a device clock that runs early puts the extract before its frame
    late = clock(9, 25)
    assert late["pairs"] == 2 and late["share"] == 0.5
    assert late["early"] == {"essr_extract": 1}
    assert late["run_after_call_us"]["min"] == pytest.approx(-2000.0)


def test_a_program_without_names_yields_no_phases():
    ops, modules, host = _frame()
    plain = lambda evs: [(d, m.replace("jit_essr_", "jit_"), s, t)
                         for d, m, s, t in evs]
    host = [h for h in host if not h[0].startswith("essr.")]
    red = pt.reduce_events(plain(ops), plain(modules), host)
    assert not red["named"]
    assert red["program"]["frame_spans"] == 0
    assert red["chips"][0]["executables"] == 6
    assert red["idle_by_span"] == {pt.NO_SPAN: pytest.approx(0.059)}


def _ctx(red, frames=2, chips=1):
    return {"trace": {}, "frames": frames, "chips": chips,
            "window_s": red["window_s"] if red else 0.0}


@pytest.mark.parametrize("metric,want", [
    ("extract_ms_per_frame", 5.0),
    ("route_ms_per_frame", 3.0),
    ("lane_dispatch_ms_per_frame", 0.0),
    ("bilinear_ms_per_frame", 0.0),
    ("subnet_lane_ms_per_frame", 15.0),
    ("fuse_ms_per_frame", 6.0),
    ("unnamed_ms_per_frame", 1.5),
    ("executables_per_frame", 3.0),
    ("host_ms_per_frame", 32.5),
    ("host_waits_per_frame", 1.5),
    ("program_idle_ms_per_frame", 19.5)])
def test_readers_on_a_synthetic_trace(metric, want, monkeypatch):
    red = pt.reduce_events(*_frame())
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: red)
    read = harness.load_reader(tinyroot.REPO, metric)
    assert read(_ctx(red)) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_readers_are_silent_without_a_trace(metric, monkeypatch):
    monkeypatch.setattr(pt, "for_ctx", lambda ctx: None)
    assert harness.load_reader(tinyroot.REPO, metric)(_ctx(None)) is None


def test_every_new_metric_is_declared_for_the_three_cells():
    bench = json.loads((tinyroot.REPO / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == CELLS
        assert (tinyroot.BENCH / "metrics" / f"{name}.py").exists()


def _on_cpu(monkeypatch):
    import jax
    monkeypatch.setattr(harness, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "device_peaks",
                        lambda peaks, kind: peaks["TPU v5 lite"])
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    monkeypatch.setattr(harness, "planned_label",
                        lambda config: f"{config['backend']}-interpret")


def test_traced_run_reads_the_program_spans(tmp_path, monkeypatch):
    """The harness's traced path on the CPU: the trace has no TPU plane,
    so the device metrics stay silent, and the host's spans are read."""
    root = tinyroot.make(tmp_path)
    _on_cpu(monkeypatch)
    monkeypatch.setattr(pt, "TRACE_DIR", root / ".bench_trace")
    res = harness.run_cell(root, tinyroot.TINY_CELL, 2**33 + 5, 3.0, True,
                           time.perf_counter())
    assert res["correct"], res["checks"]
    metrics = res["metrics"]
    # health, scores, the switcher's routing and the image: four host
    # waits in every host-dispatch frame
    assert metrics["host_waits_per_frame"]["value"] == 4.0
    assert metrics["host_ms_per_frame"]["value"] > 0
    assert "extract_ms_per_frame" not in metrics
