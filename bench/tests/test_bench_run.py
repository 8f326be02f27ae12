"""``bench/run.py`` refuses to run without a TPU, and the harness finds a
configuration, a traffic mix and a per-layer metric by name alone."""
import json
import os
import pathlib
import subprocess
import sys

import tinyroot
import harness


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(tinyroot.BENCH / "run.py"), "--workload",
         "x4_1080p_edges", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tinyroot.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_every_cell_finds_its_files():
    bench = json.loads((tinyroot.REPO / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        spec = harness.load_cell(tinyroot.REPO, cell["name"])
        assert spec["config"]["name"] == cell["config"]
        assert {m["name"] for m in spec["end_to_end"]} >= {"fps", "setup_s"}
        for m in spec["per_layer"]:
            assert callable(harness.load_reader(tinyroot.REPO, m["name"]))
    assert "TPU v5 lite" in json.loads(
        (tinyroot.BENCH / "peaks.json").read_text())


def test_added_files_are_found_by_name(tmp_path):
    root = tinyroot.make(tmp_path)
    (root / "bench" / "metrics" / "frames_in_trace.py").write_text(
        "def read(ctx):\n    return ctx['frames']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "frames_in_trace", "unit": "frames", "better": "higher",
        "source": "host_clock", "layer": "stream control", "moves": "fps",
        "workloads": [tinyroot.TINY_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_cell(root, tinyroot.TINY_CELL)
    assert spec["config"]["name"] == "tiny"
    assert spec["traffic"]["name"] == "tiny_mix"
    names = [m["name"] for m in spec["per_layer"]]
    assert "frames_in_trace" in names
    assert harness.load_reader(root, "frames_in_trace")({"frames": 7}) == 7
    assert pathlib.Path(root / "bench" / "run.py").exists()
