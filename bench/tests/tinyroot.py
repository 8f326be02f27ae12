"""A checkout-shaped directory holding the benchmark and one tiny cell,
for tests on the CPU: the real harness, at a size the interpreter runs in
seconds."""
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELL = "tiny_mixed"


def make(tmp: pathlib.Path, hw=(60, 90), scale=2, shares=None) -> pathlib.Path:
    """Copy of the benchmark under ``tmp`` with a tiny cell added by files
    and one ``BENCHMARK.json`` entry each, as a later change would add it."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    config = json.loads((BENCH / "configs" / "essr_x4_1080p.json").read_text())
    config.update(name="tiny", lr_hw=list(hw))
    config["model"]["scale"] = scale
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    traffic = {"name": "tiny_mix", "loop": "closed", "streams": 1, "pool_frames": 3,
               "shares": shares or {"smooth": 1, "texture": 1, "edges": 1}}
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps(traffic))
    bench["configs"].append({"name": "tiny", "source": "https://arxiv.org/abs/2503.20245",
                             "file": "bench/configs/tiny.json", "reduced": ["lr_hw"],
                             "why": "a CPU-sized frame"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "tiny_mix", "chips": 1, "why": "CPU test"})
    for m in bench["per_layer"]:
        m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
