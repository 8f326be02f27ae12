"""Readings that the correctness limits of a cell are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--seconds 2]

For each of ``--seeds``: one run of the cell with a short window, the
program under test serving; prints the numbers that the check compared.
These give the lower reading of each limit. For each of
``--control-seeds``: the control, the reference computed one precision
below the configuration's (``bf16_3x`` for fp32 at HIGHEST) put in the
program's place on as many pool frames as a run compares; prints the same
numbers. These give the upper reading. Every line is one JSON object.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_checks(root, workload: str, seed: int) -> dict:
    """The check's numbers with the control in the program's place."""
    import frames
    import harness
    import reference
    spec = harness.load_cell(root, workload)
    config, traffic = spec["config"], spec["traffic"]
    plan = config["plan"]
    params = reference.init_weights(seed, config["model"])
    pool = frames.make_pool(seed, config["lr_hw"],
                            int(plan["patch"]) - int(plan["overlap"]),
                            traffic["shares"], int(traffic["pool_frames"]))
    sample = {}
    for j in range(min(harness.SAMPLE_FRAMES, len(pool))):
        out, ids, _ = reference.reference_frame(
            params, pool[j], config["model"], plan, precision="bf16_3x")
        sample[j] = {"j": j, "pool": j, "image": out, "ids": ids}
    return harness.check(sample, pool, params, config, config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import harness
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        res = harness.run_cell(ROOT, args.workload, seed, args.seconds, False,
                               time.perf_counter())
        print(json.dumps({"side": "program", "seed": seed,
                          "correct": res["correct"], "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        checks = control_checks(ROOT, args.workload, seed)
        print(json.dumps({"side": "control", "seed": seed,
                          "correct": harness.verdict(checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
