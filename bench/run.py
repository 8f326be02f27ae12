"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits nonzero, printing no result, where JAX sees no TPU or fewer chips
than the cell asks for. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` profiles part of the window and reports its
per-layer metrics, the device's busy and traced seconds and a breakdown.
The numbers the correctness check compared, each with its limit, are the
last lines on standard error and the last key of the result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import harness
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    for name, c in checks.items():
        rel = ">=" if name == "frames_compared" else "<="
        harness.log(f"check {name}: {c['value']} {rel} {c['limit']}")
    harness.log(f"correct: {result['correct']}")
    result["checks"] = checks                     # last key of the line
    print(json.dumps(result, default=_plain))
    return 0


def _plain(v):
    if hasattr(v, "item"):
        return v.item()
    raise TypeError(f"{type(v)} is not JSON")


if __name__ == "__main__":
    sys.exit(main())
