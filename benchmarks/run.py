"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. First run trains+caches the small
benchmark supernet (~minutes on 1 CPU core); subsequent runs reuse it.

    PYTHONPATH=src python -m benchmarks.run [--only tableX]
"""
import argparse
import sys
import time
import traceback

MODULES = [
    "table1_patch_size",
    "table2_sfb",
    "table34_boundary",
    "table56_quality",
    "table7_gan",
    "table9_dynamic",
    "table10_threshold",
    "table11_throughput",
    "table12_utilization",
    "fig4_edge_curves",
    "table_fusion",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:                                # noqa: BLE001
            failures.append(mod_name)
            print(f"# {mod_name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
