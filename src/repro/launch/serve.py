"""Serving launcher: the paper's deployment loop at reduced scale.

Streams synthetic frames through ``SREngine.stream`` (edge scores ->
Algorithm-1 adaptive thresholds -> per-subnet batched ESSR -> overlap+average
fusion) and prints the Table-XI-style summary (subnet shares, MAC saving,
latency). ``--quant fxp10|int8`` serves the PAMS quantized datapath instead
of fp32 (see docs/api.md "Quantized serving"). ``--dispatch fused`` serves
every frame as ONE compiled executable (in-graph capacity routing), and
``--inflight 2`` double-buffers the stream on top of it (see docs/api.md
"Dispatch modes & async streaming").

    PYTHONPATH=src python -m repro.launch.serve --frames 4 --hw 96
"""
from __future__ import annotations

import argparse
import collections

import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--hw", type=int, default=96, help="LR frame size (square)")
    ap.add_argument("--scale", type=int, default=4)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir from train.py")
    ap.add_argument("--budget", type=int, default=25500)
    ap.add_argument("--deadline-ms", type=float, default=0.0)
    ap.add_argument("--backend", default="ref", choices=("ref", "pallas"),
                    help="forward path: pure-JAX jit or fused Pallas kernels")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-parallel patch-stream shards (each gets its "
                         "own Algorithm-1 controller; dispatch uses up to "
                         "this many devices, degrading to one transparently)")
    ap.add_argument("--quant", default="none",
                    choices=("none", "fxp10", "int8"),
                    help="PAMS quantized serving: fxp10 (paper Sec. IV-H) or "
                         "int8 (TPU MXU datapath); alphas PTQ-calibrate at "
                         "engine construction")
    ap.add_argument("--dispatch", default="host", choices=("host", "fused"),
                    help="frame dispatch: host routing (default) or the "
                         "fused single-dispatch frame executable (capacity-"
                         "slotted in-graph routing; see docs/api.md)")
    ap.add_argument("--inflight", type=int, default=1,
                    help="async double-buffering depth for fused-dispatch "
                         "streaming: >= 2 overlaps frame N's compute with "
                         "frame N+1's ingest (one-frame control delay)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from repro.api import ExecutionPlan, SREngine
    from repro.core.adaptive import SwitchingConfig
    from repro.data.synthetic import degrade, random_image
    from repro.models.essr import ESSRConfig
    from repro.train.losses import psnr_y

    # frame counts scaled down from 8K: thresholds adapt around per-frame C54 share
    n_patches = (args.hw // 30 + 1) ** 2
    sw = SwitchingConfig(c54_per_sec_budget=args.budget,
                         frame_high=max(2, int(n_patches * 0.45)),
                         frame_low=max(1, int(n_patches * 0.30)))
    engine = SREngine.from_checkpoint(
        args.ckpt, cfg=ESSRConfig(scale=args.scale), backend=args.backend,
        plan=ExecutionPlan(shards=args.shards,
                           quant=None if args.quant == "none" else args.quant,
                           dispatch=args.dispatch, inflight=args.inflight),
        switching=sw, deadline_s=args.deadline_ms / 1e3 or None, verbose=True)
    print(f"serving backend: {engine.backend_label} "
          f"(dispatch={args.dispatch}, inflight={args.inflight})")
    engine.warmup((args.hw, args.hw))   # pre-pay trace+compile; the printed
                                        # per-frame latencies are steady-state

    # lazy frame source: only the in-flight window of HR frames stays live
    # (stream() pulls at most plan.inflight ahead of the results it yields,
    # so hr_pending never holds more than that — an 8K stream must not
    # materialize every frame up front)
    hr_pending = collections.deque()

    def lr_stream():
        for i in range(args.frames):
            hr = jnp.asarray(random_image(100 + i, args.hw * args.scale,
                                          args.hw * args.scale))
            hr_pending.append(hr)
            yield degrade(hr, args.scale)

    psnrs = []
    # stream() rather than per-frame serve(): under --dispatch fused with
    # --inflight >= 2 this is the double-buffered async executor
    for i, res in enumerate(engine.stream(lr_stream())):
        psnrs.append(float(psnr_y(res.image, hr_pending.popleft())))
        line = (f"frame {i}: PSNR_Y {psnrs[-1]:.2f} dB  "
                f"thresholds={res.thresholds}")
        if res.dispatch == "fused" and any(res.spill_counts):
            line += f"  spilled={res.spill_counts}"
        if res.shard_counts is not None and res.shard_deadline_missed is not None:
            line += (f"  shard_c54={[c[2] for c in res.shard_counts]}"
                     f"  demoted={list(res.shard_deadline_missed)}")
        print(line)
    s = engine.summary()
    print("\nsummary:", {k: v for k, v in s.items()})
    print(f"mean PSNR_Y {np.mean(psnrs):.2f} dB")


if __name__ == "__main__":
    main()
