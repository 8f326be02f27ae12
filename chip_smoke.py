#!/usr/bin/env python3
"""Serve the paper's deployment once on a TPU and check every answer.

ESSR_X4 at full width (the C54 supernet with 5 SFBs, its C27 subnet and
the bilinear floor) upscales seeded synthetic 1920x1080 LR frames to
7680x4320 through `SREngine`, the front door a user calls:

    python chip_smoke.py            # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4  # four chips: the sharded path only

One chip runs, in one process:
  (a) the ``ref`` backend in fp32 at full matmul precision: the reference;
  (b) ``pallas``, compiled kernels, layer fusion, host dispatch;
  (c) the same with fused dispatch, two frames in flight, four frames
      streamed through ``SREngine.stream``;
  (d) group fusion (one megakernel per subnet);
  (e) int8 with group fusion, against ``ref`` with int8 on the same frame,
      and against the integer reference for bit-exactness (reported).
``--chips 4`` serves one frame with the patch batch sharded over four
chips, host and fused dispatch, against the one-device result.

Each phase must route every patch as its reference does, stay within the
error bound printed with it, run compiled (no ``-interpret`` label) and
record no step down the degradation ladder. Any failure raises, so the
script exits nonzero; it prints its JSON verdict only as the very last
line of a run that passed. It needs a TPU: with no accelerator visible it
exits nonzero before any phase. Seconds and ms/frame it prints are
informational, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: LR frame (height, width): 1080p, upscaled x4 to 8K.
LR_HW = (1080, 1920)
#: Seed of the synthetic frames and of the random weights.
SEED = 0
#: fp32 phases: max |out - ref| / max |ref| (the weights are random, so a
#: PSNR on the [0, 1] range says nothing). Kernels whose matmuls took one
#: bf16 pass would land near 2e-2 on this chain; fp32 ones near 1e-6.
BOUND_FP32 = 1e-3
#: Sharded vs one-device result, same kernels: max |out - one| / max |one|.
BOUND_SHARDED = 1e-5
#: int8 vs the fake-quant ``ref`` int8: max |out - ref| in recon-site
#: steps. The two datapaths round at different sites; on the CPU they
#: differ by up to 13 steps on this frame (99.99th percentile: 6).
INT8_STEPS = 16


def require_devices(count: int):
    import jax
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU visible (JAX platform "
                         f"{devices[0].platform!r}); this script does not "
                         f"fall back to the CPU")
    if len(devices) < count:
        raise SystemExit(f"--chips {count} needs {count} TPU devices, "
                         f"{len(devices)} visible")
    return devices[0]


def render(seed: int):
    """One seeded LR frame: a bicubic-degraded synthetic 8K image."""
    import jax.numpy as jnp
    from repro.data.synthetic import degrade, random_image
    from repro.models.essr import ESSR_X4
    h, w = LR_HW
    s = ESSR_X4.scale
    return degrade(jnp.asarray(random_image(seed, h * s, w * s)), s)


def engine(plan, backend="pallas", switching=None):
    from repro.api import SREngine
    from repro.models.essr import ESSR_X4
    return SREngine.from_config(ESSR_X4, seed=SEED, plan=plan,
                                backend=backend, switching=switching)


def rel_err(img, ref) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(img - ref)) / jnp.max(jnp.abs(ref)))


def check_served(name: str, eng, results, expect_label: str) -> None:
    """Compiled, as planned, and never stepped down the ladder."""
    labels = {r.backend for r in results} | {eng.backend_label}
    assert labels == {expect_label}, f"{name}: served as {labels}"
    steps = eng.summary().get("degradations")
    assert steps is None, f"{name}: degradation ladder stepped: {steps}"


def check_routing(name: str, res, ref) -> None:
    ids, want = np.asarray(res.ids), np.asarray(ref.ids)
    assert np.array_equal(ids, want), (
        f"{name}: {int((ids != want).sum())} of {ids.size} patches routed "
        f"differently from the reference")


def check_close(name: str, res, ref, bound: float) -> None:
    err = rel_err(res.image, ref.image)
    print(f"  {name}: max|out-ref|/max|ref| = {err!r} (bound {bound!r})",
          flush=True)
    assert err <= bound, f"{name}: error {err} exceeds {bound}"


def timed_upscale(eng, frame, reps: int = 3):
    """(result, first-call seconds incl. compile, steady ms/frame)."""
    t0 = time.perf_counter()
    res = eng.upscale(frame)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        eng.upscale(frame)
    return res, first, (time.perf_counter() - t0) / reps * 1e3


def report(name: str, res, first: float, steady_ms: float) -> None:
    print(f"  {name}: counts (bilinear, C27, C54) = {res.counts}; "
          f"informational: first frame {first:.1f} s incl. compile, "
          f"steady {steady_ms:.1f} ms/frame", flush=True)


def phase_reference(frames):
    import jax
    from repro.api import ExecutionPlan
    eng = engine(ExecutionPlan(), backend="ref")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        refs = [eng.upscale(f) for f in frames]
    print(f"(a) ref fp32, {len(frames)} frames, "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    for i, r in enumerate(refs):
        print(f"  frame {i}: counts {r.counts}", flush=True)
    check_served("(a)", eng, refs, "ref")
    return refs


def phase_host(name: str, plan, frame, ref):
    eng = engine(plan)
    res, first, steady = timed_upscale(eng, frame)
    print(f"{name} pallas, fusion={plan.fusion}, dispatch={plan.dispatch}",
          flush=True)
    report(name, res, first, steady)
    check_served(name, eng, [res], "pallas")
    check_routing(name, res, ref)
    check_close(name, res, ref, BOUND_FP32)


def phase_fused_stream(frames, refs):
    from repro.api import ExecutionPlan
    from repro.core.adaptive import SwitchingConfig
    from repro.core.pipeline import snap_capacity
    # thresholds held and the C54 budget open, so the stream routes exactly
    # as the reference; capacity pinned to hold every frame's routing
    plan = ExecutionPlan(interpret=False, dispatch="fused", inflight=2)
    n = refs[0].ids.size
    caps = (0,) + tuple(snap_capacity(max(r.counts[k] for r in refs),
                                      plan.buckets, n) for k in (1, 2))
    plan = plan.replace(capacity=caps)
    eng = engine(plan, switching=SwitchingConfig(
        frame_high=10 ** 9, frame_low=0, c54_per_sec_budget=10 ** 9))
    t0 = time.perf_counter()
    results, stamps = [], []
    for res in eng.stream(frames):
        results.append(res)
        stamps.append(time.perf_counter())
    print(f"(c) pallas, fusion=layer, dispatch=fused, inflight=2, "
          f"capacity={caps}, {len(frames)} frames streamed", flush=True)
    report("(c)", results[0], stamps[0] - t0,
           (stamps[-1] - stamps[0]) / (len(stamps) - 1) * 1e3)
    assert len(results) == len(frames)
    check_served("(c)", eng, results, "pallas")
    for i, (res, ref) in enumerate(zip(results, refs)):
        assert not any(res.spill_counts), f"(c) frame {i} spilled"
        check_routing(f"(c) frame {i}", res, ref)
        check_close(f"(c) frame {i}", res, ref, BOUND_FP32)


def integer_reference(eng, frame, ids):
    """The frame through the jnp integer-domain forward, bucket by bucket:
    what the int8 kernels match bit for bit on the CPU."""
    import jax.numpy as jnp
    from repro.kernels.qconv import essr_forward_qref
    from repro.models.layers import bilinear_resize
    cfg = eng.cfg
    geom = eng.plan.geometry(frame.shape[0], frame.shape[1], cfg.scale)
    patches = geom.extract(frame)
    p = eng.plan.patch * cfg.scale
    out = jnp.zeros((patches.shape[0], p, p, 3), jnp.float32)
    for k, w in enumerate(cfg.subnet_widths()):
        idx = np.flatnonzero(ids == k)
        if idx.size == 0:
            continue
        batch = jnp.take(patches, jnp.asarray(idx), axis=0)
        y = (bilinear_resize(batch, cfg.scale) if w == 0 else
             essr_forward_qref(eng.params, batch, cfg, w, pack=eng.qpack))
        out = out.at[jnp.asarray(idx)].set(y)
    return geom.fuse_average(out)


def phase_int8(frame):
    import jax
    import jax.numpy as jnp
    from repro.api import ExecutionPlan
    from repro.kernels.qconv import act_qconsts
    ref_eng = engine(ExecutionPlan(quant="int8"), backend="ref")
    with jax.default_matmul_precision("highest"):
        ref = ref_eng.upscale(frame)
    check_served("(e) ref-int8", ref_eng, [ref], "ref-int8")
    eng = engine(ExecutionPlan(interpret=False, fusion="group",
                               quant="int8"))
    assert eng.qpack == ref_eng.qpack, "(e) calibrations differ"
    res, first, steady = timed_upscale(eng, frame)
    print("(e) pallas-int8, fusion=group, dispatch=host", flush=True)
    report("(e)", res, first, steady)
    check_served("(e)", eng, [res], "pallas-int8")
    check_routing("(e)", res, ref)
    step = max(act_qconsts(eng.qpack.act_scales(w)["recon"],
                           eng.qpack.qmax)[1]
               for w in eng.cfg.subnet_widths()[1:])
    err = float(jnp.max(jnp.abs(res.image - ref.image)))
    print(f"  (e) vs ref-int8: max|out-ref| = {err!r} = {err / step!r} "
          f"recon steps (bound {INT8_STEPS}); max|out-ref|/max|ref| = "
          f"{rel_err(res.image, ref.image)!r}", flush=True)
    assert err <= INT8_STEPS * step, f"(e) error {err} > {INT8_STEPS} steps"
    want = integer_reference(eng, frame, np.asarray(res.ids))
    diff = np.abs(np.asarray(res.image) - np.asarray(want))
    print(f"  (e) vs integer reference: bit_exact={not diff.any()}, "
          f"{int(np.count_nonzero(diff))} of {diff.size} values differ, "
          f"max|diff| = {float(diff.max())!r} "
          f"({float(diff.max()) / step!r} recon steps)", flush=True)


def run_one_chip() -> None:
    from repro.api import ExecutionPlan
    t0 = time.perf_counter()
    f0, f1 = render(SEED), render(SEED + 1)
    # flipped copies: a stream of four different frames from two renders
    frames = [f0, f1, f0[::-1], f1[:, ::-1]]
    print(f"set-up: {len(frames)} LR frames {frames[0].shape}, "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    refs = phase_reference(frames)
    phase_host("(b)", ExecutionPlan(interpret=False, fusion="layer"),
               frames[0], refs[0])
    phase_fused_stream(frames, refs)
    phase_host("(d)", ExecutionPlan(interpret=False, fusion="group"),
               frames[0], refs[0])
    phase_int8(frames[0])


def run_four_chips() -> None:
    import jax
    from repro.api import ExecutionPlan
    from repro.core.pipeline import sharded_forward
    frame = render(SEED)
    one_eng = engine(ExecutionPlan(interpret=False))
    one = one_eng.upscale(frame)
    print(f"one device: counts {one.counts}", flush=True)
    for dispatch in ("host", "fused"):
        name = f"shards=4 {dispatch}"
        eng = engine(ExecutionPlan(interpret=False, shards=4,
                                   dispatch=dispatch))
        mesh = eng.mesh
        assert mesh is not None and mesh.size == 4, f"{name}: mesh {mesh}"
        # the same TPU platform `require_devices` found
        assert {d.platform for d in mesh.devices.flat} == {
            jax.devices()[0].platform}
        res, first, steady = timed_upscale(eng, frame)
        report(name, res, first, steady)
        check_served(name, eng, [res], "pallas")
        check_routing(name, res, one)
        check_close(name, res, one, BOUND_SHARDED)
    # the patch batch itself lands on four devices, a quarter each
    geom = one_eng.plan.geometry(frame.shape[0], frame.shape[1],
                                 one_eng.cfg.scale)
    patches = geom.extract(frame)
    out = jax.block_until_ready(sharded_forward(
        one_eng.params, patches, one_eng.cfg, one_eng.cfg.channels,
        mesh=mesh, backend="pallas", interpret=False))
    shard_devices = {s.device for s in out.addressable_shards}
    rows = sorted(s.data.shape[0] for s in out.addressable_shards)
    print(f"sharded batch: {out.shape[0]} patches over "
          f"{len(out.sharding.device_set)} devices, rows per shard {rows}",
          flush=True)
    assert len(out.sharding.device_set) == 4 and len(shard_devices) == 4
    assert rows == [out.shape[0] // 4] * 4


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its comparison")
    args = ap.parse_args()
    if not __debug__:
        raise SystemExit("every check here is an assert: run without -O")
    import jax
    device = require_devices(args.chips)
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    run_four_chips() if args.chips == 4 else run_one_chip()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
