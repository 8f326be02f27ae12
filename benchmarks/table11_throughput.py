"""Paper Table XI: throughput/energy. No silicon here — we report
(a) before/after frames-per-second of the patch pipeline itself: the seed's
    host-side per-patch extract/fuse loops vs the device-resident
    gather/scatter paths, written to BENCH_table11_throughput.json so the
    perf trajectory is tracked across PRs,
(b) a ``--shards`` sweep of the data-parallel patch stream (shard_map over
    the 1-D patch mesh) on the same micro-config frame, recorded into the
    same JSON — on CPU the virtual devices share cores so this measures
    dispatch overhead + correctness, on real hardware it measures scaling,
(c) a quant sweep (``ExecutionPlan.quant``-style serving through the same
    pipeline): per PAMS mode (fxp10/int8) the ref-backend fake-quant frame
    fps and its SNR vs the fp32 pipeline, plus a pallas-int8
    integer-consistency flag (kernel codes bit-exact vs the jnp integer
    reference on one patch batch) — all recorded into the same JSON,
(d) a dispatch sweep on the same mixed frame: host dispatch (per-frame
    edge-score sync + Python bucket loop) vs the fused single-dispatch frame
    executable (``ExecutionPlan.dispatch="fused"``), single-frame and
    streamed (double-buffered ``inflight=2``), plus fused-vs-host allclose
    conformance across backends and int8 quant — recorded into the same
    JSON and gated by scripts/bench_gate.py (fused must never be slower
    than host beyond tolerance),
(e) a fusion sweep (``ExecutionPlan.fusion``): the layer-fused per-op
    kernel stack vs the group-fused subnet megakernel on the same mixed
    frame — interleaved best-of wall time (group must never lose beyond
    tolerance) plus the static ``feature_hbm_bytes`` of both traced chains
    (priced by analysis/cost_model.py; the >= 50% reduction the gate
    enforces is the portable form of the paper's 79% claim),
(f) a multi-stream sweep (``ExecutionPlan.streams``): N tenant streams
    packed into ONE fused dispatch per admission tick
    (``SREngine.serve_streams``) vs N solo fused engines serving the same
    frames — aggregate fps both ways, the mux/solo ratio, and a
    zero-tolerance conformance flag (capacity pinned identically on both
    sides, so the multiplexed outputs must match the solo engines exactly)
    — recorded into the same JSON and gated by scripts/bench_gate.py,
(g) measured CPU frame throughput per subnet through `SREngine`, once per
    backend ("ref" pure-JAX jit vs "pallas" fused kernel groups, interpret
    mode on CPU), exercising the full patch->route->batch->fuse pipeline, and
(h) the TPU-side projection from the dry-run roofline (results/dryrun),
    i.e. the frames/s one v5e chip supports at the measured bytes/flops.
Power/gate count are N/A on CPU and stated as such."""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, get_trained_essr, timed
from repro.api import ExecutionPlan, SREngine
from repro.core.adaptive import SwitchingConfig
from repro.core.pipeline import edge_selective_sr
from repro.launch.cache import REPO_ROOT
from repro.launch.mesh import make_patch_mesh
from repro.models.essr import ESSRConfig, init_essr
from repro.runtime.guard import FaultPlan

BENCH_JSON = os.path.join(os.path.dirname(__file__), os.pardir,
                          "BENCH_table11_throughput.json")


def _best_of(fn, reps: int) -> float:
    """us per call, minimum over ``reps`` — the noise-robust estimator for a
    deterministic computation on a shared CPU (means smear scheduler jitter
    into the ratio)."""
    import time
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _measure_frame(params, cfg, frame, label: str) -> dict:
    """One frame, both ways: seed per-patch loops ("before") vs vectorized
    gather/scatter ("after"), identical weights and routing; the subnet
    forward is byte-for-byte the same code on both sides."""
    run_new = lambda: edge_selective_sr(params, frame, cfg, backend="ref").image
    run_loop = lambda: edge_selective_sr(params, frame, cfg, backend="ref",
                                         use_loop_reference=True).image
    img_new = jax.block_until_ready(run_new())      # warm jit + geometry cache
    img_loop = jax.block_until_ready(run_loop())
    allclose = bool(np.allclose(np.asarray(img_new), np.asarray(img_loop),
                                rtol=1e-5, atol=1e-5))
    us_new = _best_of(run_new, reps=5)
    us_loop = _best_of(run_loop, reps=3)
    emit(f"table11_patch_pipeline_{label}_before_loop", us_loop,
         f"fps={1e6 / us_loop:.3f}")
    emit(f"table11_patch_pipeline_{label}_after_vectorized", us_new,
         f"fps={1e6 / us_new:.3f};speedup_x={us_loop / us_new:.2f};"
         f"allclose={allclose}")
    return {
        "before_seed_loop": {"us_per_frame": round(us_loop, 1),
                             "fps": round(1e6 / us_loop, 3)},
        "after_vectorized": {"us_per_frame": round(us_new, 1),
                             "fps": round(1e6 / us_new, 3)},
        "speedup_x": round(us_loop / us_new, 2),
        "allclose_vs_seed_loop": allclose,
    }


def _measure_shards(params, cfg, frame, shard_counts) -> dict:
    """The ``--shards`` sweep: the same micro-config frame through the
    data-parallel patch stream at each shard count. Counts beyond the
    visible device count are recorded as skipped (never silently dropped);
    every row is checked against the UNSHARDED pipeline (computed up front,
    so a ``--shards 4,2`` sweep order cannot silently compare sharded vs
    sharded)."""
    ref_img = np.asarray(jax.block_until_ready(
        edge_selective_sr(params, frame, cfg, backend="ref").image))
    rows = {}
    for s in shard_counts:
        if s > jax.device_count():
            rows[str(s)] = {"skipped": f"{jax.device_count()} devices visible"}
            emit(f"table11_shard_sweep_{s}", 0.0,
                 f"skipped;devices={jax.device_count()}")
            continue
        mesh = make_patch_mesh(s) if s > 1 else None
        run = lambda: edge_selective_sr(params, frame, cfg, backend="ref",
                                        mesh=mesh).image
        img = jax.block_until_ready(run())       # warm jit + shard_map cache
        allclose = bool(np.allclose(np.asarray(img), ref_img,
                                    rtol=1e-5, atol=1e-5))
        us = _best_of(run, reps=5)
        emit(f"table11_shard_sweep_{s}", us,
             f"fps={1e6 / us:.3f};allclose_vs_1shard={allclose}")
        rows[str(s)] = {"us_per_frame": round(us, 1),
                        "fps": round(1e6 / us, 3),
                        "allclose_vs_1shard": allclose}
    return rows


def _measure_quant(params, cfg, frame) -> dict:
    """The quant sweep: the mixed-content frame through the quantized
    serving path per PAMS mode. Alphas are PTQ-calibrated from the frame's
    own patch batch (the content being served is the honest calibration
    set for a single-frame micro-benchmark). SNR is measured against the
    fp32 pipeline output — the machine-portable accuracy signal the bench
    gate defends (absolute PSNR would move with the random-init weights)."""
    from repro.core.patching import get_geometry
    from repro.kernels.qconv import essr_forward_qkernels, essr_forward_qref
    from repro.quant.pams import build_quant_pack

    h, w = int(frame.shape[0]), int(frame.shape[1])
    g = get_geometry(h, w, 32, 2, cfg.scale)
    sample = g.extract(frame)[:16]
    fp_img = np.asarray(jax.block_until_ready(
        edge_selective_sr(params, frame, cfg, backend="ref").image))
    rows = {}
    packs = {}
    for mode in ("fxp10", "int8"):
        pack = packs[mode] = build_quant_pack(params, cfg, mode, sample)
        run = lambda: edge_selective_sr(params, frame, cfg, backend="ref",
                                        quant=pack).image
        img = np.asarray(jax.block_until_ready(run()))    # warm quant jits
        err = img - fp_img
        snr_db = float(10 * np.log10(np.mean(fp_img ** 2)
                                     / max(np.mean(err ** 2), 1e-20)))
        us = _best_of(run, reps=3)
        emit(f"table11_quant_{mode}", us,
             f"fps={1e6 / us:.3f};snr_db_vs_fp32={snr_db:.2f}")
        rows[mode] = {"us_per_frame": round(us, 1),
                      "fps": round(1e6 / us, 3),
                      "snr_db_vs_fp32": round(snr_db, 2)}

    # integer-consistency spot check (cheap, hard-gated in CI): the pallas
    # int8 kernel chain must be bit-exact vs the jnp integer reference
    batch = g.extract(frame)[:8]
    ker = essr_forward_qkernels(params, batch, cfg, width=cfg.channels,
                                pack=packs["int8"])
    ref = essr_forward_qref(params, batch, cfg, cfg.channels,
                            pack=packs["int8"])
    bitexact = bool(np.array_equal(np.asarray(ker), np.asarray(ref)))
    emit("table11_quant_pallas_int8_bitexact", 0.0, f"bitexact={bitexact}")
    return {"modes": rows, "pallas_int8_bitexact": bitexact}


def _stable_switching() -> SwitchingConfig:
    """Threshold adaptation frozen (never raise, never decay): the stream
    rows below measure dispatch rate, not Algorithm-1 behaviour — moving
    thresholds would change routing (and recompile fused capacity profiles)
    mid-measurement."""
    return SwitchingConfig(frame_high=10 ** 9, frame_low=0)


def _measure_dispatch(params, cfg, frame, stream_frames: int = 6) -> dict:
    """Host vs fused dispatch on the steady-state mixed-routing frame.

    The single-frame rows time post-warmup ``upscale`` calls with host and
    fused reps INTERLEAVED (best-of each): machine-load drift then shifts
    both sides together instead of masquerading as a dispatch speedup —
    ``fused_speedup_x`` is the ratio the CI gate defends. The stream rows
    time ``SREngine.stream`` end-to-end over ``stream_frames`` identical
    frames (host dispatch vs the double-buffered fused executor at
    ``inflight=2``), thresholds frozen so every frame routes identically."""
    host = SREngine(params, cfg)
    fused = SREngine(params, cfg, plan=ExecutionPlan(dispatch="fused"))
    img_h = np.asarray(jax.block_until_ready(host.upscale(frame).image))
    r_f = fused.upscale(frame)                   # warm: probe + compile
    allclose = bool(np.allclose(np.asarray(r_f.image), img_h,
                                rtol=1e-5, atol=1e-5))
    spilled = list(r_f.spill_counts)
    us_host = us_fused = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(host.upscale(frame).image)
        us_host = min(us_host, (time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(fused.upscale(frame).image)
        us_fused = min(us_fused, (time.perf_counter() - t0) * 1e6)

    def stream_rate(plan) -> float:
        eng = SREngine(params, cfg, plan=plan, switching=_stable_switching())
        list(eng.stream([frame] * 2))            # warm compile + capacity
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            list(eng.stream([frame] * stream_frames))
            best = min(best, (time.perf_counter() - t0) / stream_frames)
        return best * 1e6

    us_stream_host = stream_rate(ExecutionPlan())
    us_stream_async = stream_rate(ExecutionPlan(dispatch="fused", inflight=2))

    speedup = us_host / us_fused
    async_speedup = us_stream_host / us_stream_async
    emit("table11_dispatch_host", us_host, f"fps={1e6 / us_host:.3f}")
    emit("table11_dispatch_fused", us_fused,
         f"fps={1e6 / us_fused:.3f};speedup_x={speedup:.2f};"
         f"allclose={allclose}")
    emit("table11_dispatch_fused_async", us_stream_async,
         f"fps={1e6 / us_stream_async:.3f};"
         f"stream_speedup_x={async_speedup:.2f}")
    return {
        "host": {"us_per_frame": round(us_host, 1),
                 "fps": round(1e6 / us_host, 3)},
        "fused": {"us_per_frame": round(us_fused, 1),
                  "fps": round(1e6 / us_fused, 3),
                  "allclose_vs_host": allclose,
                  "spilled_patches": spilled},
        "host_stream": {"us_per_frame": round(us_stream_host, 1),
                        "fps": round(1e6 / us_stream_host, 3)},
        "fused_async_inflight2": {"us_per_frame": round(us_stream_async, 1),
                                  "fps": round(1e6 / us_stream_async, 3)},
        # the headline ratios: single-frame dispatch win + streamed
        # double-buffered win, both measured back-to-back on this machine
        "fused_speedup_x": round(speedup, 2),
        "fused_async_stream_speedup_x": round(async_speedup, 2),
    }


def _measure_streams(params, cfg, frame, n_streams: int = 4,
                     ticks: int = 3) -> dict:
    """Multi-stream continuous batching (``ExecutionPlan.streams``): N
    tenant streams through ONE fused dispatch per admission tick vs N solo
    fused engines serving the same frames back-to-back. Capacity is PINNED
    identically on both sides — with auto-probed capacity the shared pool
    lends a tenant the other streams' slack (statistical multiplexing, a
    feature), which makes exact solo-conformance ill-posed. Per-stream
    content differs (rolled copies of the mixed frame), so a cross-stream
    scatter-back mixup cannot hide behind identical tenants. The
    ``mux_vs_solo_x`` ratio is measured in the SAME run on the SAME
    machine, so it travels across hosts; the CI gate floors it at 0.9x and
    zero-tolerates conformance drift. Both sides serve at the recommended
    streaming config (``inflight=2``): double-buffering overlaps each
    side's host-side control work with device compute, which is exactly
    the steady state a deployment runs in."""
    h, w = int(frame.shape[0]), int(frame.shape[1])
    geom = ExecutionPlan().geometry(h, w, cfg.scale)
    cap = (0, geom.n, geom.n)                    # per-stream; spill-free
    streams = [[jnp.roll(frame, 17 * (s + 1) * (t + 1), axis=1)
                for t in range(ticks)] for s in range(n_streams)]

    solo_plan = ExecutionPlan(dispatch="fused", capacity=cap, inflight=2)
    solos = [SREngine(params, cfg, plan=solo_plan,
                      switching=_stable_switching())
             for _ in range(n_streams)]
    solo_imgs = [[np.asarray(r.image) for r in eng.stream(fs)]     # warm
                 for eng, fs in zip(solos, streams)]

    mux = SREngine(params, cfg, switching=_stable_switching(),
                   plan=ExecutionPlan(dispatch="fused", capacity=cap,
                                      streams=n_streams, inflight=2))
    results = list(mux.serve_streams([list(fs) for fs in streams]))  # warm
    allclose = all(
        np.allclose(np.asarray(r.image),
                    solo_imgs[r.stream_id][i // n_streams],
                    rtol=1e-5, atol=1e-5)
        for i, r in enumerate(results))
    bit_equal = all(
        np.array_equal(np.asarray(r.image),
                       solo_imgs[r.stream_id][i // n_streams])
        for i, r in enumerate(results))
    # interleaved best-of-5: solo and mux alternate within each round so a
    # slow machine phase (allocator churn, background load) penalizes both
    # sides, and the min of 5 lets each reach its floor — separate
    # best-of-2 loops made the ratio swing ~15% run to run
    t_solo = t_mux = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for eng, fs in zip(solos, streams):
            list(eng.stream(fs))
        t_solo = min(t_solo, time.perf_counter() - t0)
        t0 = time.perf_counter()
        list(mux.serve_streams([list(fs) for fs in streams]))
        t_mux = min(t_mux, time.perf_counter() - t0)

    one = SREngine(params, cfg, switching=_stable_switching(),
                   plan=ExecutionPlan(dispatch="fused", capacity=cap,
                                      inflight=2))
    list(one.serve_streams([list(streams[0])]))                      # warm
    t_one = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        list(one.serve_streams([list(streams[0])]))
        t_one = min(t_one, time.perf_counter() - t0)

    total = n_streams * ticks
    fps_solo, fps_mux = total / t_solo, total / t_mux
    ratio = fps_mux / fps_solo
    emit("table11_multi_stream_solo_aggregate", t_solo / total * 1e6,
         f"fps={fps_solo:.3f};engines={n_streams}")
    emit("table11_multi_stream_mux_aggregate", t_mux / total * 1e6,
         f"fps={fps_mux:.3f};mux_vs_solo_x={ratio:.3f};"
         f"allclose={allclose};bit_equal={bit_equal}")
    return {
        "streams": n_streams, "ticks": ticks,
        "capacity_per_stream": list(cap),
        "solo_aggregate": {"fps": round(fps_solo, 3),
                           "engines": n_streams},
        "mux_aggregate": {"fps": round(fps_mux, 3),
                          "allclose_vs_solo": allclose,
                          "bit_equal_vs_solo": bit_equal},
        "single_stream": {"fps": round(ticks / t_one, 3)},
        "mux_vs_solo_x": round(ratio, 3),
    }


def _measure_resilience(params, cfg, frame, reps: int = 5) -> dict:
    """Cost and conformance of the serving guard (``plan.on_poison`` /
    `repro.runtime.guard`):

      * ``guarded_vs_unguarded_x`` — fused-dispatch fps with in-graph
        health verdicts + sanitize vs verdicts off, INTERLEAVED best-of
        like the dispatch sweep so load drift cancels. The CI gate floors
        this at 0.95x: the verdict is three fused reductions and must stay
        under a 5% tax.
      * ``clean_bit_equal`` — on a clean frame the sanitize path must be a
        bit-level no-op (zero tolerance: a guarded server that perturbs
        healthy output is wrong, not slow).
      * ``chaos`` — a seeded `FaultPlan` storm through ``serve_streams``
        (poison + injected backend failures + quarantine): the run must
        finish without an escaped exception and two identical runs must
        produce identical degradation ledgers (zero tolerance on both)."""
    off = SREngine(params, cfg, switching=_stable_switching(),
                   plan=ExecutionPlan(dispatch="fused", on_poison="off"))
    on = SREngine(params, cfg, switching=_stable_switching(),
                  plan=ExecutionPlan(dispatch="fused",
                                     on_poison="sanitize"))
    img_off = np.asarray(jax.block_until_ready(off.upscale(frame).image))
    img_on = np.asarray(jax.block_until_ready(on.upscale(frame).image))
    clean_bit_equal = bool(np.array_equal(img_off, img_on))
    us_off = us_on = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(off.upscale(frame).image)
        us_off = min(us_off, (time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(on.upscale(frame).image)
        us_on = min(us_on, (time.perf_counter() - t0) * 1e6)
    ratio = us_off / us_on                       # guarded fps / unguarded fps

    def chaos_run():
        fp = FaultPlan(seed=7, poison_rate=0.5, poison_kinds=("nan", "inf"),
                       backend_failure_rate=0.2, target_streams=(1,))
        h, w = int(frame.shape[0]), int(frame.shape[1])
        geom = ExecutionPlan().geometry(h, w, cfg.scale)
        eng = SREngine(params, cfg, switching=_stable_switching(),
                       plan=ExecutionPlan(dispatch="fused", streams=3,
                                          capacity=(0, geom.n, geom.n),
                                          on_poison="raise",
                                          quarantine_ticks=1, faults=fp))
        streams = [[jnp.roll(frame, 13 * (s + 1) * (t + 1), axis=1)
                    for t in range(3)] for s in range(3)]
        outs = list(eng.serve_streams(streams))
        trace = [(o.stream_id, o.health, o.degraded) for o in outs]
        return trace, eng.summary().get("degradations", {}).get("by_kind",
                                                                {})

    crash_free = True
    deterministic = False
    by_kind = {}
    try:
        t1, k1 = chaos_run()
        t2, k2 = chaos_run()
        deterministic = (t1 == t2 and k1 == k2)
        by_kind = k1
    except Exception as e:
        crash_free = False
        by_kind = {"escaped": repr(e)}
    emit("table11_resilience_guarded", us_on,
         f"fps={1e6 / us_on:.3f};guarded_vs_unguarded_x={ratio:.3f};"
         f"clean_bit_equal={clean_bit_equal};crash_free={crash_free};"
         f"deterministic={deterministic}")
    return {
        "unguarded": {"us_per_frame": round(us_off, 1),
                      "fps": round(1e6 / us_off, 3)},
        "guarded_sanitize": {"us_per_frame": round(us_on, 1),
                             "fps": round(1e6 / us_on, 3)},
        "guarded_vs_unguarded_x": round(ratio, 3),
        "clean_bit_equal": clean_bit_equal,
        "chaos": {"crash_free": crash_free,
                  "deterministic": deterministic,
                  "by_kind": by_kind},
    }


def _measure_fusion(params, cfg, frame) -> dict:
    """Layer fusion (per-op kernel stack: BSConv -> 5xSFB -> DSConv, features
    crossing HBM at every group boundary) vs group fusion (the
    `kernels/megakernel.py` single launch, features resident in VMEM scratch)
    on the mixed-routing frame.

    Two signals, both gated by scripts/bench_gate.py:

      * measured: interleaved best-of wall time of the edge-selective frame
        with ``fusion="layer"`` vs ``fusion="group"`` on the pallas backend —
        group must never be slower beyond tolerance;
      * static: `analysis.cost_model.price_jaxpr` over the traced all-C54
        patch batch through both chains — ``feature_hbm_bytes`` (rank-4
        activation traffic across HBM) must shrink by >= 50%, the
        machine-portable form of the paper's 79% inter-group traffic
        reduction (Table XI rides on exactly this VMEM residency).
    """
    from repro.analysis.cost_model import price_jaxpr
    from repro.core.patching import get_geometry
    from repro.kernels.megakernel import (autotune_report,
                                          essr_forward_megakernel,
                                          essr_forward_qmegakernel)
    from repro.kernels.ops import essr_forward_kernels
    from repro.kernels.qconv import essr_forward_qkernels
    from repro.quant.pams import build_quant_pack

    h, w = int(frame.shape[0]), int(frame.shape[1])
    g = get_geometry(h, w, 32, 2, cfg.scale)
    batch = g.extract(frame)          # every patch as C54: the traffic ceiling
    pack = build_quant_pack(params, cfg, "int8", batch[:16])
    chains = {
        "layer": lambda p, x: essr_forward_kernels(p, x, cfg, interpret=True),
        "group": lambda p, x: essr_forward_megakernel(p, x, cfg,
                                                      interpret=True),
        "layer-int8": lambda p, x: essr_forward_qkernels(
            p, x, cfg, pack=pack, interpret=True),
        "group-int8": lambda p, x: essr_forward_qmegakernel(
            p, x, cfg, pack=pack, interpret=True),
    }
    static = {}
    for label, fn in chains.items():
        c = price_jaxpr(jax.make_jaxpr(fn)(params, batch))
        static[label] = {"macs": c.macs, "hbm_bytes": c.hbm_bytes,
                         "feature_hbm_bytes": c.feature_bytes}
    red_fp = 1.0 - (static["group"]["feature_hbm_bytes"]
                    / max(static["layer"]["feature_hbm_bytes"], 1))
    red_q = 1.0 - (static["group-int8"]["feature_hbm_bytes"]
                   / max(static["layer-int8"]["feature_hbm_bytes"], 1))

    run_layer = lambda: edge_selective_sr(params, frame, cfg,
                                          backend="pallas",
                                          fusion="layer").image
    run_group = lambda: edge_selective_sr(params, frame, cfg,
                                          backend="pallas",
                                          fusion="group").image
    img_l = jax.block_until_ready(run_layer())          # warm both jits
    img_g = jax.block_until_ready(run_group())
    allclose = bool(np.allclose(np.asarray(img_l), np.asarray(img_g),
                                rtol=1e-5, atol=1e-5))
    # interleaved best-of: machine-load drift shifts both fusion modes
    # together instead of masquerading as a fusion speedup (same estimator
    # as the dispatch sweep)
    us_layer = us_group = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run_layer())
        us_layer = min(us_layer, (time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter()
        jax.block_until_ready(run_group())
        us_group = min(us_group, (time.perf_counter() - t0) * 1e6)
    speedup = us_layer / us_group
    tune = autotune_report(cfg.channels, 32, cfg.scale, cfg.n_sfb)
    emit("table11_fusion_layer", us_layer, f"fps={1e6 / us_layer:.3f}")
    emit("table11_fusion_group", us_group,
         f"fps={1e6 / us_group:.3f};speedup_x={speedup:.2f};"
         f"allclose={allclose};feature_reduction={red_fp:.3f}")
    return {
        "layer": {"us_per_frame": round(us_layer, 1),
                  "fps": round(1e6 / us_layer, 3)},
        "group": {"us_per_frame": round(us_group, 1),
                  "fps": round(1e6 / us_group, 3),
                  "allclose_vs_layer": allclose},
        "group_speedup_x": round(speedup, 2),
        "static_costs": static,
        # the headline ratios the gate floors at 0.5 (paper: 0.79)
        "feature_hbm_reduction": round(red_fp, 4),
        "feature_hbm_reduction_int8": round(red_q, 4),
        "paper_feature_hbm_reduction": 0.79,
        # the roofline-driven block pick the megakernel launches with
        "autotune": {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in tune.items()},
    }


def _dispatch_conformance(params, cfg, hw: int = 96) -> dict:
    """Fused-vs-host allclose across backends and quant on a small mixed
    frame (small because pallas-interpret is the CPU correctness path, not
    a fast one): the zero-tolerance flags the bench gate enforces."""
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, hw), jnp.linspace(0, 1, hw),
                          indexing="ij")
    smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
    noise = jax.random.uniform(jax.random.PRNGKey(3), (hw, hw, 3))
    frame = jnp.where((yy < 0.5)[..., None], smooth, noise)
    rows = {}
    for backend in ("ref", "pallas"):
        for quant in (None, "int8"):
            plan_h = ExecutionPlan(quant=quant)
            host = SREngine(params, cfg, plan=plan_h, backend=backend)
            fused = SREngine(params, cfg,
                             plan=plan_h.replace(dispatch="fused"),
                             backend=backend)
            r_h, r_f = host.upscale(frame), fused.upscale(frame)
            ok = bool(np.allclose(np.asarray(r_h.image),
                                  np.asarray(r_f.image),
                                  rtol=1e-5, atol=1e-5)
                      and np.array_equal(np.asarray(r_h.ids),
                                         np.asarray(r_f.ids)))
            # key by the REQUESTED backend+quant, not the served label: the
            # served label carries the platform-dependent "-interpret"
            # suffix, which would make a CPU-committed baseline structurally
            # unmatchable on accelerator hardware in bench_gate
            label = backend + ("" if quant is None else f"-{quant}")
            rows[label] = ok
            emit(f"table11_dispatch_conformance_{label}", 0.0,
                 f"allclose={ok};served={r_f.backend}")
    return rows


def bench_patch_pipeline(out_json: str = BENCH_JSON,
                         shard_counts=(1, 2, 4)) -> dict:
    """Host-loop removal, measured on one 480x270 -> x4 frame through the
    full edge-selective pipeline (threshold routing):

      * "smooth" — a gradient frame every patch of which routes to bilinear,
        the content the paper's edge-selective premise optimizes for; frame
        time is the patch pipeline itself, so this row isolates the
        extract/route/fuse speedup;
      * "noise"  — uniform noise routes everything to C54, so the (unchanged,
        shared) conv forward dominates and bounds the frame-level gain.

    Fresh-init weights: routing depends only on frame content, and the
    forward pass is identical on both sides of the comparison."""
    lr_h, lr_w, scale = 270, 480, 4
    cfg = ESSRConfig(scale=scale)
    params = init_essr(jax.random.PRNGKey(0), cfg)
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, lr_h), jnp.linspace(0, 1, lr_w),
                          indexing="ij")
    smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
    noise = jax.random.uniform(jax.random.PRNGKey(1), (lr_h, lr_w, 3))

    rows = {"smooth_all_bilinear": _measure_frame(params, cfg, smooth,
                                                  "smooth"),
            "noise_all_c54": _measure_frame(params, cfg, noise, "noise")}
    mixed = jnp.where((yy < 0.5)[..., None], smooth, noise)
    shard_rows = _measure_shards(params, cfg, mixed, shard_counts)
    # how much slower the sharded dispatch runs on THIS host relative to the
    # same run's single-device path: > 1 on virtual CPU meshes, where the
    # "devices" share cores and shard_map only adds partition overhead (see
    # docs/api.md — a real accelerator mesh is where shards>1 pays off)
    fps1 = shard_rows.get("1", {}).get("fps")
    sharded_fps = [r["fps"] for s, r in shard_rows.items()
                   if s != "1" and "fps" in r]
    shard_overhead = (round(fps1 / min(sharded_fps), 2)
                      if fps1 and sharded_fps else None)
    payload = {
        "bench": "table11_patch_pipeline",
        "frame_lr_hw": [lr_h, lr_w], "scale": scale, "backend": "ref",
        "patch": 32, "overlap": 2,
        # headline: the host-loop-removal speedup this PR targets (the smooth
        # frame, where the patch pipeline IS the frame cost); the noise row
        # reports the conv-bound worst case alongside
        "speedup_x": rows["smooth_all_bilinear"]["speedup_x"],
        "frames": rows,
        # the mixed-content frame routes to all three subnets, so the sweep
        # exercises sharded dispatch of every bucket
        "shard_sweep": shard_rows,
        "shard_sweep_devices": jax.device_count(),
        "shard_overhead_x": shard_overhead,
        # same mixed frame through the PAMS quantized serving path
        "quant_sweep": _measure_quant(params, cfg, mixed),
        # host vs fused single-dispatch frame executable (+ async stream)
        # on the same mixed-routing frame, post-warmup
        "dispatch_sweep": _measure_dispatch(params, cfg, mixed),
        # layer-fused per-op stack vs the group-fused megakernel on the
        # same mixed frame: measured wall time + static feature-HBM traffic
        "fusion_sweep": _measure_fusion(params, cfg, mixed),
        "dispatch_conformance": _dispatch_conformance(params, cfg),
        # N tenant streams through one fused dispatch vs N solo engines.
        # Cropped frame: the full mixed frame puts ~113 MB of patch
        # buffers in flight per conv lane, and the ratio of two such runs
        # inside one long-lived process is dominated by allocator/cache
        # noise, not packing cost. The crop keeps every subnet routed
        # (it straddles the smooth/noise boundary) with a working set
        # small enough that repeated measurements agree.
        "multi_stream": _measure_streams(params, cfg, mixed[:192, :192]),
        # serving-guard tax (in-graph health verdicts) + chaos conformance
        # on the same cropped mixed frame as the multi-stream rows
        "resilience": _measure_resilience(params, cfg, mixed[:192, :192]),
    }
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", default="1,2,4",
                    help="comma-separated shard counts for the sharded patch "
                         "stream sweep (counts beyond the visible devices are "
                         "recorded as skipped)")
    ap.add_argument("--out-json", default=BENCH_JSON,
                    help="where the patch-pipeline/shard-sweep record lands")
    ap.add_argument("--pipeline-only", action="store_true",
                    help="only the JSON-recorded pipeline + shard benches "
                         "(skip the trained-supernet CPU table and the TPU "
                         "projection; what scripts/bench_gate.py runs)")
    args = ap.parse_args()
    shard_counts = tuple(int(s) for s in args.shards.split(","))

    bench_patch_pipeline(out_json=args.out_json, shard_counts=shard_counts)
    if args.pipeline_only:
        return
    hw, scale = 96, 4
    frame = jax.random.uniform(jax.random.PRNGKey(0), (hw, hw, 3))
    hr_pix = (hw * scale) ** 2
    params, cfg = get_trained_essr(scale=scale)     # restore weights once
    engines = {"jax": SREngine(params, cfg, backend="ref"),
               "kernels": SREngine(params, cfg, backend="pallas")}

    for name, engine in engines.items():
        for width in (27, 54):
            reps = 3 if name == "jax" else 1
            us = timed(lambda: engine.upscale(frame, mode="all_patches",
                                              width=width).image, reps=reps)
            note = ("" if engine.backend_label != "pallas-interpret"
                    else ";note=interpret-mode(correctness path)")
            emit(f"table11_cpu_{name}_c{width}", us,
                 f"mpixels_per_s={hr_pix / us:.2f}{note}")

    # TPU projection from the dry-run artifact
    f = str(REPO_ROOT / "results/dryrun/single/essr-x4__serve_8k.json")
    if os.path.exists(f):
        d = json.load(open(f))
        r = d["roofline"]
        step_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
        fps_mesh = 1.0 / step_s if step_s > 0 else float("inf")
        emit("table11_tpu_projection", 0.0,
             f"dominant={r['dominant']};frame_step_s={step_s:.2e};"
             f"fps_on_256chips={fps_mesh:.0f};"
             f"mpixels_per_j=NA(no power on CPU);paper=4797")


if __name__ == "__main__":
    main()
