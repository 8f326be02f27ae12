"""SREngine — the single facade over every ESSR inference entry point.

One engine owns:
  * ``params``  — the supernet weights (all subnets weight-shared, Sec. II-B),
  * ``cfg``     — the `ESSRConfig` architecture description,
  * ``plan``    — an `ExecutionPlan` (patch geometry, thresholds, bucket
                  schedule, subnet policy), frozen at construction,
  * ``backend`` — "ref" (pure-JAX jit) or "pallas" (fused kernel groups),
                  chosen ONCE instead of per call. For "pallas",
                  ``plan.interpret`` picks compiled vs interpreter dispatch
                  (None = auto: compiled on TPU/GPU, interpreter on CPU);
                  what actually ran is surfaced as FrameResult.backend
                  ("pallas" vs "pallas-interpret").

With ``plan.quant`` set ("fxp10" | "int8") the engine serves the PAMS
quantized datapath: per-subnet activation alphas are PTQ-calibrated at
construction (``calibrate=`` batch, or a deterministic synthetic default)
and cached as JSON alongside the bench-model cache; the "ref" backend serves
fake-quant emulation, "pallas" the integer-domain kernel stack
(`repro.kernels.qconv`). The served mode is appended to the backend label
("ref-fxp10", "pallas-int8", "pallas-interpret-int8", ...).

and exposes the paper's modes as methods returning one `FrameResult` shape:

  * ``upscale(frame)``                    — Fig. 1 edge-selective pipeline
  * ``upscale(frame, mode="all_patches")``— every patch through one subnet
  * ``reference(frame)``                  — whole-image convolution (Table III)
  * ``stream(frames)``                    — Algorithm-1 adaptive serving with
                                            deadline/straggler handling

Construction absorbs the checkpoint / cached-bench-model discovery that was
previously copy-pasted across `launch/serve.py` and the benchmarks:
``SREngine.from_config`` (fresh init) and ``SREngine.from_checkpoint``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re
import time
import warnings
from typing import Any, Deque, Dict, Iterable, Iterator, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.api.plan import ExecutionPlan
from repro.api.result import FrameResult, summarize_stats
from repro.core import subnet_policy as sp
from repro.core.adaptive import (AdaptiveSwitcher, ShardSwitcherBank,
                                 StreamSwitcherBank, SwitchingConfig)
from repro.core.edge_score import edge_score
from repro.core.phases import frame_span, span
from repro.core.pipeline import (compiled_cache_occupancy,
                                 configure_compiled_caches,
                                 edge_selective_sr, frame_health,
                                 fused_frame_fn, resolve_backend,
                                 sanitize_frame, snap_capacity,
                                 sr_all_patches_result, sr_whole)
from repro.kernels.dispatch import resolve_interpret
from repro.launch.cache import REPO_ROOT
from repro.launch.mesh import make_patch_mesh
from repro.models.essr import ESSRConfig, init_essr
from repro.runtime.guard import (FaultInjector, PoisonFrameError,
                                 ResilienceGuard)

#: Default location of the cached briefly-trained benchmark supernets
#: (written by benchmarks/common.get_trained_essr), under the checkout's
#: root; ``BENCH_CACHE`` overrides it.
DEFAULT_BENCH_CACHE = os.environ.get(
    "BENCH_CACHE", str(REPO_ROOT / "results" / "bench_models"))

MODES = ("edge_select", "all_patches", "whole")


def default_calibration_batch(patch: int, scale: int, n: int = 16,
                              seed: int = 1234) -> jax.Array:
    """Deterministic PTQ calibration batch: ``n`` synthetic LR patches in
    [0,1], one per procedural frame (the plain/texture/edges mixture the
    edge-selective router discriminates), sized to the plan's patch so the
    calibration forward sees serving-shaped batches."""
    from repro.data.synthetic import degrade, random_image
    return jnp.stack([
        degrade(jnp.asarray(random_image(seed + i, patch * scale,
                                         patch * scale)), scale)
        for i in range(n)])


class SREngine:
    """Facade over the ESSR inference pipeline. See module docstring."""

    def __init__(self, params: Dict[str, Any], cfg: ESSRConfig,
                 plan: Optional[ExecutionPlan] = None, backend: str = "ref",
                 switching: Optional[SwitchingConfig] = None,
                 deadline_s: Optional[float] = None,
                 calibrate: Optional[jax.Array] = None,
                 quant_cache: Optional[str] = None):
        resolve_backend(backend)            # fail fast on typos
        self.params = params
        self.cfg = cfg
        self.plan = plan if plan is not None else ExecutionPlan()
        self.backend = backend
        self.deadline_s = deadline_s
        # quantized serving (plan.quant): PTQ-calibrate per-subnet alphas
        # once, here — the pack is engine state like the mesh, so every
        # frame reuses the same lattice. ``calibrate`` is a (N,h,w,3) LR
        # batch in [0,1]; None falls back to a deterministic synthetic
        # batch covering the three content classes. ``quant_cache`` is a
        # directory to cache the alphas in (from_checkpoint passes the
        # bench-model cache), only consulted for the default batch.
        self.qpack = self._resolve_quant_pack(calibrate, quant_cache)
        # serving resilience (repro.runtime.guard): the sticky degradation
        # ladder from this engine's configured serving point, plus the
        # optional seeded fault harness (plan.faults). Engine state like the
        # mesh — the ladder level survives across frames by design.
        self.guard = ResilienceGuard(
            backend=backend, interpret=self.plan.interpret,
            quant_on=self.plan.quant is not None, fusion=self.plan.fusion,
            max_retries=self.plan.max_retries)
        self.injector = (FaultInjector(self.plan.faults)
                         if self.plan.faults is not None else None)
        self._frame_idx = 0            # monotone launch index (fault coins)
        base_switching = (switching if switching is not None
                          else SwitchingConfig(t1=self.plan.t1, t2=self.plan.t2))
        self.switcher = AdaptiveSwitcher(base_switching)
        # sharded patch stream (plan.shards > 1): routing/straggler control is
        # per-shard regardless of hardware (one Algorithm-1 controller each,
        # budgets split evenly); the device mesh only exists when more than
        # one device is visible — otherwise dispatch degrades transparently
        # to the single-device path with identical numerics.
        self.bank: Optional[ShardSwitcherBank] = None
        self.mesh = None
        self.lane_params = params
        if self.plan.shards > 1:
            self.bank = ShardSwitcherBank(base_switching,
                                          shards=self.plan.shards)
            avail = jax.device_count()
            if avail > 1:
                self.mesh = make_patch_mesh(min(self.plan.shards, avail))
                # every chip holds the whole network for the host-dispatch
                # lanes, placed once here: a lane given the weights on one
                # device would copy all of them to the other chips again at
                # every call. Every other path keeps ``params`` as given.
                self.lane_params = jax.device_put(
                    params, NamedSharding(self.mesh, PartitionSpec()))
                if avail < self.plan.shards:
                    warnings.warn(
                        f"plan.shards={self.plan.shards} but only {avail} "
                        f"devices visible; dispatching over {avail} "
                        f"(per-shard routing control unchanged)")
            else:
                warnings.warn(
                    f"plan.shards={self.plan.shards} on a single-device "
                    f"host; dispatch falls back to one device "
                    f"(per-shard routing control unchanged)")
        # multi-stream serving (plan.streams > 1): one Algorithm-1 controller
        # per tenant stream, budgets split by normalized QoS share. Engine
        # state like the shard bank — a per-call plan cannot change the
        # tenant set. serve_streams() drives it via StreamMultiplexer.
        self.stream_bank: Optional[StreamSwitcherBank] = None
        if self.plan.streams > 1:
            self.stream_bank = StreamSwitcherBank(
                base_switching, streams=self.plan.streams,
                shares=self.plan.stream_shares)
        self._macs = sp.SubnetMacs.make(cfg, self.plan.patch)
        # per-frame stream records, bounded: a long-running stream must not
        # grow host memory without limit (plan.stats_window newest frames;
        # summary() notes the window)
        self.stats: Deque[FrameResult] = collections.deque(
            maxlen=self.plan.stats_window)
        # fused dispatch state: the live capacity profile per geometry
        # (plan.capacity pins it; otherwise probed on the first frame of a
        # geometry and grown after any frame that spilled), and the set of
        # executables this engine has already traced+compiled — the
        # bookkeeping behind FrameResult.compiled / warmup()
        self._fused_caps: Dict[Tuple, Tuple[int, ...]] = {}
        self._warm: set = set()
        self._fused_last_done = 0.0    # marginal-latency clock (async stream)
        # compiled-object caches (frame executables, admission ticks, patch
        # geometries) are process-wide BoundedCaches; size them from the
        # plan's serving horizon — stats_window // 32, floored at 16 and
        # capped at 512, which lands on the historical 128 at the default
        # window of 4096. Last-constructed engine wins (the caches are
        # shared), which is the right bias: the most recent plan reflects
        # the live serving regime. Occupancy: FrameResult.summary() /
        # SREngine.summary().
        configure_compiled_caches(
            max(16, min(512, self.plan.stats_window // 32)))

    def _resolve_quant_pack(self, calibrate, quant_cache):
        """plan.quant -> calibrated `QuantPack` (None for fp32 serving)."""
        mode = self.plan.quant
        if mode is None:
            return None
        from repro.quant.pams import (build_quant_pack, load_quant_pack,
                                      params_fingerprint, save_quant_pack)
        if calibrate is None:
            calibrate = default_calibration_batch(self.plan.patch,
                                                  self.cfg.scale)
            cache_path = None
            if quant_cache:
                # keyed by the weights' content hash AND the plan's patch
                # size (the default calibration batch is patch-shaped, so
                # alphas from one patch size must not serve another): alphas
                # calibrated for other weights/configs never serve here
                fp = params_fingerprint(self.params)
                cache_path = os.path.join(
                    quant_cache, f"quant_alphas_{mode}_x{self.cfg.scale}"
                                 f"_sfb{self.cfg.n_sfb}_p{self.plan.patch}"
                                 f"_{fp}.json")
                cached = load_quant_pack(cache_path, fp)
                if cached is not None:
                    return cached
            pack = build_quant_pack(self.params, self.cfg, mode, calibrate)
            if cache_path:
                try:
                    os.makedirs(quant_cache, exist_ok=True)
                    save_quant_pack(cache_path, pack, fp)
                except OSError as e:
                    warnings.warn(f"quant alpha cache write failed: {e!r}")
            return pack
        # user-supplied calibration data: always calibrate fresh (the cache
        # is keyed by weights only and cannot tell batches apart)
        return build_quant_pack(self.params, self.cfg, mode,
                                jnp.asarray(calibrate))

    def _backend_label(self, plan: ExecutionPlan) -> str:
        """What actually executes, surfaced in FrameResult.backend: "pallas"
        only when the kernels compile (TPU/GPU or interpret=False); the CPU
        interpreter fallback is labeled "pallas-interpret" so consumers never
        mistake the correctness path for the fast one. A quant mode is
        appended ("ref-fxp10", "pallas-int8", "pallas-interpret-int8", ...)
        so a quantized frame can never masquerade as fp32."""
        base = self.backend
        if self.backend == "pallas" and resolve_interpret(plan.interpret):
            base = "pallas-interpret"
        return base if plan.quant is None else f"{base}-{plan.quant}"

    @property
    def backend_label(self) -> str:
        return self._backend_label(self.plan)

    def _variant_label(self, plan: ExecutionPlan, v) -> str:
        """`_backend_label` for a degradation-ladder rung: labels what the
        (possibly stepped-down) variant actually executes, so a frame served
        at a degraded level can never masquerade as the planned one."""
        base = v.backend
        if v.backend == "pallas" and resolve_interpret(v.interpret):
            base = "pallas-interpret"
        return (base if (plan.quant is None or not v.quant)
                else f"{base}-{plan.quant}")

    # -- serving resilience (plan.on_poison / plan.faults) -------------------

    def _next_index(self) -> int:
        """Monotone launch index — the deterministic coordinate fault coins
        and degradation events key on."""
        i = self._frame_idx
        self._frame_idx += 1
        return i

    def _ingest_frame(self, frame, p: ExecutionPlan, index: int):
        """Host-side dtype gate on every entry path. Wrong-dtype frames are
        the one poison class the traced graph cannot express (the executable
        is typed), so they resolve here: "raise" rejects, every other policy
        normalizes integer payloads by their dtype range (uint8 -> /255, so
        the content recovers instead of serving garbage)."""
        if not isinstance(frame, (jax.Array, np.ndarray)):
            frame = jnp.asarray(frame)
        if jnp.issubdtype(frame.dtype, jnp.floating):
            return jnp.asarray(frame)
        if p.on_poison == "raise":
            self.guard.record(index, "poison",
                              f"non-float frame dtype {frame.dtype}")
            raise PoisonFrameError(
                f"frame dtype {frame.dtype} is not floating point "
                f"(plan.on_poison='raise')")
        if p.on_poison != "off":
            self.guard.record(index, "poison",
                              f"non-float frame dtype {frame.dtype} "
                              f"normalized to float32")
        try:
            span = float(np.iinfo(np.dtype(str(frame.dtype))).max)
        except ValueError:
            span = 1.0
        return jnp.asarray(frame).astype(jnp.float32) / max(span, 1.0)

    def _host_health(self, frame, p: ExecutionPlan, index: int):
        """Health verdict + on_poison policy for the host-dispatch paths
        (they already sync per frame, so the jitted reduce costs nothing;
        fused dispatch computes the same verdict in-graph instead).
        Returns (frame, health tuple or None, route-to-bilinear flag)."""
        if p.on_poison == "off":
            return frame, None, False
        with span("essr.health"):
            health = frame_health(frame)
            with span("essr.wait.health"):
                health_t = tuple(int(c) for c in np.asarray(health))
            if not any(health_t):
                return frame, health_t, False
            self.guard.record(index, "poison",
                              f"frame health nan/inf/oob={health_t} "
                              f"(policy {p.on_poison})")
            if p.on_poison == "raise":
                raise PoisonFrameError(
                    f"frame failed health verdict nan/inf/oob={health_t} "
                    f"(plan.on_poison='raise')", health=health_t)
            return sanitize_frame(frame), health_t, p.on_poison == "bilinear"

    def _guarded_frames(self, frames: Iterable, stream_id: int = 0,
                        ) -> Iterator:
        """Iterate a tenant stream under the fault harness: ``plan.faults``
        wraps the iterator with seeded poison/error injection, and an
        iterator that raises ends the stream with a recorded retirement
        instead of killing the serving loop (the solo-stream analog of the
        multiplexer's per-tenant quarantine)."""
        it = iter(frames)
        if self.injector is not None:
            it = self.injector.wrap_stream(stream_id, it)
        n = 0
        while True:
            try:
                frame = next(it)
            except StopIteration:
                return
            except Exception as e:
                self.guard.record(n, "retire",
                                  f"stream {stream_id} iterator raised: "
                                  f"{e!r}")
                return
            yield frame
            n += 1

    # -- fused dispatch (plan.dispatch == "fused") ---------------------------

    def _mark_warm(self, key) -> bool:
        """True when ``key``'s executable was already compiled by this
        engine; marks it warm either way (the caller is about to run it).

        Best-effort bookkeeping: it mirrors the process-wide executable
        caches (`fused_frame_fn` / `get_geometry` BoundedCaches — sized from
        ``plan.stats_window`` at construction, 128 at the default window —
        and XLA's own jit cache) without sharing their eviction — an engine
        cycling through more combos than those caches hold can see a
        re-tracing frame reported ``compiled=True``. Cache occupancy (and
        the eviction count that diagnoses this) rides
        `FrameResult.summary()` / `SREngine.summary()`."""
        warm = key in self._warm
        self._warm.add(key)
        return warm

    def _snap_profile(self, desired, geom, p: ExecutionPlan
                      ) -> Tuple[int, ...]:
        """Per-subnet desired counts -> a capacity profile: entry 0 is 0
        (the bilinear lane runs dense), conv entries snap to the plan's
        bucket ladder (bounded recompilation). Profiles are cached
        UNclamped — the streaming C54 budget ceiling is applied per call
        in `_fused_caps_for`, so the same geometry serves both upscale()
        (full profile) and the stream (ceiling enforced) correctly no
        matter which seeded the cache."""
        return tuple([0] + [snap_capacity(int(d), p.buckets, geom.n)
                            for d in desired[1:]])

    def _c54_frame_budget(self) -> int:
        """Per-frame share of the Algorithm-1 C54/sec budget — the hard
        ceiling fused streaming enforces in-graph via the C54 capacity
        (overflow spills to C27, the paper's "the rest of the patches run
        with C27")."""
        c = self.switcher.cfg
        return max(1, int(c.c54_per_sec_budget) // max(c.fps, 1))

    def _fused_caps_for(self, geom, p: ExecutionPlan, frame,
                        thresholds: Tuple[float, float],
                        streaming: bool) -> Tuple[int, ...]:
        """Resolve the capacity profile for one frame. ``plan.capacity``
        pins it; otherwise the FIRST frame of a geometry is probed on the
        host (the only host routing sync fused dispatch ever pays — later
        frames reuse/grow the cached profile with no sync)."""
        widths = self.cfg.subnet_widths()
        if p.capacity is not None:
            if len(p.capacity) != len(widths):
                raise ValueError(
                    f"plan.capacity {p.capacity} must have one entry per "
                    f"subnet width {widths}")
            # a pinned profile is served verbatim, streaming or not: the
            # operator fixed the compiled shape, so its C54 entry IS the
            # per-frame ceiling (the budget-derived clamp below applies
            # only to auto profiles) — documented on ExecutionPlan.capacity
            return p.capacity
        key = geom.cache_key
        caps = self._fused_caps.get(key)
        if caps is None:
            t1, t2 = thresholds
            if p.on_poison != "off":
                # probe on the sanitized frame: a poisoned first frame must
                # not seed a garbage capacity profile for its whole geometry
                frame = sanitize_frame(frame)
            with span("essr.extract"):
                patches = geom.extract(frame)
            with span("essr.route"):
                scores = edge_score(patches)
                with span("essr.wait.scores"):
                    scores = np.asarray(scores)
                ids = sp.decide(scores, t1, t2)
                with span("essr.wait.route"):
                    ids = np.asarray(ids)
            counts = sp.subnet_counts(ids)
            caps = self._snap_profile(counts, geom, p)
            self._fused_caps[key] = caps
        if streaming:
            # the hard C54 ceiling applies to the STREAM only, per call:
            # the cached profile stays unclamped so a warmup()/upscale()
            # seeding cannot smuggle an over-budget capacity into serving,
            # and a stream-seeded profile does not force spills on later
            # single-frame upscale() calls
            caps = caps[:-1] + (min(caps[-1], self._c54_frame_budget()),)
        return caps

    def _grow_caps(self, geom, p: ExecutionPlan, counts, spills) -> None:
        """After a frame that spilled, grow the geometry's capacity profile
        to the bucket ceiling of the demand actually seen (served + spilled)
        so the next frame routes without demotion. Grow-only: shrinking
        would churn recompiles; the bucket ladder bounds total growth."""
        if p.capacity is not None or not any(spills[1:]):
            return
        old = self._fused_caps.get(geom.cache_key)
        if old is None:
            return
        desired = [c + s for c, s in zip(counts, spills)]
        new = self._snap_profile(desired, geom, p)
        merged = tuple(max(o, n) for o, n in zip(old, new))
        if merged != old:
            self._fused_caps[geom.cache_key] = merged

    def _launch_fused(self, frame, p: ExecutionPlan,
                      thresholds: Tuple[float, float],
                      streaming: bool) -> dict:
        """Dispatch one frame into the fused executable WITHOUT blocking.
        Returns the in-flight record the double-buffered stream finalizes
        later; host work here is bounded (geometry/caps lookups + the async
        dispatch), so frame N+1's ingest overlaps frame N's compute."""
        t0 = time.perf_counter()
        index = self._next_index()
        with frame_span("essr.launch", index):
            frame = self._ingest_frame(frame, p, index)
            geom = p.geometry(frame.shape[0], frame.shape[1], self.cfg.scale)
            caps = self._fused_caps_for(geom, p, frame, thresholds, streaming)
            if self.injector is not None:
                self.injector.maybe_delay(index)
            t1, t2 = thresholds

            def attempt(v):
                if self.injector is not None:
                    self.injector.maybe_fail_launch(index)
                fn = fused_frame_fn(geom, caps, self.cfg, v.backend,
                                    v.interpret, self.mesh,
                                    self.qpack if v.quant else None,
                                    v.fusion, p.on_poison)
                return fn(self.params, frame, t1, t2)

            # the degradation ladder owns retries: a failed launch (injected
            # or genuine) steps down fusion -> ref -> fp32 and re-runs
            outs, steps = self.guard.run(attempt, index)
            v = self.guard.variant
            compiled = self._mark_warm(("fused", geom.cache_key, caps,
                                        v.backend, v.interpret, v.quant,
                                        v.fusion, p.on_poison))
            return {"outs": outs, "geom": geom, "caps": caps, "t0": t0,
                    "plan": p, "thresholds": (t1, t2), "compiled": compiled,
                    "streaming": streaming, "variant": v, "steps": steps,
                    "index": index}

    def _finalize_fused(self, rec: dict) -> FrameResult:
        """Block on one in-flight fused frame, materialize its routing
        telemetry (ids/scores/counts/spills), and run the host-side control
        that fused dispatch deferred: Algorithm-1 threshold trim from the
        (possibly one-frame-old) counts, straggler demotion on a missed
        deadline, and capacity growth after spill."""
        with frame_span("essr.finalize", rec["index"]):
            img, ids, scores, counts, spills, health = rec["outs"]
            with span("essr.wait.image"):
                img.block_until_ready()
            done = time.perf_counter()
            # marginal frame time: under async streaming a frame's launch-
            # to-ready wall clock includes the device time of EARLIER
            # in-flight frames — clocking from whichever is later (this
            # frame's launch or the previous frame's completion) reports the
            # pipelined per-frame service time, so fps aggregates are
            # meaningful and a per-frame deadline does not fire spuriously
            # on every steady-state frame. Synchronous calls are unaffected
            # (the previous finalize always precedes the next launch).
            dt = done - max(rec["t0"], self._fused_last_done)
            self._fused_last_done = done
            p, geom, streaming = rec["plan"], rec["geom"], rec["streaming"]
            # materialize the in-graph health verdict with the counts (one
            # host wait) and apply the host-visible side of the on_poison
            # policy
            with span("essr.wait.counts"):
                health_t = (None if p.on_poison == "off" else
                            tuple(int(c) for c in np.asarray(health)))
                counts_t = tuple(int(c) for c in np.asarray(counts))
                spills_t = tuple(int(s) for s in np.asarray(spills))
            if health_t is not None and any(health_t):
                self.guard.record(rec["index"], "poison",
                                  f"frame health nan/inf/oob={health_t} "
                                  f"(policy {p.on_poison})")
                if p.on_poison == "raise":
                    raise PoisonFrameError(
                        f"frame failed health verdict "
                        f"nan/inf/oob={health_t} (plan.on_poison='raise')",
                        health=health_t)
            steps = rec["steps"]
            if streaming and p.watchdog_s is not None and dt > p.watchdog_s:
                steps = steps + self.guard.note_watchdog(rec["index"], dt,
                                                         p.watchdog_s)
            macs = (self._macs if p.patch == self.plan.patch
                    else sp.SubnetMacs.make(self.cfg, p.patch))
            saving = macs.saving_vs_c54(counts_t)
            self._grow_caps(geom, p, counts_t, spills_t)
            live = rec["thresholds"]
            missed = False
            shard_counts = None
            if streaming:
                self.switcher.observe_frame(counts_t[sp.C54])
                missed = bool(self.deadline_s and dt > self.deadline_s)
                if missed:
                    self.switcher.demote_for_straggler(severity=1.0)
                live = self.switcher.thresholds
                if self.bank is not None:
                    # reporting only: fused routing is one in-graph decision,
                    # so per-shard threshold control is a host-dispatch
                    # feature — strip counts are still surfaced for
                    # observability
                    with span("essr.wait.counts"):
                        ids_np = np.asarray(ids)
                    shard_counts = tuple(
                        sp.subnet_counts(ids_np[sl])
                        for sl in geom.shard_slices(self.plan.shards))
            # ids/scores stay device arrays: the control loop only needs the
            # scalar counts/spills, so the per-patch telemetry transfers
            # lazily — consumers that index it (np.asarray) pay the copy,
            # the steady-state stream does not
            out = FrameResult(
                image=img, mode="edge_select",
                backend=self._variant_label(p, rec["variant"]),
                ids=ids, scores=scores, counts=counts_t, mac_saving=saving,
                latency_s=dt, thresholds=live, deadline_missed=missed,
                shards=self.plan.shards, shard_counts=shard_counts,
                dispatch="fused", spill_counts=spills_t,
                compiled=rec["compiled"], health=health_t, degraded=steps)
            if streaming:
                self.stats.append(dataclasses.replace(out, image=None,
                                                      ids=None, scores=None))
            return out

    def _upscale_fused(self, frame, p: ExecutionPlan) -> FrameResult:
        """upscale()'s fused path: launch + finalize back-to-back (single
        frames have nothing to overlap with)."""
        return self._finalize_fused(
            self._launch_fused(frame, p, (p.t1, p.t2), streaming=False))

    def warmup(self, shape: Tuple[int, int]) -> FrameResult:
        """Pre-pay trace+compile for an ``(h, w)`` LR frame shape.

        Runs one deterministic synthetic frame — thirds of smooth gradient /
        mild texture / checkerboard, so all three subnets populate — through
        the plan's dispatch path without touching ``stats`` or the adaptive
        thresholds. Returns its FrameResult (``compiled=False`` on a cold
        engine); the next real frame of this shape reports
        ``compiled=True`` and a latency free of compile time. Under fused
        dispatch with ``plan.capacity=None`` this also seeds the capacity
        profile from the synthetic routing — live content that routes past
        it still spills once (that frame's ``spill_counts`` say so; it runs
        the already-warm executable, so ``compiled`` stays True) and the
        profile regrows, with the NEXT frame paying the recompile and
        reporting ``compiled=False``."""
        h, w = int(shape[0]), int(shape[1])
        yy, xx = jnp.meshgrid(jnp.linspace(0.0, 1.0, h),
                              jnp.linspace(0.0, 1.0, w), indexing="ij")
        checker = ((jnp.arange(h)[:, None] + jnp.arange(w)[None, :]) % 2
                   ).astype(jnp.float32)
        smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
        frame = jnp.where((xx < 1 / 3)[..., None], smooth,
                          jnp.where((xx < 2 / 3)[..., None],
                                    smooth + 0.03 * checker[..., None],
                                    checker[..., None] * jnp.ones(3)))
        return self.upscale(jnp.clip(frame, 0.0, 1.0))

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_config(cls, cfg: Optional[ESSRConfig] = None, *, seed: int = 0,
                    plan: Optional[ExecutionPlan] = None, backend: str = "ref",
                    switching: Optional[SwitchingConfig] = None,
                    deadline_s: Optional[float] = None,
                    calibrate: Optional[jax.Array] = None) -> "SREngine":
        """Fresh engine with randomly initialised supernet weights.

        ``calibrate``: PTQ calibration batch for ``plan.quant`` modes
        ((N,h,w,3) LR in [0,1]; None = deterministic synthetic default)."""
        cfg = cfg if cfg is not None else ESSRConfig()
        params = init_essr(jax.random.PRNGKey(seed), cfg)
        return cls(params, cfg, plan=plan, backend=backend,
                   switching=switching, deadline_s=deadline_s,
                   calibrate=calibrate)

    @classmethod
    def from_checkpoint(cls, ckpt_dir: Optional[str] = None, *,
                        cfg: Optional[ESSRConfig] = None, scale: int = 4,
                        prefer: str = "ema",
                        bench_cache: Optional[str] = DEFAULT_BENCH_CACHE,
                        plan: Optional[ExecutionPlan] = None,
                        backend: str = "ref",
                        switching: Optional[SwitchingConfig] = None,
                        deadline_s: Optional[float] = None,
                        calibrate: Optional[jax.Array] = None,
                        verbose: bool = False) -> "SREngine":
        """Engine with trained weights, resolved in priority order:

        1. ``ckpt_dir`` — a train.py checkpoint holding {"params", "ema"};
           ``prefer`` selects which tree serves ("ema" by default).
        2. the newest cached benchmark supernet under ``bench_cache``
           matching this config (``essr_x<scale>_sfb<n>_*``);
        3. fresh random init (so demos never hard-fail on a cold cache).

        ``calibrate``: PTQ calibration batch for ``plan.quant`` modes; when
        None the deterministic synthetic default is used and the resulting
        alphas are cached as JSON alongside ``bench_cache`` (keyed by the
        weights' content hash, so new weights always recalibrate).
        """
        from repro.ckpt.checkpoint import CheckpointManager

        cfg = cfg if cfg is not None else ESSRConfig(scale=scale)
        params = init_essr(jax.random.PRNGKey(0), cfg)
        if ckpt_dir:
            cm = CheckpointManager(ckpt_dir)
            # peek at the stored tree so a checkpoint written without an
            # "ema" tree is detected instead of silently mis-restored
            template = {"params": params, "ema": params}
            try:
                top = set(json.loads(cm.read_manifest()["tree_template"]))
            except Exception as e:
                top = None                       # legacy/unreadable manifest
                warnings.warn(f"checkpoint manifest unreadable for "
                              f"{ckpt_dir} ({e!r}); restoring with the "
                              f"default template")
            if top is not None and top and top <= {"params", "ema"}:
                template = {k: params for k in top}
            try:
                restored, _ = cm.restore(template)
            except Exception as e:
                # truncated/corrupted payload: degrade to fresh init rather
                # than dying mid-construction (demos and serving stay up)
                restored = None
                warnings.warn(f"checkpoint restore failed for {ckpt_dir}: "
                              f"{e!r}; serving fresh random init")
            if restored is not None:
                use = prefer
                if use not in restored:
                    # fall back to whatever tree the checkpoint does hold
                    # ("params" when present, else e.g. an ema-only one)
                    use = ("params" if "params" in restored
                           else next(iter(sorted(restored))))
                    warnings.warn(
                        f"checkpoint {ckpt_dir} has no {prefer!r} tree "
                        f"(found {sorted(restored)}); serving {use!r} instead")
                params = restored[use]
                if verbose:
                    print(f"(restored {use!r} weights from {ckpt_dir})")
        elif bench_cache:
            pattern = os.path.join(bench_cache, f"essr_x{cfg.scale}_sfb{cfg.n_sfb}_*")

            def _steps(d: str) -> int:
                # names are essr_x<scale>_sfb<n>_<steps><tag>; "newest" means
                # highest step count, not lexicographic order (800 > 6000)
                m = re.match(r"(\d+)", d.rsplit("_", 1)[-1])
                return int(m.group(1)) if m else -1

            cands = sorted(glob.glob(pattern), key=_steps, reverse=True)
            restored_ok = False
            for cand in cands:
                try:
                    restored, _ = CheckpointManager(cand).restore({"params": params})
                    params = restored["params"]
                    restored_ok = True
                    if verbose:
                        print(f"(using trained weights from {cand})")
                    break
                except Exception as e:
                    warnings.warn(f"bench-cache restore failed for {cand}: "
                                  f"{e!r}; trying next candidate")
            if cands and not restored_ok:
                warnings.warn(f"no bench-cache candidate under {bench_cache} "
                              f"restored cleanly; serving fresh random init")
        return cls(params, cfg, plan=plan, backend=backend,
                   switching=switching, deadline_s=deadline_s,
                   calibrate=calibrate, quant_cache=bench_cache)

    # -- single-frame inference ---------------------------------------------

    def upscale(self, frame: jax.Array, mode: str = "edge_select",
                width: Optional[int] = None,
                ids_override: Optional[np.ndarray] = None,
                plan: Optional[ExecutionPlan] = None) -> FrameResult:
        """One frame through the pipeline. ``frame``: (H,W,3) in [0,1].

        ``mode``:
          * "edge_select"  — routing per the plan's subnet policy (or an
            explicit ``ids_override``);
          * "all_patches"  — every patch through the subnet of ``width``
            (the non-edge-selective ablation reference);
          * "whole"        — whole-image convolution, no patching (the
            lossless software reference; ``width`` optional). Always fp32,
            even on a quantized engine — it is the baseline the quant
            accuracy budget is measured against.

        ``plan`` overrides the engine's plan for this call only (benchmark
        sweeps over the patch-based modes; "whole" has no plan knobs).
        """
        if mode not in MODES:
            raise ValueError(f"mode {mode!r} not in {MODES}")
        if mode == "edge_select" and width is not None:
            raise ValueError("width only applies to mode='all_patches'/'whole'; "
                             "for forced routing use mode='all_patches'")
        if mode != "edge_select" and ids_override is not None:
            raise ValueError("ids_override requires mode='edge_select'")
        p = plan if plan is not None else self.plan
        if p.quant != self.plan.quant:
            # quant is engine state (calibrated alphas + compiled lattice
            # executables), exactly like backend/shards
            raise ValueError(
                f"plan.quant is engine-level: engine was built with "
                f"{self.plan.quant!r}, per-call plan asks for {p.quant!r}; "
                f"construct a second engine for a different quant mode")
        if (p.dispatch == "fused" and mode == "edge_select"
                and ids_override is None and p.subnet_policy == "threshold"):
            # the single-dispatch frame executable; every other combination
            # (forced policies, ids_override, all_patches, whole) routes on
            # the host and says so in FrameResult.dispatch
            return self._upscale_fused(frame, p)
        t0 = time.perf_counter()
        index = self._next_index()
        with frame_span("essr.serve", index):
            frame = self._ingest_frame(frame, p, index)
            # host dispatch syncs per frame anyway, so the verdict runs
            # eagerly; under "bilinear" a poisoned threshold-routed frame is
            # forced to the dense fallback lane below (forced-width modes
            # serve the sanitized frame through the requested subnet — the
            # caller pinned the route)
            frame, health_t, force_bilinear = self._host_health(frame, p,
                                                                index)

            widths = self.cfg.subnet_widths()
            if mode == "whole":
                if width is not None and width not in widths:
                    raise ValueError(f"mode='whole' needs width in {widths} "
                                     f"(or None for full), got {width}")
                compiled = self._mark_warm(
                    ("whole", (int(frame.shape[0]), int(frame.shape[1])),
                     width))
                img = sr_whole(self.params, frame, self.cfg, width=width)
                with span("essr.wait.image"):
                    img.block_until_ready()
                # sr_whole always runs the pure-JAX path; label it honestly
                return FrameResult(image=img, mode=mode, backend="ref",
                                   latency_s=time.perf_counter() - t0,
                                   compiled=compiled, health=health_t)

            # cached gather/scatter maps for this frame shape (zero host
            # setup after the first frame of a given geometry)
            geom = p.geometry(frame.shape[0], frame.shape[1], self.cfg.scale)
            # first frame of a geometry pays trace+compile (an approximation
            # for host dispatch, where unseen bucket sizes can still
            # recompile later; exact for the fused path, which keys on its
            # capacity profile)
            compiled = self._mark_warm(("host", geom.cache_key))
            scored = False
            routed_by_thresholds = False
            result_mode = mode
            if mode == "all_patches":
                if width not in widths:
                    raise ValueError(f"mode='all_patches' needs width in "
                                     f"{widths}, got {width}")
                res = sr_all_patches_result(
                    self.lane_params, frame, self.cfg, width, patch=p.patch,
                    overlap=p.overlap, buckets=p.buckets,
                    backend=self.backend, interpret=p.interpret,
                    geometry=geom, mesh=self.mesh, quant=self.qpack,
                    fusion=p.fusion)
            elif ids_override is None and p.subnet_policy != "threshold":
                # forced policies ignore edge scores — reuse the no-scoring
                # path; plan.decide is the single policy-name -> subnet-id
                # mapping. Label what actually ran, so consumers keying on
                # mode don't expect edge scores from a forced run.
                result_mode = "all_patches"
                forced = widths[int(p.decide(np.zeros(1))[0])]
                res = sr_all_patches_result(
                    self.lane_params, frame, self.cfg, forced, patch=p.patch,
                    overlap=p.overlap, buckets=p.buckets,
                    backend=self.backend, interpret=p.interpret,
                    geometry=geom, mesh=self.mesh, quant=self.qpack,
                    fusion=p.fusion)
            else:
                if force_bilinear and ids_override is None:
                    # poisoned frame under on_poison="bilinear": the dense
                    # fallback lane serves every patch (sanitized above)
                    ids_override = np.zeros(geom.n, np.int64)
                # an explicit ids_override skips the edge unit entirely, so
                # there are no scores to report for that path
                scored = ids_override is None
                routed_by_thresholds = ids_override is None
                res = edge_selective_sr(
                    self.lane_params, frame, self.cfg, t1=p.t1, t2=p.t2,
                    patch=p.patch, overlap=p.overlap,
                    ids_override=ids_override, buckets=p.buckets,
                    backend=self.backend, interpret=p.interpret,
                    geometry=geom, mesh=self.mesh, quant=self.qpack,
                    fusion=p.fusion)
            with span("essr.wait.image"):
                res.image.block_until_ready()
            return FrameResult(
                image=res.image, mode=result_mode,
                backend=self._backend_label(p), ids=res.ids,
                scores=res.scores if scored else None, counts=res.counts,
                mac_saving=res.mac_saving,
                latency_s=time.perf_counter() - t0,
                # thresholds only meaningful when routing used them
                thresholds=(p.thresholds if routed_by_thresholds
                            else (0.0, 0.0)),
                # sharding is engine-level (like backend): a per-call plan
                # cannot rebuild the mesh
                shards=self.plan.shards, compiled=compiled, health=health_t)

    def reference(self, frame: jax.Array, width: Optional[int] = None) -> FrameResult:
        """Whole-image convolution — the lossless reference of Table III."""
        return self.upscale(frame, mode="whole", width=width)

    # -- streaming (Algorithm 1 + deadline control loop) ---------------------

    def serve(self, frame: jax.Array) -> FrameResult:
        """One frame of the adaptive stream: edge scores -> Algorithm-1
        thresholds (with per-second C54 ceiling) -> edge-selective SR.
        Appends to ``self.stats``; a missed deadline raises the thresholds
        (the paper's resource-adaptive mechanism as straggler mitigation).

        With ``plan.shards > 1`` the frame's raster strips are routed by
        per-shard controllers (`ShardSwitcherBank`), the routed buckets run
        data-parallel over the patch mesh, and a missed deadline demotes
        only the shards whose estimated MAC cost exceeds the balanced share
        (a host-side load model — the deadline itself is the frame's global
        wall clock) — their next-frame C54 share drops while balanced shards
        keep their thresholds. Per-shard counts/thresholds/demotions are
        surfaced on the `FrameResult`."""
        if self.plan.streams > 1:
            raise ValueError(
                f"plan.streams={self.plan.streams}: multi-stream serving "
                f"admits one frame per tenant per tick — use serve_streams()")
        if self.plan.subnet_policy != "threshold":
            raise ValueError(
                f"streaming routes adaptively and cannot honour forced "
                f"subnet_policy {self.plan.subnet_policy!r}; use upscale() "
                f"for forced routing")
        if self.plan.dispatch == "fused":
            # the single-dispatch stream path: routing + the C54 ceiling run
            # in-graph (capacity slots), Algorithm-1 trim runs host-side
            # from the materialized counts (see _finalize_fused)
            return self._finalize_fused(self._launch_fused(
                frame, self.plan, self.switcher.thresholds, streaming=True))
        t0 = time.perf_counter()
        index = self._next_index()
        with frame_span("essr.serve", index):
            frame = self._ingest_frame(frame, self.plan, index)
            frame, health_t, force_bilinear = self._host_health(
                frame, self.plan, index)
            geom = self.plan.geometry(frame.shape[0], frame.shape[1],
                                      self.cfg.scale)
            compiled = self._mark_warm(("host", geom.cache_key))
            with span("essr.extract"):
                patches, pos = geom.extract(frame), geom.pos
            sharded = self.bank is not None
            slices = (geom.shard_slices(self.plan.shards) if sharded
                      else None)
            with span("essr.route"):
                scores = edge_score(patches)
                with span("essr.wait.scores"):
                    scores = np.asarray(scores)
                if force_bilinear:
                    # poisoned frame under on_poison="bilinear": serve the
                    # dense fallback lane; the switcher still observes (zero
                    # C54 load)
                    ids = np.zeros(len(scores), np.int64)
                elif sharded:
                    ids = self.bank.assign(scores, slices)
                else:
                    ids = self.switcher.assign(scores)
            res = edge_selective_sr(
                self.lane_params, frame, self.cfg, patch=self.plan.patch,
                overlap=self.plan.overlap, ids_override=ids,
                buckets=self.plan.buckets, backend=self.backend,
                interpret=self.plan.interpret, geometry=geom,
                mesh=self.mesh, quant=self.qpack, fusion=self.plan.fusion,
                precomputed=(patches, pos, scores))
            with span("essr.wait.image"):
                res.image.block_until_ready()
            dt = time.perf_counter() - t0
            missed = bool(self.deadline_s and dt > self.deadline_s)
            shard_counts = shard_thresholds = shard_missed = None
            if sharded:
                shard_counts = tuple(sp.subnet_counts(ids[sl])
                                     for sl in slices)
                shard_missed = self.bank.note_frame(
                    missed, [self._macs.total(c) for c in shard_counts])
                shard_thresholds = self.bank.thresholds
                # scalar thresholds field: across-shard mean (the per-shard
                # truth is in shard_thresholds)
                live = tuple(float(np.mean([t[i] for t in shard_thresholds]))
                             for i in (0, 1))
            else:
                if missed:
                    self.switcher.demote_for_straggler(severity=1.0)
                live = self.switcher.thresholds
            out = FrameResult(
                image=res.image, mode="edge_select",
                backend=self.backend_label, ids=ids, scores=scores,
                counts=res.counts, mac_saving=res.mac_saving, latency_s=dt,
                thresholds=live, deadline_missed=missed,
                shards=self.plan.shards, shard_counts=shard_counts,
                shard_thresholds=shard_thresholds,
                shard_deadline_missed=shard_missed, compiled=compiled,
                health=health_t)
            # retain only the compact record: holding every SR image would
            # grow unboundedly over a long stream (one 8K frame is ~100s of
            # MB)
            self.stats.append(dataclasses.replace(out, image=None,
                                                  ids=None, scores=None))
            return out

    def stream(self, frames: Iterable[jax.Array]) -> Iterator[FrameResult]:
        """Serve a frame stream; yields one FrameResult per frame.

        Under fused dispatch with ``plan.inflight >= 2`` the stream is
        double-buffered: up to ``inflight`` frames stay in flight, so frame
        N's device compute overlaps frame N+1's host-side ingest and the
        per-frame Python round-trip leaves the steady-state critical path.
        The cost is a documented one-frame control delay: the Algorithm-1
        switcher (and capacity growth) adapt from the newest *materialized*
        frame, which trails the newest *launched* frame by up to
        ``inflight - 1``. Results still arrive strictly in frame order."""
        if self.plan.streams > 1:
            raise ValueError(
                f"plan.streams={self.plan.streams}: multi-stream serving "
                f"admits one frame per tenant per tick — use serve_streams()")
        # fault harness + iterator isolation: an iterator that raises ends
        # the stream with a recorded retirement, never a serving-loop crash
        frames = self._guarded_frames(frames)
        if self.plan.dispatch == "fused" and self.plan.inflight > 1:
            yield from self._stream_fused_async(frames)
            return
        for frame in frames:
            yield self.serve(frame)

    def _stream_fused_async(self, frames: Iterable[jax.Array]
                            ) -> Iterator[FrameResult]:
        pending: Deque[dict] = collections.deque()
        for frame in frames:
            pending.append(self._launch_fused(
                frame, self.plan, self.switcher.thresholds, streaming=True))
            while len(pending) >= self.plan.inflight:
                yield self._finalize_fused(pending.popleft())
        while pending:
            yield self._finalize_fused(pending.popleft())

    def serve_streams(self, streams: Iterable[Iterable[jax.Array]]
                      ) -> Iterator[FrameResult]:
        """Serve ``plan.streams`` tenant frame streams through ONE fused
        dispatch per admission tick (the multi-tenant front door).

        ``streams``: one frame iterable per tenant, ``plan.streams`` of
        them, in stream-id order. Each admission tick pulls the next frame
        from every still-live stream (round-robin admission — no tenant can
        starve another), packs the tick's routed patches from ALL streams
        into the same capacity-slotted fused executable, and yields one
        `FrameResult` per live stream (tagged ``stream_id``), ticks in
        admission order and streams in id order within a tick. Per-stream
        QoS: every stream keeps its own Algorithm-1 switcher with a
        share-weighted budget split (``plan.stream_shares``); under
        aggregate overload C54 slots degrade per stream in share proportion,
        raster-deterministically — frames are never dropped. Streams may
        have different lengths: exhausted streams leave the tick (one
        recompile per distinct live-stream count). ``plan.inflight >= 2``
        double-buffers whole ticks, with the same one-tick control delay as
        the single-stream async path.

        With ``plan.streams == 1`` this is exactly ``stream()`` over the
        single iterable."""
        streams = list(streams)
        if len(streams) != self.plan.streams:
            raise ValueError(
                f"serve_streams got {len(streams)} streams for "
                f"plan.streams={self.plan.streams}")
        if self.plan.streams == 1:
            yield from self.stream(streams[0])
            return
        from repro.runtime.multiplex import StreamMultiplexer
        yield from StreamMultiplexer(self).serve(streams)

    # -- aggregate reporting -------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        """Table-XI-style aggregate over all streamed frames."""
        s = summarize_stats(self.stats)
        if s:
            s["backend"] = self.backend_label
            # the record list is a bounded deque: aggregates cover at most
            # the newest stats_window streamed frames
            s["stats_window"] = self.plan.stats_window
            # process-wide compiled/geometry cache pressure (satellite of the
            # bounded-cache work): nonzero evictions under a steady geometry
            # set means executables are silently re-tracing.
            s["compiled_caches"] = compiled_cache_occupancy()
        if self.guard.events:
            # the resilience ledger: every degradation-ladder step, poison
            # verdict, quarantine/retire and watchdog event, deterministic
            # under a seeded FaultPlan (watchdog events are timing-dependent
            # and excluded from determinism assertions)
            s["degradations"] = self.guard.summary()
        return s
