"""ExecutionPlan — the frozen, hashable description of HOW a frame is run.

Consolidates every knob that used to travel as loose keyword arguments
through the legacy free functions and benchmark helpers: patch geometry,
edge thresholds, the jit bucket schedule, the subnet policy, and the Pallas
interpret policy. One plan == one compilation/routing regime; `SREngine`
holds exactly one and every call reuses it (override per call with
``plan.replace(...)`` only when a benchmark sweeps a knob).

``plan.geometry(h, w, scale)`` resolves the cached `PatchGeometry` (gather/
scatter index maps + overlap counts) for a frame shape under this plan's
patch/overlap — computed once per geometry, so repeated frames of a stream
pay zero host-side setup.

Validation is declarative: ``_FIELD_RULES`` (one predicate + allowed-set
description per field) and ``_CROSS_RULES`` (cross-field constraints like
``inflight -> fused``), both enforced in ``__post_init__`` with ONE error
format — ``ExecutionPlan.<field>=<got!r>: allowed <set>`` — so every
rejection names the field, the offending value, and what would have passed.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core import subnet_policy as sp
from repro.core.patching import PatchGeometry, get_geometry
from repro.core.pipeline import DEFAULT_BUCKETS, FUSION_MODES, HEALTH_POLICIES
from repro.quant.pams import QUANT_MODES as pams_quant_modes
from repro.runtime.guard import FaultPlan

#: Subnet-policy names accepted by :class:`ExecutionPlan`.
#: ``threshold``     — paper Sec. II-C routing on the (t1, t2) edge thresholds
#: ``all_bilinear``  / ``all_c27`` / ``all_c54`` — force every patch through
#:                     one subnet (the ablation references of Tables III/IX).
SUBNET_POLICIES = ("threshold", "all_bilinear", "all_c27", "all_c54")

#: Dispatch modes accepted by :class:`ExecutionPlan`:
#: ``"host"``  — routing on the host: per-frame edge-score sync, Python loop
#:               over subnet buckets (supports every mode/policy/override)
#: ``"fused"`` — one compiled frame executable per (geometry, capacity
#:               profile): extract -> edge-score -> threshold routing ->
#:               capacity-slotted per-subnet forward -> overlap-average fusion,
#:               no host in the loop (threshold-routed edge_select only;
#:               other modes fall back to host dispatch, documented in
#:               docs/api.md "Dispatch modes & async streaming").
DISPATCH_MODES = ("host", "fused")

#: Serving quantization modes accepted by :class:`ExecutionPlan`:
#: ``None``    — fp32 serving (the default)
#: ``"fxp10"`` — the paper's whole-model FXP10 (Sec. IV-H)
#: ``"int8"``  — TPU-native int8 (the MXU integer datapath)
#: Derived from `repro.quant.pams.QUANT_MODES` (the mode -> bits mapping),
#: the single source of truth for which lattices exist.
QUANT_MODES = (None, *pams_quant_modes)


def _plan_error(field: str, got, allowed: str) -> ValueError:
    """The one validation error shape every rule raises through."""
    return ValueError(f"ExecutionPlan.{field}={got!r}: allowed {allowed}")


def _is_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _pos_int(v) -> bool:
    return _is_int(v) and v >= 1


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


#: Declarative per-field validation: field -> (predicate over the normalized
#: value, human-readable allowed set). Single-field rules only; constraints
#: spanning fields live in `_CROSS_RULES`. New plan knobs add one row here
#: instead of growing an if-chain in ``__post_init__``.
_FIELD_RULES: Dict[str, Tuple[Callable, str]] = {
    "patch": (_pos_int, "a positive int"),
    "overlap": (lambda v: _is_int(v) and v >= 0, "an int >= 0"),
    "t1": (_is_num, "a number"),
    "t2": (_is_num, "a number"),
    "buckets": (lambda v: bool(v) and all(_pos_int(b) for b in v)
                and list(v) == sorted(set(v)),
                "a non-empty ascending tuple of positive ints"),
    "subnet_policy": (lambda v: v in SUBNET_POLICIES,
                      f"one of {SUBNET_POLICIES}"),
    "interpret": (lambda v: v in (None, True, False), "None/True/False"),
    "quant": (lambda v: v in QUANT_MODES, f"one of {QUANT_MODES}"),
    "dispatch": (lambda v: v in DISPATCH_MODES, f"one of {DISPATCH_MODES}"),
    "fusion": (lambda v: v in FUSION_MODES, f"one of {FUSION_MODES}"),
    "capacity": (lambda v: v is None or all(c >= 0 for c in v),
                 "None or a tuple of ints >= 0"),
    "inflight": (_pos_int, "a positive int"),
    "stats_window": (_pos_int, "a positive int"),
    "shards": (_pos_int, "a positive int"),
    "streams": (_pos_int, "a positive int"),
    "stream_shares": (lambda v: v is None or (bool(v)
                      and all(s > 0 and np.isfinite(s) for s in v)),
                      "None or a tuple of finite floats > 0"),
    "on_poison": (lambda v: v in HEALTH_POLICIES, f"one of {HEALTH_POLICIES}"),
    "faults": (lambda v: v is None or isinstance(v, FaultPlan),
               "None or a repro.runtime.guard.FaultPlan"),
    "max_retries": (lambda v: _is_int(v) and v >= 0, "an int >= 0"),
    "quarantine_ticks": (lambda v: _is_int(v) and v >= 0,
                         "an int >= 0 (0 retires a quarantined stream "
                         "permanently)"),
    "watchdog_s": (lambda v: v is None or (_is_num(v) and v > 0),
                   "None or a number > 0"),
}

#: Cross-field constraints: (field to blame, predicate over the whole plan,
#: allowed-set description builder). Same error format as `_FIELD_RULES`.
_CROSS_RULES: Tuple[Tuple[str, Callable, Callable], ...] = (
    ("overlap", lambda p: p.overlap < p.patch,
     lambda p: f"an int < patch ({p.patch})"),
    ("t2", lambda p: p.t2 >= p.t1,
     lambda p: f"a number >= t1 ({p.t1})"),
    # host dispatch blocks per frame, so inflight > 1 would be silently
    # inert — refuse rather than let a user believe the stream is
    # double-buffered
    ("inflight", lambda p: p.inflight == 1 or p.dispatch == "fused",
     lambda p: "1 unless dispatch='fused' (host dispatch serves "
               "synchronously)"),
    # multi-stream admission packs every tick into the fused executable;
    # there is no host-dispatch multiplexer
    ("streams", lambda p: p.streams == 1 or p.dispatch == "fused",
     lambda p: "1 unless dispatch='fused' (stream packing rides the fused "
               "executable)"),
    # per-stream QoS is Algorithm-1 threshold control; a forced policy has
    # no thresholds to degrade
    ("streams", lambda p: p.streams == 1 or p.subnet_policy == "threshold",
     lambda p: "1 unless subnet_policy='threshold' (per-stream QoS adapts "
               "thresholds)"),
    ("stream_shares", lambda p: (p.stream_shares is None
                                 or len(p.stream_shares) == p.streams),
     lambda p: f"None or a tuple of exactly streams={p.streams} shares"),
    # the watchdog meters fused admission ticks / frame launches; host
    # dispatch has no tick clock to meter
    ("watchdog_s", lambda p: p.watchdog_s is None or p.dispatch == "fused",
     lambda p: "None unless dispatch='fused' (the watchdog meters fused "
               "admission ticks)"),
)


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    patch: int = 32
    overlap: int = 2
    t1: float = sp.DEFAULT_T1
    t2: float = sp.DEFAULT_T2
    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    subnet_policy: str = "threshold"
    #: Pallas dispatch: None = auto (compiled on TPU/GPU, interpreter as the
    #: CPU-correctness fallback); True/False force it. Only consulted by the
    #: "pallas" backend.
    interpret: Optional[bool] = None
    #: Serving quantization: None (fp32), "fxp10" (paper Sec. IV-H) or
    #: "int8" (TPU MXU datapath). Engine-level like ``shards``: the engine
    #: PTQ-calibrates per-subnet activation alphas at construction, so a
    #: per-call plan override cannot change the mode. The "ref" backend
    #: serves fake-quant emulation; "pallas" serves the integer-domain
    #: kernel stack (kernels/qconv.py). Surfaced as a FrameResult.backend
    #: suffix ("ref-fxp10", "pallas-int8", "pallas-interpret-int8", ...).
    quant: Optional[str] = None
    #: Frame dispatch: "host" (routing on the host, the default) or "fused"
    #: (one compiled executable per (geometry, capacity profile) — see
    #: DISPATCH_MODES above and docs/api.md). Applies to threshold-routed
    #: edge_select calls; forced policies / ids_override / all_patches /
    #: whole always run host dispatch.
    dispatch: str = "host"
    #: Kernel fusion granularity of the "pallas" backend
    #: (`core.pipeline.FUSION_MODES`): "layer" (default) runs one Pallas
    #: kernel per layer group — BSConv, each SFB, DSConv — with the feature
    #: map round-tripping HBM between groups; "group" runs a subnet's WHOLE
    #: layer group in ONE megakernel (`kernels.megakernel`) with the feature
    #: (and, under ``quant``, the integer lattice codes) held in VMEM
    #: scratch across the chain — the TPU analog of the paper's 79%
    #: feature-SRAM-access saving. Numerics: fp32 group fusion is allclose
    #: to layer fusion; quantized group fusion is BIT-EXACT (same shared
    #: integer math, same site constants). The "ref" backend has no kernels
    #: to fuse and serves identically under both values.
    fusion: str = "layer"
    #: Fused-dispatch per-subnet slot capacities, aligned with
    #: ``cfg.subnet_widths()`` (entry 0 — bilinear — is ignored: that lane
    #: runs dense as the spill floor). None = automatic: the engine probes
    #: the first frame of each geometry on the host, snaps counts to
    #: ``buckets`` (`core.pipeline.snap_capacity`), and grows a subnet's
    #: capacity after any frame that spilled; when streaming, the C54 entry
    #: is additionally clamped to the per-frame share of the Algorithm-1
    #: C54/sec budget. Pin explicitly to fix the compiled shape (tests;
    #: validated deployments) — a pinned profile is served VERBATIM, so its
    #: C54 entry *replaces* the budget-derived ceiling: the pin is the
    #: per-frame hard ceiling, and it is on the operator to size it within
    #: the deployment's compute budget.
    capacity: Optional[Tuple[int, ...]] = None
    #: Async double-buffering depth for ``SREngine.stream`` under fused
    #: dispatch: 1 (default) serves synchronously; >= 2 keeps that many
    #: frames in flight — frame N's device compute overlaps frame N+1's
    #: host-side ingest, and the Algorithm-1 switcher reads routing
    #: telemetry one frame behind (a documented control delay).
    inflight: int = 1
    #: Bound on the per-frame records ``SREngine.stats`` retains (a deque:
    #: the newest ``stats_window`` streamed frames). Generous by default;
    #: ``summary()`` aggregates over at most this window and says so.
    stats_window: int = 4096
    #: Data-parallel patch-stream shards. 1 = the single-device path. > 1
    #: splits each frame's routed patch buckets across that many devices
    #: (shard_map over a 1-D mesh) and gives each shard its own Algorithm-1
    #: controller in the streaming path. When fewer devices are visible the
    #: engine degrades transparently: routing/straggler control stays
    #: per-shard, dispatch falls back to one device.
    shards: int = 1
    #: Concurrent tenant streams multiplexed into ONE fused dispatch per
    #: admission tick (`SREngine.serve_streams`). 1 = today's single-stream
    #: serving; >= 2 requires dispatch="fused" and the threshold policy
    #: (per-stream QoS is Algorithm-1 control). Every stream keeps its own
    #: switcher; the compiled (geometry, capacity-profile) executable — and
    #: the PTQ calibration and warmup behind it — is shared across tenants.
    streams: int = 1
    #: Relative QoS weight per stream (len == streams), normalized by the
    #: engine: stream s gets share_s/sum(shares) of the aggregate C54/sec
    #: budget and of the per-frame trim bands. None = equal shares. Under
    #: aggregate overload the capacity router degrades each stream's C54
    #: share raster-deterministically in this proportion — frames are never
    #: dropped.
    stream_shares: Optional[Tuple[float, ...]] = None
    #: Poison-frame policy (`core.pipeline.HEALTH_POLICIES`): what serving
    #: does about a frame with NaN/Inf/out-of-[0,1] pixels. "raise" (default)
    #: raises `PoisonFrameError` (multi-tenant serving quarantines the
    #: offending stream instead — see ``quarantine_ticks``); "sanitize"
    #: clamps in-graph (bit-identical on clean frames); "bilinear" routes the
    #: poisoned frame to the dense fallback lane; "off" disables the health
    #: verdict entirely (`FrameResult.health` is None — the unguarded
    #: baseline `bench_gate.py` measures overhead against).
    on_poison: str = "raise"
    #: Optional seeded chaos schedule (`repro.runtime.guard.FaultPlan`):
    #: injects poison pixels, tenant-iterator errors, simulated backend
    #: failures and launch delays deterministically. None = no injection
    #: (production). Fault handling itself (the degradation ladder, the
    #: quarantine loop) is always on.
    faults: Optional[FaultPlan] = None
    #: Extra launch attempts the degradation ladder may spend per frame/tick
    #: (`runtime.guard.ResilienceGuard`): on a failed launch the engine steps
    #: down (fusion group->layer, backend pallas->ref, quant
    #: ->fp32; sticky) or retries at the ref/fp32/layer floor, at most this
    #: many times, then re-raises.
    max_retries: int = 2
    #: Multi-tenant poison quarantine (`SREngine.serve_streams` under
    #: on_poison="raise"): a poisoned stream stops being admitted for this
    #: many ticks, then re-admits; 0 retires it permanently. Iterator errors
    #: always retire permanently (a raised iterator cannot resume).
    quarantine_ticks: int = 0
    #: Optional wall-clock budget (seconds) per fused frame launch/admission
    #: tick: a slower tick steps the degradation ladder down one rung
    #: (recorded as a "watchdog" event; timing-dependent, so excluded from
    #: determinism assertions). None = no watchdog.
    watchdog_s: Optional[float] = None

    def __post_init__(self):
        # -- normalization (keeps the frozen/hashable contract when callers
        # pass lists; coercion failures blame the field like any rule) ------
        object.__setattr__(self, "buckets", tuple(self.buckets))
        if self.capacity is not None:
            try:
                caps = tuple(int(c) for c in self.capacity)
            except (TypeError, ValueError) as e:
                raise _plan_error("capacity", self.capacity,
                                  _FIELD_RULES["capacity"][1]) from e
            object.__setattr__(self, "capacity", caps)
        if self.stream_shares is not None:
            try:
                shares = tuple(float(s) for s in self.stream_shares)
            except (TypeError, ValueError) as e:
                raise _plan_error("stream_shares", self.stream_shares,
                                  _FIELD_RULES["stream_shares"][1]) from e
            object.__setattr__(self, "stream_shares", shares)
        # -- the declarative tables ----------------------------------------
        for field, (ok, allowed) in _FIELD_RULES.items():
            value = getattr(self, field)
            if not ok(value):
                raise _plan_error(field, value, allowed)
        for field, ok, allowed in _CROSS_RULES:
            if not ok(self):
                raise _plan_error(field, getattr(self, field), allowed(self))

    def replace(self, **kw) -> "ExecutionPlan":
        """Functional update (plans are frozen)."""
        return dataclasses.replace(self, **kw)

    def decide(self, scores: np.ndarray) -> np.ndarray:
        """Edge scores -> subnet ids under this plan's policy.

        (The streaming path does not use this: there `AdaptiveSwitcher.assign`
        owns the live thresholds and the per-second C54 ceiling.)
        """
        scores = np.asarray(scores)
        if self.subnet_policy == "threshold":
            return np.asarray(sp.decide(scores, self.t1, self.t2))
        fixed = {"all_bilinear": sp.BILINEAR, "all_c27": sp.C27,
                 "all_c54": sp.C54}[self.subnet_policy]
        return np.full(scores.shape, fixed, dtype=np.int64)

    def geometry(self, h: int, w: int, scale: int) -> PatchGeometry:
        """Cached gather/scatter maps for an (h, w) frame under this plan.

        Backed by the process-wide LRU in `repro.core.patching`; the first
        frame of a given shape pays the host-side index build, every later
        frame of the stream reuses it."""
        return get_geometry(int(h), int(w), self.patch, self.overlap,
                            int(scale))

    @property
    def thresholds(self) -> Tuple[float, float]:
        return (self.t1, self.t2)
