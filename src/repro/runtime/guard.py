"""Serving-side resilience: fault injection, poison quarantine, degradation.

The serving stack adapts to *load* (Algorithm-1 switching, capacity spill
cascades) but, before this module, not to *faults*: a NaN pixel, a tenant
iterator that raises mid-tick, or a corrupted QuantPack cache killed the
whole `SREngine`.  Real-time SR parts (ACNPU, the Tilted-Layer-Fusion
accelerator) are 30FPS video pipelines where a garbage frame must degrade,
never crash.  Three pieces live here:

* `FaultPlan` / `FaultInjector` — a **deterministic, seeded** chaos harness.
  Every injection decision is a pure function of
  ``sha256(f"{seed}:{kind}:{stream}:{index}")``, so two runs with the same
  plan inject the identical fault sequence regardless of timing, and the
  degradation ledger can be asserted bit-for-bit in CI.
* `ResilienceGuard` — the **degradation ladder**.  From the configured
  serving point it precomputes the deterministic step-down order
  (fusion ``group→layer``, backend ``pallas→ref``, quant
  ``int8/fxp10→fp32``); on a failed launch it steps down (or retries at the
  floor) up to ``plan.max_retries`` times, recording every step.  The
  ladder is *sticky*: later frames serve at the degraded level.
* Typed faults — `PoisonFrameError` (a frame failed its health verdict
  under ``plan.on_poison="raise"``) and the injected-fault family the
  harness raises.

Engine/multiplexer integration, the in-graph health verdicts themselves
(`core.pipeline.frame_health` / the 6th fused output) and the per-tenant
quarantine loop live in `api/engine.py`, `core/pipeline.py` and
`runtime/multiplex.py`; everything is configured through validated
`ExecutionPlan` fields (``faults``, ``on_poison``, ``max_retries``,
``quarantine_ticks``, ``watchdog_s``) — no free-function entry points.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

__all__ = [
    "FaultPlan",
    "FaultInjector",
    "InjectedFault",
    "InjectedBackendFailure",
    "InjectedStreamError",
    "PoisonFrameError",
    "LadderVariant",
    "ResilienceGuard",
    "build_ladder",
    "POISON_KINDS",
]

POISON_KINDS = ("nan", "inf", "range", "dtype")


class PoisonFrameError(RuntimeError):
    """A frame failed its health verdict under ``plan.on_poison="raise"``.

    ``health`` carries the ``(nan, inf, out_of_range)`` pixel counts when the
    verdict came from the in-graph check (None for host-side dtype rejects).
    """

    def __init__(self, msg: str, health: Optional[Tuple[int, int, int]] = None):
        super().__init__(msg)
        self.health = health


class InjectedFault(RuntimeError):
    """Base class for faults raised by the `FaultInjector` harness."""


class InjectedBackendFailure(InjectedFault):
    """Simulated backend/kernel launch failure (chaos harness)."""


class InjectedStreamError(InjectedFault):
    """Simulated tenant-iterator exception (chaos harness)."""


def _check(field_name: str, ok: bool, got, allowed: str) -> None:
    if not ok:
        raise ValueError(f"FaultPlan.{field_name}={got!r}: allowed {allowed}")


@dataclass(frozen=True)
class FaultPlan:
    """Declarative, seeded chaos schedule — attach via ``ExecutionPlan.faults``.

    All rates are per-event probabilities in [0, 1]; decisions are derived
    from ``seed`` alone (see `FaultInjector`), never from wall-clock or RNG
    state, so identical plans replay identical fault sequences.
    """

    seed: int = 0
    # probability that a given (stream, frame) gets its pixels poisoned
    poison_rate: float = 0.0
    # which corruptions to draw from: nan / inf / range (1e6 pixels) / dtype
    poison_kinds: Tuple[str, ...] = ("nan",)
    # probability that a given stream frame raises from the tenant iterator
    iterator_error_rate: float = 0.0
    # probability a launch index raises InjectedBackendFailure (once per index)
    backend_failure_rate: float = 0.0
    # probability / duration of an injected delay before a launch (for
    # exercising plan.watchdog_s; excluded from determinism assertions)
    delay_rate: float = 0.0
    delay_s: float = 0.0
    # restrict stream-level faults to these stream ids (None = all streams)
    target_streams: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        _check("seed", isinstance(self.seed, int) and not isinstance(self.seed, bool),
               self.seed, "an int")
        for name in ("poison_rate", "iterator_error_rate", "backend_failure_rate",
                     "delay_rate"):
            v = getattr(self, name)
            _check(name, isinstance(v, (int, float)) and not isinstance(v, bool)
                   and 0.0 <= float(v) <= 1.0, v, "a number in [0, 1]")
        _check("delay_s", isinstance(self.delay_s, (int, float))
               and not isinstance(self.delay_s, bool) and float(self.delay_s) >= 0.0,
               self.delay_s, "a number >= 0")
        object.__setattr__(self, "poison_kinds", tuple(self.poison_kinds))
        _check("poison_kinds", bool(self.poison_kinds)
               and all(k in POISON_KINDS for k in self.poison_kinds),
               self.poison_kinds, f"a non-empty subset of {POISON_KINDS}")
        if self.target_streams is not None:
            object.__setattr__(self, "target_streams", tuple(self.target_streams))
            _check("target_streams",
                   all(isinstance(s, int) and not isinstance(s, bool) and s >= 0
                       for s in self.target_streams),
                   self.target_streams, "None or a tuple of stream ids >= 0")


class FaultInjector:
    """Deterministic fault harness driven by a `FaultPlan`.

    Every decision is a coin ``sha256(f"{seed}:{kind}:{stream}:{index}")``
    mapped to [0, 1) — order-independent and replayable.  Backend failures
    fire **at most once per launch index** (the injector remembers indices it
    already failed), so a guarded retry at the degraded ladder level succeeds
    and the recorded degradation sequence is deterministic.
    """

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self._failed_launches: set = set()

    def _coin(self, kind: str, stream: int, index: int) -> float:
        key = f"{self.plan.seed}:{kind}:{stream}:{index}".encode()
        return int.from_bytes(hashlib.sha256(key).digest()[:8], "big") / 2.0 ** 64

    def _targets(self, stream: int) -> bool:
        t = self.plan.target_streams
        return t is None or stream in t

    # -- pixel poison -----------------------------------------------------
    def poison_frame(self, frame, stream: int, index: int):
        """Corrupt ``frame`` deterministically; returns a numpy array."""
        kinds = self.plan.poison_kinds
        kind = kinds[int(self._coin("poison-kind", stream, index) * len(kinds))
                     % len(kinds)]
        arr = np.array(frame, dtype=np.float32, copy=True)
        if kind == "dtype":
            return (np.clip(arr, 0.0, 1.0) * 255.0).astype(np.uint8)
        h = max(1, arr.shape[0] // 8)
        w = max(1, arr.shape[1] // 8) if arr.ndim > 1 else 1
        y = int(self._coin("poison-y", stream, index) * max(1, arr.shape[0] - h))
        x = int(self._coin("poison-x", stream, index) * max(1, arr.shape[1] - w))
        val = {"nan": np.nan, "inf": np.inf, "range": 1.0e6}[kind]
        arr[y:y + h, x:x + w] = val
        return arr

    def wrap_stream(self, stream: int, frames: Iterable) -> Iterator:
        """Wrap a tenant iterator with seeded poison / iterator-error faults."""
        for index, frame in enumerate(frames):
            if self._targets(stream):
                if self._coin("iter-error", stream, index) < self.plan.iterator_error_rate:
                    raise InjectedStreamError(
                        f"injected iterator error (stream {stream}, frame {index})")
                if self._coin("poison", stream, index) < self.plan.poison_rate:
                    frame = self.poison_frame(frame, stream, index)
            yield frame

    # -- launch-level faults ----------------------------------------------
    def maybe_fail_launch(self, index: int) -> None:
        """Raise `InjectedBackendFailure` for this launch index, once ever."""
        if index in self._failed_launches:
            return
        if self._coin("backend", 0, index) < self.plan.backend_failure_rate:
            self._failed_launches.add(index)
            raise InjectedBackendFailure(
                f"injected backend failure (launch {index})")

    def maybe_delay(self, index: int) -> None:
        """Sleep ``delay_s`` before this launch (exercises the watchdog)."""
        if self.plan.delay_s > 0.0 and \
                self._coin("delay", 0, index) < self.plan.delay_rate:
            time.sleep(self.plan.delay_s)

    # -- payload corruption (for cache-robustness tests) -------------------
    @staticmethod
    def corrupt_file(path: str) -> None:
        """Overwrite a cache/checkpoint payload with garbage bytes."""
        with open(path, "wb") as f:
            f.write(b'{"mode": "int8", "scales": [NOT JSON')


@dataclass(frozen=True)
class LadderVariant:
    """One rung of the degradation ladder: a complete serving variant."""

    backend: str
    interpret: Optional[bool]
    quant: bool          # serve the calibrated QuantPack (False = fp32)
    fusion: str
    step: str = ""       # the step label that produced this rung ("" = as planned)


def build_ladder(backend: str, interpret: Optional[bool], quant_on: bool,
                 fusion: str) -> Tuple[LadderVariant, ...]:
    """Deterministic step-down order from the configured serving point.

    Order (each step only present when it changes something):
    fusion ``group→layer``, backend ``→ref``, quant ``int8/fxp10→fp32``.
    A compiled Pallas deployment steps straight to ``ref``: the Pallas
    interpreter cannot meet any deadline on a chip, so it is never a rung.
    The last rung is always the ref/fp32/layer floor; a failure there
    (retried up to ``plan.max_retries`` total attempts) propagates to the
    caller.
    """
    rungs = [LadderVariant(backend, interpret, quant_on, fusion)]

    def push(step, **delta):
        prev = rungs[-1]
        nxt = LadderVariant(
            backend=delta.get("backend", prev.backend),
            interpret=delta.get("interpret", prev.interpret),
            quant=delta.get("quant", prev.quant),
            fusion=delta.get("fusion", prev.fusion),
            step=step,
        )
        if (nxt.backend, nxt.interpret, nxt.quant, nxt.fusion) != \
                (prev.backend, prev.interpret, prev.quant, prev.fusion):
            rungs.append(nxt)

    if fusion == "group":
        push("fusion:group->layer", fusion="layer")
    if backend != "ref":
        push("backend:->ref", backend="ref", interpret=None)
    if quant_on:
        push("quant:->fp32", quant=False)
    return tuple(rungs)


class ResilienceGuard:
    """Sticky degradation ladder + the serving-side event ledger.

    ``run(attempt, index)`` calls ``attempt(variant)`` at the current rung;
    on any exception other than `PoisonFrameError` it steps down (or, at the
    floor, retries in place) and records the event, up to ``max_retries``
    extra attempts per call.  All quarantine/retire/poison/watchdog events
    funnel through ``record`` so ``SREngine.summary()["degradations"]`` is
    one deterministic ledger.
    """

    def __init__(self, backend: str, interpret: Optional[bool], quant_on: bool,
                 fusion: str, max_retries: int = 2):
        self.ladder = build_ladder(backend, interpret, quant_on, fusion)
        self.level = 0
        self.max_retries = max_retries
        self.events: List[Dict[str, Any]] = []

    @property
    def variant(self) -> LadderVariant:
        return self.ladder[self.level]

    def record(self, index, kind: str, reason: str) -> None:
        self.events.append({"index": index, "kind": kind, "reason": reason})

    def run(self, attempt: Callable[[LadderVariant], Any], index) -> Tuple[Any, Tuple[str, ...]]:
        """Execute ``attempt`` under the ladder; returns (result, new steps)."""
        steps: List[str] = []
        tries = 0
        while True:
            try:
                return attempt(self.ladder[self.level]), tuple(steps)
            except PoisonFrameError:
                raise                      # policy verdicts are not launch failures
            except Exception as e:
                tries += 1
                if tries > self.max_retries:
                    self.record(index, "failure",
                                f"ladder exhausted after {tries} attempts: {e!r}")
                    raise
                if self.level + 1 < len(self.ladder):
                    self.level += 1
                    step = self.ladder[self.level].step
                else:
                    step = "retry"         # already at the ref/fp32/layer floor
                steps.append(step)
                self.record(index, "degrade", f"{step}: {e!r}")

    def note_watchdog(self, index, dt: float, limit: float) -> Tuple[str, ...]:
        """An admission tick exceeded ``plan.watchdog_s``: step the ladder."""
        if self.level + 1 < len(self.ladder):
            self.level += 1
            step = self.ladder[self.level].step
        else:
            step = "floor"
        self.record(index, "watchdog",
                    f"{step}: tick took {dt:.4f}s > watchdog_s={limit}")
        return (step,) if step != "floor" else ()

    def summary(self) -> Dict[str, Any]:
        """Deterministic ledger for ``SREngine.summary()["degradations"]``."""
        by_kind: Dict[str, int] = {}
        by_step: Dict[str, int] = {}
        for e in self.events:
            by_kind[e["kind"]] = by_kind.get(e["kind"], 0) + 1
            if e["kind"] in ("degrade", "watchdog"):
                step = e["reason"].split(":", 1)[0]
                by_step[step] = by_step.get(step, 0) + 1
        return {
            "total": len(self.events),
            "by_kind": by_kind,
            "by_step": by_step,
            "level": self.level,
            "variant": self.variant.step or "as-planned",
            "events": list(self.events[-32:]),
        }
