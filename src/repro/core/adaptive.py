"""Resource-adaptive model switching (paper Sec. IV-A, Algorithm 1).

Host-side feedback controller over the two edge thresholds:

  * hard compute ceiling: if the number of C54 patches this second exceeds
    ``c54_per_sec_budget`` (25 500 for 8K@30FPS on the paper's PE array), the
    *rest of the patches run with C27* — throughput guaranteed, quality floor
    kept at C27;
  * per-frame trim: > ``frame_high`` C54 patches in a frame  -> (t1,t2) += (1,5)
                    < ``frame_low``  C54 patches in a frame  -> (t1,t2) -= (1,5)

The same controller is reused by the serving runtime as *straggler
mitigation*: a shard that falls behind its deadline raises the local
thresholds, demoting its patches. `ShardSwitcherBank` implements that for
the sharded patch stream: one `AdaptiveSwitcher` per shard (budgets split
evenly), contiguous raster strips of each frame routed by each shard's local
thresholds. The miss signal is the frame's single wall-clock deadline;
*which* shards back off is attributed by a host-side load model — each
shard's estimated MAC cost vs the balanced share — not by per-device
timing (dispatch splits every subnet bucket evenly across devices, so no
device maps 1:1 to a routing strip). A missed frame demotes the shards
contributing the most compute, proportionally to their overload, shedding
load where the C54 work originates while lightly-loaded strips keep their
quality.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core import subnet_policy as sp
from repro.core.phases import span


@dataclasses.dataclass
class SwitchingConfig:
    t1: float = sp.DEFAULT_T1
    t2: float = sp.DEFAULT_T2
    c54_per_sec_budget: int = 25_500
    frame_high: int = 1000
    frame_low: int = 700
    fps: int = 30
    t1_step: float = 1.0
    t2_step: float = 5.0
    t1_bounds: Tuple[float, float] = (0.0, 255.0)
    t2_bounds: Tuple[float, float] = (1.0, 255.0)


class AdaptiveSwitcher:
    """Stateful Algorithm-1 controller. One instance per stream (or shard)."""

    def __init__(self, cfg: Optional[SwitchingConfig] = None):
        self.cfg = cfg = cfg if cfg is not None else SwitchingConfig()
        self.t1 = float(cfg.t1)
        self.t2 = float(cfg.t2)
        self._c54_this_second = 0
        self._frames_this_second = 0

    # -- public -------------------------------------------------------------

    def assign(self, scores: np.ndarray) -> np.ndarray:
        """Edge scores of one frame's patches (raster order) -> subnet ids.

        Applies the per-second C54 ceiling (demote overflow to C27 in raster
        order, exactly "the rest of the patches run with C27"), then the
        per-frame threshold adaptation.
        """
        scores = np.asarray(scores)
        ids = sp.decide(scores, self.t1, self.t2)
        with span("essr.wait.route"):
            ids = np.array(ids)                      # writable copy

        # --- hard ceiling over the current second -------------------------
        budget_left = self.cfg.c54_per_sec_budget - self._c54_this_second
        c54_idx = np.flatnonzero(ids == sp.C54)
        if len(c54_idx) > budget_left:
            overflow = c54_idx[max(budget_left, 0):]
            ids[overflow] = sp.C27
        n_c54 = int((ids == sp.C54).sum())
        self.observe_frame(n_c54)
        return ids

    def observe_frame(self, n_c54: int) -> None:
        """Feed back one served frame's C54 count: the per-frame threshold
        trim (Algorithm 1's else-branch) plus the per-second bookkeeping.

        This is ``assign`` minus the routing itself — the fused-dispatch
        stream uses it because routing happened *in the frame executable*
        (the C54 capacity slots enforce the hard ceiling in-graph, the
        overflow spilling to C27 exactly as "the rest of the patches run
        with C27"); the host only adapts thresholds from the materialized
        counts, one frame behind under async streaming."""
        n_c54 = int(n_c54)
        self._c54_this_second += n_c54

        # --- per-frame threshold trim (Algorithm 1's else-branch) ---------
        if n_c54 > self.cfg.frame_high:
            self.t1 += self.cfg.t1_step
            self.t2 += self.cfg.t2_step
        elif n_c54 < self.cfg.frame_low:
            self.t1 -= self.cfg.t1_step
            self.t2 -= self.cfg.t2_step
        self._clamp()

        # --- second roll-over ---------------------------------------------
        self._frames_this_second += 1
        if self._frames_this_second >= self.cfg.fps:
            self._frames_this_second = 0
            self._c54_this_second = 0

    def demote_for_straggler(self, severity: float = 1.0) -> None:
        """Straggler hook: a late shard raises thresholds proportionally."""
        self.t1 += self.cfg.t1_step * severity
        self.t2 += self.cfg.t2_step * severity
        self._clamp()

    # -- internals ----------------------------------------------------------

    def _clamp(self) -> None:
        c = self.cfg
        self.t1 = float(np.clip(self.t1, *c.t1_bounds))
        self.t2 = float(np.clip(self.t2, *c.t2_bounds))
        if self.t2 <= self.t1:          # keep the decision boundary ordered
            self.t2 = self.t1 + 1.0

    @property
    def thresholds(self) -> Tuple[float, float]:
        return (self.t1, self.t2)


# ---------------------------------------------------------------------------
# sharded streaming: one Algorithm-1 controller per shard
# ---------------------------------------------------------------------------

def per_shard_config(cfg: SwitchingConfig, shards: int) -> SwitchingConfig:
    """Split a stream-level SwitchingConfig across ``shards`` equal shards.

    Each shard sees ~1/shards of every frame's patches, so the per-second C54
    budget and the per-frame trim bands scale down with it (positive values
    floored at 1 so a tiny shard still adapts; a 0 stays 0 — ``frame_low=0``
    means "never decay thresholds" and splitting must not re-enable it);
    thresholds, steps and bounds are per-controller quantities and stay
    as-is."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards == 1:
        return cfg
    split = lambda v: max(1, v // shards) if v > 0 else v
    return dataclasses.replace(
        cfg,
        c54_per_sec_budget=split(cfg.c54_per_sec_budget),
        frame_high=split(cfg.frame_high),
        frame_low=split(cfg.frame_low))


class ShardSwitcherBank:
    """Per-shard Algorithm-1 controllers + lock-step straggler mitigation.

    ``assign`` routes one frame: shard ``k`` decides its contiguous slice of
    the raster-order scores under its OWN live thresholds. ``note_frame``
    feeds back the frame outcome: on a missed (global wall-clock) deadline,
    the shards whose estimated MAC cost exceeds the balanced share are
    treated as the overload source and get ``demote_for_straggler`` with
    severity = overload ratio — a cost-model attribution, not a per-device
    measurement; a uniformly loaded frame demotes every shard (aggregate
    throughput must recover).
    """

    def __init__(self, cfg: Optional[SwitchingConfig] = None, shards: int = 1):
        cfg = cfg if cfg is not None else SwitchingConfig()
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.shards = shards
        self.switchers: List[AdaptiveSwitcher] = [
            AdaptiveSwitcher(per_shard_config(cfg, shards))
            for _ in range(shards)]

    def assign(self, scores: np.ndarray,
               slices: Sequence[slice]) -> np.ndarray:
        """Frame scores (raster order) + shard slices -> subnet ids."""
        if len(slices) != self.shards:
            raise ValueError(f"got {len(slices)} slices for "
                             f"{self.shards} shards")
        scores = np.asarray(scores)
        ids = np.empty(len(scores), dtype=np.int64)
        for sw, sl in zip(self.switchers, slices):
            ids[sl] = sw.assign(scores[sl])
        return ids

    def note_frame(self, missed: bool,
                   costs: Sequence[float]) -> Tuple[bool, ...]:
        """Feed back one frame's outcome; returns which shards were demoted.

        ``costs``: estimated per-shard MAC cost of the frame just served
        (`sp.SubnetMacs.total` over each shard's counts)."""
        if len(costs) != self.shards:
            raise ValueError(f"got {len(costs)} costs for "
                             f"{self.shards} shards")
        if not missed:
            return (False,) * self.shards
        costs = np.asarray(costs, np.float64)
        mean = float(costs.mean())
        if mean <= 0 or np.allclose(costs, mean):
            # no imbalance signal: global overload, every shard backs off
            demoted = [True] * self.shards
            severities = [1.0] * self.shards
        else:
            demoted = [bool(c > mean) for c in costs]
            # severity = how far past the balanced share, capped so one
            # pathological frame cannot slam thresholds to the bound
            severities = [min(float(c / mean), 3.0) for c in costs]
        for sw, d, sev in zip(self.switchers, demoted, severities):
            if d:
                sw.demote_for_straggler(severity=sev)
        return tuple(demoted)

    @property
    def thresholds(self) -> Tuple[Tuple[float, float], ...]:
        return tuple(sw.thresholds for sw in self.switchers)


# ---------------------------------------------------------------------------
# multi-stream serving: one Algorithm-1 controller per tenant stream
# ---------------------------------------------------------------------------

def per_stream_config(cfg: SwitchingConfig, share: float) -> SwitchingConfig:
    """Scale a stream-level SwitchingConfig down to one tenant's QoS share.

    ``share`` is the stream's normalized fraction of the aggregate (0, 1].
    The per-second C54 budget and the per-frame trim bands scale with it
    (positive values floored at 1 so a thin stream still adapts; 0 stays 0 —
    ``frame_low=0`` means "never decay thresholds" and splitting must not
    re-enable it); thresholds, steps and bounds are per-controller
    quantities and stay as-is. The same contract as :func:`per_shard_config`,
    with a real-valued weight instead of an even split."""
    if not (0.0 < share <= 1.0):
        raise ValueError(f"share must be in (0, 1], got {share}")
    if share == 1.0:
        return cfg
    split = lambda v: max(1, int(v * share)) if v > 0 else v
    return dataclasses.replace(
        cfg,
        c54_per_sec_budget=split(cfg.c54_per_sec_budget),
        frame_high=split(cfg.frame_high),
        frame_low=split(cfg.frame_low))


class StreamSwitcherBank:
    """Per-stream Algorithm-1 controllers + share-weighted QoS attribution.

    One `AdaptiveSwitcher` per tenant stream, each seeded with the
    stream-level config split by that stream's normalized share
    (:func:`per_stream_config`) — thresholds adapt independently, so one
    tenant's content can never move another tenant's decision boundary.
    ``tick_quotas`` turns each stream's split per-second budget into its
    per-admission-tick C54 slot quota (the traced ``quotas`` argument of the
    fused multi-stream executable). ``note_tick`` attributes a missed tick
    deadline by *share-weighted* cost — a stream is the overload source when
    its MAC cost exceeds what its share entitles it to — mirroring
    `ShardSwitcherBank.note_frame`'s cost-model attribution.
    """

    def __init__(self, cfg: Optional[SwitchingConfig] = None,
                 streams: int = 1,
                 shares: Optional[Sequence[float]] = None):
        cfg = cfg if cfg is not None else SwitchingConfig()
        if streams < 1:
            raise ValueError(f"streams must be >= 1, got {streams}")
        if shares is None:
            shares = (1.0,) * streams
        if len(shares) != streams:
            raise ValueError(f"got {len(shares)} shares for {streams} streams")
        total = float(sum(shares))
        if not (total > 0 and np.isfinite(total)):
            raise ValueError(f"shares must sum to a positive finite value, "
                             f"got {tuple(shares)}")
        self.streams = streams
        self.shares: Tuple[float, ...] = tuple(float(s) / total for s in shares)
        self.switchers: List[AdaptiveSwitcher] = [
            AdaptiveSwitcher(per_stream_config(cfg, sh))
            for sh in self.shares]

    def tick_quotas(self) -> Tuple[int, ...]:
        """Per-stream C54 slot quota for one admission tick: each tenant's
        split per-second budget spread over its fps, floored at 1 (a live
        stream always keeps at least one C54 slot — shares degrade quality,
        they never starve a tenant)."""
        return tuple(max(1, sw.cfg.c54_per_sec_budget // max(1, sw.cfg.fps))
                     for sw in self.switchers)

    def observe(self, stream: int, n_c54: int) -> None:
        """Feed one stream's served-frame C54 count to its own controller."""
        self.switchers[stream].observe_frame(n_c54)

    def note_tick(self, missed: bool, costs: Sequence[float],
                  streams: Optional[Sequence[int]] = None
                  ) -> Tuple[bool, ...]:
        """Feed back one tick's outcome; returns which streams were demoted.

        ``costs``: estimated per-stream MAC cost of the tick just served;
        ``streams``: the live stream indices those costs belong to (defaults
        to all). On a missed (shared wall-clock) deadline the streams whose
        *share-weighted* cost — cost divided by normalized share — exceeds
        the weighted mean are demoted with severity = overweight ratio; a
        tick loaded exactly in share proportion demotes every live stream
        (aggregate throughput must recover, and no tenant is entitled to the
        others' backing off alone)."""
        live = tuple(range(self.streams)) if streams is None else tuple(streams)
        if len(costs) != len(live):
            raise ValueError(f"got {len(costs)} costs for {len(live)} "
                             f"live streams")
        if not missed:
            return (False,) * self.streams
        weighted = np.asarray(
            [float(c) / self.shares[s] for c, s in zip(costs, live)],
            np.float64)
        mean = float(weighted.mean())
        demoted = [False] * self.streams
        if mean <= 0 or np.allclose(weighted, mean):
            # loaded exactly in share proportion: every live stream backs off
            for s in live:
                demoted[s] = True
                self.switchers[s].demote_for_straggler(severity=1.0)
        else:
            for w, s in zip(weighted, live):
                if w > mean:
                    demoted[s] = True
                    # severity capped like the shard bank: one pathological
                    # tick cannot slam a tenant's thresholds to the bound
                    self.switchers[s].demote_for_straggler(
                        severity=min(float(w / mean), 3.0))
        return tuple(demoted)

    @property
    def thresholds(self) -> Tuple[Tuple[float, float], ...]:
        return tuple(sw.thresholds for sw in self.switchers)
