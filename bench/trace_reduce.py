"""Reduce a profiler trace to what the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes. From it:

* device ops: the events of each device's ``XLA Ops`` line, each with its
  start, duration and name; an op is a Pallas kernel when the compiler
  marked it a TPU custom call (``tpu_custom_call`` in its stats or its
  HLO text); every other op is XLA's own. An op is named by the jitted
  module it ran in (the ``XLA Modules`` line) and its HLO instruction name,
  e.g. ``jit__take/fusion.1``;
* the traced window: the host span ``WINDOW_SPAN`` that the harness opens
  around the traced part of the measured window; device time is clipped
  to it;
* host spans: the events of the host's threads, to say what the host was
  doing during each idle gap of a device.

`reduce_events` works on plain tuples, so that it can be checked on a
small synthetic list; `reduce_file` reads a trace and calls it.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
BENCH_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS_MARK = "tpu_custom_call"
TOP = 10

# (device, name, start_ns, dur_ns, is_pallas)
DeviceOp = Tuple[int, str, float, float, bool]
# (name, start_ns, dur_ns)
HostSpan = Tuple[str, float, float]


def is_pallas(name: str, stats: Dict) -> bool:
    if PALLAS_MARK in name:
        return True
    return any(PALLAS_MARK in str(v) for v in stats.values())


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The uncovered stretches of [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _host_label(t: float, host: Sequence[HostSpan]) -> str:
    """What the host was doing at ``t``: the innermost harness span and the
    innermost other host event that cover it."""
    bench, other = None, None
    for name, s, d in host:
        if s <= t <= s + d:
            if name.startswith(BENCH_PREFIX):
                if name != WINDOW_SPAN and (bench is None or d < bench[1]):
                    bench = (name, d)
            elif other is None or d < other[1]:
                other = (name, d)
    parts = [p[0] for p in (bench, other) if p is not None]
    return " / ".join(parts) if parts else "host idle"


def op_name(hlo: str, module: str = "") -> str:
    """``%fusion.1 = f32[...] fusion(...)`` in ``jit__take(123)`` ->
    ``jit__take/fusion.1``."""
    short = hlo.split(" = ", 1)[0].strip().lstrip("%")
    mod = module.split("(", 1)[0]
    return f"{mod}/{short}" if mod else short


def _module_at(modules: Sequence[Tuple[float, float, str]], t: float) -> str:
    """Name of the module interval (start, end, name) that holds ``t``."""
    i = bisect.bisect_right(modules, (t, math.inf, "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def reduce_events(ops: Sequence[DeviceOp], host: Sequence[HostSpan],
                  window: Optional[Tuple[float, float]] = None) -> Dict:
    """Per-device busy, Pallas and XLA seconds inside the window, the top
    ops by time and the longest idle gaps with their host labels."""
    if window is None:
        spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        window = max(spans, key=lambda w: w[1] - w[0])
    lo, hi = window
    per_dev: Dict[int, List] = defaultdict(list)
    for dev, name, s, d, pallas in ops:
        s2, e2 = max(s, lo), min(s + d, hi)
        if e2 > s2:
            per_dev[dev].append((name, s2, e2, pallas))
    chips, op_time = [], defaultdict(float)
    gap_list = []
    for dev in sorted(per_dev):
        evs = per_dev[dev]
        iv = [(s, e) for _, s, e, _ in evs]
        busy = union_length(iv)
        pallas = union_length([(s, e) for _, s, e, p in evs if p])
        chips.append({"device": dev, "busy_s": busy * 1e-9,
                      "pallas_s": pallas * 1e-9,
                      "xla_s": union_length([(s, e) for _, s, e, p in evs
                                             if not p]) * 1e-9,
                      "ops": len(evs)})
        for name, s, e, _ in evs:
            op_time[name] += (e - s) * 1e-9
        for s, e in gaps(iv, lo, hi):
            gap_list.append((e - s, s, e, dev))
    gap_list.sort(reverse=True)
    top_gaps = [[f"TPU:{dev} " + _host_label((s + e) / 2, host), d * 1e-9]
                for d, s, e, dev in gap_list[:TOP]]
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (hi - lo) * 1e-9, "chips": chips,
            "device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": top_gaps}


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_file(path: str) -> Tuple[List[DeviceOp], List[HostSpan]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[DeviceOp] = []
    host: List[HostSpan] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get(MODULES_LINE, []))
            for e in lines.get(OPS_LINE, []):
                name = op_name(e.name, _module_at(modules, e.start_ns))
                ops.append((dev, name, e.start_ns, e.duration_ns,
                            is_pallas(e.name, dict(e.stats))))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, e.start_ns, e.duration_ns))
    return ops, host


def reduce_file(log_dir: str) -> Dict:
    ops, host = read_file(find_trace(log_dir))
    return reduce_events(ops, host)
