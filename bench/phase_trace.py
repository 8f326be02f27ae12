"""Split a profiler trace by the program's own names.

The program names each device executable of its serving path
``jit_essr_<phase>`` and opens host spans ``essr.*`` around the work of each
frame (``repro.core.phases``; the names are listed in docs/api.md,
"Tracing"). From the ``.xplane.pb`` of a traced run this module reduces,
inside the window the harness traced (``trace_reduce.WINDOW_SPAN``):

* per chip, ``phase_s``: device seconds of each phase, the union of the op
  intervals of its modules; ``unnamed_s``: the same for every other module,
  with the largest of them in ``unnamed_modules``; ``executables``: module
  executions that start in the window;
* per chip, ``program_idle_s``: device idle time inside the frames' top
  spans (``essr.serve``, or ``essr.launch`` / ``essr.finalize`` under fused
  dispatch): the chip waiting on the program's host code; and
  ``idle_by_span``: all idle time of the window by the innermost ``essr.*``
  span open over it (``none`` where no program span is open);
* ``program``: seconds and count of the frames' top spans and of the
  ``essr.wait.*`` spans inside them (the host blocked on the device);
* ``clock``: of the phase executions that can be paired, in order, with the
  host call that dispatched them (``PjitFunction(essr_<phase>)``), how many
  start no earlier than the top span of the frame that made that call.

Metric readers call `for_ctx`, which finds the trace the harness just
reduced. A trace of a program without these names yields no phases and no
program spans, and the readers that need them return None. Run as a
script, it prints the reduction of one trace as JSON:

    python3 bench/phase_trace.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import pathlib
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"      # where the harness writes traces
#: the program's phase names (docs/api.md, "Tracing"); any other module,
#: a Pallas kernel's own jit among them, is unnamed
PHASES = (r"essr_(health|extract|edge_score|lane_gather|lane_scatter|"
          r"bilinear|c\d+|fuse|fused_frame|fused_streams)")
PHASE_MODULE = re.compile(rf"^jit_({PHASES})(\(.*)?$")
DISPATCH = re.compile(rf"^PjitFunction\(({PHASES})\)$")
FRAME_SPANS = ("essr.serve", "essr.launch", "essr.finalize")
SPAN_PREFIX = "essr."
WAIT_PREFIX = "essr.wait."
NO_SPAN = "none"

# (device, module, start_ns, dur_ns): one device op, or one module execution
DeviceEvent = Tuple[int, str, float, float]


def phase_of(module: str) -> Optional[str]:
    """``jit_essr_extract(1234)`` -> ``essr_extract``; None for any other
    module."""
    m = PHASE_MODULE.match(module)
    return m.group(1) if m else None


def _clip(events, lo: float, hi: float):
    """(start, end) of each event, clipped to [lo, hi], empty ones dropped,
    alongside the event."""
    for ev in events:
        s, e = max(ev[-2], lo), min(ev[-2] + ev[-1], hi)
        if e > s:
            yield s, e, ev


def _overlap(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _merge(intervals) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _segments(spans: Sequence[Tuple[str, float, float]], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut where program spans open and close, each piece labelled
    by the innermost (shortest) span open over it, or `NO_SPAN`."""
    points = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                                if lo < t < hi})
    starts = sorted(spans, key=lambda x: x[1])
    out, active, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(starts) and starts[k][1] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        label = min(active, key=lambda sp: sp[2] - sp[1])[0] if active \
            else NO_SPAN
        out.append((a, b, label))
    return out


def _by_label(segments: Sequence[Tuple[float, float, str]],
              idle: Sequence[Tuple[float, float]]) -> Dict[str, float]:
    """Length of ``idle`` (sorted, disjoint) under each segment's label."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b, label in segments:
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            out[label] += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
    return out


def _containing(frames: Sequence[Tuple[float, float]], t: float
                ) -> Optional[Tuple[float, float]]:
    """The (start, end) of the sorted, disjoint frame spans that holds t."""
    i = bisect.bisect_right(frames, (t, float("inf"))) - 1
    if i >= 0 and frames[i][0] <= t <= frames[i][1]:
        return frames[i]
    return None


def _clock(modules: Sequence[DeviceEvent], host: Sequence[tr.HostSpan],
           frames: Sequence[Tuple[float, float]], lo: float, hi: float
           ) -> Dict:
    """Pair each phase's executions on a device, in order, with the host
    calls that dispatched them, and count the executions that start no
    earlier than the frame span around their call."""
    calls: Dict[str, List[float]] = defaultdict(list)
    ends: Dict[str, float] = {}
    for name, s, d in sorted(host, key=lambda h: h[1]):
        m = DISPATCH.match(name)
        if m and s >= ends.get(m.group(1), -1.0):   # outermost of a nest
            calls[m.group(1)].append(s)
            ends[m.group(1)] = s + d
    runs: Dict[Tuple[int, str], List[float]] = defaultdict(list)
    for dev, module, s, _ in modules:
        phase = phase_of(module)
        if phase is not None:
            runs[(dev, phase)].append(s)
    lags: List[float] = []
    early: Dict[str, int] = defaultdict(int)
    for (dev, phase), starts in runs.items():
        starts, made = sorted(starts), calls.get(phase, [])
        n = min(len(starts), len(made))
        # executions of calls made before the trace began lead the list,
        # calls whose executions the trace missed trail theirs
        for t_run, t_call in zip(starts[len(starts) - n:], made[:n]):
            frame = _containing(frames, t_call)
            if frame is None or not lo <= t_run <= hi:
                continue
            lags.append((t_run - t_call) * 1e-3)
            if t_run < frame[0]:
                early[phase] += 1
    lags.sort()
    pick = lambda q: lags[min(int(q * len(lags)), len(lags) - 1)]
    return {"pairs": len(lags),
            "share": 1 - sum(early.values()) / len(lags) if lags else None,
            "early": dict(sorted(early.items())),
            # start of each execution minus its call, in us: below 0, the
            # device's clock runs ahead of the host's by at least that much
            "run_after_call_us": ({"min": lags[0], "p01": pick(0.01),
                                   "p50": pick(0.5)} if lags else None)}


def reduce_events(ops: Sequence[DeviceEvent], modules: Sequence[DeviceEvent],
                  host: Sequence[tr.HostSpan],
                  window: Optional[Tuple[float, float]] = None) -> Dict:
    """The reduction described in the module docstring, on plain tuples."""
    if window is None:
        spans = [(s, s + d) for n, s, d in host if n == tr.WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {tr.WINDOW_SPAN!r} span in the trace")
        window = max(spans, key=lambda w: w[1] - w[0])
    lo, hi = window
    program = [(n, s, e) for s, e, (n, _, _) in _clip(host, lo, hi)
               if n.startswith(SPAN_PREFIX)]
    frames = _merge((s, e) for n, s, e in program if n in FRAME_SPANS)
    waits = [(n, s, e) for n, s, e in program
             if n.startswith(WAIT_PREFIX) and _containing(frames, s)]
    segments = _segments(program, lo, hi)

    per_dev: Dict[int, List] = defaultdict(list)
    for s, e, (dev, module, _, _) in _clip(ops, lo, hi):
        per_dev[dev].append((phase_of(module), module.split("(", 1)[0], s, e))
    executions: Dict[int, int] = defaultdict(int)
    for dev, _, s, _ in modules:
        executions[dev] += lo <= s < hi
    chips, idle_all = [], defaultdict(float)
    for dev in sorted(set(per_dev) | set(executions)):
        evs = per_dev.get(dev, [])
        by_phase, unnamed = defaultdict(list), defaultdict(list)
        for phase, module, s, e in evs:
            (by_phase[phase] if phase else unnamed[module]).append((s, e))
        idle = tr.gaps([(s, e) for _, _, s, e in evs], lo, hi)
        idle_by = {k: v * 1e-9 for k, v in _by_label(segments, idle).items()}
        for label, t in idle_by.items():
            idle_all[label] += t
        top = sorted(((m, tr.union_length(iv) * 1e-9)
                      for m, iv in unnamed.items()), key=lambda kv: -kv[1])
        chips.append({
            "device": dev,
            "phase_s": {p: tr.union_length(iv) * 1e-9
                        for p, iv in sorted(by_phase.items())},
            "unnamed_s": tr.union_length(
                [iv for ivs in unnamed.values() for iv in ivs]) * 1e-9,
            "unnamed_modules": [[m, t] for m, t in top[:tr.TOP]],
            "executables": executions[dev],
            "program_idle_s": _overlap(frames, idle) * 1e-9,
            "idle_by_span": dict(sorted(idle_by.items(),
                                        key=lambda kv: -kv[1]))})
    by_wait: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for n, s, e in waits:
        by_wait[n][0] += 1
        by_wait[n][1] += (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "chips": chips,
        "named": any(c["phase_s"] for c in chips),
        "program": {
            "frame_spans": sum(1 for n, _, _ in program if n in FRAME_SPANS),
            "frame_s": sum(e - s for s, e in frames) * 1e-9,
            "waits": len(waits),
            "wait_s": tr.union_length([(s, e) for _, s, e in waits]) * 1e-9,
            "by_wait": dict(sorted(by_wait.items()))},
        "idle_by_span": dict(sorted(idle_all.items(), key=lambda kv: -kv[1])),
        "clock": _clock(modules, host, frames, lo, hi)}


def read_file(path: str) -> Tuple[List[DeviceEvent], List[DeviceEvent],
                                  List[tr.HostSpan]]:
    """Device ops (named by their module), module executions and host
    events of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[DeviceEvent] = []
    modules: List[DeviceEvent] = []
    host: List[tr.HostSpan] = []
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get(tr.MODULES_LINE, []))
            modules.extend((dev, n, s, e - s) for s, e, n in mods)
            for e in lines.get(tr.OPS_LINE, []):
                ops.append((dev, tr._module_at(mods, e.start_ns),
                            e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, e.start_ns, e.duration_ns))
    return ops, modules, host


@functools.lru_cache(maxsize=2)
def _reduce_path(path: str, mtime_ns: int) -> Dict:
    return reduce_events(*read_file(path))


def for_ctx(ctx: Dict) -> Optional[Dict]:
    """The reduction of the trace behind a metric reader's ``ctx``: the
    newest trace under `TRACE_DIR` whose traced window is the one the
    harness reduced. None when there is none; never raises, so a reader
    stays silent where nothing can be read."""
    paths = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            red = _reduce_path(path, os.stat(path).st_mtime_ns)
        except Exception as e:            # an unreadable or foreign trace
            print(f"phase_trace: {path}: {e!r}", file=sys.stderr)
            continue
        if abs(red["window_s"] - ctx["window_s"]) <= 1e-6:
            return red
    return None


def phase_ms_per_frame(ctx: Dict, pattern: str) -> Optional[float]:
    """Device ms a frame of the phases whose name matches ``pattern``
    (``re.fullmatch``), averaged over the cell's chips; None where the
    program names no phase."""
    red = for_ctx(ctx)
    if red is None or not red["named"] or ctx["frames"] < 1:
        return None
    rx = re.compile(pattern)
    s = sum(t for c in red["chips"] for p, t in c["phase_s"].items()
            if rx.fullmatch(p))
    return 1e3 * s / ctx["chips"] / ctx["frames"]


def program(ctx: Dict) -> Optional[Dict]:
    """The ``program`` and chips of the reduction, where the program opened
    its frame spans in the window."""
    red = for_ctx(ctx)
    if red is None or not red["program"]["frame_spans"] or ctx["frames"] < 1:
        return None
    return red


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    path = args[0] if args[0].endswith(".xplane.pb") else tr.find_trace(args[0])
    print(json.dumps(reduce_events(*read_file(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
