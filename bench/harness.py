"""One run of one benchmark cell: set-up, measured window, trace, check.

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, ``bench/traffic/<traffic>.json`` and, in a traced run,
``bench/metrics/<metric>.py`` for each per-layer metric the cell reports.
The program is reached only through ``repro.api.SREngine``; it gets the
benchmark's weights and frames, both made on the device from ``--seed``.

The window is a closed loop over one stream: ``SREngine.stream`` pulls the
next frame of the pool when it is ready for it, and the harness waits for
each served image. A frame's latency runs from that hand-off to its image
being ready. After the window, a seeded sample of the served frames is
compared with the plain reference (`reference.py`).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = "bench"
SAMPLE_FRAMES = 4           # served frames kept for the check (reservoir)
TRACE_LEAD_S = 1.0          # window seconds before the profiler starts
TRACE_SPAN_S = 10.0         # longest traced part of the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BAD = 3.4e38                # a number that could not be measured


class NoAccelerator(RuntimeError):
    """The cell cannot run here: no TPU, or fewer chips than it asks for."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: pathlib.Path, name: str) -> Dict:
    """The workload ``name`` with its configuration, traffic and metrics."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(root / BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    if traffic.get("loop") != "closed" or traffic.get("streams") != 1:
        raise SystemExit(f"traffic {cell['traffic']!r}: the harness drives one "
                         f"closed-loop stream, not {traffic.get('loop')!r} x "
                         f"{traffic.get('streams')!r}")
    reported = [m for m in bench["end_to_end"]
                if name in m.get("workloads", [name])]
    moved = {m["name"] for m in reported}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name]) and m["moves"] in moved]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": reported, "per_layer": per_layer,
            "peaks": load_json(root / BENCH_DIR / "peaks.json")}


def load_reader(root: pathlib.Path, metric: str) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = root / BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int) -> List:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX sees {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_peaks(peaks: Dict, kind: str) -> Dict:
    """The peaks of ``kind`` from ``peaks.json``; an unknown device is an error."""
    if kind not in peaks:
        raise NoAccelerator(f"no peaks for device kind {kind!r} in "
                            f"{BENCH_DIR}/peaks.json")
    return peaks[kind]


def planned_label(config: dict) -> str:
    """The backend label every frame of the window must be served under."""
    return config["backend"]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


@contextlib.contextmanager
def _no_span(name):
    yield


def build_engine(config: dict, params):
    """The program under test, configured as the configuration file says."""
    from repro.api import ExecutionPlan, SREngine
    from repro.core.adaptive import SwitchingConfig
    from repro.models.essr import ESSRConfig
    cfg = ESSRConfig(**config["model"])
    plan = ExecutionPlan(**config["plan"])
    return SREngine(params, cfg, plan=plan, backend=config["backend"],
                    switching=SwitchingConfig(**config["switching"]))


class Window:
    """The closed-loop stream of one run; fills the per-frame records."""

    def __init__(self, engine, pool, planned: str, seed: int, span):
        self.engine, self.pool, self.planned = engine, pool, planned
        self.span = span
        self.handed: List[float] = []
        self.done: List[float] = []        # ready time of frame j, or nan
        self.counts: List[tuple] = []
        self.failed = 0
        self.errors: List[str] = []
        self.sample: Dict[int, dict] = {}  # slot -> kept frame
        self.rng = np.random.default_rng(seed)
        self.t_end = math.inf

    def frames(self):
        while time.perf_counter() < self.t_end:
            with self.span("bench.handoff"):
                j = len(self.handed)
                frame = self.pool[j % len(self.pool)]
                self.handed.append(time.perf_counter())
                self.done.append(math.nan)
                self.counts.append((0, 0, 0))
            yield frame

    def _keep(self, j: int, r) -> None:
        slot = j if j < SAMPLE_FRAMES else int(self.rng.integers(0, j + 1))
        if slot < SAMPLE_FRAMES:
            self.sample[slot] = {"j": j, "pool": j % len(self.pool),
                                 "image": r.image, "ids": np.asarray(r.ids)}

    def run(self, t_end: float, on_frame: Optional[Callable] = None) -> None:
        """Serve until ``t_end``. Results come in frame order, so the k-th
        result is the k-th frame handed; a frame that raises ends its
        stream, and every frame handed and not yet served counts failed."""
        self.t_end = t_end
        frames = self.frames()
        n_events = len(self.engine.guard.events)
        k = 0
        while True:
            stream = self.engine.stream(frames)
            try:
                while True:
                    with self.span("bench.serve"):
                        r = next(stream)
                    with self.span("bench.wait"):
                        r.image.block_until_ready()
                    t = time.perf_counter()
                    j, k = k, k + 1
                    self.done[j] = t
                    self.counts[j] = tuple(int(c) for c in r.counts)
                    events = len(self.engine.guard.events)
                    if (r.backend != self.planned or r.degraded
                            or events != n_events):
                        self.failed += 1
                        self.errors.append(f"frame {j}: served by {r.backend} "
                                           f"{tuple(r.degraded)}")
                    n_events = events
                    self._keep(j, r)
                    if on_frame is not None:
                        on_frame(t)
            except StopIteration:
                return
            except Exception as e:          # the frame raised: count, go on
                self.failed += len(self.handed) - k
                k = len(self.handed)
                self.errors.append(f"frame {k - 1}: {e!r}")
                if len(self.errors) > 50 or time.perf_counter() >= t_end:
                    return


def device_info(devices, chips_used: int) -> Dict:
    import jax
    peak = 0
    for d in devices[:chips_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def check(sample: Dict[int, dict], pool, params, config: dict,
          limits: Dict) -> Dict:
    """Compare the kept frames with the plain reference; returns the
    numbers compared, each with its limit."""
    import jax
    import jax.numpy as jnp
    import reference
    dev0 = jax.devices()[0]
    refs: Dict[int, tuple] = {}
    mismatch, err, n = 0, 0.0, 0
    for kept in sorted(sample.values(), key=lambda k: k["j"]):
        p = kept["pool"]
        if p not in refs:
            out, ids, _ = reference.reference_frame(
                params, pool[p], config["model"], config["plan"])
            refs[p] = (out, ids, jnp.max(jnp.abs(out)))
        out, ids, scale = refs[p]
        got = jax.device_put(kept["image"], dev0)
        if got.shape != out.shape or kept["ids"].shape != ids.shape:
            mismatch += ids.size
            err = BAD
        else:
            mismatch += int((kept["ids"] != ids).sum())
            d = float(jnp.max(jnp.abs(got - out)) / scale)
            err = max(err, d if math.isfinite(d) else BAD)
        n += 1
    return {"frames_compared": {"value": n, "limit": 1},
            "route_mismatch": {"value": mismatch,
                               "limit": limits["route_mismatch"]},
            "image_err": {"value": err, "limit": limits["image_err"]}}


def verdict(checks: Dict) -> bool:
    """At least one frame compared, every other number within its limit."""
    if checks["frames_compared"]["value"] < 1:
        return False
    return all(v["value"] <= v["limit"] for k, v in checks.items()
               if k != "frames_compared")


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float) -> Dict:
    """One run; returns the result object that ``run.py`` prints."""
    spec = load_cell(root, workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])
    import jax
    devices = check_devices(chips)
    log(f"compile cache: {enable_compile_cache(root)}")
    sys.path.insert(0, str(root / "src"))
    import frames
    import reference
    import trace_reduce

    peaks = device_peaks(spec["peaks"], devices[0].device_kind)
    plan = config["plan"]
    cell_px = int(plan["patch"]) - int(plan["overlap"])
    params = reference.init_weights(seed, config["model"])
    pool = frames.make_pool(seed, config["lr_hw"], cell_px, traffic["shares"],
                            int(traffic["pool_frames"]))
    engine = build_engine(config, params)
    planned = planned_label(config)
    log(f"engine: backend label {engine.backend_label}, planned {planned}, "
        f"plan {plan}")

    compiles = {"window": False, "n": 0}

    def on_event(name, secs, **kw):
        if name == COMPILE_EVENT and compiles["window"]:
            compiles["n"] += 1
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(on_event)

    # warm-up: the whole pool once, which is every shape the window uses
    for j, r in enumerate(engine.stream(pool)):
        r.image.block_until_ready()
        log(f"warm-up frame {j}: routing (bilinear, C27, C54) = "
            f"{tuple(int(c) for c in r.counts)}, served by {r.backend}")

    span = _no_span
    if trace:
        span = jax.profiler.TraceAnnotation
    win = Window(engine, pool, planned, seed, span)
    tr = {"state": "off", "t0": None, "t1": None, "ann": None}
    trace_dir = str(root / ".bench_trace" / workload)

    def on_frame(t):
        """Profile whole frames: start the profiler after one frame, open
        the traced span after the next (the first frame under the profiler
        pays its start-up), close both after another ``TRACE_SPAN_S``."""
        if not trace:
            return
        if tr["state"] == "off" and t >= t_win + TRACE_LEAD_S:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tr["state"] = "starting"
        elif tr["state"] == "starting":
            tr["ann"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            tr["ann"].__enter__()
            tr["state"], tr["t0"] = "on", time.perf_counter()
        elif tr["state"] == "on" and (t >= tr["t0"] + TRACE_SPAN_S
                                      or t >= t_win + seconds - 1.0):
            stop_trace()

    def stop_trace():
        tr["t1"] = time.perf_counter()
        tr["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        tr["state"] = "done"

    t_win = time.perf_counter()
    setup_s = t_win - t_start
    compiles["window"] = True
    win.run(t_win + seconds, on_frame)
    t_close = t_win + seconds
    compiles["window"] = False
    if tr["state"] == "on":
        stop_trace()
    elif tr["state"] == "starting":
        jax.profiler.stop_trace()
    device = device_info(devices, chips)

    done = np.asarray(win.done, np.float64)
    handed = np.asarray(win.handed, np.float64)
    in_window = np.isfinite(done) & (done <= t_close)
    lat_ms = (done[in_window] - handed[in_window]) * 1e3
    log(f"window: {len(handed)} frames handed, {int(in_window.sum())} ready "
        f"in {seconds} s, {win.failed} failed")
    for e in win.errors[:10]:
        log(f"  {e}")

    result: Dict = {"attempted": len(handed), "failed": win.failed}
    metrics: Dict = {}
    if not trace:
        values = {"fps": int(in_window.sum()) / seconds,
                  "frame_ms_p90": float(np.percentile(lat_ms, 90)) if lat_ms.size
                  else BAD,
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        red = trace_reduce.reduce_file(trace_dir)
        t0, t1 = tr["t0"], tr["t1"]
        traced = np.isfinite(done) & (done >= t0) & (done <= t1)
        counts = [win.counts[j] for j in np.flatnonzero(traced)]
        ctx = {"trace": red, "frames": int(traced.sum()), "counts": counts,
               "window_s": red["window_s"], "compiles_in_window": compiles["n"],
               "model": config["model"], "plan": plan, "peaks": peaks,
               "chips": chips}
        for m in spec["per_layer"]:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        chips_red = red["chips"] or [{"busy_s": 0.0}]
        device["busy_s"] = sum(c["busy_s"] for c in chips_red) / max(chips, 1)
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace: {ctx['frames']} frames in {red['window_s']:.3f} s traced; "
            f"chips {red['chips']}")
    result["metrics"] = metrics
    result["device"] = device

    # the check: after the window, with the memory peak read
    del engine
    win.engine = None
    checks = check(win.sample, pool, params, config, config["limits"])
    result["correct"] = verdict(checks)
    result["checks"] = checks
    return result
