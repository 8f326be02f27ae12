"""Every phase of the serving path carries its name on a profiler trace.

A frame served under ``jax.profiler`` shows the host spans ``essr.*``, each
with the frame's launch index as its ``frame`` stat, and a host call
``PjitFunction(essr_<phase>)`` for each phase it dispatches (whose
executable is the module ``jit_essr_<phase>``). Profiling changes no
served bit.
"""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import ExecutionPlan, SREngine
from repro.core.adaptive import SwitchingConfig
from repro.core.patching import get_geometry
from repro.core.phases import lane_phase, phase_jit
from repro.models.essr import ESSRConfig, init_essr

CFG = ESSRConfig(scale=2)
#: thresholds held, as in the benchmark: routing depends on content alone
HELD = SwitchingConfig(t1=8.0, t2=40.0, c54_per_sec_budget=10 ** 9,
                       frame_high=10 ** 9, frame_low=0)
HOST_SPANS = ("essr.serve", "essr.health", "essr.wait.health",
              "essr.extract", "essr.route", "essr.wait.scores",
              "essr.wait.route", "essr.fuse", "essr.wait.image")
HOST_PHASES = ("essr_health", "essr_extract", "essr_edge_score",
               "essr_lane_gather", "essr_lane_scatter", "essr_bilinear",
               "essr_c27", "essr_c54", "essr_fuse")


def _frames():
    """Two 60x90 frames, each a third smooth, textured and edged, so that
    every lane takes part of each."""
    h, w = 60, 90
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, h), jnp.linspace(0, 1, w),
                          indexing="ij")
    checker = ((jnp.arange(h)[:, None] + jnp.arange(w)[None, :]) % 2
               ).astype(jnp.float32)
    smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
    frame = jnp.where((xx < 1 / 3)[..., None], smooth,
                      jnp.where((xx < 2 / 3)[..., None],
                                smooth + 0.03 * checker[..., None],
                                checker[..., None] * jnp.ones(3)))
    frame = jnp.clip(frame, 0.0, 1.0)
    return [frame, frame[:, ::-1]]


def _engine(dispatch):
    params = init_essr(jax.random.PRNGKey(0), CFG)
    return SREngine(params, CFG, backend="ref", switching=HELD,
                    plan=ExecutionPlan(dispatch=dispatch))


def _served(dispatch, trace_dir=None):
    """Serve the frames on a fresh, warm engine; with ``trace_dir`` under
    the profiler. Returns (images, ids, host events of the trace)."""
    eng = _engine(dispatch)
    frames = _frames()
    list(eng.stream(frames))                 # warm: nothing compiles below
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        out = [(np.asarray(r.image), np.asarray(r.ids))
               for r in eng.stream(frames)]
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return out, (_host_events(trace_dir) if trace_dir is not None else [])


def _host_events(trace_dir):
    """(name, stats, start_ns) of every host event of the trace, in order."""
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events.extend((e.name, dict(e.stats), e.start_ns)
                              for e in line.events)
    return sorted(events, key=lambda e: e[2])


@pytest.fixture(scope="module")
def host(tmp_path_factory):
    return _served("host", tmp_path_factory.mktemp("host"))


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    return _served("fused", tmp_path_factory.mktemp("fused"))


def _spans_by_frame(events):
    by = collections.defaultdict(collections.Counter)
    for name, stats, _ in events:
        if name.startswith("essr."):
            by[stats.get("frame")][name] += 1
    return by


def test_host_dispatch_spans_share_the_frame_stat(host):
    _, events = host
    by = _spans_by_frame(events)
    assert len(by) == 2 and -1 not in by       # every span inside a frame
    for frame, spans in by.items():
        for name in HOST_SPANS:
            assert spans[name] >= 1, (frame, name, spans)
        assert spans["essr.serve"] == 1
        assert spans["essr.lane"] == 3          # bilinear, C27 and C54
        # the host blocks on the device four times a frame: the health
        # verdict, the edge scores, the switcher's routing and the image
        waits = sum(n for k, n in spans.items()
                    if k.startswith("essr.wait."))
        assert waits == 4, spans
    widths = sorted(s["width"] for n, s, _ in events if n == "essr.lane")
    assert widths == [0, 0, 27, 27, 54, 54]


def test_fused_dispatch_spans_share_the_frame_stat(fused):
    _, events = fused
    by = _spans_by_frame(events)
    assert len(by) == 2 and -1 not in by
    for frame, spans in by.items():
        assert spans["essr.launch"] == spans["essr.finalize"] == 1
        assert spans["essr.wait.image"] == spans["essr.wait.counts"] == 1
        assert "essr.serve" not in spans


@pytest.mark.parametrize("dispatch", ["host", "fused"])
def test_every_phase_dispatches_under_its_name(dispatch, request):
    _, events = request.getfixturevalue(dispatch)
    calls = {n[len("PjitFunction("):-1] for n, _, _ in events
             if n.startswith("PjitFunction(")}
    want = HOST_PHASES if dispatch == "host" else ("essr_fused_frame",)
    assert set(want) <= calls, sorted(calls)
    # nothing of the frame dispatches outside a phase, but the switcher's
    # threshold compare (jnp.where and a dtype convert on the host's scores)
    unnamed = {c for c in calls if not c.startswith("essr_")}
    assert unnamed <= {"_where", "convert_element_type"}, unnamed


@pytest.mark.parametrize("dispatch", ["host", "fused"])
def test_profiling_changes_no_served_bit(dispatch, request):
    traced, _ = request.getfixturevalue(dispatch)
    plain, _ = _served(dispatch)
    assert len(traced) == len(plain) == 2
    for (img, ids), (img0, ids0) in zip(traced, plain):
        np.testing.assert_array_equal(img, img0)
        np.testing.assert_array_equal(ids, ids0)


@pytest.mark.parametrize("hw,fold", [((64, 94), "slices"),
                                     ((60, 90), "scatter")])
def test_fuse_span_names_its_fold(hw, fold, tmp_path):
    """``essr.fuse`` carries the stat ``fold``: ``"slices"`` where every
    pixel lies in at most two neighbouring patches along each axis,
    ``"scatter"`` on a frame under two patches a side."""
    eng = _engine("host")
    frame = jnp.asarray(np.random.default_rng(1).uniform(
        0, 1, (*hw, 3)).astype(np.float32))
    eng.upscale(frame).image.block_until_ready()      # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.upscale(frame).image.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    folds = [stats.get("fold") for name, stats, _ in _host_events(tmp_path)
             if name == "essr.fuse"]
    assert folds == [fold]
    assert get_geometry(*hw, 32, 2, CFG.scale).fold == fold


@pytest.mark.parametrize("hw", [(60, 90), (20, 50)])    # no pad; pad
def test_extract_is_one_executable(hw, tmp_path):
    """One frame's extract launches exactly one executable, the phase
    ``essr_extract``, whether or not the frame is padded to the patch."""
    g = get_geometry(*hw, 32, 2, 2)
    frame = jnp.asarray(np.random.default_rng(0).uniform(
        0, 1, (*hw, 3)).astype(np.float32))
    g.extract(frame).block_until_ready()        # warm: nothing compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        g.extract(frame).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    calls = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:CPU")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("PjitFunction("))
    # a call may record nested events of the same name: count the outermost
    outer, end = [], -1.0
    for start, stop, name in calls:
        if start >= end:
            outer.append(name)
            end = stop
    assert outer == ["PjitFunction(essr_extract)"], calls


def test_phase_jit_names_the_module():
    @phase_jit("essr_probe", static_argnames=("n",))
    def head(x, n):
        return x[:n]
    text = head.lower(jnp.ones((8, 3)), n=4).as_text()
    assert text.startswith("module @jit_essr_probe")
    assert head(jnp.arange(8.0), n=3).tolist() == [0.0, 1.0, 2.0]
    assert [lane_phase(w) for w in (0, 27, 54)] == [
        "essr_bilinear", "essr_c27", "essr_c54"]
