"""Pass 1 — jaxpr audit of the real inference entry points (ESSR1xx).

Traces the engine's compiled surfaces (`core.pipeline.fused_frame_fn`, the
multi-stream admission tick `fused_stream_frame_fn`, the sharded shard_map
forward, the integer qconv kernel chain, `edge_score`)
with `jax.make_jaxpr` on a small-but-representative configuration and walks
every equation — including nested jit / shard_map / pallas_call / control-
flow sub-jaxprs — for the graph hazards the 8K@30FPS budget cannot absorb:

  ESSR101  host callbacks / infeed-outfeed transfers inside the graph: a
           single one re-introduces the per-frame host round-trip the fused
           dispatch exists to eliminate.
  ESSR102  fp64/complex128 values or f32->f64 promotions anywhere, and
           weak-typed *outputs* of the whole graph: silent widening doubles
           the SRAM/HBM traffic budget the paper's dataflow argument rests
           on, and a weak-typed output re-promotes downstream consumers.
  ESSR103  scatters without a determinism guarantee: ``mode=None`` (backend-
           dependent out-of-bounds semantics), or set-semantics ``scatter``
           with ``unique_indices=False`` (which update wins on a duplicate
           index is undefined). The overlap-add fusion and capacity dispatch
           must stay bit-reproducible across backends.
  ESSR104  constants baked into the graph above a byte budget: the geometry
           index maps close over deliberately (small), but an accidentally
           captured weight tree or frame silently bloats every executable.
  ESSR105  recompile leaks: re-runs the fused executable with perturbed
           thresholds (traced arguments) and with a within-bucket capacity
           perturbation, and fails if either re-lowers — `ExecutionPlan`'s
           contract is that Algorithm-1 adaptation never recompiles and
           capacities snap to the bucket ladder.

Everything here is CPU-safe: Pallas enters the graph via ``interpret=True``
and the shard mesh is a single host device.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.analysis.report import Violation

#: Primitives that put the host on the traced path (ESSR101). Matched by
#: exact name plus a "callback" substring catch-all for version drift.
HOST_PRIMITIVES = frozenset({
    "io_callback", "pure_callback", "python_callback", "debug_callback",
    "infeed", "outfeed", "host_local_array_to_global_array",
})

#: Default ESSR104 budget: the largest constant a graph may bake in. The
#: audit geometries keep legitimate index-map constants well under this;
#: a captured weight tree or frame blows straight past it.
DEFAULT_CONST_BUDGET = 1 << 20          # 1 MiB

_WIDE_DTYPES = ("float64", "complex128")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: Dict) -> Iterator[ClosedJaxpr | Jaxpr]:
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for vv in vs:
            if isinstance(vv, (ClosedJaxpr, Jaxpr)):
                yield vv


def iter_eqns(jaxpr: Jaxpr) -> Iterator:
    """Every equation of ``jaxpr``, recursing into sub-jaxprs (jit bodies,
    shard_map bodies, pallas kernels, scan/cond/while branches, custom-vjp
    call jaxprs)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            inner = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            yield from iter_eqns(inner)


def iter_consts(closed: ClosedJaxpr) -> Iterator:
    """Every constant of ``closed`` and of any nested ClosedJaxpr, plus
    every Literal bound as an equation input."""
    yield from closed.consts
    for eqn in iter_eqns(closed.jaxpr):
        for var in eqn.invars:
            if isinstance(var, Literal):
                yield var.val
        for sub in _sub_jaxprs(eqn.params):
            if isinstance(sub, ClosedJaxpr):
                yield from sub.consts


# ---------------------------------------------------------------------------
# per-graph rules (ESSR101-104)
# ---------------------------------------------------------------------------

def audit_jaxpr(closed: ClosedJaxpr, entry: str,
                const_budget: int = DEFAULT_CONST_BUDGET) -> List[Violation]:
    """Walk one traced graph for ESSR101/102/103/104."""
    out: List[Violation] = []
    site = f"entrypoint:{entry}"

    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in HOST_PRIMITIVES or "callback" in name:
            out.append(Violation(
                "ESSR101", site,
                f"host primitive '{name}' inside the traced graph"))
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and str(dt) in _WIDE_DTYPES:
                out.append(Violation(
                    "ESSR102", site,
                    f"'{name}' produces {dt} — wide-dtype promotion in the "
                    f"graph"))
        if name.startswith("scatter"):
            mode = eqn.params.get("mode")
            if mode is None:
                out.append(Violation(
                    "ESSR103", site,
                    f"'{name}' with mode=None: out-of-bounds semantics are "
                    f"backend-dependent"))
            if name == "scatter" and not eqn.params.get("unique_indices"):
                out.append(Violation(
                    "ESSR103", site,
                    "set-semantics scatter with unique_indices=False: which "
                    "update wins on a duplicate index is undefined"))

    for var in closed.jaxpr.outvars:
        aval = getattr(var, "aval", None)
        if getattr(aval, "weak_type", False):
            out.append(Violation(
                "ESSR102", site,
                f"graph output {aval} is weak-typed; downstream consumers "
                f"re-promote on contact"))

    for const in iter_consts(closed):
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            try:
                nbytes = np.asarray(const).nbytes
            except Exception:
                continue
        if nbytes > const_budget:
            shape = getattr(const, "shape", ())
            out.append(Violation(
                "ESSR104", site,
                f"baked-in constant of {nbytes} bytes (shape {shape}) "
                f"exceeds the {const_budget}-byte budget"))
    return out


# ---------------------------------------------------------------------------
# recompile-leak check (ESSR105)
# ---------------------------------------------------------------------------

def check_recompile(fn, args_a: Tuple, args_b: Tuple, entry: str,
                    expect: str = "perturbed traced arguments"
                    ) -> List[Violation]:
    """Run a jitted ``fn`` with two argument tuples that `ExecutionPlan`
    promises share one executable, and fail if the jit cache re-lowered.

    Relies on the jit cache-size introspection every supported jax version
    exposes; a jax build without it makes the check vacuous (reported as
    clean, not as a crash)."""
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        return []
    jax.block_until_ready(fn(*args_a))
    first = cache_size()
    jax.block_until_ready(fn(*args_b))
    second = cache_size()
    if second > first:
        return [Violation(
            "ESSR105", f"entrypoint:{entry}",
            f"{expect} re-lowered the executable "
            f"(jit cache grew {first} -> {second})")]
    return []


# ---------------------------------------------------------------------------
# the audited entry points
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class AuditSetup:
    """The shared toy-scale audit configuration (see `_audit_setup`)."""
    cfg: object
    params: object
    geom: object
    frame: jax.Array
    patches: jax.Array
    pack: object            # int8 QuantPack
    pack_fxp10: object      # paper-faithful FXP10 QuantPack


@dataclasses.dataclass(frozen=True, eq=False)
class EntrySpec:
    """One audited entry point, in the form every analysis pass consumes.

    ``make`` is a lazy thunk returning ``(fn, args)`` — lazy so a broken
    entry point reports as its own audit failure instead of killing the
    whole pass. ``abstract`` maps top-level argument positions to the
    interval the range pass seeds them with (the proof quantifies over these
    — frames over [0,1], thresholds over their plausible band); every other
    argument is seeded with its CONCRETE traced value (real weights, real
    quant codes). ``labels`` is the (backend, quant, dispatch) identity the
    cost report keys rows by.
    """
    name: str
    make: Callable[[], Tuple[Callable, Tuple]]
    abstract: Dict[int, Tuple[float, float]]
    labels: Dict[str, str]


def _audit_setup() -> AuditSetup:
    """Small-but-complete audit configuration: a 3-subnet supernet, a
    64x64 frame (3x3 patch grid with real overlap), and calibrated int8 +
    fxp10 packs — every routing/fusion/quant feature of the serving graph is
    exercised at toy scale."""
    from repro.core.patching import get_geometry
    from repro.models.essr import ESSRConfig, init_essr
    from repro.quant.pams import build_quant_pack

    cfg = ESSRConfig(scale=2, n_sfb=2, channels=8)
    params = init_essr(jax.random.PRNGKey(0), cfg)
    geom = get_geometry(64, 64, 32, 2, 2)
    frame = jnp.linspace(0.0, 1.0, 64 * 64 * 3,
                         dtype=jnp.float32).reshape(64, 64, 3)
    patches = geom.extract(frame)
    pack = build_quant_pack(params, cfg, "int8", patches)
    pack_fxp10 = build_quant_pack(params, cfg, "fxp10", patches)
    return AuditSetup(cfg, params, geom, frame, patches, pack, pack_fxp10)


#: Seed intervals: frames/patches live in [0,1]; Algorithm-1 thresholds stay
#: inside the edge-score band (edge scores of [0,1] frames are bounded far
#: below this).
_FRAME_IV = (0.0, 1.0)
_THRESH_IV = (0.0, 512.0)


def entry_point_specs() -> Dict[str, EntrySpec]:
    """Every audited entry point — the (backend, quant, dispatch) matrix the
    jaxpr audit walks, the range pass certifies, and the cost pass prices."""
    s = _audit_setup()
    cfg, params, frame, patches = s.cfg, s.params, s.frame, s.patches

    def fused(pack=None, backend="ref", interpret=None, fusion="layer"):
        def make():
            from repro.core.pipeline import fused_frame_fn
            fn = fused_frame_fn(s.geom, (0, 4, 4), cfg, backend, interpret,
                                None, pack, fusion)
            return fn, (params, frame, 8.0, 40.0)
        return make

    def mux(streams=2):
        def make():
            from repro.core.pipeline import fused_stream_frame_fn
            fn = fused_stream_frame_fn(s.geom, streams, (0, 8, 8), cfg,
                                       "ref", None, None, None)
            frames = jnp.stack([frame] * streams)
            ones = jnp.ones((streams,), jnp.float32)
            quotas = jnp.full((streams,), 4, jnp.int32)
            return fn, (params, frames, 8.0 * ones, 40.0 * ones, quotas)
        return make

    def sharded():
        from repro.core.pipeline import _sharded_forward_fn
        from repro.launch.mesh import make_patch_mesh
        fn = _sharded_forward_fn("ref", make_patch_mesh(1), cfg, 8, None,
                                 None)
        return fn, (params, patches)

    def qconv(pack, ref: bool):
        def make():
            from repro.kernels.qconv import (essr_forward_qkernels,
                                             essr_forward_qref)
            if ref:
                fn = lambda p, x: essr_forward_qref(p, x, cfg, width=8,
                                                    pack=pack)
            else:
                fn = lambda p, x: essr_forward_qkernels(
                    p, x, cfg, width=8, pack=pack, interpret=True)
            return fn, (params, patches)
        return make

    def perop():
        def make():
            from repro.kernels.ops import essr_forward_kernels
            fn = lambda p, x: essr_forward_kernels(p, x, cfg, width=8,
                                                   interpret=True)
            return fn, (params, patches)
        return make

    def mega(pack=None):
        def make():
            from repro.kernels.megakernel import (essr_forward_megakernel,
                                                  essr_forward_qmegakernel)
            if pack is None:
                fn = lambda p, x: essr_forward_megakernel(
                    p, x, cfg, width=8, interpret=True)
            else:
                fn = lambda p, x: essr_forward_qmegakernel(
                    p, x, cfg, width=8, pack=pack, interpret=True)
            return fn, (params, patches)
        return make

    def edge():
        from repro.core.edge_score import edge_score
        return edge_score, (patches,)

    fr, th = _FRAME_IV, _THRESH_IV
    specs = [
        EntrySpec("core.pipeline.fused_frame_fn[ref]",
                  fused(), {1: fr, 2: th, 3: th},
                  {"backend": "ref", "quant": "none", "dispatch": "fused"}),
        EntrySpec("core.pipeline.fused_frame_fn[pallas-int8]",
                  fused(s.pack, "pallas", True), {1: fr, 2: th, 3: th},
                  {"backend": "pallas", "quant": "int8",
                   "dispatch": "fused"}),
        # the multi-tenant admission tick: 2 streams, one shared pool; the
        # per-stream C54 quotas quantify over [1, pool] so the proof covers
        # every share rebalancing the StreamSwitcherBank can emit
        EntrySpec("core.pipeline.fused_stream_frame_fn[ref]",
                  mux(), {1: fr, 2: th, 3: th, 4: (1.0, 18.0)},
                  {"backend": "ref", "quant": "none", "dispatch": "mux"}),
        EntrySpec("core.pipeline.sharded_forward",
                  sharded, {1: fr},
                  {"backend": "ref", "quant": "none", "dispatch": "sharded"}),
        EntrySpec("kernels.qconv.essr_forward_qkernels[int8]",
                  qconv(s.pack, ref=False), {1: fr},
                  {"backend": "pallas", "quant": "int8", "dispatch": "host"}),
        EntrySpec("kernels.qconv.essr_forward_qkernels[fxp10]",
                  qconv(s.pack_fxp10, ref=False), {1: fr},
                  {"backend": "pallas", "quant": "fxp10",
                   "dispatch": "host"}),
        EntrySpec("kernels.qconv.essr_forward_qref[int8]",
                  qconv(s.pack, ref=True), {1: fr},
                  {"backend": "ref", "quant": "int8", "dispatch": "host"}),
        EntrySpec("kernels.qconv.essr_forward_qref[fxp10]",
                  qconv(s.pack_fxp10, ref=True), {1: fr},
                  {"backend": "ref", "quant": "fxp10", "dispatch": "host"}),
        EntrySpec("core.edge_score.edge_score",
                  edge, {0: fr},
                  {"backend": "ref", "quant": "none", "dispatch": "host"}),
        # the layer-fused per-op stack vs its group-fused megakernel twin:
        # the cost pass prices both, and the feature_hbm_bytes ratio between
        # them is the static form of the paper's 79% traffic-reduction claim
        # (gated end-to-end by bench_gate --audit).
        EntrySpec("kernels.ops.essr_forward_kernels",
                  perop(), {1: fr},
                  {"backend": "pallas", "quant": "none", "dispatch": "host"}),
        EntrySpec("kernels.megakernel.essr_forward_megakernel",
                  mega(), {1: fr},
                  {"backend": "pallas", "quant": "none", "dispatch": "host"}),
        EntrySpec("kernels.megakernel.essr_forward_qmegakernel[int8]",
                  mega(s.pack), {1: fr},
                  {"backend": "pallas", "quant": "int8", "dispatch": "host"}),
        EntrySpec("kernels.megakernel.essr_forward_qmegakernel[fxp10]",
                  mega(s.pack_fxp10), {1: fr},
                  {"backend": "pallas", "quant": "fxp10",
                   "dispatch": "host"}),
        EntrySpec("core.pipeline.fused_frame_fn[pallas-int8-group]",
                  fused(s.pack, "pallas", True, "group"),
                  {1: fr, 2: th, 3: th},
                  {"backend": "pallas", "quant": "int8",
                   "dispatch": "fused"}),
    ]
    return {spec.name: spec for spec in specs}


def entry_point_jaxprs() -> Dict[str, Callable[[], ClosedJaxpr]]:
    """name -> thunk tracing that entry point (the ESSR1xx walk's view of
    `entry_point_specs`)."""
    def tracer(spec: EntrySpec) -> Callable[[], ClosedJaxpr]:
        def thunk() -> ClosedJaxpr:
            fn, args = spec.make()
            return jax.make_jaxpr(fn)(*args)
        return thunk
    return {name: tracer(spec)
            for name, spec in entry_point_specs().items()}


def audit_recompile_leaks() -> List[Violation]:
    """ESSR105 over the fused frame executable:

    * threshold perturbation (traced arguments) must not re-lower;
    * a desired-capacity perturbation *within one bucket* must snap to the
      same profile and therefore the same cached executable (object
      identity through the `fused_frame_fn` LRU) — this is also the check
      that every static argument (ESSRConfig, QuantPack, geometry) stays
      hashable, because an unhashable one throws right here.
    """
    from repro.core.pipeline import fused_frame_fn, snap_capacity

    s = _audit_setup()
    cfg, params, geom, frame, pack = s.cfg, s.params, s.geom, s.frame, s.pack
    out: List[Violation] = []

    caps_a = (0, snap_capacity(3, n_total=geom.n),
              snap_capacity(3, n_total=geom.n))
    caps_b = (0, snap_capacity(4, n_total=geom.n),
              snap_capacity(4, n_total=geom.n))
    if caps_a != caps_b:
        out.append(Violation(
            "ESSR105", "entrypoint:core.pipeline.snap_capacity",
            f"within-bucket capacity perturbation changed the profile "
            f"{caps_a} -> {caps_b}: every demand delta would recompile"))

    fn_a = fused_frame_fn(geom, caps_a, cfg, "ref", None, None, None)
    fn_b = fused_frame_fn(geom, caps_b, cfg, "ref", None, None, None)
    if fn_a is not fn_b:
        out.append(Violation(
            "ESSR105", "entrypoint:core.pipeline.fused_frame_fn",
            "equal (geometry, caps, cfg, backend, interpret, mesh, quant) "
            "keys resolved to distinct executables: the LRU key leaks"))

    out.extend(check_recompile(
        fn_a, (params, frame, 8.0, 40.0), (params, frame, 9.5, 37.0),
        entry="core.pipeline.fused_frame_fn",
        expect="threshold perturbation (traced t1/t2)"))

    # quantized fused graph: QuantPack must behave as a hashable static —
    # same pack, perturbed thresholds, still one executable
    fn_q = fused_frame_fn(geom, caps_a, cfg, "pallas", True, None, pack)
    out.extend(check_recompile(
        fn_q, (params, frame, 8.0, 40.0), (params, frame, 10.0, 44.0),
        entry="core.pipeline.fused_frame_fn[pallas-int8]",
        expect="threshold perturbation (traced t1/t2)"))
    return out


def run_jaxpr_audit(const_budget: int = DEFAULT_CONST_BUDGET
                    ) -> List[Violation]:
    """The whole pass: trace+walk every entry point, then the recompile-leak
    checks. A trace failure is itself reported as an ESSR101 violation
    (an entry point the auditor cannot even trace is a hazard, not an
    excuse)."""
    out: List[Violation] = []
    for entry, thunk in entry_point_jaxprs().items():
        try:
            closed = thunk()
        except Exception as e:                          # pragma: no cover
            out.append(Violation(
                "ESSR101", f"entrypoint:{entry}",
                f"entry point failed to trace: {e!r}"))
            continue
        out.extend(audit_jaxpr(closed, entry, const_budget))
    out.extend(audit_recompile_leaks())
    return out
