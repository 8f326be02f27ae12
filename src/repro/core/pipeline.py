"""End-to-end edge-selective SR of full frames (paper Fig. 1).

frame -> slim-overlap patches -> edge scores -> subnet decision ->
per-subnet batched forward -> thick-overlap overlap+average fusion.

Two execution styles:
  * ``edge_selective_sr``: device-resident serving path. Patch extraction is
    one cached-index gather, fusion one scatter-add (`PatchGeometry`, cached
    per frame shape); per-subnet batches are padded to bucketed sizes so jit
    recompilation is bounded (the shape-static analog of the GLNPU's fixed PE
    array). Routing itself stays host-side: which subnet a patch takes is
    data-dependent, and the host grouping is what keeps each subnet batch
    shape-static.
  * ``sr_whole`` / ``sr_all_patches``: non-dynamic references for ablations.

``backend`` picks the per-subnet forward: "ref" (pure-JAX jit) or "pallas"
(fused kernel groups); ``interpret`` (None/True/False) selects compiled vs
interpreter Pallas — None auto-compiles on TPU/GPU and falls back to the
interpreter on CPU (see repro.kernels.dispatch).

``quant`` (a `repro.quant.pams.QuantPack`, or None for fp32) swaps the
per-subnet forward for the quantized serving path: PAMS fake-quant emulation
on the "ref" backend, the integer-domain kernel stack (`kernels/qconv.py`:
integer codes between fused groups, int32-accumulate matmuls,
requantize-on-output) on the "pallas" backend. Routing, patch geometry and
fusion are untouched — edge scores are computed on the fp input frame, so a
quant mode can never shift the C54/C27/bilinear routing decision. Bilinear
patches (width 0) bypass the conv lattice entirely, exactly as on the ASIC.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import subnet_policy as sp
from repro.core.caching import bounded_cache
from repro.core.edge_score import edge_score
from repro.core.patching import (PatchGeometry, extract_patches_loop,
                                 fuse_patches_average_loop, get_geometry)
from repro.core.phases import lane_phase, phase_jit, span
from repro.models.essr import ESSRConfig, essr_forward


DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def _bucket(n: int, buckets=DEFAULT_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return int(np.ceil(n / buckets[-1]) * buckets[-1])


# inline=True on the per-subnet forwards (here and in repro.kernels): a
# caller's jit, such as a lane's named executable or the fused frame, traces
# them in place instead of nesting a call, which keeps its lowering as cheap
# as calling them directly
@functools.partial(jax.jit, static_argnames=("cfg", "width"), inline=True)
def _forward_width_jit(params, patches, cfg: ESSRConfig, width: int):
    return essr_forward(params, patches, cfg, width=width)


def _forward_width(params, patches, cfg: ESSRConfig, width: int,
                   interpret: Optional[bool] = None):
    # pure-JAX path has no interpret knob; accepted for a uniform signature
    return _forward_width_jit(params, patches, cfg, width)


def _forward_width_pallas(params, patches, cfg: ESSRConfig, width: int,
                          interpret: Optional[bool] = None):
    """Fused-kernel backend: same contract as ``_forward_width``.

    Bilinear patches never reach the conv kernels (handled by the router on
    the ASIC), so width 0 falls back to the reference resize."""
    from repro.kernels.ops import essr_forward_kernels
    from repro.models.layers import bilinear_resize
    if width == 0:
        return bilinear_resize(patches, cfg.scale)
    return essr_forward_kernels(params, patches, cfg, width=width,
                                interpret=interpret)


def _forward_width_mega(params, patches, cfg: ESSRConfig, width: int,
                        interpret: Optional[bool] = None):
    """Group-fused Pallas backend (``ExecutionPlan.fusion="group"``): the
    whole subnet layer group in ONE pallas_call, features VMEM-resident
    between groups (`kernels.megakernel`). Same contract as
    ``_forward_width_pallas``; width 0 is the same bilinear bypass."""
    from repro.kernels.megakernel import essr_forward_megakernel
    from repro.models.layers import bilinear_resize
    if width == 0:
        return bilinear_resize(patches, cfg.scale)
    return essr_forward_megakernel(params, patches, cfg, width=width,
                                   interpret=interpret)


BACKENDS = {"ref": _forward_width, "pallas": _forward_width_pallas}

#: Kernel fusion granularity of the "pallas" backend (`ExecutionPlan.fusion`):
#: "layer" — one pallas_call per layer group (BSConv / SFB / DSConv), the
#:           feature map round-trips HBM between groups;
#: "group" — ONE pallas_call per subnet running the full group chain with the
#:           feature (and, under quant, the integer codes) in VMEM scratch —
#:           the TPU analog of the paper's 79% feature-SRAM-access saving.
#: The "ref" backend has no kernels to fuse; it accepts both values and runs
#: identically (so plans stay backend-portable).
FUSION_MODES = ("layer", "group")


def resolve_backend(name: str):
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; choose from {sorted(BACKENDS)}")


# ---------------------------------------------------------------------------
# quantized per-subnet forwards (ExecutionPlan.quant = "fxp10" | "int8")
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "width", "quant"),
                   inline=True)
def _forward_width_quant_ref_jit(params, patches, cfg: ESSRConfig, width: int,
                                 quant):
    from repro.quant.pams import quantized_essr_forward
    if width == 0:
        from repro.models.layers import bilinear_resize
        return bilinear_resize(patches, cfg.scale)
    scales = {k: jnp.asarray(v, jnp.float32)
              for k, v in quant.act_scales(width).items()}
    return quantized_essr_forward(params, scales, patches, cfg, quant.qcfg,
                                  width=width)


def _forward_width_quant_ref(params, patches, cfg: ESSRConfig, width: int,
                             interpret: Optional[bool] = None, *, quant):
    """PAMS fake-quant emulation of the whole forward (W/A quantized at every
    conv boundary with the pack's PTQ alphas) — the "ref" quant backend."""
    return _forward_width_quant_ref_jit(params, patches, cfg, width, quant)


def _forward_width_quant_pallas(params, patches, cfg: ESSRConfig, width: int,
                                interpret: Optional[bool] = None, *, quant):
    """Integer-domain quantized kernel stack — the "pallas" quant backend."""
    from repro.kernels.qconv import essr_forward_qkernels
    if width == 0:
        from repro.models.layers import bilinear_resize
        return bilinear_resize(patches, cfg.scale)
    return essr_forward_qkernels(params, patches, cfg, width=width,
                                 pack=quant, interpret=interpret)


def _forward_width_quant_mega(params, patches, cfg: ESSRConfig, width: int,
                              interpret: Optional[bool] = None, *, quant):
    """Group-fused integer megakernel (quant x fusion="group"): bit-exact vs
    the per-op quant stack, with the inter-group lattice codes VMEM-resident
    (they never touch HBM between layer groups)."""
    from repro.kernels.megakernel import essr_forward_qmegakernel
    if width == 0:
        from repro.models.layers import bilinear_resize
        return bilinear_resize(patches, cfg.scale)
    return essr_forward_qmegakernel(params, patches, cfg, width=width,
                                    pack=quant, interpret=interpret)


QUANT_BACKENDS = {"ref": _forward_width_quant_ref,
                  "pallas": _forward_width_quant_pallas}


def resolve_forward(backend: str, quant=None, fusion: str = "layer"):
    """(backend, QuantPack-or-None, fusion) -> the per-subnet forward
    callable with the uniform ``(params, patches, cfg, width, interpret=)``
    signature.

    ``fusion`` (see `FUSION_MODES`) selects the "pallas" backend's kernel
    granularity; the "ref" backend is already one jit graph per subnet, so
    both values resolve to the same forward there."""
    resolve_backend(backend)            # single source of name validation
    if fusion not in FUSION_MODES:
        raise ValueError(f"unknown fusion {fusion!r}; choose from "
                         f"{FUSION_MODES}")
    if backend == "pallas" and fusion == "group":
        if quant is None:
            return _forward_width_mega
        return functools.partial(_forward_width_quant_mega, quant=quant)
    if quant is None:
        return BACKENDS[backend]
    return functools.partial(QUANT_BACKENDS[backend], quant=quant)


# ---------------------------------------------------------------------------
# data-parallel per-subnet forward (the sharded patch stream)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _sharded_forward_fn(backend: str, mesh, cfg: ESSRConfig, width: int,
                        interpret: Optional[bool], quant=None,
                        fusion: str = "layer"):
    """jit(shard_map(forward)) splitting the patch batch over ``mesh``'s single
    axis, params replicated. Cached per (backend, mesh, cfg, width, interpret,
    quant, fusion) so the shard_map callable (and its compiled executable) is
    built once per routing regime (`QuantPack` is frozen/hashable for exactly
    this). ``check_vma=False``: pallas_call has no replication rule, and the
    batch axis carries no collectives anyway."""
    from repro.distributed.sharding import patch_batch_spec
    from jax.sharding import PartitionSpec as P

    forward = resolve_forward(backend, quant, fusion)
    spec = patch_batch_spec(mesh)

    def local(params, patches):
        return forward(params, patches, cfg, width, interpret=interpret)

    return phase_jit(lane_phase(width))(
        jax.shard_map(local, mesh=mesh, in_specs=(P(), spec),
                      out_specs=spec, check_vma=False))


def sharded_forward(params, patches: jax.Array, cfg: ESSRConfig, width: int,
                    *, mesh, backend: str = "ref",
                    interpret: Optional[bool] = None,
                    quant=None, fusion: str = "layer") -> jax.Array:
    """Run one subnet's patch batch data-parallel across ``mesh`` devices.

    Pads the batch up to a multiple of the mesh size by repeating the last
    patch (cache-friendly duplicate work, never another subnet's patch) and
    slices the output back, so callers need no divisibility guarantees."""
    n = int(patches.shape[0])
    k = int(mesh.size)
    pad = (-n) % k
    if pad:
        patches = jnp.concatenate(
            [patches, jnp.repeat(patches[-1:], pad, axis=0)], axis=0)
    out = _sharded_forward_fn(backend, mesh, cfg, width, interpret, quant,
                              fusion)(params, patches)
    return _lane_rows(lane_phase(width))(out, n=n) if pad else out


# The cross-chip movement of a host-dispatch lane over the patch mesh. The
# frame's chip (the root) keeps the extract, the routing and the fuse; a
# lane's batch crosses to the shards in `essr_shard_scatter` and its HR
# outputs come back in `essr_shard_gather`, each one executable over the
# mesh whose transfers are collective permutes from and to the root. A
# shard_map takes and gives one equal block per chip, so the scatter's
# input stacks the root's batch with a filler block on every other chip,
# and the gather's output block is the whole batch on the root and zeros,
# left unread, on the other chips.

@bounded_cache(maxsize=16)             # resized and reported with the
                                       # compiled caches
def _shard_filler(mesh, root: int, shape: Tuple[int, ...], dtype):
    """One zero block of ``shape`` on every chip of ``mesh`` but the root,
    made once per lane shape and kept."""
    return {i: jax.device_put(np.zeros(shape, dtype), d)
            for i, d in enumerate(mesh.devices.flat) if i != root}


@functools.lru_cache(maxsize=8)
def _shard_scatter_fn(mesh, root: int):
    """``essr_shard_scatter``: the root's ``(n, ...)`` block of the stacked
    input -> ``ceil(n / k)`` patches a chip, sharded over the mesh in
    raster order (the last patch repeated into the padding)."""
    from repro.distributed.sharding import patch_batch_spec
    spec, k = patch_batch_spec(mesh), int(mesh.size)
    axis = spec[0]

    def local(x):
        n = x.shape[0]
        m = -(-n // k)
        if k * m > n:
            x = jnp.concatenate([x, jnp.repeat(x[-1:], k * m - n, axis=0)])
        i = jax.lax.axis_index(axis)
        out = x[root * m:(root + 1) * m]
        for d in range(k):
            if d != root:
                got = jax.lax.ppermute(x[d * m:(d + 1) * m], axis,
                                       perm=[(root, d)])
                out = jnp.where(i == d, got, out)
        return out

    return phase_jit("essr_shard_scatter")(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False))


@functools.lru_cache(maxsize=16)
def _shard_gather_fn(mesh, root: int, n: int):
    """``essr_shard_gather``: each chip's block of lane outputs -> the first
    ``n`` rows of all of them, in shard order, on the root (zeros on the
    other chips, which only send)."""
    from repro.distributed.sharding import patch_batch_spec
    spec, k = patch_batch_spec(mesh), int(mesh.size)
    axis = spec[0]

    def local(y):
        parts = [y if d == root else
                 jax.lax.ppermute(y, axis, perm=[(d, root)])
                 for d in range(k)]
        return jax.lax.cond(
            jax.lax.axis_index(axis) == root,
            lambda: jnp.concatenate(parts, axis=0)[:n],
            lambda: jnp.zeros((n, *y.shape[1:]), y.dtype))

    return phase_jit("essr_shard_gather")(
        jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False))


def sharded_lane(params, patches: jax.Array, cfg: ESSRConfig, width: int, *,
                 mesh, backend: str, interpret: Optional[bool], quant,
                 fusion: str) -> jax.Array:
    """One host-dispatch lane over ``mesh``: scatter the batch from its chip
    to the shards, run the subnet on every shard (`_sharded_forward_fn`),
    gather the outputs back. Returns ``(n, p*s, p*s, C)`` on the chip that
    held ``patches``, which is that of the frame."""
    from repro.distributed.sharding import patch_batch_sharding
    devices = list(mesh.devices.flat)
    (dev,) = patches.devices()
    if dev not in devices:
        dev = devices[0]
        patches = jax.device_put(patches, dev)
    root, n = devices.index(dev), int(patches.shape[0])
    with span("essr.shard"):
        filler = _shard_filler(mesh, root, tuple(patches.shape),
                               patches.dtype)
        stacked = jax.make_array_from_single_device_arrays(
            (len(devices) * n, *patches.shape[1:]),
            patch_batch_sharding(mesh),
            [patches if i == root else filler[i]
             for i in range(len(devices))])
        shards = _shard_scatter_fn(mesh, root)(stacked)
    out = _sharded_forward_fn(backend, mesh, cfg, width, interpret, quant,
                              fusion)(params, shards)
    with span("essr.shard"):
        out = _shard_gather_fn(mesh, root, n)(out)
        return next(s.data for s in out.addressable_shards if s.device == dev)


@functools.lru_cache(maxsize=64)
def _lane_forward(backend: str, quant, fusion: str, cfg: ESSRConfig,
                  width: int, interpret: Optional[bool]):
    """One subnet width's forward on one device as the executable of its
    lane phase (`lane_phase`): the ``(params, patches)`` callable of the
    host-dispatch path, cached per routing regime like
    `_sharded_forward_fn`."""
    forward = resolve_forward(backend, quant, fusion)

    def lane(params, patches):
        return forward(params, patches, cfg, width, interpret=interpret)

    return phase_jit(lane_phase(width))(lane)


@functools.lru_cache(maxsize=8)
def _lane_rows(phase: str):
    """``x[:n]`` as an executable of ``phase``: trims a lane's bucket
    padding off its outputs."""
    return phase_jit(phase, static_argnames=("n",))(lambda x, n: x[:n])


# The lane dispatch phases of host dispatch: the bucket gather, and the
# zeroed patch batch plus the scatter of each lane's outputs into it.
@phase_jit("essr_lane_gather")
def _lane_gather(patches, rows):
    return jnp.take(patches, rows, axis=0)


@phase_jit("essr_lane_scatter", static_argnames=("shape", "dtype"))
def _lane_zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


@phase_jit("essr_lane_scatter")
def _lane_scatter(out, idx, sr):
    # idx is np.flatnonzero output: strictly increasing, so the set-scatter
    # is unique by construction and deterministic
    return out.at[idx].set(sr, unique_indices=True, mode="drop")


# ---------------------------------------------------------------------------
# fused single-dispatch frame graph (ExecutionPlan.dispatch = "fused")
#
# The host-dispatch path above keeps routing on the host: a per-frame
# ``np.asarray(edge_score(...))`` sync, a Python loop over subnet buckets,
# and a trailing ``block_until_ready`` — so frame N+1 cannot start until
# frame N's full host round-trip completes. The fused path collapses
# extract -> edge-score -> threshold routing -> capacity-slotted per-subnet
# forward -> scatter-add fusion into ONE jitted executable per
# (geometry, capacity profile): patches are one-hot dispatched into fixed
# per-subnet capacity slots (the same slot-dispatch shape as
# distributed/moe.py, and the shape-static analog of the ASIC's fixed PE
# array / "configurable group of layer mapping"). Capacities are snapped to
# the plan's bucket ladder so recompilation stays bounded; patches beyond a
# subnet's capacity spill deterministically (raster order) to the next
# cheaper subnet, with subnet 0 (bilinear) as the dense floor that never
# overflows. Thresholds are traced arguments, so Algorithm-1 adaptation
# never recompiles the frame.
# ---------------------------------------------------------------------------

#: ``ExecutionPlan.on_poison`` — what serving does about a frame that fails
#: its health verdict (any NaN/Inf/out-of-[0,1] pixel):
#:   "off"      — verdicts not computed (the unguarded baseline; FrameResult
#:                .health is None);
#:   "raise"    — verdict computed in-graph, `PoisonFrameError` raised at
#:                materialize time (multi-tenant serving quarantines the
#:                stream instead — the per-tenant analog of raising);
#:   "sanitize" — nan_to_num + clamp to [0,1] in-graph before routing.
#:                Bit-identical on clean in-range frames;
#:   "bilinear" — sanitize, then force the poisoned frame's patches to the
#:                dense bilinear floor lane (subnet 0) in-graph.
#: All variants are branch-free in the traced graph — the verdict is three
#: int32 reduces riding the existing outputs, no host sync (ESSR1xx-clean).
HEALTH_POLICIES = ("off", "raise", "sanitize", "bilinear")


def _health_counts(frame: jax.Array) -> jax.Array:
    """(nan, inf, out-of-[0,1]) pixel counts of one frame — int32 (3,)."""
    nan = jnp.sum(jnp.isnan(frame))
    inf = jnp.sum(jnp.isinf(frame))
    oob = jnp.sum(jnp.isfinite(frame) & ((frame < 0.0) | (frame > 1.0)))
    return jnp.stack([nan, inf, oob]).astype(jnp.int32)


def _sanitize(frame: jax.Array) -> jax.Array:
    """nan->0, +/-inf->1/0, clamp to [0,1]. Identity (bit-exact) on clean
    in-range frames — the sanitize/bilinear policies apply it unconditionally
    so the traced graph stays branch-free."""
    return jnp.clip(jnp.nan_to_num(frame, nan=0.0, posinf=1.0, neginf=0.0),
                    0.0, 1.0)


# the frame guard's executables on the host-dispatch paths: one phase
_health_jit = phase_jit("essr_health")(_health_counts)
_sanitize_jit = phase_jit("essr_health")(_sanitize)


def frame_health(frame: jax.Array) -> jax.Array:
    """Jitted health verdict for the host-dispatch paths (which already sync
    per frame; the fused paths compute the same counts in-graph instead)."""
    return _health_jit(frame)


def sanitize_frame(frame: jax.Array) -> jax.Array:
    """Jitted sanitize for the host-dispatch paths."""
    return _sanitize_jit(frame)


def snap_capacity(n: int, buckets: Tuple[int, ...] = DEFAULT_BUCKETS,
                  n_total: Optional[int] = None) -> int:
    """Desired slot count -> capacity: 0 stays 0 (the subnet lane is elided
    from the graph), otherwise the bucket ceiling, clamped to ``n_total``
    (the full patch count recurs per geometry, so an all-one-subnet frame
    compiles the exact full-batch shape instead of a padded bucket)."""
    if n <= 0:
        return 0
    cap = _bucket(n, buckets)
    return min(cap, n_total) if n_total is not None else cap


def capacity_route(ids: jax.Array, caps: Tuple[int, ...]
                   ) -> Tuple[jax.Array, jax.Array]:
    """In-graph capacity routing: (N,) subnet ids + static per-subnet slot
    capacities -> (effective ids, per-subnet spill counts).

    Processed priciest-first: the patches of subnet ``k`` beyond ``caps[k]``
    (raster order — deterministic, matching the paper's "the rest of the
    patches run with C27") are demoted to subnet ``k-1``, where they compete
    for slots in raster order together with that subnet's native patches.
    Subnet 0 (bilinear) is the dense floor and never spills; ``caps[0]`` is
    ignored. ``spills[k]`` counts the patches that wanted ``k`` (natively or
    by spill-in) but ran ``k-1``."""
    spills = [jnp.zeros((), jnp.int32)]          # subnet 0 never spills
    eff = ids
    for k in range(len(caps) - 1, 0, -1):
        member = eff == k
        pos = jnp.cumsum(member.astype(jnp.int32)) - 1
        over = member & (pos >= caps[k])
        spills.append(jnp.sum(over).astype(jnp.int32))
        eff = jnp.where(over, k - 1, eff)
    spills = spills[:1] + spills[1:][::-1]       # ascending subnet order
    return eff, jnp.stack(spills)


def capacity_dispatch(patches: jax.Array, eff_ids: jax.Array, subnet: int,
                      cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-hot dispatch of subnet ``subnet``'s patches into ``cap`` fixed
    slots (raster order). Returns (slot batch (cap, p, p, C), per-patch slot
    index with ``cap`` as the non-member dustbin, membership mask).

    Callers must have routed ``eff_ids`` through :func:`capacity_route`
    first — post-spill every member's raster rank is < ``cap``."""
    member = eff_ids == subnet
    pos = jnp.cumsum(member.astype(jnp.int32)) - 1
    slot = jnp.where(member, pos, cap)
    disp = jnp.zeros((cap + 1,) + patches.shape[1:], patches.dtype)
    disp = disp.at[slot].add(
        jnp.where(member[:, None, None, None], patches, 0), mode="drop")
    return disp[:cap], slot, member


def capacity_combine(out_patches: jax.Array, sr_slots: jax.Array,
                     slot: jax.Array, member: jax.Array) -> jax.Array:
    """Scatter one subnet's slot outputs back over the patch axis: patch n
    takes ``sr_slots[slot[n]]`` where it is a member (the dustbin row reads
    zeros and is masked off)."""
    y = jnp.concatenate(
        [sr_slots, jnp.zeros((1,) + sr_slots.shape[1:], sr_slots.dtype)], 0)
    return jnp.where(member[:, None, None, None], jnp.take(y, slot, axis=0),
                     out_patches)


@bounded_cache(maxsize=128)            # sized with get_geometry's cache: an
                                       # evicted executable would silently
                                       # re-trace under SREngine's warm-key
                                       # bookkeeping. BoundedCache: the
                                       # engine resizes all three together
                                       # (configure_compiled_caches).
def fused_frame_fn(geometry: PatchGeometry, caps: Tuple[int, ...],
                   cfg: ESSRConfig, backend: str,
                   interpret: Optional[bool], mesh, quant,
                   fusion: str = "layer", on_poison: str = "raise"):
    """The compiled frame executable: one per (geometry, capacity profile,
    backend, interpret, mesh, quant, fusion, on_poison). Signature of the
    returned callable:

        (params, frame, t1, t2)
            -> (image, eff_ids, scores, counts, spills, health)

    ``t1``/``t2`` are traced (threshold adaptation never recompiles); every
    other knob is static. All six outputs are device arrays — callers
    materialize them lazily (the async stream reads routing telemetry one
    frame behind). ``health`` is the (nan, inf, oob) int32 verdict of the
    *raw* input frame (all zeros under ``on_poison="off"``, where the checks
    are elided); the ``on_poison`` policy (see `HEALTH_POLICIES`) is applied
    in-graph, branch-free, with no host sync."""
    from repro.models.layers import bilinear_resize

    if on_poison not in HEALTH_POLICIES:
        raise ValueError(f"unknown on_poison {on_poison!r}; choose from "
                         f"{HEALTH_POLICIES}")
    base_forward = resolve_forward(backend, quant, fusion)
    if mesh is not None and int(mesh.size) > 1:
        def forward(params, patches, cfg, width, interpret=None):
            return sharded_forward(params, patches, cfg, width, mesh=mesh,
                                   backend=backend, interpret=interpret,
                                   quant=quant, fusion=fusion)
    else:
        forward = base_forward
    widths = cfg.subnet_widths()
    if len(caps) != len(widths):
        raise ValueError(f"capacity profile {caps} must have one entry per "
                         f"subnet width {widths}")

    def run(params, frame, t1, t2):
        if on_poison == "off":
            health = jnp.zeros((3,), jnp.int32)
        else:
            health = _health_counts(frame)
            if on_poison in ("sanitize", "bilinear"):
                frame = _sanitize(frame)
        patches = geometry.extract(frame)
        scores = edge_score(patches)
        eff, spills = capacity_route(sp.decide(scores, t1, t2), caps)
        if on_poison == "bilinear":
            # poisoned frame -> dense fallback lane: every patch serves from
            # the bilinear floor (branch-free demotion; the conv lanes still
            # run on their now-empty slots, keeping the graph shape-static)
            eff = jnp.where(jnp.any(health > 0), jnp.zeros_like(eff), eff)
        # subnet 0 is the dense floor: bilinear for every patch (it is the
        # spill target of last resort and costs no conv — the ASIC's router
        # bypass), overwritten wherever a conv subnet owns the patch
        out = bilinear_resize(patches, cfg.scale)
        for k in range(1, len(widths)):
            if caps[k] == 0:
                continue                         # lane elided from the graph
            disp, slot, member = capacity_dispatch(patches, eff, k, caps[k])
            sr = forward(params, disp, cfg, widths[k], interpret=interpret)
            out = capacity_combine(out, sr, slot, member)
        counts = jnp.stack([jnp.sum(eff == k).astype(jnp.int32)
                            for k in range(len(widths))])
        return geometry.fuse_average(out), eff, scores, counts, spills, health

    return phase_jit("essr_fused_frame")(run)


@bounded_cache(maxsize=128)
def fused_stream_frame_fn(geometry: PatchGeometry, streams: int,
                          caps: Tuple[int, ...], cfg: ESSRConfig,
                          backend: str, interpret: Optional[bool],
                          mesh, quant, fusion: str = "layer",
                          on_poison: str = "raise"):
    """The compiled multi-tenant admission-tick executable: ``streams``
    same-geometry frames (one per live tenant stream) through ONE
    capacity-slotted dispatch. Signature of the returned callable:

        (params, frames, t1s, t2s, quotas)
            -> (images, eff_ids, scores, counts, spills, health)

    ``health`` is the per-stream (S, 3) int32 (nan, inf, oob) verdict of the
    raw input frames (zeros under ``on_poison="off"``); the policy (see
    `HEALTH_POLICIES`) is applied per stream, in-graph and branch-free —
    under "bilinear" only the poisoned streams' patches demote to the dense
    floor, healthy tenants route normally.

    ``frames`` is (S, H, W, C); ``t1s``/``t2s``/``quotas`` are (S,) traced
    arrays — per-stream Algorithm-1 adaptation and share rebalancing never
    recompile the tick. ``quotas`` is each stream's top-subnet (C54) slot
    share for this tick: the router demotes a stream's top-subnet patches
    beyond its quota to the next subnet in raster order *before* the
    aggregate capacity cascade, so under aggregate overload degradation is
    share-weighted and raster-deterministic — frames are never dropped.

    Patch provenance is positional: the flat patch axis is stream-major
    (``stream_id = i // geometry.n``, ``patch_id = i % geometry.n``), so
    ``capacity_route``/``capacity_dispatch``/``capacity_combine`` run on the
    shared pool unchanged and the scatter-back fuses each stream's frame
    independently. Outputs: ``images`` (S, sH, sW, C); ``eff_ids``/``scores``
    flat (S*N,); ``counts``/``spills`` per-stream (S, n_subnets), where
    ``spills[s, k]`` counts stream s's patches that wanted subnet ``k``
    (pre-quota) but ran below it — quota demotions and aggregate spill
    cascade land in the same ledger, exactly like the solo streaming path's
    budget-clamped capacity."""
    from repro.models.layers import bilinear_resize

    base_forward = resolve_forward(backend, quant, fusion)
    if mesh is not None and int(mesh.size) > 1:
        def forward(params, patches, cfg, width, interpret=None):
            return sharded_forward(params, patches, cfg, width, mesh=mesh,
                                   backend=backend, interpret=interpret,
                                   quant=quant, fusion=fusion)
    else:
        forward = base_forward
    widths = cfg.subnet_widths()
    if len(caps) != len(widths):
        raise ValueError(f"capacity profile {caps} must have one entry per "
                         f"subnet width {widths}")
    if streams < 1:
        raise ValueError(f"streams must be >= 1, got {streams}")
    if on_poison not in HEALTH_POLICIES:
        raise ValueError(f"unknown on_poison {on_poison!r}; choose from "
                         f"{HEALTH_POLICIES}")
    top = len(widths) - 1
    n = geometry.n
    # On CPU the aggregate pool's conv batch (streams x per-stream slots)
    # falls out of cache and runs ~1.4x slower than per-stream batches, so
    # the shared lanes are chunked stream-count-wise through lax.map (the
    # fp32 conv forward is row-wise bit-identical across batch sizes — the
    # packing stays conformant, see tests/test_multiplex.py). Accelerator
    # backends keep the single dense batch (the MXU wants it as wide as the
    # pool allows), sharded forward is never chunked (shard_map owns the
    # batch axis), and quantized graphs keep it too: the fake-quant chain's
    # fp rounding is not bit-stable across the scan boundary, and the quant
    # conformance contract is bit-oriented.
    chunks = (streams if (streams > 1 and mesh is None and quant is None
                          and jax.default_backend() == "cpu") else 1)

    def run(params, frames, t1s, t2s, quotas):
        if on_poison == "off":
            health = jnp.zeros((streams, 3), jnp.int32)
        else:
            health = jax.vmap(_health_counts)(frames)       # (S, 3)
            if on_poison in ("sanitize", "bilinear"):
                frames = _sanitize(frames)
        patches = jax.vmap(geometry.extract)(frames)        # (S, N, p, p, C)
        flat = patches.reshape((streams * n,) + patches.shape[2:])
        scores = edge_score(flat)
        want = sp.decide(scores, jnp.repeat(t1s, n), jnp.repeat(t2s, n))
        want2 = want.reshape(streams, n)
        routed2 = want2
        if top > 0:
            # per-stream C54 quota: the share-weighted per-tick ceiling —
            # overflow demotes in raster order, like the solo budget clamp
            member = want2 == top
            pos = jnp.cumsum(member.astype(jnp.int32), axis=1) - 1
            over = member & (pos >= quotas[:, None])
            routed2 = jnp.where(over, top - 1, want2)
        if on_poison == "bilinear":
            # per-stream dense-fallback demotion: only the poisoned streams'
            # patches drop to the bilinear floor, healthy tenants untouched
            poisoned = jnp.any(health > 0, axis=1)          # (S,)
            routed2 = jnp.where(poisoned[:, None],
                                jnp.zeros_like(routed2), routed2)
        eff, _ = capacity_route(routed2.reshape(-1), caps)
        out = bilinear_resize(flat, cfg.scale)
        for k in range(1, len(widths)):
            if caps[k] == 0:
                continue                         # lane elided from the graph
            disp, slot, memberk = capacity_dispatch(flat, eff, k, caps[k])
            if chunks > 1:
                pad = (-caps[k]) % chunks
                disp_p = jnp.pad(
                    disp, ((0, pad),) + ((0, 0),) * (disp.ndim - 1))
                sr = jax.lax.map(
                    functools.partial(forward, params, cfg=cfg,
                                      width=widths[k], interpret=interpret),
                    disp_p.reshape((chunks, -1) + disp.shape[1:]))
                sr = sr.reshape((-1,) + sr.shape[2:])[:caps[k]]
            else:
                sr = forward(params, disp, cfg, widths[k],
                             interpret=interpret)
            out = capacity_combine(out, sr, slot, memberk)
        images = jax.vmap(geometry.fuse_average)(
            out.reshape((streams, n) + out.shape[1:]))
        eff2 = eff.reshape(streams, n)
        counts = jnp.stack(
            [jnp.sum(eff2 == k, axis=1) for k in range(len(widths))],
            axis=1).astype(jnp.int32)
        # hop ledger: wanted >= k but ran < k — transitive, so the aggregate
        # cascade's spill-throughs and the quota demotions both register
        spills = jnp.stack(
            [jnp.zeros((streams,), jnp.int32)] +
            [jnp.sum((want2 >= k) & (eff2 < k), axis=1).astype(jnp.int32)
             for k in range(1, len(widths))], axis=1)
        return images, eff, scores, counts, spills, health

    return phase_jit("essr_fused_streams")(run)


# essr: allow[ESSR201] — legacy surface kept for tests/benches; new modes go through SREngine
def fused_frame_forward(params, frame, cfg: ESSRConfig, *,
                        geometry: PatchGeometry, caps: Tuple[int, ...],
                        t1: float = sp.DEFAULT_T1, t2: float = sp.DEFAULT_T2,
                        backend: str = "ref",
                        interpret: Optional[bool] = None,
                        mesh=None, quant=None, fusion: str = "layer",
                        on_poison: str = "raise"):
    """One frame through the fused single-dispatch graph (see
    :func:`fused_frame_fn`). Returns the raw device-array six-tuple
    (..., health); the engine wraps it into a `FrameResult` and owns
    capacity-profile and on_poison policy."""
    return fused_frame_fn(geometry, tuple(int(c) for c in caps), cfg,
                          backend, interpret, mesh, quant, fusion,
                          on_poison)(params, frame, t1, t2)


# ---------------------------------------------------------------------------
# bounded compiled-object caches (runtime-sized, occupancy-observable)
# ---------------------------------------------------------------------------

#: The process-wide `BoundedCache`s holding compiled/prepared per-frame
#: objects. Keyed by what each cache memoizes; the geometry cache lives in
#: core.patching but is sized and surfaced together with the executables
#: (an evicted geometry would re-key — and silently re-trace — the frame
#: executables built on its identity). The sharded lane's zero filler
#: blocks (`_shard_filler`, device memory per lane shape) are bounded and
#: surfaced with them.
COMPILED_CACHES = {
    "fused_frame_fn": fused_frame_fn,
    "fused_stream_frame_fn": fused_stream_frame_fn,
    "get_geometry": get_geometry,
    "shard_filler": _shard_filler,
}


def configure_compiled_caches(maxsize: int) -> None:
    """Resize every compiled-object cache to ``maxsize`` entries (lru
    eviction; shrinking evicts immediately). `SREngine` derives the bound
    from ``plan.stats_window`` at construction so cache depth follows the
    serving horizon; call directly to pin it."""
    for cache in COMPILED_CACHES.values():
        cache.resize(maxsize)


def compiled_cache_occupancy() -> Dict[str, Dict[str, int]]:
    """{cache: {size, maxsize, hits, misses, evictions}} over the
    compiled-object caches — the snapshot `FrameResult.summary()` and
    `SREngine.summary()` surface. Nonzero evictions under a steady set of
    geometries/plans means the bound is too small and executables are being
    silently re-traced."""
    return {name: cache.occupancy()
            for name, cache in COMPILED_CACHES.items()}


@dataclasses.dataclass
class SRResult:
    image: jax.Array
    ids: np.ndarray
    scores: np.ndarray
    counts: Tuple[int, int, int]
    mac_saving: float


# essr: allow[ESSR201] — legacy surface kept for tests/benches; new modes go through SREngine
def edge_selective_sr(params: Dict[str, Any], frame: jax.Array, cfg: ESSRConfig,
                      t1: float = sp.DEFAULT_T1, t2: float = sp.DEFAULT_T2,
                      patch: int = 32, overlap: int = 2,
                      ids_override: Optional[np.ndarray] = None,
                      buckets: Tuple[int, ...] = DEFAULT_BUCKETS,
                      backend: str = "ref",
                      interpret: Optional[bool] = None,
                      geometry: Optional[PatchGeometry] = None,
                      precomputed: Optional[Tuple[jax.Array, np.ndarray,
                                                  np.ndarray]] = None,
                      mesh=None,
                      quant=None,
                      fusion: str = "layer",
                      use_loop_reference: bool = False) -> SRResult:
    """frame: (H,W,3) in [0,1] -> SRResult with (H*s, W*s, 3) image.

    ``geometry``: optional pre-fetched `PatchGeometry` (SREngine passes its
    plan's); resolved from the cache otherwise — either way the per-frame
    host work is index-free.

    ``mesh``: optional 1-D device mesh (``launch.mesh.make_patch_mesh``).
    When given with size > 1, every per-subnet batch crosses from the
    frame's chip to the mesh, is split across its devices (shard_map data
    parallel, params replicated) and comes back to the frame's chip for the
    fuse (`sharded_lane`) — numerically identical to the single-device
    path. ``None`` or size 1 is exactly the old path.

    ``precomputed``: optional (patches, pos, scores) from a caller that
    already extracted/scored this frame (the streaming path scores patches
    for the adaptive switcher) — avoids doing that work twice per frame.

    ``quant``: optional `repro.quant.pams.QuantPack` — serve this frame
    through the quantized forward of the chosen backend (see module
    docstring). Edge scoring/routing stay fp either way.

    ``use_loop_reference``: run the seed per-patch extract/fuse loops instead
    of the vectorized gather/scatter — the equivalence oracle for tests and
    the "before" side of benchmarks/table11_throughput.py. Never the serving
    path.
    """
    if mesh is not None and int(mesh.size) > 1:
        def lane(params, patches, width):
            return sharded_lane(params, patches, cfg, width, mesh=mesh,
                                backend=backend, interpret=interpret,
                                quant=quant, fusion=fusion)
    else:
        def lane(params, patches, width):
            return _lane_forward(backend, quant, fusion, cfg, width,
                                 interpret)(params, patches)
    s = cfg.scale
    h, w = int(frame.shape[0]), int(frame.shape[1])
    g = geometry if geometry is not None else get_geometry(h, w, patch,
                                                           overlap, s)
    if precomputed is not None:
        patches, pos, scores = precomputed
        scores = np.asarray(scores)
    else:
        with span("essr.extract"):
            if use_loop_reference:
                patches, pos = extract_patches_loop(frame, patch, overlap)
            else:
                patches, pos = g.extract(frame), g.pos
        # forced routing never consults the edge unit (as on the ASIC);
        # scores are reported as zeros rather than computed and discarded
        scores = (None if ids_override is None
                  else np.zeros(len(pos), np.float32))
    if ids_override is not None:
        ids = ids_override
    else:
        with span("essr.route"):
            if scores is None:
                scores = edge_score(patches)
                with span("essr.wait.scores"):
                    scores = np.asarray(scores)
            ids = sp.decide(scores, t1, t2)
            with span("essr.wait.route"):
                ids = np.asarray(ids)

    out_patches = _lane_zeros(
        shape=(patches.shape[0], patch * s, patch * s, 3), dtype=patches.dtype)
    widths = cfg.subnet_widths()
    for k, width in enumerate(widths):
        idx = np.flatnonzero(ids == k)
        if idx.size == 0:
            continue
        with span("essr.lane", width=width):
            if idx.size == len(ids):
                # one subnet took the whole frame: no gather/scatter, and no
                # bucket padding (the full-batch shape recurs per geometry,
                # so compilation stays bounded without it)
                out_patches = lane(params, patches, width)
                continue
            cap = _bucket(idx.size, buckets)
            # pad with the bucket's own last index (not patch 0): the
            # duplicate work is cache-friendly and never re-runs another
            # subnet's patch
            pad = np.concatenate([idx, np.full(cap - idx.size, idx[-1],
                                               idx.dtype)])
            sr = lane(params, _lane_gather(patches, jnp.asarray(pad)), width)
            if idx.size < cap:
                sr = _lane_rows(lane_phase(width))(sr, n=idx.size)
            out_patches = _lane_scatter(out_patches, jnp.asarray(idx), sr)

    with span("essr.fuse"):
        if use_loop_reference:
            img = fuse_patches_average_loop(out_patches, pos, s,
                                            (h * s, w * s))
        else:
            img = g.fuse_average(out_patches)
    counts = sp.subnet_counts(ids)
    saving = sp.SubnetMacs.make(cfg, patch).saving_vs_c54(counts)
    return SRResult(image=img, ids=ids, scores=scores, counts=counts, mac_saving=saving)


# essr: allow[ESSR201] — legacy surface kept for tests/benches; new modes go through SREngine
def sr_all_patches_result(params, frame, cfg: ESSRConfig, width: int,
                          patch: int = 32, overlap: int = 2,
                          buckets: Tuple[int, ...] = DEFAULT_BUCKETS,
                          backend: str = "ref",
                          interpret: Optional[bool] = None,
                          geometry: Optional[PatchGeometry] = None,
                          mesh=None, quant=None,
                          fusion: str = "layer") -> SRResult:
    """Every patch through one subnet (the non-edge-selective reference).

    The single implementation of forced routing — the edge-score pass is
    skipped entirely (scores are reported as zeros)."""
    widths = cfg.subnet_widths()
    if width not in widths:
        raise ValueError(f"width {width} not one of the subnet widths {widths}")
    g = geometry if geometry is not None else get_geometry(
        int(frame.shape[0]), int(frame.shape[1]), patch, overlap, cfg.scale)
    with span("essr.extract"):
        patches, pos = g.extract(frame), g.pos
    ids = np.full((len(pos),), widths.index(width), dtype=np.int64)
    return edge_selective_sr(params, frame, cfg, patch=patch, overlap=overlap,
                             ids_override=ids, buckets=buckets, backend=backend,
                             interpret=interpret, geometry=g, mesh=mesh,
                             quant=quant, fusion=fusion,
                             precomputed=(patches, pos,
                                          np.zeros(len(pos), np.float32)))


# essr: allow[ESSR201] — legacy surface kept for tests/benches; new modes go through SREngine
def sr_all_patches(params, frame, cfg: ESSRConfig, width: int,
                   patch: int = 32, overlap: int = 2,
                   backend: str = "ref") -> jax.Array:
    """Image-only wrapper over ``sr_all_patches_result``."""
    return sr_all_patches_result(params, frame, cfg, width,
                                 patch=patch, overlap=overlap,
                                 backend=backend).image


# essr: allow[ESSR201] — legacy surface kept for tests/benches; new modes go through SREngine
def sr_whole(params, frame, cfg: ESSRConfig, width: Optional[int] = None) -> jax.Array:
    """Whole-image convolution (the lossless 'software' reference of Table III)."""
    return essr_forward(params, frame[None], cfg, width=width)[0]
