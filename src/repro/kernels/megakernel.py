"""Whole-subnet-group Pallas megakernel (the paper's "configurable group of
layer mapping" + "structure-friendly fusion block", Secs. IV-F/G).

The per-op kernel stack (bsconv/sfb/dsconv/qconv) already fuses *within* each
layer group, but still round-trips the feature map through HBM *between*
groups: BSConv -> HBM -> SFB -> HBM -> ... -> DSConv is exactly the feature
traffic the ASIC's 79% SRAM-access reduction eliminates. This module fuses a
subnet's FULL layer group — BSConv, every SFB (shortcut adders and trailing
1x1 fuses included), DSConv — into ONE ``pallas_call``: the patch block is
staged HBM->VMEM once on entry, the running feature lives in a VMEM scratch
buffer across all layers (the mamba-kernel idiom: fused residual, scratch
reuse), and one HBM store on exit. Weights use constant index maps, so Mosaic
keeps them VMEM-resident across grid steps ("weights remain stationary
during computing").

Two datapaths, selected by ``ExecutionPlan(fusion="group")``:

  * fp32 (``essr_forward_megakernel``): composes the same pointwise-dot +
    shifted-MAC depthwise bodies as the per-op kernels, wrapped in
    ``jax.custom_jvp`` whose tangent defers to a pure-JAX twin of
    ``models.essr.essr_forward`` — the fused serving path stays trainable
    in BOTH autodiff modes (grad via transpose, jvp natively).
  * integer (``essr_forward_qmegakernel``): composes the shared
    ``kernels.qconv._*_math`` bodies, so it is bit-exact vs
    ``essr_forward_qref`` by construction — and the inter-group lattice
    codes NEVER leave VMEM (the per-op quant chain at least halves their
    width; the megakernel removes them from HBM entirely).

Block sizing is the roofline-driven ``autotune_block_patches``: the fused
group's arithmetic intensity (MACs per streamed feature byte) is fixed by
the model, so the block is the largest patch count whose VMEM working set,
tiled as the chip lays it out (weights, input and output blocks double
buffered, scratch feature, body temporaries), fits the scoped-VMEM limit
every kernel passes to the compiler (`kernels.dispatch.VMEM_LIMIT_BYTES`).
`launch/roofline.py`'s hardware constants decide which side of the ridge
the fused group lands on (reported by ``autotune_report``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsconv import _dw3x3, _pw_dot
from repro.kernels.dispatch import (VMEM_LIMIT_BYTES, compiler_params,
                                    pad_batch, resolve_block,
                                    resolve_interpret, tiled_bytes)
from repro.kernels.qconv import (_qbsconv_math, _qdsconv_math, _qsfb_math,
                                 _quantize_math, _sfb_consts, prepare_qparams)
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.models import layers as L
from repro.models.essr import (ESSRConfig, essr_macs_per_lr_pixel,
                               slice_width)
from repro.models.layers import pixel_shuffle
from repro.quant.pams import QuantPack, code_dtype


# ---------------------------------------------------------------------------
# roofline-driven block-size autotuner (static — shapes and dtypes only)
# ---------------------------------------------------------------------------

#: MXU systolic array rows: pointwise matmuls want at least this many rows
#: per grid step, or the array runs partially empty.
_MXU_ROWS = 256

#: Feature-sized fp32 values the kernel body keeps live besides its scratch
#: (pointwise outputs, the padded depthwise input, its accumulator; the
#: integer body also holds dequantized inputs and int32 accumulators).
#: Calibrated against the v5e compiler's scoped-VMEM report for 32x32
#: patches in a 4096-patch batch: fp32 needs 4.54/9.27/13.94/18.59 MiB at
#: blocks 1/2/3/4, int8 12.04/16.57 MiB at blocks 3/4 (short grids need
#: less) — the model stays above both.
_BODY_TEMPS = {False: 5, True: 7}


def _group_weight_bytes(width: int, n_sfb: int, out_channels: int,
                        in_channels: int = 3) -> int:
    """Tiled fp32 VMEM bytes of every stationary operand the fused group
    keeps resident (weights + biases/scales, each padded to the (8, 128)
    tile; the quant variants are no larger, so fp32 is the bound)."""
    c = width
    row, dw = tiled_bytes((1, c), jnp.float32), tiled_bytes((3, 3, c),
                                                            jnp.float32)
    pw = lambda cin, cout: tiled_bytes((cin, cout), jnp.float32)
    first = pw(in_channels, c) + row + dw + row
    sfb = 2 * (pw(c, c) + row + dw + row) + pw(c, c) + row
    recon = (dw + row + pw(c, out_channels)
             + tiled_bytes((1, out_channels), jnp.float32))
    return first + n_sfb * sfb + recon


def autotune_report(width: int, patch: int, scale: int, n_sfb: int = 5,
                    *, in_channels: int = 3,
                    quant: bool = False) -> Dict[str, Any]:
    """Static roofline sizing of the fused group at one (width, patch) point.

    The streamed HBM traffic per patch is fixed (input block in, SR block
    out — intermediates never leave VMEM), so arithmetic intensity does not
    depend on the block size; what the block controls is VMEM occupancy and
    MXU row utilization (lower bound: ``block * patch^2 >= 256`` rows). The
    tuner takes the largest block in that feasible band.

    VMEM is counted as the chip lays it out (`tiled_bytes`: channels padded
    to 128 lanes): the double-buffered weights, and per patch the
    double-buffered input and output blocks, the scratch feature and the
    body's live temporaries, against ``VMEM_LIMIT_BYTES`` — the same limit
    every ``pallas_call`` passes to the compiler. ``quant`` sizes the
    integer kernel (int8 codes in scratch and output, more temporaries)."""
    out_channels = in_channels * scale * scale
    code = jnp.int8 if quant else jnp.float32
    tile = lambda c, dt: tiled_bytes((patch, patch, c), dt)
    weight_b = 2 * _group_weight_bytes(width, n_sfb, out_channels,
                                       in_channels)
    per_patch_b = (2 * tile(in_channels, jnp.float32)
                   + 2 * tile(out_channels, code) + tile(width, code)
                   + _BODY_TEMPS[quant] * tile(width, jnp.float32))
    vmem_cap = max(0, VMEM_LIMIT_BYTES - weight_b) // per_patch_b
    mxu_floor = max(1, -(-_MXU_ROWS // (patch * patch)))
    block = max(1, min(vmem_cap, 512))           # grid-step sanity ceiling
    macs_pp = essr_macs_per_lr_pixel(
        ESSRConfig(channels=width, n_sfb=n_sfb, scale=scale,
                   in_channels=in_channels)) * patch * patch
    stream_bpp = 4 * patch * patch * (in_channels + out_channels)
    intensity = macs_pp / stream_bpp
    ridge = PEAK_FLOPS / (2.0 * HBM_BW)          # MAC/byte at the ridge
    return {
        "block_patches": int(block),
        "weight_bytes": int(weight_b),
        "per_patch_bytes": int(per_patch_b),
        "vmem_budget_bytes": VMEM_LIMIT_BYTES,
        "mxu_row_floor": int(mxu_floor),
        "fits_vmem": weight_b + block * per_patch_b <= VMEM_LIMIT_BYTES,
        "arith_intensity_mac_per_byte": float(intensity),
        "roofline_ridge_mac_per_byte": float(ridge),
        "bound": "compute" if intensity >= ridge else "memory",
    }


@functools.lru_cache(maxsize=256)
def autotune_block_patches(width: int, patch: int, scale: int,
                           n_sfb: int = 5, *, in_channels: int = 3,
                           quant: bool = False) -> int:
    """The block size `autotune_report` picks (cached — pure shape math)."""
    return autotune_report(width, patch, scale, n_sfb,
                           in_channels=in_channels,
                           quant=quant)["block_patches"]


# ---------------------------------------------------------------------------
# operand flattening: the param tree -> the kernel's positional ref list
# ---------------------------------------------------------------------------

def _flat_fp_operands(params) -> list:
    """Width-sliced fp param tree -> kernel operand list, in the exact order
    `_mega_kernel` consumes them (biases pre-reshaped to (1, C) rows)."""
    r2 = lambda v: v.reshape(1, -1)
    ops = [params["first"]["pw"][0, 0], r2(params["first"]["pw_b"]),
           params["first"]["dw"][:, :, 0, :], r2(params["first"]["dw_b"])]
    for p in params["sfbs"]:
        for b in ("b1", "b2"):
            ops += [p[b]["pw"][0, 0], r2(p[b]["pw_b"]),
                    p[b]["dw"][:, :, 0, :], r2(p[b]["dw_b"])]
        ops += [p["fuse"][0, 0], r2(p["fuse_b"])]
    ops += [params["recon"]["dw"][:, :, 0, :], r2(params["recon"]["dw_b"]),
            params["recon"]["pw"][0, 0], r2(params["recon"]["pw_b"])]
    return ops


def _flat_q_operands(q) -> list:
    """`prepare_qparams` tree -> kernel operand list (scales/biases as (1,C)
    rows), in the exact order `_qmega_kernel` consumes them."""
    r2 = lambda v: v.reshape(1, -1)
    ops = [q["first"]["pwq"], r2(q["first"]["pw_scale"]),
           r2(q["first"]["pwb"]), q["first"]["dw_fq"], r2(q["first"]["dwb"])]
    for sfb in q["sfbs"]:
        for b in ("b1", "b2"):
            ops += [sfb[f"{b}_pwq"], r2(sfb[f"{b}_pw_scale"]),
                    r2(sfb[f"{b}_pwb"]), sfb[f"{b}_dw_fq"],
                    r2(sfb[f"{b}_dwb"])]
        ops += [sfb["fuseq"], r2(sfb["fuse_scale_y"]),
                r2(sfb["fuse_scale_x"]), r2(sfb["fuseb"])]
    ops += [q["recon"]["dwq"], r2(q["recon"]["dw_scale"]),
            r2(q["recon"]["dwb"]), q["recon"]["pw_fq"],
            r2(q["recon"]["pwb"])]
    return ops


def _weight_specs(ops) -> list:
    """Stationary BlockSpecs (constant index map: block 0 every grid step,
    so Mosaic keeps every weight VMEM-resident across the whole grid)."""
    specs = []
    for arr in ops:
        zero = (0,) * arr.ndim
        specs.append(pl.BlockSpec(arr.shape, lambda i, _z=zero: _z))
    return specs


# ---------------------------------------------------------------------------
# fp32 megakernel
# ---------------------------------------------------------------------------

def _mega_kernel(*refs, n_sfb: int):
    """One grid step of the fused fp32 group: the staged patch block runs
    BSConv -> n_sfb x SFB -> DSConv with the running feature ping-ponging
    through the VMEM scratch — no HBM touch until the single output store."""
    x_ref, wrefs, o_ref, feat_ref = refs[0], refs[1:-2], refs[-2], refs[-1]
    x = x_ref[...]
    b, h, w, cin = x.shape
    it = iter(wrefs)

    def take(k):
        return [next(it)[...] for _ in range(k)]

    def bs(v, pw, pwb, dw, dwb):
        c_in = v.shape[-1]
        y = _pw_dot(v.reshape(b * h * w, c_in), pw)
        y = (y + pwb).reshape(b, h, w, -1)
        return _dw3x3(y, dw) + dwb

    pw, pwb, dw, dwb = take(4)
    feat_ref[...] = bs(x, pw, pwb, dw, dwb)
    for _ in range(n_sfb):
        b1 = take(4)
        b2 = take(4)
        fuse, fuseb = take(2)
        xin = feat_ref[...]
        c = xin.shape[-1]
        y = jnp.maximum(bs(xin, *b1), 0.0)
        y = jnp.maximum(bs(y, *b2), 0.0)
        y = y + xin                                  # shortcut adder
        y = _pw_dot(y.reshape(b * h * w, c), fuse) + fuseb
        feat_ref[...] = jnp.maximum(y, 0.0).reshape(b, h, w, c)
    rdw, rdwb, rpw, rpwb = take(4)
    f = feat_ref[...]
    y = _dw3x3(f, rdw) + rdwb
    y = _pw_dot(y.reshape(b * h * w, f.shape[-1]), rpw) + rpwb
    o_ref[...] = y.reshape(b, h, w, -1).astype(o_ref.dtype)


def _jvp_forward(params, x, cfg: ESSRConfig):
    """`essr_forward` on pre-sliced params with the depthwise conv in raw
    shift form: `layers._dw3` is a custom_vjp (reverse-only), so the
    megakernel's JVP rule needs this forward-differentiable twin — same
    math to the op (`_dw3` merely wraps `_dw3_shift`)."""
    def bs(p, v):
        y = L.pointwise(v, p["pw"], p.get("pw_b"))
        y = L._dw3_shift(y, p["dw"][:, :, 0, :])
        return y + p["dw_b"] if "dw_b" in p else y

    f = bs(params["first"], x)
    for p in params["sfbs"]:
        y = jax.nn.relu(bs(p["b1"], f))
        y = jax.nn.relu(bs(p["b2"], y))
        f = jax.nn.relu(L.pointwise(y + f, p["fuse"], p.get("fuse_b")))
    r = params["recon"]
    y = L._dw3_shift(f, r["dw"][:, :, 0, :])
    if "dw_b" in r:
        y = y + r["dw_b"]
    up = L.pointwise(y, r["pw"], r.get("pw_b"))
    return pixel_shuffle(up, cfg.scale)


@functools.partial(jax.custom_jvp, nondiff_argnums=(2, 3, 4, 5))
def _mega_forward(params, x, cfg: ESSRConfig, width: int,
                  block_patches: int, interpret: Optional[bool]):
    """(width-sliced params, padded-ready batch) -> SR patches, one
    pallas_call for the whole group. Differentiable via the custom JVP
    below — the Pallas primal with the pure-JAX tangent."""
    interp = resolve_interpret(interpret)
    bblk = resolve_block(x.shape[0], block_patches)
    x, n = pad_batch(x, bblk)
    _, h, w, cin = x.shape
    cout = cfg.out_channels
    wops = _flat_fp_operands(params)
    up = pl.pallas_call(
        functools.partial(_mega_kernel, n_sfb=cfg.n_sfb),
        grid=(x.shape[0] // bblk,),
        in_specs=[pl.BlockSpec((bblk, h, w, cin), lambda i: (i, 0, 0, 0))]
        + _weight_specs(wops),
        out_specs=pl.BlockSpec((bblk, h, w, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], h, w, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((bblk, h, w, width), jnp.float32)],
        interpret=interp,
        compiler_params=compiler_params(),
    )(x, *wops)
    return pixel_shuffle(up, cfg.scale)[:n]


@_mega_forward.defjvp
def _mega_forward_jvp(cfg, width, block_patches, interpret,
                      primals, tangents):
    # primal through the fused kernel, tangent through the pure-JAX forward:
    # the two forwards are the same math, so the pairing is consistent and
    # the fp32 serving path stays trainable without a Pallas transpose rule
    params, x = primals
    dparams, dx = tangents
    primal_out = _mega_forward(params, x, cfg, width, block_patches,
                               interpret)
    _, tangent_out = jax.jvp(lambda p, v: _jvp_forward(p, v, cfg),
                             (params, x), (dparams, dx))
    return primal_out, tangent_out


@functools.partial(jax.jit, static_argnames=("cfg", "width", "block_patches",
                                             "interpret"), inline=True)
def essr_forward_megakernel(params, x, cfg: ESSRConfig,
                            width: Optional[int] = None,
                            block_patches: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Patch-batch ESSR forward through ONE fused Pallas kernel per grid
    step (`ExecutionPlan(fusion="group")`'s fp32 path).

    Same contract as `kernels.ops.essr_forward_kernels`: x (N,p,p,3), width
    in {27, 54} (bilinear never reaches the kernels), zero-pad + re-slice
    for non-divisible batches, empty batches return an empty output."""
    w = width if width is not None else cfg.channels
    assert w > 0, "bilinear subnet does not use the conv kernels"
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        s = cfg.scale
        return jnp.zeros((0, x.shape[1] * s, x.shape[2] * s, cfg.in_channels),
                         x.dtype)
    if w != cfg.channels:
        params = slice_width(params, w)
    bp = block_patches if block_patches is not None else \
        autotune_block_patches(w, int(x.shape[1]), cfg.scale, cfg.n_sfb,
                               in_channels=cfg.in_channels)
    return _mega_forward(params, x, cfg, w, bp, interpret)


# ---------------------------------------------------------------------------
# integer-domain megakernel (quant x group fusion)
# ---------------------------------------------------------------------------

def _qmega_kernel(*refs, n_sfb: int, consts: Tuple[float, ...],
                  code_dt):
    """One grid step of the fused integer group: quantize-once at the input
    site, then the whole lattice chain — the inter-group codes that the
    per-op stack writes to HBM stay in the VMEM scratch."""
    x_ref, wrefs, o_ref, feat_ref = refs[0], refs[1:-2], refs[-2], refs[-1]
    it = iter(wrefs)

    def take(k):
        return [next(it)[...] for _ in range(k)]

    a_in, s_in = consts[0], consts[1]
    a_first, s_first = consts[2], consts[3]
    xq = _quantize_math(x_ref[...], a_in, s_in, code_dt)
    pwq, pws, pwb, dwf, dwb = take(5)
    feat_ref[...] = _qbsconv_math(xq, pwq, pws, pwb, dwf, dwb, relu=False,
                                  a_out=a_first, s_out=s_first)
    for i in range(n_sfb):
        a_b1, s_b1, a_b2, s_b2, a_out, s_out = consts[4 + 6 * i:10 + 6 * i]
        b1 = take(5)
        b2 = take(5)
        fuseq, fsy, fsx, fuseb = take(4)
        q = {"b1_pwq": b1[0], "b1_pw_scale": b1[1], "b1_pwb": b1[2],
             "b1_dw_fq": b1[3], "b1_dwb": b1[4],
             "a_b1": a_b1, "s_b1": s_b1,
             "b2_pwq": b2[0], "b2_pw_scale": b2[1], "b2_pwb": b2[2],
             "b2_dw_fq": b2[3], "b2_dwb": b2[4],
             "a_b2": a_b2, "s_b2": s_b2,
             "fuseq": fuseq, "fuse_scale_y": fsy, "fuse_scale_x": fsx,
             "fuseb": fuseb}
        feat_ref[...] = _qsfb_math(feat_ref[...], q, a_out=a_out,
                                   s_out=s_out)
    a_recon, s_recon = consts[-2], consts[-1]
    dwq, dws, dwb, pwf, pwb = take(5)
    o_ref[...] = _qdsconv_math(feat_ref[...], dwq, dws, dwb, pwf, pwb,
                               a_out=a_recon, s_out=s_recon)


@functools.partial(jax.jit, static_argnames=("cfg", "width", "pack",
                                             "block_patches", "interpret"),
                   inline=True)
def essr_forward_qmegakernel(params, x, cfg: ESSRConfig,
                             width: Optional[int] = None, *,
                             pack: QuantPack,
                             block_patches: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Quantized patch-batch forward through ONE fused integer Pallas kernel
    (`ExecutionPlan(fusion="group")` composed with `quant`).

    Same contract as `kernels.qconv.essr_forward_qkernels` and bit-exact
    against it (and `essr_forward_qref`): the kernel body composes the same
    shared `_*_math` group functions with the same compile-time site
    constants — but the integer codes between groups never leave VMEM."""
    w = width if width is not None else cfg.channels
    assert w > 0, "bilinear subnet does not use the conv kernels"
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        s = cfg.scale
        return jnp.zeros((0, x.shape[1] * s, x.shape[2] * s, cfg.in_channels),
                         x.dtype)
    interp = resolve_interpret(interpret)
    q, c = prepare_qparams(params, cfg, w, pack)
    bp = block_patches if block_patches is not None else \
        autotune_block_patches(w, int(x.shape[1]), cfg.scale, cfg.n_sfb,
                               in_channels=cfg.in_channels, quant=True)
    bblk = resolve_block(x.shape[0], bp)
    x, n = pad_batch(x, bblk)
    _, h, wdim, cin = x.shape
    cout = cfg.out_channels
    cdt = code_dtype(pack.bits)
    consts = (c["a_in"], c["s_in"], c["a_first"], c["s_first"])
    for i in range(cfg.n_sfb):
        consts += _sfb_consts(c, i)
    consts += (c["a_recon"], c["s_recon"])
    wops = _flat_q_operands(q)
    r = pl.pallas_call(
        functools.partial(_qmega_kernel, n_sfb=cfg.n_sfb, consts=consts,
                          code_dt=cdt),
        grid=(x.shape[0] // bblk,),
        in_specs=[pl.BlockSpec((bblk, h, wdim, cin), lambda i: (i, 0, 0, 0))]
        + _weight_specs(wops),
        out_specs=pl.BlockSpec((bblk, h, wdim, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], h, wdim, cout), cdt),
        scratch_shapes=[pltpu.VMEM((bblk, h, wdim, w), cdt)],
        interpret=interp,
        compiler_params=compiler_params(),
    )(x, *wops)
    up = r.astype(jnp.float32) * c["s_recon"]         # single dequant
    return pixel_shuffle(up, cfg.scale)[:n]


__all__ = ["essr_forward_megakernel", "essr_forward_qmegakernel",
           "autotune_block_patches", "autotune_report"]
