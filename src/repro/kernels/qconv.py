"""Integer-domain quantized ESSR kernels (PAMS serving path, Sec. IV-H).

The fp kernel stack (bsconv/sfb/dsconv) re-expressed on the PAMS integer
lattice: activations travel between fused groups as **integer codes**
(int8 for the TPU-native ``"int8"`` mode, int32 for the paper-faithful
``"fxp10"`` mode), every 1x1 pointwise whose input sits on a lattice runs as
a genuine integer matmul — int codes in, int32 accumulate
(``preferred_element_type=jnp.int32``, the MXU int8 datapath), dequantize +
bias on the way out — and each fused group requantizes its output once before
it returns to HBM. Codes at int8 halve the inter-group HBM bytes vs fp32.

Where a conv reads a *wide* intermediate instead of a lattice (the 3x3
depthwise inside BSConv, the trailing 1x1 of DSConv — the fake-quant
reference has no activation-quant site there; on the ASIC these feed the
24-bit accumulator chain), it runs in fp with **fake-quantized weights**:
exactly the values ``quant.pams.quantize_weight_tree`` produces, so the
integer path stays layer-for-layer consistent with the fake-quant reference.

The SFB shortcut adder sums two different lattices (block input at the
previous site's step, b2 output at its own), so the fuse 1x1 distributes over
them: two integer matmuls against the same weight codes, combined in fp —
``fuse(y + x) == fuse(y) + fuse(x)``.

Conformance contract (tests/test_quant_conformance.py):
  * every code tensor is bit-exact vs ``quant.pams.int_codes`` of the value
    it quantizes (the kernel bodies and the pure-jnp reference
    ``essr_forward_qref`` share the `_*_math` functions below, so kernel
    vs reference is bit-exact by construction in interpret mode);
  * each fused group is allclose to the fake-quant emulation of the same
    layers (`quantized_essr_forward`) within a few quantization steps.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bsconv import _dw3x3, _pw_dot
from repro.kernels.dispatch import (compiler_params, pad_batch, resolve_block,
                                    resolve_interpret)
from repro.models.essr import ESSRConfig, slice_width
from repro.models.layers import pixel_shuffle
from repro.quant.pams import (EPS, QuantPack, code_dtype, step_size,
                              weight_alpha)


# ---------------------------------------------------------------------------
# scalar quant constants — computed in float32 numpy so the compile-time
# closures match the float32 jnp arithmetic of quant.pams bit-for-bit
# ---------------------------------------------------------------------------

def act_qconsts(alpha_raw: float, qmax: int) -> Tuple[float, float]:
    """(clip, step) for an activation site: the same ``|alpha| + 1e-8`` clip
    and epsilon-floored step that `quant.pams.effective_alpha`/`step_size`
    produce, evaluated in f32 so kernel constants equal traced scalars."""
    a = np.float32(np.abs(np.float32(alpha_raw)) + np.float32(1e-8))
    s = np.maximum(a / np.float32(qmax), np.float32(EPS))
    return float(a), float(s)


def _dw3x3_i32(y: jax.Array, dw: jax.Array) -> jax.Array:
    """`_dw3x3` on the integer lattice: int32 shifted multiply-accumulate
    (exact — FXP10 worst case 511*511*9 ≈ 2.4e6 is far from overflow)."""
    b, h, w, c = y.shape
    yp = jnp.pad(y, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros_like(y)
    for dy in range(3):
        for dx in range(3):
            acc = acc + yp[:, dy:dy + h, dx:dx + w, :] * dw[dy, dx]
    return acc


# ---------------------------------------------------------------------------
# shared math — the kernel bodies AND the jnp reference call these, so the
# Pallas path is bit-exact vs `essr_forward_qref` by construction
# ---------------------------------------------------------------------------

def _quantize_math(x, a: float, s: float, dtype):
    return jnp.round(jnp.clip(x, -a, a) / s).astype(dtype)


def _qbsconv_math(xq, pwq, pw_scale, pw_b, dw_fq, dw_b, *, relu: bool,
                  a_out: float, s_out: float):
    """Lattice codes -> lattice codes through one BSConv group.

    1x1 pointwise: integer matmul, int32 accumulate; dequant folds the input
    step and the per-channel weight step into one scale array. 3x3 depthwise:
    fp on the wide intermediate with fake-quant weights."""
    b, h, w, cin = xq.shape
    acc = jnp.dot(xq.reshape(b * h * w, cin), pwq,
                  preferred_element_type=jnp.int32)
    y = (acc.astype(jnp.float32) * pw_scale + pw_b).reshape(b, h, w, -1)
    y = _dw3x3(y, dw_fq) + dw_b
    if relu:
        y = jnp.maximum(y, 0.0)
    return _quantize_math(y, a_out, s_out, xq.dtype)


def _qsfb_math(xq, q, *, a_out: float, s_out: float):
    """Whole SFB on the lattice: two quantized BSConv groups, then the fuse
    1x1 distributed over the two input lattices (shortcut adder)."""
    b, h, w, c = xq.shape
    y1 = _qbsconv_math(xq, q["b1_pwq"], q["b1_pw_scale"], q["b1_pwb"],
                       q["b1_dw_fq"], q["b1_dwb"], relu=True,
                       a_out=q["a_b1"], s_out=q["s_b1"])
    y2 = _qbsconv_math(y1, q["b2_pwq"], q["b2_pw_scale"], q["b2_pwb"],
                       q["b2_dw_fq"], q["b2_dwb"], relu=True,
                       a_out=q["a_b2"], s_out=q["s_b2"])
    acc_y = jnp.dot(y2.reshape(b * h * w, c), q["fuseq"],
                    preferred_element_type=jnp.int32)
    acc_x = jnp.dot(xq.reshape(b * h * w, c), q["fuseq"],
                    preferred_element_type=jnp.int32)
    y = (acc_y.astype(jnp.float32) * q["fuse_scale_y"]
         + acc_x.astype(jnp.float32) * q["fuse_scale_x"] + q["fuseb"])
    y = jnp.maximum(y, 0.0).reshape(b, h, w, c)
    return _quantize_math(y, a_out, s_out, xq.dtype)


def _qdsconv_math(xq, dwq, dw_scale, dw_b, pw_fq, pw_b, *, a_out: float,
                  s_out: float):
    """DSConv on the lattice: 3x3 depthwise as an exact int32 shifted MAC
    (input IS a lattice here), then the 1x1 pointwise in fp with fake-quant
    weights (its input is the wide depthwise output)."""
    b, h, w, cin = xq.shape
    acc = _dw3x3_i32(xq.astype(jnp.int32), dwq)
    y = acc.astype(jnp.float32) * dw_scale + dw_b
    y = _pw_dot(y.reshape(b * h * w, cin), pw_fq) + pw_b
    y = y.reshape(b, h, w, -1)
    return _quantize_math(y, a_out, s_out, xq.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels: grid over the patch batch, weights stationary (constant
# index_map), exactly like the fp stack in bsconv/sfb/dsconv.py
# ---------------------------------------------------------------------------

def _quantize_kernel(x_ref, o_ref, *, a: float, s: float):
    o_ref[...] = _quantize_math(x_ref[...], a, s, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("a", "s", "bits",
                                             "block_patches", "interpret"))
def quantize_fused(x, *, a: float, s: float, bits: int,
                   block_patches: int = 4, interpret: Optional[bool] = None):
    """fp tensor -> integer lattice codes (`int_codes` bit-exact)."""
    interpret = resolve_interpret(interpret)
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        return jnp.zeros(x.shape, code_dtype(bits))
    bblk = resolve_block(x.shape[0], block_patches)
    x, n = pad_batch(x, bblk)
    shp = x.shape[1:]
    return pl.pallas_call(
        functools.partial(_quantize_kernel, a=a, s=s),
        grid=(x.shape[0] // bblk,),
        in_specs=[pl.BlockSpec((bblk,) + shp, lambda i: (i,) + (0,) * len(shp))],
        out_specs=pl.BlockSpec((bblk,) + shp, lambda i: (i,) + (0,) * len(shp)),
        out_shape=jax.ShapeDtypeStruct(x.shape, code_dtype(bits)),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(x)[:n]


def _qbsconv_kernel(x_ref, pwq_ref, pws_ref, pwb_ref, dw_ref, dwb_ref, o_ref,
                    *, relu: bool, a_out: float, s_out: float):
    o_ref[...] = _qbsconv_math(x_ref[...], pwq_ref[...], pws_ref[...],
                               pwb_ref[...], dw_ref[...], dwb_ref[...],
                               relu=relu, a_out=a_out, s_out=s_out)


@functools.partial(jax.jit, static_argnames=("relu", "a_out", "s_out",
                                             "block_patches", "interpret"))
def qbsconv_fused(xq, pwq, pw_scale, pw_b, dw_fq, dw_b, *, relu: bool,
                  a_out: float, s_out: float, block_patches: int = 4,
                  interpret: Optional[bool] = None):
    """xq: (N,H,W,Cin) codes; pwq: (Cin,Cout) codes; pw_scale: (Cout,) folded
    input*weight step; dw_fq: (3,3,Cout) fake-quant fp. Returns codes."""
    interpret = resolve_interpret(interpret)
    cout = pwq.shape[-1]
    if xq.shape[0] == 0:     # emptied routing bucket: no grid to launch
        return jnp.zeros((0,) + xq.shape[1:3] + (cout,), xq.dtype)
    bblk = resolve_block(xq.shape[0], block_patches)
    xq, n = pad_batch(xq, bblk)
    _, h, w, cin = xq.shape
    return pl.pallas_call(
        functools.partial(_qbsconv_kernel, relu=relu, a_out=a_out,
                          s_out=s_out),
        grid=(xq.shape[0] // bblk,),
        in_specs=[
            pl.BlockSpec((bblk, h, w, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),      # stationary
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, cout), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((xq.shape[0], h, w, cout), xq.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(xq, pwq, pw_scale.reshape(1, cout), pw_b.reshape(1, cout), dw_fq,
      dw_b.reshape(1, cout))[:n]


def _qsfb_kernel(x_ref, b1pw_ref, b1s_ref, b1pwb_ref, b1dw_ref, b1dwb_ref,
                 b2pw_ref, b2s_ref, b2pwb_ref, b2dw_ref, b2dwb_ref,
                 fuse_ref, fsy_ref, fsx_ref, fuseb_ref, o_ref, *,
                 consts: Tuple[float, ...]):
    a_b1, s_b1, a_b2, s_b2, a_out, s_out = consts
    q = {"b1_pwq": b1pw_ref[...], "b1_pw_scale": b1s_ref[...],
         "b1_pwb": b1pwb_ref[...], "b1_dw_fq": b1dw_ref[...],
         "b1_dwb": b1dwb_ref[...], "a_b1": a_b1, "s_b1": s_b1,
         "b2_pwq": b2pw_ref[...], "b2_pw_scale": b2s_ref[...],
         "b2_pwb": b2pwb_ref[...], "b2_dw_fq": b2dw_ref[...],
         "b2_dwb": b2dwb_ref[...], "a_b2": a_b2, "s_b2": s_b2,
         "fuseq": fuse_ref[...], "fuse_scale_y": fsy_ref[...],
         "fuse_scale_x": fsx_ref[...], "fuseb": fuseb_ref[...]}
    o_ref[...] = _qsfb_math(x_ref[...], q, a_out=a_out, s_out=s_out)


@functools.partial(jax.jit, static_argnames=("consts", "block_patches",
                                             "interpret"))
def qsfb_fused(xq, q: Dict[str, jax.Array], *, consts: Tuple[float, ...],
               block_patches: int = 4, interpret: Optional[bool] = None):
    """Whole SFB on the lattice in ONE pallas_call: the five wide
    intermediates AND the two internal code tensors stay in VMEM.

    ``q``: array operands from `prepare_qparams`; ``consts``: the six scalar
    quant constants (a_b1, s_b1, a_b2, s_b2, a_out, s_out)."""
    interpret = resolve_interpret(interpret)
    if xq.shape[0] == 0:     # emptied routing bucket: no grid to launch
        return jnp.zeros(xq.shape, xq.dtype)
    bblk = resolve_block(xq.shape[0], block_patches)
    xq, n = pad_batch(xq, bblk)
    _, h, w, c = xq.shape
    r2 = lambda v: v.reshape(1, c)
    stationary_w = lambda: pl.BlockSpec((c, c), lambda i: (0, 0))
    stationary_b = lambda: pl.BlockSpec((1, c), lambda i: (0, 0))
    stationary_d = lambda: pl.BlockSpec((3, 3, c), lambda i: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_qsfb_kernel, consts=consts),
        grid=(xq.shape[0] // bblk,),
        in_specs=[
            pl.BlockSpec((bblk, h, w, c), lambda i: (i, 0, 0, 0)),
            stationary_w(), stationary_b(), stationary_b(),
            stationary_d(), stationary_b(),
            stationary_w(), stationary_b(), stationary_b(),
            stationary_d(), stationary_b(),
            stationary_w(), stationary_b(), stationary_b(), stationary_b(),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((xq.shape[0], h, w, c), xq.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(xq, q["b1_pwq"], r2(q["b1_pw_scale"]), r2(q["b1_pwb"]), q["b1_dw_fq"],
      r2(q["b1_dwb"]), q["b2_pwq"], r2(q["b2_pw_scale"]), r2(q["b2_pwb"]),
      q["b2_dw_fq"], r2(q["b2_dwb"]), q["fuseq"], r2(q["fuse_scale_y"]),
      r2(q["fuse_scale_x"]), r2(q["fuseb"]))[:n]


def _qdsconv_kernel(x_ref, dwq_ref, dws_ref, dwb_ref, pw_ref, pwb_ref, o_ref,
                    *, a_out: float, s_out: float):
    o_ref[...] = _qdsconv_math(x_ref[...], dwq_ref[...], dws_ref[...],
                               dwb_ref[...], pw_ref[...], pwb_ref[...],
                               a_out=a_out, s_out=s_out)


@functools.partial(jax.jit, static_argnames=("a_out", "s_out",
                                             "block_patches", "interpret"))
def qdsconv_fused(xq, dwq, dw_scale, dw_b, pw_fq, pw_b, *, a_out: float,
                  s_out: float, block_patches: int = 4,
                  interpret: Optional[bool] = None):
    """xq: (N,H,W,Cin) codes; dwq: (3,3,Cin) int32 codes; pw_fq: (Cin,Cout)
    fake-quant fp. Returns (N,H,W,Cout) codes at the recon site."""
    interpret = resolve_interpret(interpret)
    cout = pw_fq.shape[-1]
    if xq.shape[0] == 0:     # emptied routing bucket: no grid to launch
        return jnp.zeros((0,) + xq.shape[1:3] + (cout,), xq.dtype)
    bblk = resolve_block(xq.shape[0], block_patches)
    xq, n = pad_batch(xq, bblk)
    _, h, w, cin = xq.shape
    return pl.pallas_call(
        functools.partial(_qdsconv_kernel, a_out=a_out, s_out=s_out),
        grid=(xq.shape[0] // bblk,),
        in_specs=[
            pl.BlockSpec((bblk, h, w, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((xq.shape[0], h, w, cout), xq.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(xq, dwq, dw_scale.reshape(1, cin), dw_b.reshape(1, cin), pw_fq,
      pw_b.reshape(1, cout))[:n]


# ---------------------------------------------------------------------------
# operand preparation: weight codes + folded scales, per subnet width
# ---------------------------------------------------------------------------

def _qweight(w: jax.Array, per_channel: bool, qmax: int):
    """Weight -> (integer codes fp-valued, per-channel step). The codes times
    the step reproduce `quantize_weight_tree`'s fake-quant values exactly.

    The step always comes back (1,1,1,Cout)-shaped: per-tensor alphas
    (``per_channel=False``) produce a 0-d step from `weight_alpha`, which is
    broadcast up so the downstream ``[..., 0, :]``/``[0, 0, 0]`` scale
    extraction is shape-uniform across both weight-quant modes."""
    a = weight_alpha(w, per_channel)
    s = step_size(a, qmax)
    codes = jnp.round(jnp.clip(w, -a, a) / s)
    if s.ndim == 0:
        s = jnp.broadcast_to(s, (1, 1, 1, w.shape[-1]))
    return codes, s


def prepare_qparams(params, cfg: ESSRConfig, width: int, pack: QuantPack
                    ) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """Width-sliced param tree -> kernel operands + scalar site constants.

    Folds each integer matmul's dequant into one per-channel scale array
    (input step x weight step) and bakes every activation site's (clip, step)
    into compile-time floats, so the kernels carry no quant bookkeeping."""
    if width != cfg.channels:
        params = slice_width(params, width)
    qmax, pc = pack.qmax, pack.per_channel_weights
    cdt = code_dtype(pack.bits)
    alphas = pack.act_scales(width)
    consts: Dict[str, float] = {}
    for site, raw in alphas.items():
        consts[f"a_{site}"], consts[f"s_{site}"] = act_qconsts(raw, qmax)

    def pw_ops(p, key, s_in: float):
        codes, s_w = _qweight(p[key], pc, qmax)
        return {f"{key}q": codes[0, 0].astype(cdt),
                f"{key}_scale": (s_in * s_w)[0, 0, 0],
                f"{key}b": p.get(f"{key}_b",
                                 jnp.zeros(p[key].shape[-1], jnp.float32))}

    def dw_fq(p):
        codes, s_w = _qweight(p["dw"], pc, qmax)
        return (codes * s_w)[:, :, 0, :], p["dw_b"]

    q: Dict[str, Any] = {}
    first = pw_ops(params["first"], "pw", consts["s_in"])
    first["dw_fq"], first["dwb"] = dw_fq(params["first"])
    q["first"] = first

    q["sfbs"] = []
    prev = "first"
    for i, p in enumerate(params["sfbs"]):
        sfb: Dict[str, Any] = {}
        b1 = pw_ops(p["b1"], "pw", consts[f"s_{prev}"])
        sfb.update({"b1_pwq": b1["pwq"], "b1_pw_scale": b1["pw_scale"],
                    "b1_pwb": b1["pwb"]})
        sfb["b1_dw_fq"], sfb["b1_dwb"] = dw_fq(p["b1"])
        b2 = pw_ops(p["b2"], "pw", consts[f"s_sfb{i}_b1"])
        sfb.update({"b2_pwq": b2["pwq"], "b2_pw_scale": b2["pw_scale"],
                    "b2_pwb": b2["pwb"]})
        sfb["b2_dw_fq"], sfb["b2_dwb"] = dw_fq(p["b2"])
        fcodes, fs = _qweight(p["fuse"], pc, qmax)
        sfb["fuseq"] = fcodes[0, 0].astype(cdt)
        sfb["fuse_scale_y"] = (consts[f"s_sfb{i}_b2"] * fs)[0, 0, 0]
        sfb["fuse_scale_x"] = (consts[f"s_{prev}"] * fs)[0, 0, 0]
        sfb["fuseb"] = p.get("fuse_b", jnp.zeros(width, jnp.float32))
        q["sfbs"].append(sfb)
        prev = f"sfb{i}_out"

    rcodes, rs = _qweight(params["recon"]["dw"], pc, qmax)
    pw_fq_codes, pw_fq_s = _qweight(params["recon"]["pw"], pc, qmax)
    q["recon"] = {
        "dwq": rcodes[:, :, 0, :].astype(jnp.int32),
        "dw_scale": (consts[f"s_{prev}"] * rs)[0, 0, 0],
        "dwb": params["recon"]["dw_b"],
        "pw_fq": (pw_fq_codes * pw_fq_s)[0, 0],
        "pwb": params["recon"]["pw_b"],
    }
    return q, consts


def _sfb_consts(consts: Dict[str, float], i: int) -> Tuple[float, ...]:
    return (consts[f"a_sfb{i}_b1"], consts[f"s_sfb{i}_b1"],
            consts[f"a_sfb{i}_b2"], consts[f"s_sfb{i}_b2"],
            consts[f"a_sfb{i}_out"], consts[f"s_sfb{i}_out"])


# ---------------------------------------------------------------------------
# whole-model chains: Pallas serving path + the pure-jnp reference spec
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "width", "pack",
                                             "block_patches", "interpret"))
def essr_forward_qkernels(params, x, cfg: ESSRConfig,
                          width: Optional[int] = None, *,
                          pack: QuantPack, block_patches: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Patch-batch quantized ESSR forward through the fused integer groups.

    x: (N,p,p,3) fp in [0,1]. Quantize once at the input site, run every
    group on the lattice, dequantize once after the recon site. Bilinear
    patches (width 0) never reach these kernels (the router handles them)."""
    from repro.kernels.ops import default_block_patches
    w = width if width is not None else cfg.channels
    assert w > 0, "bilinear subnet does not use the conv kernels"
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        s = cfg.scale
        return jnp.zeros((0, x.shape[1] * s, x.shape[2] * s, cfg.in_channels),
                         x.dtype)
    q, c = prepare_qparams(params, cfg, w, pack)
    bp = block_patches if block_patches is not None else \
        default_block_patches(w, cfg.channels)
    bp = resolve_block(x.shape[0], bp)
    x, n = pad_batch(x, bp)
    # Zero-pad rows re-quantize to NONZERO codes (the dequant folds biases
    # back in before the requantize clip), so without masking they flow as
    # garbage through every later group's int32 accumulate. Force pad rows
    # back to exact-zero codes after each group — integer multiply by
    # {0,1}, exact, and a no-op for the valid rows sliced out at the end.
    valid = (jnp.arange(x.shape[0]) < n)[:, None, None, None]

    def mask(codes):
        return codes * valid.astype(codes.dtype)

    f = mask(quantize_fused(x, a=c["a_in"], s=c["s_in"], bits=pack.bits,
                            block_patches=bp, interpret=interpret))
    f = mask(qbsconv_fused(f, q["first"]["pwq"], q["first"]["pw_scale"],
                           q["first"]["pwb"], q["first"]["dw_fq"],
                           q["first"]["dwb"], relu=False, a_out=c["a_first"],
                           s_out=c["s_first"], block_patches=bp,
                           interpret=interpret))
    for i, sfb in enumerate(q["sfbs"]):
        f = mask(qsfb_fused(f, sfb, consts=_sfb_consts(c, i),
                            block_patches=bp, interpret=interpret))
    r = qdsconv_fused(f, q["recon"]["dwq"], q["recon"]["dw_scale"],
                      q["recon"]["dwb"], q["recon"]["pw_fq"],
                      q["recon"]["pwb"], a_out=c["a_recon"],
                      s_out=c["s_recon"], block_patches=bp,
                      interpret=interpret)
    up = r.astype(jnp.float32) * c["s_recon"]         # single dequant
    return pixel_shuffle(up, cfg.scale)[:n]


@functools.partial(jax.jit, static_argnames=("cfg", "width", "pack",
                                             "return_codes"))
def essr_forward_qref(params, x, cfg: ESSRConfig, width: Optional[int] = None,
                      *, pack: QuantPack, return_codes: bool = False):
    """Pure-jnp integer-domain reference — the spec `essr_forward_qkernels`
    must match bit-exactly (same `_*_math` bodies, no Pallas).

    jit'd like the serving path: XLA's fp contraction (mul+add -> fma) must
    be decided identically on both sides, or a 1-ulp excess-precision
    difference can flip a code sitting exactly on a .5 rounding boundary
    (observed in practice; the integer dots themselves are always exact).

    ``return_codes``: also return the {site: codes} dict for the
    integer-consistency tests."""
    w = width if width is not None else cfg.channels
    assert w > 0
    q, c = prepare_qparams(params, cfg, w, pack)
    codes: Dict[str, jax.Array] = {}

    f = _quantize_math(x, c["a_in"], c["s_in"], code_dtype(pack.bits))
    codes["in"] = f
    f = _qbsconv_math(f, q["first"]["pwq"], q["first"]["pw_scale"],
                      q["first"]["pwb"], q["first"]["dw_fq"],
                      q["first"]["dwb"], relu=False, a_out=c["a_first"],
                      s_out=c["s_first"])
    codes["first"] = f
    for i, sfb in enumerate(q["sfbs"]):
        a_b1, s_b1, a_b2, s_b2, a_out, s_out = _sfb_consts(c, i)
        f = _qsfb_math(f, {**sfb, "a_b1": a_b1, "s_b1": s_b1,
                           "a_b2": a_b2, "s_b2": s_b2},
                       a_out=a_out, s_out=s_out)
        codes[f"sfb{i}_out"] = f
    r = _qdsconv_math(f, q["recon"]["dwq"], q["recon"]["dw_scale"],
                      q["recon"]["dwb"], q["recon"]["pw_fq"],
                      q["recon"]["pwb"], a_out=c["a_recon"],
                      s_out=c["s_recon"])
    codes["recon"] = r
    img = pixel_shuffle(r.astype(jnp.float32) * c["s_recon"], cfg.scale)
    return (img, codes) if return_codes else img
