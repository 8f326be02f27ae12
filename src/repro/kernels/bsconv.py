"""Fused BSConv Pallas kernel — the GLNPU "BSConv fusion" group (Fig. 10).

One ``pallas_call`` executes 1x1 pointwise (MXU matmul) + 3x3 depthwise
(VPU shifted-accumulate) back-to-back: the intermediate feature lives only in
VMEM/VREGs, never round-tripping HBM — the TPU analog of the paper's 43%
feature-SRAM-access saving.

Tiling: grid over patch-batch; block = (Bblk, H, W, C). Weights use a
constant index_map (block 0 every step) so Mosaic keeps them VMEM-resident
across grid steps — "weights remain stationary during computing" (Sec. IV-G).
The pointwise runs as an (Bblk*H*W, Cin)@(Cin, Cout) matmul: rows are a
multiple of 256 for 32x32 patches, MXU-aligned; channels (54) are lane-padded
by Mosaic (the C=54-vs-128 padding loss is immaterial — the op is HBM-bound,
see EXPERIMENTS.md §Roofline/ESSR).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import (compiler_params, pad_batch, resolve_block,
                                    resolve_interpret)


def _pw_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """fp32 (rows, Cin) @ (Cin, Cout) on the MXU at full fp32 precision.

    Mosaic's default for f32 operands is a single bf16 pass, which puts the
    ESSR chain ~2e-2 (relative) off its fp32 reference; HIGHEST keeps the
    fp32 datapath fp32 on the chip, as it is in interpret mode."""
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _dw3x3(y: jax.Array, dw: jax.Array) -> jax.Array:
    """3x3 depthwise, SAME zero-pad, via 9 shifted multiply-accumulates.

    y: (B,H,W,C); dw: (3,3,C). Static slices only — Mosaic-friendly."""
    b, h, w, c = y.shape
    yp = jnp.pad(y, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros_like(y)
    for dy in range(3):
        for dx in range(3):
            acc = acc + yp[:, dy:dy + h, dx:dx + w, :] * dw[dy, dx]
    return acc


def bsconv_kernel(x_ref, pw_ref, pwb_ref, dw_ref, dwb_ref, o_ref, *, relu: bool):
    x = x_ref[...]
    b, h, w, cin = x.shape
    cout = pw_ref.shape[-1]
    # --- 1x1 pointwise on the MXU -----------------------------------------
    y = _pw_dot(x.reshape(b * h * w, cin), pw_ref[...])
    y = (y + pwb_ref[...]).reshape(b, h, w, cout)
    # --- 3x3 depthwise on the VPU (feature never leaves VMEM) -------------
    y = _dw3x3(y, dw_ref[...]) + dwb_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu", "block_patches", "interpret"))
def bsconv_fused(x, pw, pw_b, dw, dw_b, *, relu: bool = False,
                 block_patches: int = 4, interpret: Optional[bool] = None):
    """x: (N,H,W,Cin); pw: (Cin,Cout); dw: (3,3,Cout); biases (Cout,).

    ``block_patches``: patches per grid step. The C27 subnet doubles it at the
    same VMEM budget (ops.py) — the "configurable group of layer mapping".
    ``interpret``: None = auto (compiled on TPU/GPU, interpreter on CPU).
    Batches not divisible by the block are zero-padded and re-sliced.
    """
    interpret = resolve_interpret(interpret)
    cout = pw.shape[-1]
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        return jnp.zeros((0,) + x.shape[1:3] + (cout,), x.dtype)
    bblk = resolve_block(x.shape[0], block_patches)
    x, n = pad_batch(x, bblk)
    _, h, w, cin = x.shape
    pwb2 = pw_b.reshape(1, cout)
    dwb2 = dw_b.reshape(1, cout)
    grid = (x.shape[0] // bblk,)
    return pl.pallas_call(
        functools.partial(bsconv_kernel, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bblk, h, w, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),      # stationary
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, cout), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], h, w, cout), x.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(x, pw, pwb2, dw, dwb2)[:n]
