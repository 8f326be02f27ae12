"""Fused whole-SFB Pallas kernel — the GLNPU "SFB mapping" (Fig. 15).

The entire Structure-Friendly Fusion Block — BSConv, ReLU, BSConv, ReLU,
shortcut add, 1x1 fuse, ReLU — runs in ONE pallas_call. Five intermediate
tensors that a layer-by-layer schedule would round-trip through HBM stay in
VMEM: the TPU analog of the paper's *79% feature-SRAM-access* saving (the
exact HBM-byte saving is measured in benchmarks/table_fusion.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bsconv import _dw3x3, _pw_dot
from repro.kernels.dispatch import (compiler_params, pad_batch, resolve_block,
                                    resolve_interpret)


def sfb_kernel(x_ref, b1pw_ref, b1pwb_ref, b1dw_ref, b1dwb_ref,
               b2pw_ref, b2pwb_ref, b2dw_ref, b2dwb_ref,
               fuse_ref, fuseb_ref, o_ref):
    x = x_ref[...]
    b, h, w, c = x.shape

    def bs(v, pw, pwb, dw, dwb):
        y = _pw_dot(v.reshape(b * h * w, c), pw)
        y = (y + pwb).reshape(b, h, w, c)
        return _dw3x3(y, dw) + dwb

    y = jnp.maximum(bs(x, b1pw_ref[...], b1pwb_ref[...], b1dw_ref[...], b1dwb_ref[...]), 0.0)
    y = jnp.maximum(bs(y, b2pw_ref[...], b2pwb_ref[...], b2dw_ref[...], b2dwb_ref[...]), 0.0)
    y = y + x                                            # shortcut adder
    y = _pw_dot(y.reshape(b * h * w, c), fuse_ref[...]) + fuseb_ref[...]
    o_ref[...] = jnp.maximum(y, 0.0).reshape(b, h, w, c).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_patches", "interpret"))
def sfb_fused(x, p, *, block_patches: int = 4, interpret: Optional[bool] = None):
    """x: (N,H,W,C); p: flat dict (see kernels/ref.py sfb_ref).

    ``interpret``: None = auto (compiled on TPU/GPU, interpreter on CPU);
    non-divisible batches are zero-padded and re-sliced."""
    interpret = resolve_interpret(interpret)
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        return jnp.zeros(x.shape, x.dtype)
    bblk = resolve_block(x.shape[0], block_patches)
    x, n = pad_batch(x, bblk)
    _, h, w, c = x.shape
    r2 = lambda v: v.reshape(1, -1)
    stationary_w = lambda: pl.BlockSpec((c, c), lambda i: (0, 0))
    stationary_b = lambda: pl.BlockSpec((1, c), lambda i: (0, 0))
    stationary_d = lambda: pl.BlockSpec((3, 3, c), lambda i: (0, 0, 0))
    return pl.pallas_call(
        sfb_kernel,
        grid=(x.shape[0] // bblk,),
        in_specs=[
            pl.BlockSpec((bblk, h, w, c), lambda i: (i, 0, 0, 0)),
            stationary_w(), stationary_b(), stationary_d(), stationary_b(),
            stationary_w(), stationary_b(), stationary_d(), stationary_b(),
            stationary_w(), stationary_b(),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], h, w, c), x.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(x, p["b1_pw"], r2(p["b1_pwb"]), p["b1_dw"], r2(p["b1_dwb"]),
      p["b2_pw"], r2(p["b2_pwb"]), p["b2_dw"], r2(p["b2_dwb"]),
      p["fuse"], r2(p["fuse_b"]))[:n]
