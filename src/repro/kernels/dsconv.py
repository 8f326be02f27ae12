"""Fused DSConv Pallas kernel — the GLNPU "DSConv fusion" group (Fig. 12).

3x3 depthwise THEN 1x1 pointwise (the order that kills the pixel-shuffle
checkerboard, Sec. III-B-3). Last conv of the model: on the ASIC its output
goes through boundary processing to DRAM; here the fused result goes straight
back to HBM once.
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


def dsconv_kernel(x_ref, dw_ref, dwb_ref, pw_ref, pwb_ref, o_ref, *, relu: bool):
    x = x_ref[...]
    b, h, w, cin = x.shape
    cout = pw_ref.shape[-1]
    y = _dw3x3(x, dw_ref[...]) + dwb_ref[...]
    y = _pw_dot(y.reshape(b * h * w, cin), pw_ref[...]) + pwb_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.reshape(b, h, w, cout).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu", "block_patches", "interpret"))
def dsconv_fused(x, dw, dw_b, pw, pw_b, *, relu: bool = False,
                 block_patches: int = 4, interpret: Optional[bool] = None):
    """x: (N,H,W,Cin); dw: (3,3,Cin); pw: (Cin,Cout).

    ``interpret``: None = auto (compiled on TPU/GPU, interpreter on CPU);
    non-divisible batches are zero-padded and re-sliced."""
    interpret = resolve_interpret(interpret)
    cout = pw.shape[-1]
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        return jnp.zeros((0,) + x.shape[1:3] + (cout,), x.dtype)
    bblk = resolve_block(x.shape[0], block_patches)
    x, n = pad_batch(x, bblk)
    _, h, w, cin = x.shape
    return pl.pallas_call(
        functools.partial(dsconv_kernel, relu=relu),
        grid=(x.shape[0] // bblk,),
        in_specs=[
            pl.BlockSpec((bblk, h, w, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bblk, h, w, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], h, w, cout), x.dtype),
        interpret=interpret,
        compiler_params=compiler_params(),
    )(x, dw, dw_b.reshape(1, cin), pw, pw_b.reshape(1, cout))[:n]
