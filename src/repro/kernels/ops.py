"""Public jit'd wrappers over the Pallas kernels (`ops.py` of the kernel set).

``essr_forward_kernels`` runs the whole ESSR patch-batch through the fused
groups exactly as the GLNPU schedules them (Figs. 10-12, 15):

    BSConv fusion -> 5 x SFB fusion -> DSConv fusion -> pixel shuffle

``block_patches`` doubles for the C27 subnet at equal VMEM budget — the
"configurable group of layer mapping" (C27 moves 2x the patches per grid
step through the same kernels, mirroring 4x 1x1 + 2x 3x3 concurrent PE use).

The quantized serving path (`ExecutionPlan.quant`) has its own fused chain,
``essr_forward_qkernels`` (kernels/qconv.py): same group structure on the
PAMS integer lattice.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax

from repro.kernels.bsconv import bsconv_fused
import jax.numpy as jnp

from repro.kernels.dispatch import (default_interpret, pad_batch,
                                    resolve_block, resolve_interpret)
from repro.kernels.dsconv import dsconv_fused
from repro.kernels.edge import edge_score_fused
from repro.kernels.qconv import (essr_forward_qkernels, essr_forward_qref,
                                 qbsconv_fused, qdsconv_fused, qsfb_fused,
                                 quantize_fused)
from repro.kernels.megakernel import (autotune_block_patches,
                                      essr_forward_megakernel,
                                      essr_forward_qmegakernel)
from repro.kernels.sfb import sfb_fused
from repro.models.essr import ESSRConfig, slice_width
from repro.models.layers import pixel_shuffle


def _flat_sfb(p: Dict[str, Any]) -> Dict[str, jax.Array]:
    return {
        "b1_pw": p["b1"]["pw"][0, 0], "b1_pwb": p["b1"]["pw_b"],
        "b1_dw": p["b1"]["dw"][:, :, 0, :], "b1_dwb": p["b1"]["dw_b"],
        "b2_pw": p["b2"]["pw"][0, 0], "b2_pwb": p["b2"]["pw_b"],
        "b2_dw": p["b2"]["dw"][:, :, 0, :], "b2_dwb": p["b2"]["dw_b"],
        "fuse": p["fuse"][0, 0], "fuse_b": p["fuse_b"],
    }


def default_block_patches(width: int, channels: int = 54, base: int = 4) -> int:
    """C27 processes 2x patches per grid step at the same VMEM budget."""
    return base * max(1, channels // max(width, 1))


@functools.partial(jax.jit, static_argnames=("cfg", "width", "block_patches", "interpret"),
                   inline=True)
def essr_forward_kernels(params, x, cfg: ESSRConfig, width: Optional[int] = None,
                         block_patches: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Patch-batch ESSR forward entirely through the fused Pallas groups.

    x: (N,p,p,3). width in {27,54}; bilinear patches never reach the kernels
    (the router handles them, as on the ASIC).

    The batch is zero-padded ONCE to a multiple of ``block_patches`` and
    sliced after the chain, so prime batch sizes keep the full grid block
    (the seed walked ``block_patches`` down to 1, a silent throughput cliff).
    ``interpret``: None = auto (compiled on TPU/GPU, interpreter on CPU)."""
    w = width if width is not None else cfg.channels
    assert w > 0, "bilinear subnet does not use the conv kernels"
    if x.shape[0] == 0:      # emptied routing bucket: no grid to launch
        s = cfg.scale
        return jnp.zeros((0, x.shape[1] * s, x.shape[2] * s, cfg.in_channels),
                         x.dtype)
    if w != cfg.channels:
        params = slice_width(params, w)
    bp = block_patches if block_patches is not None else default_block_patches(w, cfg.channels)
    bp = resolve_block(x.shape[0], bp)
    x, n = pad_batch(x, bp)

    f = bsconv_fused(x, params["first"]["pw"][0, 0], params["first"]["pw_b"],
                     params["first"]["dw"][:, :, 0, :], params["first"]["dw_b"],
                     relu=False, block_patches=bp, interpret=interpret)
    for p in params["sfbs"]:
        f = sfb_fused(f, _flat_sfb(p), block_patches=bp, interpret=interpret)
    up = dsconv_fused(f, params["recon"]["dw"][:, :, 0, :], params["recon"]["dw_b"],
                      params["recon"]["pw"][0, 0], params["recon"]["pw_b"],
                      relu=False, block_patches=bp, interpret=interpret)
    return pixel_shuffle(up, cfg.scale)[:n]


__all__ = ["bsconv_fused", "dsconv_fused", "sfb_fused", "edge_score_fused",
           "essr_forward_kernels", "default_block_patches",
           "default_interpret", "resolve_interpret",
           "quantize_fused", "qbsconv_fused", "qsfb_fused", "qdsconv_fused",
           "essr_forward_qkernels", "essr_forward_qref",
           "essr_forward_megakernel", "essr_forward_qmegakernel",
           "autotune_block_patches"]
