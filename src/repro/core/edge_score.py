"""Edge score (paper Sec. II-A).

luma -> 3x3 Laplacian -> |.| clamped to [0,255] -> mean  ==> scalar per patch.

The Laplacian runs on the *interior* (VALID) so patch borders do not inject
fake edges; this matches computing the score before the slim-overlap halo is
attached. Scores live in [0, 255].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.phases import phase_jit
from repro.models.layers import rgb_to_luma

# 4-neighbour Laplacian (the standard 3x3 form)
LAPLACIAN = jnp.array([[0.0, 1.0, 0.0],
                       [1.0, -4.0, 1.0],
                       [0.0, 1.0, 0.0]], dtype=jnp.float32)


def laplacian_response(luma: jax.Array) -> jax.Array:
    """(N,H,W) luma in [0,255] -> (N,H-2,W-2) |Laplacian| clamped to [0,255].

    The conv runs at full fp32 precision on every backend: the TPU's default
    rounds conv inputs to bf16 (luma off by up to 0.5), which would move
    patches across the routing thresholds."""
    k = LAPLACIAN.reshape(3, 3, 1, 1)
    y = lax.conv_general_dilated(
        luma[..., None], k, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)[..., 0]
    return jnp.clip(jnp.abs(y), 0.0, 255.0)


@phase_jit("essr_edge_score")
def edge_score(patches: jax.Array) -> jax.Array:
    """(N,h,w,3) RGB in [0,1]  ->  (N,) edge scores in [0,255].

    jit'd: the serving path scores every patch batch of a stream, and the
    shapes recur per geometry. Its executable is the ``essr_edge_score``
    phase."""
    luma = rgb_to_luma(patches)
    resp = laplacian_response(luma)
    return resp.mean(axis=(1, 2))


@jax.jit
def edge_score_luma(luma: jax.Array) -> jax.Array:
    """(N,h,w) luma in [0,255] -> (N,) edge scores."""
    return laplacian_response(luma).mean(axis=(1, 2))
