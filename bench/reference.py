"""Plain fp32 reference of one edge-selective ESSR frame, and the weights.

Written from the paper (arXiv:2503.20245) in straightforward ``jax.numpy``,
independent of the program under test:

* patches: ``patch`` x ``patch`` LR tiles whose starts step by
  ``patch - overlap`` and end flush with the frame (Sec. IV-I);
* edge score: BT.601 luma in [16, 235], the 4-neighbour Laplacian on the
  patch interior, its magnitude clamped to [0, 255], the mean (Sec. II-A);
* routing: score < t1 bilinear, t1 <= score < t2 C27, else C54 (Sec. II-C);
* subnets: BSConv(3->C), n_sfb SFBs, DSConv(C->3*s^2), pixel shuffle;
  C27 is the first half of every channel dimension of C54 (Sec. II-B,
  III); bilinear is ``jax.image.resize`` of the patch;
* fuse: the HR patches are added into the frame and each pixel divided by
  the number of patches that cover it (overlap-and-average).

Matmuls run at the ``precision`` asked for: ``"highest"`` is fp32 (XLA's
HIGHEST), the reference; ``"bf16_3x"`` is the next precision below, the
control that the comparison has to refuse: each fp32 operand split into a
bfloat16 high and low part, and the three larger of the four products
summed, as the TPU's HIGH precision does. It is written out, so that it
computes the same on every backend.
Patches are processed in blocks, so the reference fits beside what the run
still holds.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import frames
import work

BLOCK = 256                          # patches per reference block
BIAS_STD = 0.05


def init_weights(seed: int, model: dict) -> Dict:
    """Supernet weights from ``seed`` in the program's parameter layout, made
    on the device in one jitted call: He-normal convs, small random biases.
    """
    c, cin, s = int(model["channels"]), int(model["in_channels"]), int(model["scale"])
    cout, n_sfb = cin * s * s, int(model["n_sfb"])

    def he(key, shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return math.sqrt(2.0 / fan_in) * jax.random.normal(key, shape)

    def bias(key, n):
        return BIAS_STD * jax.random.normal(key, (n,))

    def bsconv(key, ci, co):
        k = jax.random.split(key, 4)
        return {"pw": he(k[0], (1, 1, ci, co)), "dw": he(k[1], (3, 3, 1, co)),
                "pw_b": bias(k[2], co), "dw_b": bias(k[3], co)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 2 + n_sfb)
        kr = jax.random.split(ks[1], 4)
        p = {"first": bsconv(ks[0], cin, c),
             "recon": {"dw": he(kr[0], (3, 3, 1, c)), "pw": he(kr[1], (1, 1, c, cout)),
                       "dw_b": bias(kr[2], c), "pw_b": bias(kr[3], cout)},
             "sfbs": []}
        for i in range(n_sfb):
            k = jax.random.split(ks[2 + i], 4)
            p["sfbs"].append({"b1": bsconv(k[0], c, c), "b2": bsconv(k[1], c, c),
                              "fuse": he(k[2], (1, 1, c, c)), "fuse_b": bias(k[3], c)})
        return p

    if not model["bias"]:
        raise ValueError("the benchmark's weights carry biases (bias: true)")
    return make(jax.random.fold_in(frames.root_key(seed), 0x5EED))


# -- geometry ----------------------------------------------------------------

def starts(size: int, patch: int, overlap: int) -> np.ndarray:
    if size <= patch:
        return np.zeros(1, np.int64)
    out = list(range(0, size - patch, patch - overlap)) + [size - patch]
    return np.array(sorted(set(out)), np.int64)


def patch_origins(hw, patch: int, overlap: int) -> np.ndarray:
    """(N, 2) LR (y, x) starts in raster order."""
    ys, xs = starts(hw[0], patch, overlap), starts(hw[1], patch, overlap)
    return np.stack(np.meshgrid(ys, xs, indexing="ij"), -1).reshape(-1, 2)


def extract(img: jax.Array, origins: np.ndarray, patch: int) -> jax.Array:
    ar = np.arange(patch)
    rows = origins[:, 0, None, None] + ar[None, :, None]
    cols = origins[:, 1, None, None] + ar[None, None, :]
    return img[rows, cols]


# -- edge score and routing --------------------------------------------------

def edge_scores(patches: jax.Array) -> jax.Array:
    luma = (65.481 * patches[..., 0] + 128.553 * patches[..., 1]
            + 24.966 * patches[..., 2] + 16.0)
    lap = (luma[:, :-2, 1:-1] + luma[:, 2:, 1:-1] + luma[:, 1:-1, :-2]
           + luma[:, 1:-1, 2:] - 4.0 * luma[:, 1:-1, 1:-1])
    return jnp.clip(jnp.abs(lap), 0.0, 255.0).mean(axis=(1, 2))


def route(scores: np.ndarray, t1: float, t2: float) -> np.ndarray:
    return np.where(scores >= t2, work.C54,
                    np.where(scores >= t1, work.C27, work.BILINEAR))


# -- subnets -----------------------------------------------------------------

PRECISIONS = ("highest", "bf16_3x")


def _split(a):
    # reduce_precision, not a round trip through bfloat16, which XLA may
    # drop as excess precision
    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def _pointwise(x, w, b, precision):
    mm = functools.partial(jnp.einsum, "nhwc,cd->nhwd",
                           precision=lax.Precision.HIGHEST)
    w = w[0, 0]
    if precision == "highest":
        return mm(x, w) + b
    if precision != "bf16_3x":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return mm(xh, wh) + (mm(xh, wl) + mm(xl, wh)) + b


def _depthwise(x, w, b):
    h, wd = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    y = sum(xp[:, dy:dy + h, dx:dx + wd, :] * w[dy, dx, 0]
            for dy in range(3) for dx in range(3))
    return y + b


def _bsconv(p, x, cin, cout, precision):
    y = _pointwise(x, p["pw"][:, :, :cin, :cout], p["pw_b"][:cout], precision)
    return _depthwise(y, p["dw"][..., :cout], p["dw_b"][:cout])


def subnet_forward(params, x: jax.Array, model: dict, width: int,
                   precision) -> jax.Array:
    """(n, p, p, 3) LR patches -> (n, p*s, p*s, 3) through one subnet."""
    s = int(model["scale"])
    n, h, w, cin = x.shape
    if width == 0:
        return jax.image.resize(x, (n, h * s, w * s, cin), "bilinear")
    c = width
    f = _bsconv(params["first"], x, cin, c, precision)
    for p in params["sfbs"]:
        y = jax.nn.relu(_bsconv(p["b1"], f, c, c, precision))
        y = jax.nn.relu(_bsconv(p["b2"], y, c, c, precision))
        f = jax.nn.relu(_pointwise(y + f, p["fuse"][:, :, :c, :c],
                                   p["fuse_b"][:c], precision))
    r = params["recon"]
    f = _depthwise(f, r["dw"][..., :c], r["dw_b"][:c])
    up = _pointwise(f, r["pw"][:, :, :c, :], r["pw_b"], precision)
    # pixel shuffle: channel k*s*s + i*s + j -> HR (y*s + i, x*s + j, k)
    up = up.reshape(n, h, w, cin, s, s).transpose(0, 1, 4, 2, 5, 3)
    return up.reshape(n, h * s, w * s, cin)


@functools.partial(jax.jit, static_argnames=("model_key", "width", "patch",
                                             "precision"), donate_argnums=(0,))
def _accumulate(acc, params, patches, origins, *, model_key, width, patch,
                precision):
    """Add one block's HR patches (and a count of 1 per pixel each) into
    ``acc`` = (H*s, W*s, 4); padding rows carry an origin past the frame and
    are dropped."""
    model = dict(model_key)
    s = int(model["scale"])
    hr = subnet_forward(params, patches, model, width, precision)
    ones = jnp.ones(hr.shape[:-1] + (1,), hr.dtype)
    ar = jnp.arange(patch * s)
    rows = origins[:, 0, None, None] * s + ar[None, :, None]
    cols = origins[:, 1, None, None] * s + ar[None, None, :]
    return acc.at[rows, cols].add(jnp.concatenate([hr, ones], -1), mode="drop")


def reference_frame(params, img: jax.Array, model: dict, plan: dict,
                    precision: str = "highest"
                    ) -> Tuple[jax.Array, np.ndarray, np.ndarray]:
    """(H, W, 3) LR frame -> (HR frame, subnet ids, edge scores)."""
    patch, overlap = int(plan["patch"]), int(plan["overlap"])
    s = int(model["scale"])
    h, w = int(img.shape[0]), int(img.shape[1])
    origins = patch_origins((h, w), patch, overlap)
    patches = extract(img, origins, patch)
    scores = np.asarray(jax.jit(edge_scores)(patches))
    ids = route(scores, float(plan["t1"]), float(plan["t2"]))
    model_key = tuple(sorted(model.items()))
    acc = jnp.zeros((h * s, w * s, 4), jnp.float32)
    for k, width in enumerate(work.subnet_widths(model)):
        idx = np.flatnonzero(ids == k)
        for b in range(0, idx.size, BLOCK):
            blk = idx[b:b + BLOCK]
            pad = BLOCK - blk.size
            org = np.concatenate([origins[blk], np.full((pad, 2), h * w)])
            pb = jnp.take(patches, jnp.asarray(np.pad(blk, (0, pad))), axis=0)
            acc = _accumulate(acc, params, pb, jnp.asarray(org),
                              model_key=model_key, width=width, patch=patch,
                              precision=precision)
    out = acc[..., :3] / acc[..., 3:]
    return out, ids, scores
