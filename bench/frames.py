"""Synthetic video frames, made on the device at LR size from a seed.

A frame is tiled into square cells of ``cell`` LR pixels (the patch stride,
``patch - overlap``), and each cell shows one content class:

* ``smooth``  — the frame's background alone: a bicubic-interpolated
  coarse colour field (knots every 64 px), whose Laplacian is far below
  the first edge threshold;
* ``texture`` — three summed sinusoids of period 3-6 px over the background,
  sized to a luma-Laplacian RMS of ``TEXTURE_LAP_RMS``: between the
  thresholds;
* ``edges``   — four rectangle outlines and six straight strokes, 1-2 px
  wide, in near-black ink on light ground and near-white on dark, where
  an odd number of them cross: far above the second threshold.

Textures and strokes keep a margin inside their cell, so that a patch,
whose 2-px overlap reaches into its right and lower neighbours, sees only
its own cell's content. Each class's count of cells is fixed by its share
(rounded, the remainder to the largest share); the seed only chooses which
cells get which class and what they show, so every seed asks for the same
work.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CLASSES = ("smooth", "texture", "edges")
LUMA_GAIN = 65.481 + 128.553 + 24.966      # luma per unit of grey, BT.601
KNOT = 64                                  # background knot spacing, px
BG_RANGE = (0.35, 0.65)                    # background values per channel
INK = (0.02, 0.98)                         # ink on light, on dark ground
TEXTURE_LAP_RMS = 28.0                     # luma-Laplacian RMS of a texture
TEXTURE_MARGIN = 2                         # px kept flat at a cell's border
EDGE_MARGIN = 3
N_WAVES, N_RECTS, N_LINES = 3, 4, 6


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def class_counts(shares: Dict[str, float], n_cells: int) -> List[int]:
    """Cells per class, in `CLASSES` order, summing to ``n_cells``."""
    want = [float(shares.get(c, 0.0)) for c in CLASSES]
    total = sum(want)
    if total <= 0:
        raise ValueError(f"shares {shares} name no class of {CLASSES}")
    counts = [int(round(w / total * n_cells)) for w in want]
    counts[int(np.argmax(want))] += n_cells - sum(counts)
    return counts


def _background(key, ncy: int, ncx: int, cell: int) -> jax.Array:
    hp, wp = ncy * cell, ncx * cell
    ky, kx = -(-hp // KNOT) + 3, -(-wp // KNOT) + 3
    knots = jax.random.uniform(key, (ky, kx, 3), minval=BG_RANGE[0],
                               maxval=BG_RANGE[1])
    field = jax.image.resize(knots, (ky * KNOT, kx * KNOT, 3), "cubic")
    field = field[KNOT:KNOT + hp, KNOT:KNOT + wp]
    return jnp.clip(field, *BG_RANGE).reshape(ncy, cell, ncx, cell, 3)


def _texture(key, ncy: int, ncx: int, cell: int, u, v) -> jax.Array:
    """Zero-mean modulation of grey, (ncy, cell, ncx, cell): three waves
    at orientations 60 degrees apart, so that no two can cancel."""
    ks = jax.random.split(key, 3)
    shape = (N_WAVES, ncy, 1, ncx, 1)
    period = jax.random.uniform(ks[0], shape, minval=3.0, maxval=6.0)
    theta = (jax.random.uniform(ks[1], (1, ncy, 1, ncx, 1), maxval=math.pi)
             + jnp.arange(N_WAVES).reshape(-1, 1, 1, 1, 1) * math.pi / N_WAVES)
    phase = jax.random.uniform(ks[2], shape, maxval=2 * math.pi)
    w = 2 * math.pi / period
    wy, wx = w * jnp.sin(theta), w * jnp.cos(theta)
    # the 4-neighbour Laplacian scales sin(wy*u + wx*v) by lam
    lam = 4 * jnp.sin(wy / 2) ** 2 + 4 * jnp.sin(wx / 2) ** 2
    amp = TEXTURE_LAP_RMS / math.sqrt(N_WAVES / 2) / (LUMA_GAIN * lam)
    waves = amp * jnp.sin(wy * u + wx * v + phase)
    inside = ((u >= TEXTURE_MARGIN) & (u < cell - TEXTURE_MARGIN)
              & (v >= TEXTURE_MARGIN) & (v < cell - TEXTURE_MARGIN))
    return jnp.where(inside, waves.sum(axis=0), 0.0)


def _strokes(key, ncy: int, ncx: int, cell: int, u, v) -> jax.Array:
    """Ink mask of rectangle outlines and strokes, (ncy, cell, ncx, cell)."""
    lo, hi = EDGE_MARGIN, cell - EDGE_MARGIN
    span = hi - lo
    ks = jax.random.split(key, 8)
    shape = (N_RECTS, ncy, 1, ncx, 1)
    rh = jax.random.randint(ks[0], shape, 12, span + 1)
    rw = jax.random.randint(ks[1], shape, 12, span + 1)
    y0 = lo + jnp.floor(jax.random.uniform(ks[2], shape) * (span - rh + 1))
    x0 = lo + jnp.floor(jax.random.uniform(ks[3], shape) * (span - rw + 1))
    t = jax.random.randint(ks[4], shape, 1, 3)
    outer = (u >= y0) & (u < y0 + rh) & (v >= x0) & (v < x0 + rw)
    inner = ((u >= y0 + t) & (u < y0 + rh - t)
             & (v >= x0 + t) & (v < x0 + rw - t))
    ink = (outer & ~inner).sum(axis=0)
    lshape = (N_LINES, ncy, 1, ncx, 1)
    py = jax.random.uniform(ks[5], lshape, minval=lo + 4, maxval=hi - 4)
    px = jax.random.uniform(ks[6], lshape, minval=lo + 4, maxval=hi - 4)
    kt, kw = jax.random.split(ks[7])
    theta = jax.random.uniform(kt, lshape, maxval=math.pi)
    half = jax.random.uniform(kw, lshape, minval=0.5, maxval=1.0)
    dist = jnp.abs((v - px) * jnp.sin(theta) - (u - py) * jnp.cos(theta))
    box = (u >= lo) & (u < hi) & (v >= lo) & (v < hi)
    # ink where an odd number of shapes cross: strokes that overlap still
    # leave their edges
    return (ink + ((dist < half) & box).sum(axis=0)) % 2 == 1


def cell_classes(key, hw: Sequence[int], cell: int,
                 counts: Sequence[int]) -> jax.Array:
    """The (ncy, ncx) class index of each cell of the frame of ``key``."""
    ncy, ncx = -(-int(hw[0]) // cell), -(-int(hw[1]) // cell)
    classes = np.repeat(np.arange(len(CLASSES)), counts)
    k_cls = jax.random.split(key, 5)[1]
    return jax.random.permutation(
        k_cls, jnp.asarray(classes, jnp.int32)).reshape(ncy, ncx)


def make_frame(key, hw: Sequence[int], cell: int,
               counts: Sequence[int]) -> jax.Array:
    """One (H, W, 3) float32 frame in [0, 1]."""
    h, w = int(hw[0]), int(hw[1])
    ncy, ncx = -(-h // cell), -(-w // cell)
    k_bg, _, k_tex, k_ink, _ = jax.random.split(key, 5)
    cls = cell_classes(key, hw, cell, counts).reshape(ncy, 1, ncx, 1)
    u = jnp.arange(cell, dtype=jnp.float32).reshape(1, cell, 1, 1)
    v = jnp.arange(cell, dtype=jnp.float32).reshape(1, 1, 1, cell)
    img = _background(k_bg, ncy, ncx, cell)
    tex = _texture(k_tex, ncy, ncx, cell, u, v)
    ink = _strokes(k_ink, ncy, ncx, cell, u, v)
    img = img + jnp.where(cls == 1, tex, 0.0)[..., None]
    opposite = jnp.where(img.mean(axis=-1, keepdims=True) > 0.5, *INK)
    img = jnp.where(((cls == 2) & ink)[..., None], opposite, img)
    img = jnp.clip(img, 0.0, 1.0)
    return img.reshape(ncy * cell, ncx * cell, 3)[:h, :w]


def make_pool(seed: int, hw: Sequence[int], cell: int,
              shares: Dict[str, float], n_frames: int) -> List[jax.Array]:
    """``n_frames`` distinct frames from ``seed``, each in one jitted call."""
    h, w = int(hw[0]), int(hw[1])
    counts = class_counts(shares, (-(-h // cell)) * (-(-w // cell)))
    fn = jax.jit(lambda k: make_frame(k, (h, w), cell, counts))
    keys = jax.random.split(root_key(seed), n_frames)
    return [fn(keys[i]) for i in range(n_frames)]
