"""Slim-overlap patch extraction and thick-overlap boundary fusion (Sec. IV-I).

The paper's final choice: LR patches overlap by 2 px ("slim overlap block
convolution"); after x4 upsampling the SR patches overlap by 8 px ("thick
overlap"), and overlapped pixels are averaged ("overlap and average").

Execution model: the hot path is device-resident. Every tiling is a
cartesian grid ``ys x xs``, so its index maps are per axis: the LR rows and
columns each patch reads (extraction) and the HR rows and columns each patch
writes, with per-axis overlap counts (fusion). They are computed ONCE per
(H, W, patch, overlap, scale) geometry and LRU-cached (:func:`get_geometry`),
so repeated frames of a stream pay zero host-side setup: extraction is one
executable that takes whole rows, then whole columns, and folds them into
patches; fusion is the mirror image, a row fold and a column fold.
The seed's per-patch ``dynamic_slice`` / ``dynamic_update_slice`` loops are
retained as ``*_loop`` reference oracles (equivalence-tested, and used by the
before/after measurement in benchmarks/table11_throughput.py).

Frames smaller than ``patch`` are reflect-padded up to the patch size (the
fused output is cropped back), instead of the seed's hard ``dynamic_slice``
failure.

Also implements the alternatives of Table III for the boundary benchmark:
  - 'interpolate'  : non-overlapped patches, borders blended by interpolation
  - 'recompute'    : lossless halo recompute (== whole-image convolution)
  - 'overlap_avg'  : the paper's pick
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.caching import bounded_cache
from repro.core.phases import phase_jit


def grid_starts(size: int, patch: int, overlap: int) -> np.ndarray:
    """1-D tiling start offsets with ``overlap`` px shared between neighbours.

    Every pixel is covered; the final patch is clamped to end at the image
    edge (so its overlap with its neighbour may exceed ``overlap``).
    """
    if size <= patch:
        return np.array([0], dtype=np.int64)
    stride = patch - overlap
    starts = list(range(0, size - patch, stride))
    starts.append(size - patch)
    return np.array(sorted(set(starts)), dtype=np.int64)


def shard_slices(n: int, shards: int) -> Tuple[slice, ...]:
    """Partition ``n`` raster-order patches into ``shards`` contiguous slices.

    Balanced like ``np.array_split``: the first ``n % shards`` slices get one
    extra patch, so a frame whose patch count does not divide evenly is still
    covered exactly once. ``shards > n`` yields empty trailing slices (a
    shard with no patches this frame is legal — its switcher simply sees an
    empty score vector)."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    base, extra = divmod(n, shards)
    out, start = [], 0
    for k in range(shards):
        stop = start + base + (1 if k < extra else 0)
        out.append(slice(start, stop))
        start = stop
    return tuple(out)


def _reflect_pad_hw(img: jax.Array, pad_h: int, pad_w: int) -> jax.Array:
    """Reflect-pad the bottom/right of (H,W,C) ``img``; falls back to edge
    padding for the (degenerate) remainder when a dim is shorter than the
    reflection it needs (np/jnp reflect requires pad <= dim-1)."""
    h, w = int(img.shape[0]), int(img.shape[1])
    rh, rw = min(pad_h, max(h - 1, 0)), min(pad_w, max(w - 1, 0))
    if rh or rw:
        img = jnp.pad(img, ((0, rh), (0, rw), (0, 0)), mode="reflect")
    eh, ew = pad_h - rh, pad_w - rw
    if eh or ew:
        img = jnp.pad(img, ((0, eh), (0, ew), (0, 0)), mode="edge")
    return img


@phase_jit("essr_extract", static_argnames=("patch",))
def _extract_grid(img, y_rows, x_cols, *, patch: int):
    """(H,W,C) -> (n_y*n_x, patch, patch, C), patches in raster order.

    The extract phase, one executable: pad a frame smaller than the patch,
    take the grid's rows (``y_rows``, ``n_y*patch`` LR row indices), then
    its columns (``x_cols``), and fold the ``(n_y*p, n_x*p)`` block plane
    into patches. Every index is in bounds by construction, so the takes
    clamp (a no-op) rather than build a fill mask."""
    h, w = int(img.shape[0]), int(img.shape[1])
    hp, wp = max(h, patch), max(w, patch)
    if (hp, wp) != (h, w):
        img = _reflect_pad_hw(img, hp - h, wp - w)
    n_y, n_x = y_rows.shape[0] // patch, x_cols.shape[0] // patch
    c = img.shape[-1]
    t = jnp.take(img, y_rows, axis=0, mode="clip", indices_are_sorted=True)
    t = jnp.take(t, x_cols, axis=1, mode="clip", indices_are_sorted=True)
    t = t.reshape(n_y, patch, n_x, patch, c).transpose(0, 2, 1, 3, 4)
    return t.reshape(n_y * n_x, patch, patch, c)


@dataclasses.dataclass(frozen=True, eq=False)     # identity eq: fields hold arrays
class PatchGeometry:
    """Device-resident index maps for one (H, W, patch, overlap, scale) tiling.

    Built once per geometry by :func:`get_geometry` (LRU-cached; also exposed
    as ``ExecutionPlan.geometry``). ``pos`` is in (possibly padded) LR
    coordinates; ``padded_hw >= hw`` only when the frame is smaller than the
    patch, in which case :meth:`fuse_average` crops back to ``hw * scale``.

    Both directions are *separable*: the grid is a cartesian product
    ``ys x xs``, so extraction takes ``n_y*p`` whole LR rows, then ``n_x*p``
    whole columns (``y_rows``/``x_cols``), and overlap-add runs as one
    row-slice scatter along y and one column scatter along x (``n_y*ps +
    n_x*ps`` fat slices instead of ``N*ps*ps`` scalar rows — the shape XLA
    tiles well on TPU).
    """
    hw: Tuple[int, int]            # original LR frame size
    padded_hw: Tuple[int, int]     # reflect-padded (>= patch) LR size
    patch: int
    overlap: int
    scale: int
    pos: np.ndarray                # (N, 2) LR-space (y, x) patch starts
    grid_yx: Tuple[int, int]       # (n_y, n_x): pos is their cartesian product
    y_rows: jax.Array              # (n_y*p,) LR row index per patch row
    x_cols: jax.Array              # (n_x*p,) LR col index per patch col
    y_idx: jax.Array               # (n_y*ps,) HR row index per patch row
    x_idx: jax.Array               # (n_x*ps,) HR col index per patch col
    # overlap multiplicity factors per axis (>= 1): the cartesian grid makes
    # the per-pixel count their outer product, so the cache holds two O(edge)
    # vectors instead of a full HR-resolution map (~100 KB vs ~133 MB for a
    # 1080p -> x4 geometry)
    y_cnt: jax.Array               # (Hp*s,)
    x_cnt: jax.Array               # (Wp*s,)

    @property
    def n(self) -> int:
        return len(self.pos)

    @property
    def cache_key(self) -> Tuple[int, int, int, int, int]:
        """Hashable identity of this tiling — ``(H, W, patch, overlap,
        scale)`` fully determines every index map. Used by the fused frame
        executable cache and the engine's warm-up bookkeeping (the object
        itself hashes by identity, which only coincides with this key while
        the `get_geometry` LRU retains the instance)."""
        return (*self.hw, self.patch, self.overlap, self.scale)

    def shard_slices(self, shards: int) -> Tuple[slice, ...]:
        """Contiguous raster-strip partition of this geometry's patches —
        the unit of per-shard routing/straggler control (see core.adaptive)."""
        return shard_slices(self.n, shards)

    def extract(self, img: jax.Array) -> jax.Array:
        """(H,W,C) -> (N,patch,patch,C): one executable of whole-row and
        whole-column takes (:func:`_extract_grid`).

        Traceable: safe to call on a traced ``img`` inside an enclosing jit
        (the fused frame graph does) — the index maps close over as
        constants and the reflect-pad path is shape-static."""
        return _extract_grid(img, self.y_rows, self.x_cols, patch=self.patch)

    def fuse_average(self, sr_patches: jax.Array) -> jax.Array:
        """(N, p*s, p*s, C) -> (H*s, W*s, C): separable scatter-add, then a
        precomputed per-pixel overlap division (overlap-and-average).

        Traceable like :meth:`extract`: the fused frame graph calls it on a
        traced patch tensor, inlining the (already jitted) separable fold."""
        hp, wp = self.padded_hw
        s = self.scale
        n_y, n_x = self.grid_yx
        out = _fuse_separable(sr_patches, self.y_idx, self.x_idx,
                              self.y_cnt, self.x_cnt,
                              n_y=n_y, n_x=n_x, ps=self.patch * s,
                              hh=hp * s, wh=wp * s)
        h, w = self.hw
        if (hp, wp) == (h, w):
            return out
        return _fuse_crop(out, hw=(h * s, w * s))


@phase_jit("essr_fuse", static_argnames=("hw",))
def _fuse_crop(out, hw):
    return out[:hw[0], :hw[1]]


@phase_jit("essr_fuse", static_argnames=("n_y", "n_x", "ps", "hh", "wh"))
def _fuse_separable(sr, y_idx, x_idx, y_cnt, x_cnt, *, n_y: int, n_x: int,
                    ps: int, hh: int, wh: int):
    """Overlap-and-average over a cartesian patch grid as two axis folds.

    The per-pixel overlap count is the outer product of the axis counts, so
    averaging is pre-applied as per-row/per-column reciprocal weights on the
    patch tensor — no HR-resolution count map is ever materialized outside
    the jit, and the scatters need no final divide."""
    c = sr.shape[-1]
    wy = jnp.take(1.0 / y_cnt, y_idx).astype(sr.dtype)
    wx = jnp.take(1.0 / x_cnt, x_idx).astype(sr.dtype)
    t = sr.reshape(n_y, n_x, ps, ps, c).transpose(0, 2, 1, 3, 4)
    t = t.reshape(n_y * ps, n_x, ps, c)
    t = t * wy[:, None, None, None] * wx.reshape(n_x, ps)[None, :, :, None]
    acc = jnp.zeros((hh, n_x, ps, c), sr.dtype).at[y_idx].add(
        t, mode="drop")
    return jnp.zeros((hh, wh, c), sr.dtype).at[:, x_idx].add(
        acc.reshape(hh, n_x * ps, c), mode="drop")


def _index_maps(pos: np.ndarray, patch: int, plane_w: int, scale: int
                ) -> np.ndarray:
    """(N,2) starts -> (N*ps*ps,) linear indices into the scaled plane."""
    ps = patch * scale
    ar = np.arange(ps)
    rows = pos[:, 0][:, None] * scale + ar                       # (N, ps)
    cols = pos[:, 1][:, None] * scale + ar                       # (N, ps)
    return (rows[:, :, None] * (plane_w * scale)
            + cols[:, None, :]).reshape(-1)


def _axis_idx(starts: np.ndarray, patch: int, scale: int) -> np.ndarray:
    """1-D starts -> (len(starts)*patch*scale,) scaled output offsets."""
    return (starts[:, None] * scale
            + np.arange(patch * scale)).reshape(-1)


@bounded_cache(maxsize=128)
def get_geometry(h: int, w: int, patch: int = 32, overlap: int = 2,
                 scale: int = 4) -> PatchGeometry:
    """The cached geometry for one frame shape — the hot path's only host
    work, paid once per (H, W, patch, overlap, scale).

    A `core.caching.BoundedCache` (lru semantics, runtime-resizable):
    `SREngine` sizes it together with the compiled-executable caches via
    `core.pipeline.configure_compiled_caches`, and its occupancy rides
    `FrameResult.summary()`."""
    pos, y_rows, x_cols, (hp, wp), (n_y, n_x) = _extract_maps(
        h, w, patch, overlap)
    ys, xs = np.unique(pos[:, 0]), np.unique(pos[:, 1])
    y_idx, x_idx, y_cnt, x_cnt = _cartesian_maps(
        ys.tobytes(), xs.tobytes(), patch, scale, hp, wp)
    return PatchGeometry(
        hw=(h, w), padded_hw=(hp, wp), patch=patch, overlap=overlap,
        scale=scale, pos=pos, grid_yx=(n_y, n_x),
        y_rows=y_rows, x_cols=x_cols,
        y_idx=y_idx, x_idx=x_idx, y_cnt=y_cnt, x_cnt=x_cnt)


@functools.lru_cache(maxsize=128)
def _extract_maps(h: int, w: int, patch: int, overlap: int):
    """Scale-independent LR-side maps: positions, the LR rows and columns
    each patch row/column reads, padded dims and grid shape. Shared by
    `get_geometry` (every scale) and standalone `extract_patches`, so the
    maps exist once per (h, w, patch, overlap)."""
    hp, wp = max(h, patch), max(w, patch)
    ys, xs = grid_starts(hp, patch, overlap), grid_starts(wp, patch, overlap)
    pos = np.array([(y, x) for y in ys for x in xs], dtype=np.int64)
    pos.setflags(write=False)   # cached + shared: a mutating caller would
    return (pos,                # corrupt every later frame
            jnp.asarray(_axis_idx(ys, patch, 1), jnp.int32),
            jnp.asarray(_axis_idx(xs, patch, 1), jnp.int32),
            (hp, wp), (len(ys), len(xs)))


def extract_patches(img: jax.Array, patch: int = 32, overlap: int = 2
                    ) -> Tuple[jax.Array, np.ndarray]:
    """(H,W,C) -> ((N,patch,patch,C), positions (N,2)): the same executable
    as `PatchGeometry.extract`, over the cached scale-independent LR maps."""
    h, w = int(img.shape[0]), int(img.shape[1])
    pos, y_rows, x_cols, _, _ = _extract_maps(h, w, patch, overlap)
    return _extract_grid(img, y_rows, x_cols, patch=patch), pos


def _axis_cnt(starts: np.ndarray, patch: int, scale: int,
              plane: int) -> np.ndarray:
    """Per-output-pixel coverage multiplicity along one axis (>= 1)."""
    cnt = np.zeros(plane * scale, np.float32)
    np.add.at(cnt, _axis_idx(starts, patch, scale), 1.0)
    return np.maximum(cnt, 1.0)          # pixels no patch covers: avoid 0/0


@functools.lru_cache(maxsize=128)
def _cartesian_maps(ys_bytes: bytes, xs_bytes: bytes, patch: int, scale: int,
                    plane_h: int, plane_w: int):
    """Axis index maps + per-axis overlap counts for a cartesian start grid
    (shared by `get_geometry` and the standalone `fuse_patches_average` fast
    path), cached per grid. The 2-D count is the outer product of the axis
    counts, so nothing HR-resolution is ever cached."""
    ys = np.frombuffer(ys_bytes, dtype=np.int64)
    xs = np.frombuffer(xs_bytes, dtype=np.int64)
    return (jnp.asarray(_axis_idx(ys, patch, scale), jnp.int32),
            jnp.asarray(_axis_idx(xs, patch, scale), jnp.int32),
            jnp.asarray(_axis_cnt(ys, patch, scale, plane_h)),
            jnp.asarray(_axis_cnt(xs, patch, scale, plane_w)))


@functools.lru_cache(maxsize=4)    # HR-sized entries: keep this tiny
def _fusion_maps(pos_bytes: bytes, n: int, patch: int, plane_w: int,
                 scale: int, plane_h: int) -> Tuple[jax.Array, jax.Array]:
    """Scatter map + overlap counts for an arbitrary NON-cartesian position
    list — the rare standalone-`fuse_patches_average` fallback. Unlike the
    cartesian maps these are full-plane arrays (the ~133 MB blow-up the
    separable path avoids), so only a few entries are retained."""
    pos = np.frombuffer(pos_bytes, dtype=np.int64).reshape(n, 2)
    lin = _index_maps(pos, patch, plane_w, scale)
    cnt = np.zeros(plane_h * scale * plane_w * scale, np.float32)
    np.add.at(cnt, lin, 1.0)
    cnt = np.maximum(cnt, 1.0)           # pixels no patch covers: avoid 0/0
    return jnp.asarray(lin, jnp.int32), jnp.asarray(cnt[:, None])


def _is_cartesian(pos: np.ndarray) -> bool:
    """True when ``pos`` is the row-major cartesian product of its unique
    y/x starts (every grid produced by ``grid_starts`` is)."""
    ys, xs = np.unique(pos[:, 0]), np.unique(pos[:, 1])
    if len(ys) * len(xs) != len(pos):
        return False
    grid = np.array([(y, x) for y in ys for x in xs], dtype=pos.dtype)
    return bool(np.array_equal(pos, grid))


def fuse_patches_average(sr_patches: jax.Array, pos_lr: np.ndarray, scale: int,
                         out_hw: Tuple[int, int]) -> jax.Array:
    """Overlap-and-average fusion of SR patches (the paper's boundary method).

    sr_patches: (N, p*s, p*s, C); pos_lr: LR-space (y,x); out: (H*s, W*s, C).
    Cartesian-grid positions (the ``grid_starts`` layout) take the separable
    two-fold scatter; arbitrary position lists fall back to one flat
    scatter-add over index maps cached per position list.
    """
    pos = np.asarray(pos_lr, dtype=np.int64)
    ph = int(sr_patches.shape[1])
    patch = ph // scale
    # LR canvas must hold every patch; exceeds out_hw only for the
    # reflect-padded sub-patch-size frames (cropped below).
    plane_h = max(-(-out_hw[0] // scale), int(pos[:, 0].max()) + patch)
    plane_w = max(-(-out_hw[1] // scale), int(pos[:, 1].max()) + patch)
    c = sr_patches.shape[-1]
    if _is_cartesian(pos):
        ys, xs = np.unique(pos[:, 0]), np.unique(pos[:, 1])
        y_idx, x_idx, y_cnt, x_cnt = _cartesian_maps(
            ys.tobytes(), xs.tobytes(), patch, scale, plane_h, plane_w)
        out = _fuse_separable(sr_patches, y_idx, x_idx, y_cnt, x_cnt,
                              n_y=len(ys), n_x=len(xs), ps=ph,
                              hh=plane_h * scale, wh=plane_w * scale)
        return out[:out_hw[0], :out_hw[1]]
    lin, cnt = _fusion_maps(pos.tobytes(), len(pos), patch, plane_w, scale,
                            plane_h)
    acc = jnp.zeros((plane_h * scale * plane_w * scale, c), sr_patches.dtype)
    acc = acc.at[lin].add(sr_patches.reshape(-1, c), mode="drop")
    out = (acc / cnt.astype(sr_patches.dtype)
           ).reshape(plane_h * scale, plane_w * scale, c)
    return out[:out_hw[0], :out_hw[1]]


def fuse_patches_crop(sr_patches: jax.Array, pos_lr: np.ndarray, scale: int,
                      out_hw: Tuple[int, int]) -> jax.Array:
    """'Interpolation-free' naive fusion: later patches simply overwrite.

    Used as the cheap baseline ('Interpol.' row of Table III behaves like a
    non-overlap + border-fixup scheme; overwrite is its zero-cost floor).
    Kept as a loop: XLA scatter does not guarantee last-write-wins on
    duplicate indices, and this baseline is not on the hot path.
    """
    out = jnp.zeros((out_hw[0], out_hw[1], sr_patches.shape[-1]), sr_patches.dtype)
    for i, (y, x) in enumerate(pos_lr):
        yy, xx = int(y) * scale, int(x) * scale
        out = jax.lax.dynamic_update_slice(out, sr_patches[i], (yy, xx, 0))
    return out


# ---------------------------------------------------------------------------
# seed loop implementations — kept as reference oracles (equivalence tests +
# the before/after host-loop-removal benchmark); NOT on the serving path
# ---------------------------------------------------------------------------

def extract_patches_loop(img: jax.Array, patch: int = 32, overlap: int = 2
                         ) -> Tuple[jax.Array, np.ndarray]:
    """Seed implementation: one traced ``dynamic_slice`` per patch."""
    h, w = int(img.shape[0]), int(img.shape[1])
    ys, xs = grid_starts(h, patch, overlap), grid_starts(w, patch, overlap)
    pos = np.array([(y, x) for y in ys for x in xs], dtype=np.int64)
    patches = jnp.stack([
        jax.lax.dynamic_slice(img, (int(y), int(x), 0), (patch, patch, img.shape[2]))
        for y, x in pos])
    return patches, pos


def fuse_patches_average_loop(sr_patches: jax.Array, pos_lr: np.ndarray,
                              scale: int, out_hw: Tuple[int, int]) -> jax.Array:
    """Seed implementation: two ``dynamic_update_slice`` per patch."""
    ph = sr_patches.shape[1]
    out = jnp.zeros((out_hw[0], out_hw[1], sr_patches.shape[-1]), sr_patches.dtype)
    cnt = jnp.zeros((out_hw[0], out_hw[1], 1), sr_patches.dtype)
    ones = jnp.ones((ph, ph, 1), sr_patches.dtype)
    for i, (y, x) in enumerate(pos_lr):
        yy, xx = int(y) * scale, int(x) * scale
        out = jax.lax.dynamic_update_slice(
            out, jax.lax.dynamic_slice(out, (yy, xx, 0), (ph, ph, out.shape[2]))
            + sr_patches[i], (yy, xx, 0))
        cnt = jax.lax.dynamic_update_slice(
            cnt, jax.lax.dynamic_slice(cnt, (yy, xx, 0), (ph, ph, 1)) + ones,
            (yy, xx, 0))
    return out / cnt


# ---------------------------------------------------------------------------
# cost accounting for the boundary benchmark (Tables III / IV)
# ---------------------------------------------------------------------------

def overlap_mac_overhead(patch: int, overlap: int) -> float:
    """MAC multiplier of slim-overlap tiling vs non-overlapped (Table IV)."""
    stride = patch - overlap
    return (patch / stride) ** 2


def boundary_sram_bytes(lr_w: int, overlap_lr: int, channels: int,
                        bytes_per: float = 1.25) -> float:
    """Boundary buffer estimate: one horizontal stripe of halo rows spanning
    the LR frame width across feature channels (FXP10 => 1.25 B)."""
    return lr_w * max(overlap_lr, 1) * channels * bytes_per * 2  # top+left stripes
