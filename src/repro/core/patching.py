"""Slim-overlap patch extraction and thick-overlap boundary fusion (Sec. IV-I).

The paper's final choice: LR patches overlap by 2 px ("slim overlap block
convolution"); after x4 upsampling the SR patches overlap by 8 px ("thick
overlap"), and overlapped pixels are averaged ("overlap and average").

Execution model: the hot path is device-resident. Every tiling is a
cartesian grid ``ys x xs``, so its maps are per axis: the LR rows and
columns each patch reads (extraction) and each axis's fold (fusion). They
are computed ONCE per (H, W, patch, overlap, scale) geometry and LRU-cached
(:func:`get_geometry`), so repeated frames of a stream pay zero host-side
setup: extraction is one executable that takes whole rows, then whole
columns, and folds them into patches; fusion is one executable that lays
the patches side by side and folds them along y, then x, by static slices
(the slice fold), or, on a grid where a pixel can lie in three patches
along an axis or a frame under two patches a side, by one flat
scatter-add.
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
from typing import NamedTuple, Optional, Tuple

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


class AxisFold(NamedTuple):
    """One axis of the slice fold: the HR ``starts`` of the patches along
    it, each ``patch`` pixels long, over a plane ``extent`` pixels long in
    which every pixel lies in one or two patches (`_axis_fold`)."""
    starts: Tuple[int, ...]
    patch: int
    extent: int

    def segments(self) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
        """The runs of pixels, in order, over which the same patches lie,
        each ``(length, sources)``: its offsets where the patches lie side
        by side (patch ``k``'s pixel ``p`` at ``k * patch + p``), one, or
        two on a seam."""
        ps = self.patch
        cuts = sorted(set(self.starts) | {v + ps for v in self.starts})
        return tuple(
            (hi - lo, tuple(k * ps + lo - v for k, v in enumerate(self.starts)
                            if v <= lo and hi <= v + ps))
            for lo, hi in zip(cuts[:-1], cuts[1:]))

    def weights(self) -> np.ndarray:
        """1 / the number of patches over each pixel: 1, or 1/2 on a seam."""
        cnt = np.zeros(self.extent, np.float32)
        for v in self.starts:
            cnt[v:v + self.patch] += 1
        return 1 / cnt


def _axis_fold(starts: np.ndarray, patch: int, size: int,
               scale: int) -> Optional[AxisFold]:
    """The slice fold of one axis of LR ``starts`` over ``size`` LR pixels,
    or None where that axis takes the flat scatter: a frame under two
    patches, or a pixel outside every patch or inside three."""
    cover = np.zeros(size, np.int64)
    for start in starts:
        cover[start:start + patch] += 1
    if size < 2 * patch or cover.min() < 1 or cover.max() > 2:
        return None
    return AxisFold(tuple(int(v) * scale for v in starts), patch * scale,
                    size * scale)


def _grid_folds(pos: np.ndarray, patch: int, scale: int,
                plane: Tuple[int, int]
                ) -> Optional[Tuple[AxisFold, AxisFold]]:
    """Both axes' slice folds of a position list over an LR ``plane``, or
    None where the grid needs the flat scatter (see `_axis_fold`; a list
    that is no cartesian grid always does)."""
    if not _is_cartesian(pos):
        return None
    fy = _axis_fold(np.unique(pos[:, 0]), patch, plane[0], scale)
    fx = _axis_fold(np.unique(pos[:, 1]), patch, plane[1], scale)
    return None if fy is None or fx is None else (fy, fx)


@dataclasses.dataclass(frozen=True, eq=False)     # identity eq: fields hold arrays
class PatchGeometry:
    """Device-resident maps for one (H, W, patch, overlap, scale) tiling.

    Built once per geometry by :func:`get_geometry` (LRU-cached; also exposed
    as ``ExecutionPlan.geometry``). ``pos`` is in (possibly padded) LR
    coordinates; ``padded_hw >= hw`` only when the frame is smaller than the
    patch, in which case :meth:`fuse_average` crops back to ``hw * scale``.

    The grid is a cartesian product ``ys x xs``, so extraction takes
    ``n_y*p`` whole LR rows, then ``n_x*p`` whole columns
    (``y_rows``/``x_cols``). Fusion is the slice fold (``folds``) where
    the frame is at least two patches a side and every pixel lies in one
    or two patches along each axis, as in every such grid with ``2 *
    overlap <= patch`` whose clamped last patch overlaps only its
    neighbour; any other grid (``folds`` None) takes the flat scatter of
    `_fusion_maps`.
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
    folds: Optional[Tuple[AxisFold, AxisFold]]   # (y, x); None: the scatter

    @property
    def n(self) -> int:
        return len(self.pos)

    @property
    def fold(self) -> str:
        """How :meth:`fuse_average` folds: ``"slices"`` or ``"scatter"``."""
        return "scatter" if self.folds is None else "slices"

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
        """(N, p*s, p*s, C) -> (H*s, W*s, C): overlap-and-average, one
        executable (the slice fold, else the flat scatter and the crop).

        Traceable like :meth:`extract`: the fused frame graph calls it on a
        traced patch tensor, and the multi-stream graph under ``jax.vmap``."""
        if self.folds is not None:
            return _fuse_slices(sr_patches, grid=self.grid_yx,
                                folds=self.folds)
        (hp, wp), s = self.padded_hw, self.scale
        lin, cnt = _fusion_maps(self.pos.tobytes(), self.n, self.patch, wp,
                                s, hp)
        return _fuse_scatter(sr_patches, lin, cnt, plane=(hp * s, wp * s),
                             hw=(self.hw[0] * s, self.hw[1] * s))


def _fold_rows(u: jax.Array, f: AxisFold) -> jax.Array:
    """Fold the rows of ``u``, where the patches lie one above the other,
    into the plane: each segment (`AxisFold.segments`) is one static slice,
    or on a seam the mean of two, and the segments are laid end to end."""
    parts = []
    for length, sources in f.segments():
        pieces = [u[src:src + length] for src in sources]
        parts.append(pieces[0] if len(pieces) == 1
                     else (pieces[0] + pieces[1]) * 0.5)
    return jnp.concatenate(parts)


def _fold_columns(u: jax.Array, f: AxisFold) -> jax.Array:
    """Fold the columns of ``u``, where the patches lie side by side, into
    the plane by whole patches: the even patches laid at their starts with
    zeros between, the odd ones the same, the two planes added and
    weighted (`AxisFold.weights`). Patches of one parity never overlap
    where no pixel lies in three."""
    h, ps, c = u.shape[0], f.patch, u.shape[-1]
    planes = []
    for parity in (0, 1):
        parts, end = [], 0
        for k in range(parity, len(f.starts), 2):
            if f.starts[k] > end:
                parts.append(jnp.zeros((h, f.starts[k] - end, c), u.dtype))
            parts.append(u[:, k * ps:(k + 1) * ps])
            end = f.starts[k] + ps
        if end < f.extent:
            parts.append(jnp.zeros((h, f.extent - end, c), u.dtype))
        planes.append(jnp.concatenate(parts, axis=1))
    return (planes[0] + planes[1]) * f.weights()[:, None].astype(u.dtype)


@phase_jit("essr_fuse", static_argnames=("grid", "folds"))
def _fuse_slices(sr, *, grid: Tuple[int, int],
                 folds: Tuple[AxisFold, AxisFold]):
    """Overlap-and-average over a neighbour-only cartesian grid: the
    patches laid side by side in one transpose, folded along y by segments
    (`_fold_rows`), then along x by parities (`_fold_columns`). On the TPU
    x is the lane axis of the plane, where a seam's few columns would fill
    a few lanes of each vector: whole patches and one weighted add cost
    less there. A seam's sum has two terms and its weight is a power of
    two, so the result does not depend on the order of the additions."""
    n_y, n_x = grid
    ps, c = sr.shape[1], sr.shape[-1]
    u = sr.reshape(n_y, n_x, ps, ps, c).transpose(0, 2, 1, 3, 4)
    u = _fold_rows(u.reshape(n_y * ps, n_x * ps, c), folds[0])
    return _fold_columns(u, folds[1])


@phase_jit("essr_fuse", static_argnames=("plane", "hw"))
def _fuse_scatter(sr, lin, cnt, *, plane: Tuple[int, int],
                  hw: Tuple[int, int]):
    """Overlap-and-average by one flat scatter-add over ``lin`` (see
    `_fusion_maps`) into an HR ``plane``, cropped to ``hw``."""
    c = sr.shape[-1]
    acc = jnp.zeros((plane[0] * plane[1], c), sr.dtype).at[lin].add(
        sr.reshape(-1, c), mode="drop")
    out = (acc / cnt.astype(sr.dtype)).reshape(plane[0], plane[1], c)
    return out[:hw[0], :hw[1]]


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
    work, paid once per (H, W, patch, overlap, scale). It decides here,
    once, which fold serves the geometry's frames (`_grid_folds`).

    A `core.caching.BoundedCache` (lru semantics, runtime-resizable):
    `SREngine` sizes it together with the compiled-executable caches via
    `core.pipeline.configure_compiled_caches`, and its occupancy rides
    `FrameResult.summary()`."""
    pos, y_rows, x_cols, (hp, wp), (n_y, n_x) = _extract_maps(
        h, w, patch, overlap)
    return PatchGeometry(
        hw=(h, w), padded_hw=(hp, wp), patch=patch, overlap=overlap,
        scale=scale, pos=pos, grid_yx=(n_y, n_x),
        y_rows=y_rows, x_cols=x_cols,
        folds=_grid_folds(pos, patch, scale, (hp, wp)))


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


@functools.lru_cache(maxsize=4)    # HR-sized entries: keep this tiny
def _fusion_maps(pos_bytes: bytes, n: int, patch: int, plane_w: int,
                 scale: int, plane_h: int) -> Tuple[jax.Array, jax.Array]:
    """Scatter map + overlap counts for a grid the slice fold cannot take
    (`_grid_folds`): small frames, three-deep overlaps, arbitrary position
    lists. These are full-plane arrays, so only a few entries are
    retained."""
    pos = np.frombuffer(pos_bytes, dtype=np.int64).reshape(n, 2)
    lin = _index_maps(pos, patch, plane_w, scale)
    cnt = np.zeros(plane_h * scale * plane_w * scale, np.float32)
    np.add.at(cnt, lin, 1.0)
    cnt = np.maximum(cnt, 1.0)           # pixels no patch covers: avoid 0/0
    with jax.ensure_compile_time_eval():  # cached: never a tracer
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
    The fold `PatchGeometry.fuse_average` takes: the slice fold where
    `_grid_folds` allows it, else one flat scatter-add over index maps
    cached per position list.
    """
    pos = np.asarray(pos_lr, dtype=np.int64)
    ph = int(sr_patches.shape[1])
    patch = ph // scale
    # LR canvas must hold every patch; exceeds out_hw only for the
    # reflect-padded sub-patch-size frames (cropped below).
    plane_h = max(-(-out_hw[0] // scale), int(pos[:, 0].max()) + patch)
    plane_w = max(-(-out_hw[1] // scale), int(pos[:, 1].max()) + patch)
    folds = _grid_folds(pos, patch, scale, (plane_h, plane_w))
    if folds is not None:
        grid = (len(np.unique(pos[:, 0])), len(np.unique(pos[:, 1])))
        out = _fuse_slices(sr_patches, grid=grid, folds=folds)
        return out[:out_hw[0], :out_hw[1]]
    lin, cnt = _fusion_maps(pos.tobytes(), len(pos), patch, plane_w, scale,
                            plane_h)
    return _fuse_scatter(sr_patches, lin, cnt,
                         plane=(plane_h * scale, plane_w * scale),
                         hw=tuple(out_hw))


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
