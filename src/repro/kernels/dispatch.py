"""Interpret-vs-compiled policy for the Pallas kernel stack.

Every fused kernel takes ``interpret: Optional[bool]``:

  * ``None``  (default) — auto: compile the Pallas kernel when an accelerator
    backend (TPU/GPU) is present; fall back to the interpreter on CPU, where
    Mosaic cannot compile and interpret mode is the correctness path.
  * ``True`` / ``False`` — explicit override (tests force ``True``; a TPU
    deployment that has validated the kernels may force ``False``).

Resolution happens at trace time (``interpret`` is a static argument), so the
policy costs nothing per call. ``ExecutionPlan.interpret`` carries the same
tri-state through `SREngine`.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

#: Backends whose Pallas lowering is compiled (Mosaic / Triton).
COMPILED_BACKENDS = ("tpu", "gpu")

#: Scoped VMEM one kernel may use, in bytes. A TPU v5e TensorCore has
#: 128 MiB of VMEM (Google Cloud, "TPU v5e" system architecture); the
#: compiler's default scope is 16 MiB of it. Every ``pallas_call`` passes
#: this limit (`compiler_params`) and the megakernel's block autotuner sizes
#: against the same number, so tuner and compiler agree.
VMEM_LIMIT_BYTES = 16 * 2 ** 20

#: Lane width of a TPU vreg: the minor dimension of every VMEM buffer is
#: padded to a multiple of it (layout ``T(8,128)`` for 32-bit types).
LANES = 128


def compiler_params():
    """The Mosaic compiler parameters every kernel of this package uses."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def tiled_bytes(shape, dtype) -> int:
    """VMEM bytes of a buffer of ``shape`` as the TPU lays it out: the minor
    dimension padded to 128 lanes, the second-minor to the dtype's sublane
    tile (8 rows of 32 bits, so 16 for 16-bit and 32 for 8-bit types)."""
    item = np.dtype(dtype).itemsize
    dims = list(shape)
    if dims:
        dims[-1] = -(-dims[-1] // LANES) * LANES
    if len(dims) >= 2:
        sub = 8 * max(1, 4 // item)
        dims[-2] = -(-dims[-2] // sub) * sub
    return int(np.prod(dims, dtype=np.int64)) * item


def default_interpret() -> bool:
    """True when only the interpreter can run Pallas (CPU hosts)."""
    return jax.default_backend() not in COMPILED_BACKENDS


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Tri-state -> concrete bool (None = auto-select, see module docstring)."""
    return default_interpret() if interpret is None else bool(interpret)


def resolve_block(n: int, block_patches: int) -> int:
    """Batch length + requested block -> the grid block actually launched.

    ``min(block_patches, n)`` alone has two failure modes this fixes:

      * ``n == 0`` (an emptied routing bucket) yields block 0, and
        ``pad_batch`` divides by zero — empty batches return 0 here and
        every fused wrapper early-returns an empty output before padding;
      * a remainder batch pads up to a full extra block (``n=9, block=8``
        padded 9 -> 16): the padded rows burn MACs and inflate the static
        cost model. Keeping the same grid step count but shrinking the
        block to ``ceil(n / steps)`` gives the minimal zero-pad
        (9 -> 2 steps of 5, one pad row instead of seven).
    """
    if n <= 0:
        return 0
    blk = min(block_patches, n)
    steps = -(-n // blk)
    return -(-n // steps)


def pad_batch(x: jax.Array, block: int):
    """Pad axis 0 of ``x`` up to a multiple of ``block`` (zeros).

    Returns ``(padded, n)`` where ``n`` is the original length; callers slice
    the kernel output back to ``n``. Replaces the seed's hard
    ``assert n % block == 0`` (a trap for direct callers) and the silent
    ``block -= 1`` walk-down that destroyed throughput for prime batch sizes.
    """
    if block < 1:
        raise ValueError(
            f"pad_batch block must be >= 1, got {block}: empty batches must "
            f"early-return before padding (see resolve_block)")
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, n
