"""Production mesh definition (assignment-mandated shapes).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import (dryrun.py)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n: int):
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips (pod = DP by default;
    the optional pipeline mode maps stages onto it instead)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU distributed tests (requires >=4 host devices)."""
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


#: Axis name of the 1-D patch-stream mesh (the SR serving data-parallel axis).
PATCH_AXIS = "shard"


def make_patch_mesh(shards: int):
    """1-D ``(shard,)`` mesh over the first ``shards`` devices — the SR patch
    stream's data-parallel axis (each device runs a slice of a frame's routed
    patch buckets; see repro.core.pipeline)."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards > jax.device_count():
        raise ValueError(f"requested {shards} shards but only "
                         f"{jax.device_count()} devices are visible")
    return jax.make_mesh((shards,), (PATCH_AXIS,), **_axis_kw(1))
