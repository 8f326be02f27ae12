"""The serving path's Pallas kernels compile for a TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a described chip,
so these tests catch what interpret mode accepts and Mosaic refuses:
unaligned slices, more VMEM than a kernel may use, unsupported dtypes. The
topology is described inside a fixture (never at import), and every test
here skips where it cannot be described. Shapes are the real ones: the
C54 supernet of ``ESSR_X4`` on 32x32 LR patches, the megakernels at their
autotuned block (a compile grows by 6-10 s per patch of the block: the
body's vector code is unrolled per patch).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.patching import get_geometry
from repro.kernels.dispatch import VMEM_LIMIT_BYTES, tiled_bytes
from repro.kernels.megakernel import (autotune_report,
                                      essr_forward_megakernel,
                                      essr_forward_qmegakernel)
from repro.kernels.ops import essr_forward_kernels
from repro.models.essr import ESSR_X4, init_essr
from repro.quant.pams import build_quant_pack

PATCH = 32
#: A serving-sized batch: the compiler gives a long grid more VMEM than a
#: short one, so a toy batch would hide an over-budget block.
N_PATCHES = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described device cannot read entries back from a persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _specs(sharding):
    shapes = jax.eval_shape(lambda: init_essr(jax.random.PRNGKey(0), ESSR_X4))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    x = jax.ShapeDtypeStruct((N_PATCHES, PATCH, PATCH, 3), jnp.float32,
                             sharding=sharding)
    return params, x


def _compile_text(fn, sharding) -> str:
    return jax.jit(fn).lower(*_specs(sharding)).compile().as_text()


def test_per_op_stack_compiles_c54(one_chip):
    text = _compile_text(
        lambda p, x: essr_forward_kernels(p, x, ESSR_X4, width=54,
                                          interpret=False), one_chip)
    assert "tpu_custom_call" in text


def test_megakernel_compiles_c54(one_chip):
    text = _compile_text(
        lambda p, x: essr_forward_megakernel(p, x, ESSR_X4, width=54,
                                             interpret=False), one_chip)
    assert "tpu_custom_call" in text


def test_int8_megakernel_compiles_c54(one_chip):
    params = init_essr(jax.random.PRNGKey(0), ESSR_X4)
    cal = jax.random.uniform(jax.random.PRNGKey(1), (4, PATCH, PATCH, 3))
    pack = build_quant_pack(params, ESSR_X4, "int8", cal)
    text = _compile_text(
        lambda p, x: essr_forward_qmegakernel(p, x, ESSR_X4, width=54,
                                              pack=pack, interpret=False),
        one_chip)
    assert "tpu_custom_call" in text


def test_tiled_bytes_pads_lanes_and_sublanes():
    # a 3-channel 32x32 fp32 patch takes a whole 128-lane tile per row
    assert tiled_bytes((PATCH, PATCH, 3), jnp.float32) == PATCH * PATCH * 512
    assert tiled_bytes((PATCH, PATCH, 54), jnp.int8) == PATCH * PATCH * 128
    # int8 sublane tile is 32 rows: 3x3 taps pad to (3, 32, 128)
    assert tiled_bytes((3, 3, 54), jnp.int8) == 3 * 32 * 128
    assert tiled_bytes((1, 54), jnp.float32) == 8 * 128 * 4


@pytest.mark.parametrize("quant", [False, True])
def test_autotuned_block_fits_vmem(quant):
    rep = autotune_report(54, PATCH, ESSR_X4.scale, ESSR_X4.n_sfb,
                          quant=quant)
    bp = rep["block_patches"]
    code = jnp.int8 if quant else jnp.float32
    # per patch at least: input and output double-buffered and one scratch
    # feature, each padded to 128 lanes
    assert rep["per_patch_bytes"] >= (
        2 * tiled_bytes((PATCH, PATCH, 3), jnp.float32)
        + 2 * tiled_bytes((PATCH, PATCH, ESSR_X4.out_channels), code)
        + tiled_bytes((PATCH, PATCH, 54), code))
    assert rep["vmem_budget_bytes"] == VMEM_LIMIT_BYTES
    used = rep["weight_bytes"] + bp * rep["per_patch_bytes"]
    assert used <= VMEM_LIMIT_BYTES and rep["fits_vmem"]
    # the largest block that fits, and one that fills the MXU's rows
    assert used + rep["per_patch_bytes"] > VMEM_LIMIT_BYTES
    assert bp >= rep["mxu_row_floor"]


@pytest.mark.parametrize("h,w,scale", [(1080, 1920, 4), (2160, 3840, 2)])
def test_fuse_compiles_without_scatter_gather_or_kernel(one_chip, h, w,
                                                        scale):
    """At the benchmark cells' frame sizes the overlap-average fuse takes
    the slice fold and compiles for the chip to slices, copies and
    concatenations: no scatter, no gather, no Pallas call."""
    g = get_geometry(h, w, PATCH, 2, scale)
    assert g.fold == "slices"
    ps = PATCH * scale
    x = jax.ShapeDtypeStruct((g.n, ps, ps, 3), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(g.fuse_average).lower(x).compile().as_text()
    for op in (" scatter(", " gather(", "tpu_custom_call"):
        assert op not in text
