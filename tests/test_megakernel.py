"""Group-fusion megakernel conformance (`kernels/megakernel.py` +
`ExecutionPlan.fusion`).

Contract under test (docs/api.md "Group fusion"):

  * one Pallas launch per subnet runs the whole layer group (BSConv ->
    n_sfb x SFB -> DSConv, residuals included) with features resident in
    VMEM scratch — fp32 allclose to BOTH the per-op kernel stack and the
    pure-jnp reference, for every width and ragged batch size;
  * the quantized megakernel is BIT-EXACT vs the integer-domain reference
    (same `_q*_math` lattice, codes never leave VMEM between groups);
  * `fusion="group"` threads through the engine unchanged: same routing
    (golden pins), same images (fp32 allclose / quant bit-exact vs
    `fusion="layer"`) across backends, shard counts and tenant streams;
  * empty routing buckets and padded batches are handled at every entry
    (the PR's bugfix satellites: no div-by-zero grids, no pad-row leakage
    through the integer requantize chain);
  * the compiled-executable caches are bounded (`core/caching.BoundedCache`),
    sized from ``plan.stats_window``, and surfaced via
    ``FrameResult.summary()`` / ``SREngine.summary()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionPlan, SREngine
from repro.core.caching import BoundedCache, bounded_cache
from repro.data.synthetic import degrade, random_image
from repro.kernels.dispatch import VMEM_LIMIT_BYTES, pad_batch, resolve_block
from repro.kernels.megakernel import (autotune_block_patches,
                                      autotune_report,
                                      essr_forward_megakernel,
                                      essr_forward_qmegakernel)
from repro.kernels.ops import essr_forward_kernels
from repro.kernels.qconv import essr_forward_qkernels, essr_forward_qref
from repro.models.essr import ESSRConfig, essr_forward, init_essr
from repro.quant.pams import build_quant_pack

CFG = ESSRConfig(scale=2)
TOY = ESSRConfig(scale=2, n_sfb=2, channels=8)

#: Same fixed mixed-content frame + routing pins as
#: tests/test_fused_dispatch.py / test_quant_conformance.py.
GOLDEN_COUNTS = (10, 2, 13)


def _golden_frame(hw: int = 128, seed: int = 1234):
    yy, xx = jnp.meshgrid(jnp.linspace(0, 1, hw), jnp.linspace(0, 1, hw),
                          indexing="ij")
    smooth = jnp.stack([yy, xx, (yy + xx) / 2], axis=-1)
    tex = degrade(jnp.asarray(random_image(seed, 2 * hw, 2 * hw)), 2)
    return jnp.where((yy < 0.5)[..., None], smooth, tex)


def _toy(n: int, seed: int = 0):
    params = init_essr(jax.random.PRNGKey(0), TOY)
    x = jax.random.uniform(jax.random.PRNGKey(seed), (n, 32, 32, 3))
    return params, x


# ---------------------------------------------------------------------------
# kernel level: megakernel vs per-op stack vs jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [4, 8])
@pytest.mark.parametrize("n", [1, 3, 7])
def test_megakernel_matches_reference(width, n):
    params, x = _toy(n)
    got = essr_forward_megakernel(params, x, TOY, width=width, interpret=True)
    want = essr_forward(params, x, TOY, width=width)
    assert got.shape == (n, 64, 64, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n", [1, 5, 9])
def test_megakernel_matches_perop_stack(n):
    """Group fusion rearranges WHERE features live (VMEM scratch vs HBM
    round-trips), never the math: same results as the layer-fused stack."""
    params, x = _toy(n, seed=3)
    got = essr_forward_megakernel(params, x, TOY, interpret=True)
    want = essr_forward_kernels(params, x, TOY, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["int8", "fxp10"])
@pytest.mark.parametrize("width", [4, 8])
@pytest.mark.parametrize("n", [1, 3, 7])
def test_qmegakernel_bitexact_vs_integer_reference(mode, width, n):
    """The quantized megakernel shares the `_q*_math` helpers with the
    reference chain, so its integer arithmetic must be bit-exact — any
    drift means the fused chain left the PAMS lattice."""
    params, x = _toy(n, seed=1)
    pack = build_quant_pack(params, TOY, mode, x)
    got = essr_forward_qmegakernel(params, x, TOY, width=width, pack=pack,
                                   interpret=True)
    want = essr_forward_qref(params, x, TOY, width, pack=pack)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["int8", "fxp10"])
def test_qmegakernel_bitexact_vs_perop_chain(mode):
    params, x = _toy(6, seed=2)
    pack = build_quant_pack(params, TOY, mode, x)
    got = essr_forward_qmegakernel(params, x, TOY, pack=pack, interpret=True)
    want = essr_forward_qkernels(params, x, TOY, pack=pack, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_megakernel_grad_and_jvp():
    """`jax.custom_jvp` keeps the fp32 megakernel trainable in BOTH autodiff
    modes: reverse (grad) and forward (jvp) defer to the pure-JAX twin."""
    params, x = _toy(2)

    def loss(p, v):
        return jnp.sum(essr_forward_megakernel(p, v, TOY, interpret=True) ** 2)

    def loss_ref(p, v):
        return jnp.sum(essr_forward(p, v, TOY) ** 2)

    g = jax.grad(loss)(params, x)
    g_ref = jax.grad(loss_ref)(params, x)
    for got, want in zip(jax.tree_util.tree_leaves(g),
                         jax.tree_util.tree_leaves(g_ref)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=1e-3)
    # the reference model is custom_vjp (reverse-only), so the forward-mode
    # oracle is its reverse-mode directional derivative <grad, dx>
    dx = jnp.ones_like(x) * 0.1
    _, t = jax.jvp(lambda v: loss(params, v), (x,), (dx,))
    t_ref = jnp.sum(jax.grad(loss_ref, argnums=1)(params, x) * dx)
    np.testing.assert_allclose(float(t), float(t_ref), rtol=1e-3)


# ---------------------------------------------------------------------------
# bugfix satellites: empty buckets, padded batches, resolve_block
# ---------------------------------------------------------------------------

def test_empty_bucket_every_fused_entry():
    """An emptied routing bucket (N=0) must return an empty output, not
    divide by zero sizing the grid (the seed's `min(block, 0)` bug)."""
    params, x = _toy(4)
    empty = x[:0]
    pack = build_quant_pack(params, TOY, "int8", x)
    for out in (
        essr_forward_kernels(params, empty, TOY, interpret=True),
        essr_forward_megakernel(params, empty, TOY, interpret=True),
        essr_forward_qkernels(params, empty, TOY, pack=pack, interpret=True),
        essr_forward_qmegakernel(params, empty, TOY, pack=pack,
                                 interpret=True),
    ):
        assert out.shape == (0, 64, 64, 3)


@pytest.mark.parametrize("mode", ["int8", "fxp10"])
@pytest.mark.parametrize("n", [3, 5, 7])
def test_padded_batch_no_pad_row_leakage(mode, n):
    """Prime batch sizes force zero-row padding inside the integer chain;
    those pad rows must not flow through accumulate+requantize into the
    real rows (each launch must equal the unpadded reference bit-for-bit,
    AND equal itself computed one sample at a time)."""
    params, x = _toy(n, seed=4)
    pack = build_quant_pack(params, TOY, mode, x)
    batched = essr_forward_qkernels(params, x, TOY, pack=pack, interpret=True)
    ref = essr_forward_qref(params, x, TOY, TOY.channels, pack=pack)
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(ref))
    solo = jnp.concatenate([
        essr_forward_qkernels(params, x[i:i + 1], TOY, pack=pack,
                              interpret=True) for i in range(n)])
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(solo))


def test_resolve_block_and_pad_batch():
    assert resolve_block(0, 8) == 0                  # empty bucket: no grid
    assert resolve_block(3, 8) == 3                  # never exceeds n
    assert resolve_block(9, 4) == 3                  # minimal-pad block
    assert resolve_block(16, 4) == 4                 # exact fit unchanged
    with pytest.raises(ValueError):
        pad_batch(jnp.zeros((4, 2, 2, 3)), 0)        # degenerate block
    padded, n = pad_batch(jnp.zeros((5, 2, 2, 3)), 4)
    assert padded.shape[0] == 8 and n == 5


# ---------------------------------------------------------------------------
# roofline-driven block autotuner
# ---------------------------------------------------------------------------

def test_autotune_block_bounds():
    rep = autotune_report(54, 32, 4)
    bp = rep["block_patches"]
    assert bp == autotune_block_patches(54, 32, 4)
    # VMEM ceiling: the tiled weights + per-patch working set fit the budget
    assert rep["weight_bytes"] + bp * rep["per_patch_bytes"] \
        <= VMEM_LIMIT_BYTES and rep["fits_vmem"]
    # MXU floor: the flattened (block*p*p, C) operand keeps the rows full
    assert bp * 32 * 32 >= 256
    assert rep["bound"] in ("memory", "compute")
    # narrower subnets fit more patches per block at the same budget
    assert autotune_block_patches(27, 32, 4) >= autotune_block_patches(54, 32, 4)


# ---------------------------------------------------------------------------
# engine level: fusion="group" vs fusion="layer" across the serving matrix
# ---------------------------------------------------------------------------

def _pair(backend, quant, **plan_kw):
    mk = lambda fusion: SREngine.from_config(
        CFG, seed=1, backend=backend,
        plan=ExecutionPlan(quant=quant, fusion=fusion, **plan_kw))
    return mk("layer"), mk("group")


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("quant", [None, "fxp10", "int8"])
def test_engine_group_matches_layer(backend, quant):
    frame = _golden_frame()
    layer, group = _pair(backend, quant)
    rl, rg = layer.upscale(frame), group.upscale(frame)
    np.testing.assert_array_equal(np.asarray(rl.ids), np.asarray(rg.ids))
    if quant is None:
        np.testing.assert_allclose(np.asarray(rl.image), np.asarray(rg.image),
                                   rtol=1e-5, atol=1e-5)
    else:
        # integer serving: both fusion modes walk the same PAMS lattice
        np.testing.assert_array_equal(np.asarray(rl.image),
                                      np.asarray(rg.image))


@pytest.mark.parametrize("shards", [1, 4])
def test_engine_group_matches_layer_sharded(shards):
    """shards > device_count exercises the documented transparent-degrade
    path; with forced host devices it exercises the real patch mesh —
    group fusion must match layer fusion either way."""
    frame = _golden_frame()
    layer, group = _pair("pallas", None, shards=shards)
    rl, rg = layer.serve(frame), group.serve(frame)
    assert rl.counts == rg.counts
    np.testing.assert_allclose(np.asarray(rl.image), np.asarray(rg.image),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("streams", [1, 4])
def test_engine_group_matches_layer_streams(streams):
    frame = _golden_frame()
    frames = [[jnp.roll(frame, 11 * (s + 1), axis=1)] for s in range(streams)]
    outs = {}
    for fusion in ("layer", "group"):
        eng = SREngine.from_config(
            CFG, seed=1, backend="pallas",
            plan=ExecutionPlan(dispatch="fused", streams=streams,
                               quant="int8", fusion=fusion))
        rs = list(eng.serve_streams([list(f) for f in frames]))
        # a single tenant serves on the plain streaming path (stream_id None)
        sids = [0 if r.stream_id is None else r.stream_id for r in rs]
        assert sorted(sids) == list(range(streams))
        outs[fusion] = dict(zip(sids, (np.asarray(r.image) for r in rs)))
    for sid in range(streams):
        np.testing.assert_array_equal(outs["layer"][sid], outs["group"][sid])


def test_golden_routing_pinned_under_group_fusion():
    """Fusion moves execution INSIDE the subnet forward; the edge unit and
    Algorithm-1 thresholds never see it — the golden pins must not move."""
    frame = _golden_frame()
    eng = SREngine.from_config(CFG, seed=1, backend="pallas",
                               plan=ExecutionPlan(fusion="group"))
    r = eng.upscale(frame)
    assert r.counts == GOLDEN_COUNTS, (
        f"group fusion moved routing: {r.counts} != {GOLDEN_COUNTS}")


def test_plan_rejects_unknown_fusion():
    with pytest.raises(ValueError):
        ExecutionPlan(fusion="super")


# ---------------------------------------------------------------------------
# bounded compiled-executable caches
# ---------------------------------------------------------------------------

def test_bounded_cache_lru_semantics():
    calls = []

    @bounded_cache(maxsize=2)
    def f(x):
        calls.append(x)
        return x * 10

    assert f(1) == 10 and f(2) == 20 and f(1) == 10
    assert calls == [1, 2]                       # second f(1) was a hit
    f(3)                                         # evicts 2 (LRU; 1 was touched)
    f(2)
    assert calls == [1, 2, 3, 2]
    info = f.cache_info()
    assert (info.hits, info.maxsize, info.currsize) == (1, 2, 2)
    occ = f.occupancy()
    assert occ["evictions"] == 2 and occ["size"] == 2
    f.cache_clear()
    assert f.occupancy()["size"] == 0


def test_bounded_cache_resize_evicts():
    c = BoundedCache(lambda x: x, maxsize=4)
    for i in range(4):
        c(i)
    c.resize(2)
    occ = c.occupancy()
    assert occ["size"] == 2 and occ["maxsize"] == 2 and occ["evictions"] == 2
    assert c(3) == 3 and c.occupancy()["hits"] == 1    # newest survived
    with pytest.raises(ValueError):
        c.resize(0)
    with pytest.raises(ValueError):
        BoundedCache(lambda: None, maxsize=0)


def test_engine_sizes_caches_from_stats_window_and_surfaces_occupancy():
    from repro.core.pipeline import (compiled_cache_occupancy,
                                     configure_compiled_caches)
    frame = _golden_frame()
    try:
        eng = SREngine.from_config(CFG, seed=1,
                                   plan=ExecutionPlan(stats_window=640))
        occ = compiled_cache_occupancy()
        # max(16, min(512, 640 // 32)) == 20
        assert all(v["maxsize"] == 20 for v in occ.values())
        r = eng.upscale(frame)
        s = r.summary()
        assert {"fused_frame_fn", "fused_stream_frame_fn",
                "get_geometry"} <= set(s["compiled_caches"])
        assert s["compiled_caches"]["get_geometry"]["size"] >= 1
        assert s["mode"] == "edge_select" and s["n_patches"] == r.n_patches
        eng.serve(frame)
        assert "compiled_caches" in eng.summary()
    finally:
        configure_compiled_caches(128)           # restore the default bound
