"""Extra property tests: optimizer invariants + checkpoint idempotence."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.ckpt.checkpoint import CheckpointManager
from repro.train import optimizer as O


@settings(max_examples=15, deadline=None)
@given(st.floats(0.5, 100.0), st.integers(0, 2 ** 31 - 1))
def test_lamb_update_invariant_to_gradient_scale(scale, seed):
    """LAMB's trust ratio makes the update direction+magnitude invariant to a
    uniform gradient rescale (after the adam normalizer) — the property that
    lets the paper train at batch 256 / lr 3e-3."""
    key = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(key, (8, 8)) + 2.0}
    # entries kept >= 0.1 in magnitude: Adam's eps (1e-6) breaks the
    # invariance for entries near 1e-3, which ~3% of seeds draw
    n = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, 8))
    grads = {"w": jnp.sign(n) * (0.1 + jnp.abs(n))}
    opt = O.lamb(0.1)

    def one_update(g):
        st_ = opt.init(params)
        upd, _ = opt.update(g, st_, params)
        return np.asarray(upd["w"])

    u1 = one_update(grads)
    u2 = one_update(jax.tree_util.tree_map(lambda g: g * scale, grads))
    np.testing.assert_allclose(u1, u2, rtol=2e-3, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_adam_step_bounded_by_lr(seed):
    """|adam update| <= ~lr per element (bias-corrected, eps-regularized)."""
    key = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(key, (16,))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(seed + 1), (16,)) * 100}
    opt = O.adam(1e-2)
    upd, _ = opt.update(grads, opt.init(params), params)
    assert float(jnp.abs(upd["w"]).max()) <= 1e-2 * 1.01


def test_checkpoint_save_is_idempotent(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    s = {"w": jnp.arange(6.0).reshape(2, 3)}
    cm.save(3, s)
    cm.save(3, s)                              # overwrite same step
    restored, meta = cm.restore(s)
    assert meta["step"] == 3
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(s["w"]))


def test_checkpoint_restore_specific_step(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=5)
    for step in (1, 2, 3):
        cm.save(step, {"w": jnp.full((2,), float(step))})
    restored, meta = cm.restore({"w": jnp.zeros((2,))}, step=2)
    assert meta["step"] == 2
    np.testing.assert_allclose(np.asarray(restored["w"]), 2.0)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(1, 4))
def test_pipeline_bubble_formula(n_micro, n_stages):
    from repro.distributed.pipeline import bubble_fraction
    b = bubble_fraction(n_micro, n_stages)
    assert 0.0 <= b < 1.0
    assert b == (n_stages - 1) / (n_micro + n_stages - 1)
