"""Pipeline parallelism over the 'pod' axis (GPipe-style, optional mode).

Default multi-pod strategy is pod-as-DP (DESIGN.md §6 — at 2 stages the
GPipe bubble is 1/(m+1) of the step, which napkin-math loses to pure DP for
the assigned shapes unless activations dominate the DCN). This module is the
opt-in alternative for deeper pod counts, demonstrated on reduced configs in
tests/test_pipeline.py.

Mechanics: layers are split into ``n_stages`` contiguous groups; microbatches
stream through stages with lax.scan over (n_micro + n_stages - 1) ticks; the
stage boundary hop is a collective-permute over 'pod'. All stages execute the
same program (SPMD) — stage identity comes from axis_index.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(mesh: Mesh, stage_fn: Callable, params_stacked: Any,
                      x_micro: jax.Array, n_stages: int):
    """x_micro: (n_micro, mb, S, D) microbatched inputs (replicated entering
    the pipe; each stage consumes/produces its slice via permute).

    ``params_stacked``: per-LAYER stacked params; layers are re-grouped as
    (n_stages, layers_per_stage, ...) and each pod shard keeps its stage's
    slice. ``stage_fn(stage_params, x) -> x`` runs the group's layers.
    Returns (n_micro, mb, S, D) outputs (valid on the LAST stage's shard)."""
    n_micro = x_micro.shape[0]
    layers = jax.tree_util.tree_map(
        lambda p: p.reshape((n_stages, p.shape[0] // n_stages) + p.shape[1:]),
        params_stacked)

    def local(stage_params, xs):
        stage_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        stage = lax.axis_index("pod")
        # registers must be marked pod-varying up-front so scan/cond branches
        # agree on the manual-axes type (shard_map vma rules)
        state, outputs, xs = lax.pcast(
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs), xs), ("pod",),
            to="varying")

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (others get the permuted value)
            inject = xs[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(stage == 0, inject, state)
            y = stage_fn(stage_params, cur)
            # last stage records finished microbatch (t - n_stages + 1)
            done_idx = t - (n_stages - 1)
            outputs = lax.cond(
                (stage == n_stages - 1) & (done_idx >= 0),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(done_idx, 0), 0),
                lambda o: o, outputs)
            # hop stage i -> i+1
            nxt = lax.ppermute(y, "pod",
                               [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        (_, outputs), _ = lax.scan(tick, (state, outputs),
                                   jnp.arange(n_micro + n_stages - 1))
        # broadcast final outputs from the last stage to all pods
        outputs = lax.ppermute(
            outputs, "pod",
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)])
        return outputs

    in_specs = (jax.tree_util.tree_map(lambda _: P("pod"), layers),
                P(None, None, None, None))
    # the trailing ppermute broadcast makes every pod hold identical outputs,
    # but the vma type system can't infer that replication -> check off
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(None, None, None, None), check_vma=False)
    return fn(layers, x_micro)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble = (S-1)/(M+S-1) — the napkin number behind pod-as-DP."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
