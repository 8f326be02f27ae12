"""Explicit shard_map MoE — the §Perf winner over GSPMD's auto-sharded
einsum dispatch (EXPERIMENTS.md §Perf iterations G2/D1).

Why: the einsum formulation leaves GSPMD to choose shardings for the
dispatch scatter and expert contractions; measured on grok-1/deepseek-v3
train_4k it picks TB-scale partial-sum all-reduces (baseline records).
Here every collective is explicit and minimal:

  expert_tp  (E < mesh):  tokens stay local to each (dp x mp) shard; every
      shard computes ALL experts on its own tokens with its F-slice of the
      expert weights (all-gathered over dp — ZeRO-3); one psum over mp
      combines the F-partial outputs.
  ep_alltoall (E >= mp):  experts partitioned over mp; local dispatch
      buffers exchanged with all_to_all, local expert FFN, all_to_all back.

Token routing is per-token, so local-shard routing == global routing;
capacity becomes per-shard (more realistic than a global capacity pool).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import LMConfig
from repro.models.lm.ffn import _act, mlp, moe_capacity


def _local_dispatch(xf, probs, cfg: LMConfig, cap: int):
    """Local tokens (t,d) -> dispatch (E, cap, d), combine weights, slots."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    gate, idx = lax.top_k(probs, k)                       # (t,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), flat_e[:, None], 1)[:, 0] - 1
    valid = pos < cap
    slot = jnp.where(valid, flat_e * cap + pos, e * cap)
    tok = jnp.repeat(jnp.arange(t), k)
    disp = jnp.zeros((e * cap + 1, d), xf.dtype).at[slot].add(
        xf[tok] * valid[:, None])
    return disp[:-1].reshape(e, cap, d), gate, tok, slot, valid


def _combine(y_slots, gate, tok, slot, valid, t, d, dtype):
    y = jnp.concatenate([y_slots.reshape(-1, d),
                         jnp.zeros((1, d), y_slots.dtype)], axis=0)
    w = (gate.reshape(-1) * valid).astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[tok].add(y[slot] * w[:, None])
    return out.astype(dtype)


def moe_forward_shardmap(p: Dict[str, Any], x: jax.Array, cfg: LMConfig,
                         mesh: Mesh, dp, mp: str) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) sharded P(dp, mp, None). Returns (out, aux)."""
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    d = cfg.d_model
    mp_size = mesh.shape[mp]
    ep = cfg.moe_mode == "ep_alltoall" and e % mp_size == 0
    act = _act(cfg.act)

    # weight specs must match distributed.sharding rules
    if ep:
        w_spec = P(mp, dp, None)
        wo_spec = P(mp, None, dp)
    else:
        w_spec = P(None, dp, mp)
        wo_spec = P(None, mp, dp)

    def local(x, router, w_in, w_gate, w_out):
        b_l, s_l, _ = x.shape
        t = b_l * s_l
        xf = x.reshape(t, d)
        probs = jax.nn.softmax(xf.astype(jnp.float32) @ router, axis=-1)
        cap = moe_capacity(t, cfg)
        disp, gate, tok, slot, valid = _local_dispatch(xf, probs, cfg, cap)

        density = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), e), axis=0)
        aux = e * jnp.mean(density * jnp.mean(probs, axis=0))
        aux = lax.pmean(lax.pmean(aux, mp), dp)

        # ZeRO-3: gather the dp-sharded weight dim just-in-time
        w_in_g = lax.all_gather(w_in, dp, axis=1, tiled=True)      # (E?,D,F?)
        w_gate_g = lax.all_gather(w_gate, dp, axis=1, tiled=True)
        w_out_g = lax.all_gather(w_out, dp, axis=2, tiled=True)

        if ep:
            # experts over mp: exchange dispatch so each shard owns its E/mp
            e_l = e // mp_size
            disp = disp.reshape(mp_size, e_l, cap, d)
            recv = lax.all_to_all(disp, mp, split_axis=0, concat_axis=0,
                                  tiled=False)                      # (mp,e_l,cap,d)
            recv = recv.transpose(1, 0, 2, 3).reshape(e_l, mp_size * cap, d)
            h = jnp.einsum("ecd,edf->ecf", recv, w_in_g)
            h = h * act(jnp.einsum("ecd,edf->ecf", recv, w_gate_g))
            y = jnp.einsum("ecf,efd->ecd", h, w_out_g)              # (e_l,mp*cap,d)
            y = y.reshape(e_l, mp_size, cap, d).transpose(1, 0, 2, 3)
            y = lax.all_to_all(y, mp, split_axis=0, concat_axis=0, tiled=False)
            y_slots = y.reshape(e, cap, d)
            out = _combine(y_slots, gate, tok, slot, valid, t, d, x.dtype)
        else:
            # expert-TP: all experts local, F sliced over mp. The combine is
            # LINEAR in the slot outputs, so the F-partial psum commutes with
            # it — combining FIRST shrinks the psum operand from the slot
            # buffer (E*cap, d ~ 2 GB) to the token output (t, d ~ 0.8 GB)
            # (§Perf G4: 2.5x less all-reduce volume, zero math change).
            h = jnp.einsum("ecd,edf->ecf", disp, w_in_g)
            h = h * act(jnp.einsum("ecd,edf->ecf", disp, w_gate_g))
            y_partial = jnp.einsum("ecf,efd->ecd", h, w_out_g)
            out_partial = _combine(y_partial, gate, tok, slot, valid, t, d,
                                   jnp.bfloat16 if x.dtype == jnp.bfloat16 else x.dtype)
            out = lax.psum(out_partial, mp).astype(x.dtype)
        return out.reshape(b_l, s_l, d), aux[None]

    # expert_tp combines F-partials with a psum over mp — that is only sound
    # if every mp shard holds the SAME tokens, so the sequence enters
    # un-SP'd (P(dp, None, None)); the surrounding constraints re-shard.
    # ep_alltoall keeps tokens mp-sharded (each shard dispatches its own).
    x_spec = P(dp, mp, None) if ep else P(dp, None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wo_spec),
        out_specs=(x_spec, P(None)),
        check_vma=False)

    # pad B/S to mesh multiples (e.g. deepseek's MTP shifts S to 4095); the
    # pad tokens route like real ones but their outputs are sliced off.
    b0, s0, _ = x.shape
    import math
    dp_size = math.prod(mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,)))
    s_div = mp_size if ep else 1
    pad_b = (-b0) % dp_size
    pad_s = (-s0) % s_div
    if pad_b or pad_s:
        x = jnp.pad(x, ((0, pad_b), (0, pad_s), (0, 0)))
    out, aux = fn(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    if pad_b or pad_s:
        out = out[:b0, :s0]
        x = x[:b0, :s0]
    if "shared" in p:
        out = out + mlp(p["shared"], x.reshape(-1, d), cfg.act).reshape(b0, s0, d)
    return out, aux[0]
