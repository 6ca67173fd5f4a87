"""Manual expert-parallel MoE under shard_map (beyond-paper optimization).

The pjit "gather" path (models/moe.py) lets XLA partition the capacity
buffers, which on the dry-run meshes materializes replicated scatter
operands and per-layer all-reduces of the full [E, Cap, d] buffer —
~218 GB/device/wave of AR traffic for qwen3-moe (EXPERIMENTS.md §Perf).

Here each (hdp, model)-rank routes its LOCAL C tokens, builds capacity
buffers only for its E/tp LOCAL experts, runs the local expert GEMMs, and
contributes its partial combine through one [C, d] psum — the same
collective the dense FFN already pays.  Traffic per layer drops from
O(E·Cap·d) to O(C·d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import moe_capacity


def moe_forward_manual(params: dict, cfg: ModelConfig, rt, x):
    """x [T, d] (pjit-level, T sharded over HDP) -> [T, d]."""
    spec = cfg.moe
    model = rt.model_axis
    tp = rt.tp
    assert spec.num_experts % max(tp, 1) == 0, "EP needs E % tp == 0"
    e_local = spec.num_experts // max(tp, 1)
    act = L.act_fn(cfg.act)

    def local(x_, p_):
        t = x_.shape[0]
        e, k = spec.num_experts, spec.top_k
        cap = moe_capacity(spec, t)
        m_idx = jax.lax.axis_index(model) if model and tp > 1 else 0
        e_lo = m_idx * e_local

        logits = x_.astype(jnp.float32) @ p_["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        if spec.router_norm_topk:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
        flat_oh = onehot.reshape(t * k, e)
        pos_in_e = jnp.cumsum(flat_oh, axis=0) - flat_oh
        pos = jnp.sum(pos_in_e * flat_oh, axis=-1)
        flat_idx = idx.reshape(t * k)
        local_e = flat_idx - e_lo                       # index among my experts
        mine = (local_e >= 0) & (local_e < e_local) & (pos < cap)
        slot = jnp.where(mine, local_e * cap + pos, e_local * cap)

        xk = jnp.repeat(x_, k, axis=0)
        buf = jnp.zeros((e_local * cap + 1, x_.shape[1]), x_.dtype) \
            .at[slot].add(xk)
        buf = buf[: e_local * cap].reshape(e_local, cap, -1)

        h = jnp.einsum("ecd,edf->ecf", buf, p_["w_in"])
        if cfg.gated_mlp:
            h = act(jnp.einsum("ecd,edf->ecf", buf, p_["w_gate"])) * h
        else:
            h = act(h)
        out_buf = jnp.einsum("ecf,efd->ecd", h, p_["w_out"])

        out_flat = out_buf.reshape(e_local * cap, -1)
        y_pairs = jnp.take(out_flat, jnp.minimum(slot, e_local * cap - 1),
                           axis=0)
        y_pairs = jnp.where(mine[:, None], y_pairs, 0.0)
        w = gates.reshape(t * k).astype(x_.dtype)
        y = jnp.sum((y_pairs * w[:, None]).reshape(t, k, -1), axis=1)

        if spec.num_shared:
            h_s = x_ @ p_["shared_in"]
            if cfg.gated_mlp:
                h_s = act(x_ @ p_["shared_gate"]) * h_s
            else:
                h_s = act(h_s)
            y = y + h_s @ p_["shared_out"]              # col/row-split shards

        if model and tp > 1:
            y = jax.lax.psum(y, model)
        return y.astype(x_.dtype)

    pspecs = {
        "router": P(),
        "w_in": P(model, None, None), "w_out": P(model, None, None),
    }
    if cfg.gated_mlp:
        pspecs["w_gate"] = P(model, None, None)
    if spec.num_shared:
        pspecs["shared_in"] = P(None, model)
        pspecs["shared_out"] = P(model, None)
        if cfg.gated_mlp:
            pspecs["shared_gate"] = P(None, model)
    fn = shard_map(
        local, mesh=rt.mesh,
        in_specs=(P(rt.hdp_axes, None), pspecs),
        out_specs=P(rt.hdp_axes, None),
        check_vma=False)
    return fn(x, params)
