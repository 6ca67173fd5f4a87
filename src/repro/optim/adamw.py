"""AdamW with fp32 master weights, global-norm clipping and LR schedules.

Pure-pytree implementation (no optax dependency).  The optimizer state
(master, m, v — all fp32) is what ZeRO-1 shards over the HDP axis
(parallel/zero1.py): grads arrive replicated after the data-parallel psum,
each rank updates only its opt-state shard, and XLA's all-gather of the
updated (bf16-cast) params is exactly ByteScale Fig. 8(a).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    schedule: str = "cosine"          # cosine | linear | constant


def schedule_lr(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 \
            * (1 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1 - cfg.min_lr_frac) * frac
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def init_state(params) -> dict:
    f32 = lambda p: p.astype(jnp.float32)          # noqa: E731
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # noqa: E731
    return {
        "step": jnp.zeros((), jnp.int32),
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def apply_updates(params, grads, state, cfg: AdamWConfig, gnorm=None):
    """Returns (new_params, new_state, metrics).  ``gnorm`` lets a caller
    that already reduced the global grad norm (the fused numerics
    sentinels in train_step.py) pass it in instead of paying the
    reduction tree twice."""
    step = state["step"] + 1
    lr = schedule_lr(cfg, step)
    if gnorm is None:
        gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-12)) \
        if cfg.grad_clip else 1.0

    b1, b2 = cfg.b1, cfg.b2
    bias1 = 1 - b1 ** step.astype(jnp.float32)
    bias2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, master, p):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mh = m / bias1
        vh = v / bias2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * master
        master = master - lr * delta
        return m, v, master, master.astype(p.dtype)

    flat = jax.tree.map(upd, grads, state["m"], state["v"], state["master"],
                        params)
    new_m = jax.tree.map(lambda t: t[0], flat,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[1], flat,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_master = jax.tree.map(lambda t: t[2], flat,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_params = jax.tree.map(lambda t: t[3], flat,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_state = {"step": step, "master": new_master, "m": new_m, "v": new_v}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
