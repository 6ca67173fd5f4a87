"""Shared neural-net layers: norms, rotary embeddings, activations, GQA layout.

All functions are pure; parameters are plain pytrees (nested dicts of
jnp arrays).  Initializers take an explicit PRNG key.  Computation runs in
``cfg.dtype`` (bf16 by default) with fp32 norm/softmax internals.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def activation_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def act_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> dict:
    return {"scale": jnp.zeros((d,), jnp.float32)}          # (1 + scale) form


def rmsnorm(params: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"])).astype(dtype)


def qk_head_norm(scale: jnp.ndarray, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Per-head RMS norm over head_dim (Gemma-3 / Qwen-3)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE + sinusoidal)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., T, H, D]; positions: [..., T] int32 (absolute positions)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                             # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, D/2]
    cos = jnp.cos(angles)[..., None, :]                      # [..., T, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    positions3: [..., T, 3] (t, h, w) position ids.  The D/2 frequency slots
    are split into ``sections`` (summing to D/2); each section rotates with
    its own position component.
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(d, theta)                             # [D/2]
    # section id per frequency slot -> which position component drives it
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=half)
    pos = jnp.take(positions3.astype(jnp.float32), sec_ids, axis=-1)  # [..., T, D/2]
    angles = pos * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """[..., T] -> [..., T, d_model] classic transformer sinusoids."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def positional_rotate(cfg: ModelConfig, q, k, q_pos, k_pos):
    """Apply the config's positional scheme to q/k ([..., T, H, D])."""
    if cfg.pos_embed == "rope":
        return (apply_rope(q, q_pos, cfg.rope_theta),
                apply_rope(k, k_pos, cfg.rope_theta))
    if cfg.pos_embed == "mrope":
        return (apply_mrope(q, q_pos, cfg.rope_theta, cfg.mrope_sections),
                apply_mrope(k, k_pos, cfg.rope_theta, cfg.mrope_sections))
    return q, k                                              # none / sinusoidal


def scalar_positions(cfg: ModelConfig, positions: jnp.ndarray) -> jnp.ndarray:
    """Collapse M-RoPE [T,3] ids to the scalar causal position (t component)."""
    if cfg.pos_embed == "mrope" and positions.ndim >= 2 and positions.shape[-1] == 3:
        return positions[..., 0]
    return positions


# ---------------------------------------------------------------------------
# GQA head layout under tensor parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GQALayout:
    """How (num_heads, num_kv_heads) map onto a TP axis of size `tp`.

    * q heads are padded to ``h_pad`` so that ``h_pad % tp == 0`` and every
      padded group has ``hpg_pad`` heads (group boundaries never cross ranks
      in the sharded-KV case).
    * KV heads are sharded over TP iff ``kv_heads % tp == 0``; otherwise the
      KV projections are replicated (Megatron KV-duplication).
    * padded q-head outputs are masked to zero before the output projection,
      so the architecture is bit-faithful to the unpadded model.
    """
    num_heads: int
    num_kv_heads: int
    tp: int
    hpg_pad: int          # padded q-heads per kv group
    h_pad: int            # padded total q heads
    kv_sharded: bool

    @property
    def pad_heads(self) -> int:
        return self.h_pad - self.num_heads

    def head_mask(self) -> jnp.ndarray:
        """[h_pad] 1.0 for real heads (in padded-group-major order)."""
        hpg = -(-self.num_heads // self.num_kv_heads)
        idx = jnp.arange(self.h_pad)
        within = idx % self.hpg_pad
        return (within < hpg).astype(jnp.float32) if self.hpg_pad != hpg else \
            jnp.ones((self.h_pad,), jnp.float32)

    def group_of_head(self) -> jnp.ndarray:
        """[h_pad] kv-group index of each padded q head."""
        return jnp.arange(self.h_pad) // self.hpg_pad


def gqa_layout(num_heads: int, num_kv_heads: int, tp: int) -> GQALayout:
    hpg = -(-num_heads // num_kv_heads)                      # ceil heads/group
    hpg_pad = hpg
    while (num_kv_heads * hpg_pad) % tp != 0:
        hpg_pad += 1
    return GQALayout(
        num_heads=num_heads, num_kv_heads=num_kv_heads, tp=tp,
        hpg_pad=hpg_pad, h_pad=num_kv_heads * hpg_pad,
        kv_sharded=(num_kv_heads % tp == 0))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float = 1.0) -> jnp.ndarray:
    std = scale / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * std).astype(dtype)


EMBED_INIT_STD = 0.02


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32)
            * EMBED_INIT_STD).astype(dtype)
