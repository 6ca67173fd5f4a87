"""Segment-aware blockwise attention (the compute core of HDP dist-attn).

Everything operates on *packed* token buffers: each token carries a
``segment_id`` (0 = padding) and an absolute ``position`` within its own
sequence.  Masking is derived purely from (segment, position), so the same
code handles local attention, zigzag ring blocks, sliding windows and
Gemma-style soft-capping.

Canonical shapes (G = kv groups present locally, Hg = q heads per group):
    q   [T, G, Hg, Dk]
    k   [S, G, Dk]
    v   [S, G, Dv]
returns online-softmax stats:
    acc [T, G, Hg, Dv]   (unnormalized numerator, fp32)
    m   [T, G, Hg]       (running max, fp32)
    l   [T, G, Hg]       (running denominator, fp32)

MLA uses G=1 with the shared latent as k=v; GQA reshapes padded q heads into
[G, Hg].  The jnp implementation is the oracle for the Pallas flash kernel
(kernels/flash_attention.py) and is itself memory-safe via KV chunking.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------

def attention_mask(q_seg, k_seg, q_pos, k_pos, *, causal: bool = True,
                   window: int = 0) -> jnp.ndarray:
    """[T, S] boolean mask. segment 0 is padding and never attends/attended."""
    same_seg = (q_seg[:, None] == k_seg[None, :])
    valid = (q_seg[:, None] > 0) & (k_seg[None, :] > 0)
    mask = same_seg & valid
    if causal:
        mask &= (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_attention_stats(q, k, v, q_seg, k_seg, q_pos, k_pos, *,
                          scale: float, causal: bool = True, window: int = 0,
                          softcap: float = 0.0):
    """Attention stats of one q block against one kv block (no chunking)."""
    s = jnp.einsum("tghd,sgd->gtsh", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale            # [G,T,S,Hg]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = attention_mask(q_seg, k_seg, q_pos, k_pos, causal=causal,
                          window=window)                      # [T,S]
    s = jnp.where(mask[None, :, :, None], s, NEG_INF)
    m = jnp.max(s, axis=2)                                    # [G,T,Hg]
    p = jnp.exp(s - m[:, :, None, :])
    p = jnp.where(mask[None, :, :, None], p, 0.0)             # kill exp(0)=1 rows
    l = jnp.sum(p, axis=2)                                    # [G,T,Hg]
    acc = jnp.einsum("gtsh,sgd->gthd", p, v.astype(jnp.float32))  # [G,T,Hg,Dv]
    # reorder to [T,G,Hg,...]
    return (jnp.transpose(acc, (1, 0, 2, 3)),
            jnp.transpose(m, (1, 0, 2)),
            jnp.transpose(l, (1, 0, 2)))


def merge_stats(a: Tuple, b: Tuple) -> Tuple:
    """Combine two online-softmax partial results."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    wa = jnp.exp(m_a - m)
    wb = jnp.exp(m_b - m)
    acc = acc_a * wa[..., None] + acc_b * wb[..., None]
    l = l_a * wa + l_b * wb
    return acc, m, l


def zero_stats(t: int, g: int, hg: int, dv: int):
    return (jnp.zeros((t, g, hg, dv), jnp.float32),
            jnp.full((t, g, hg), NEG_INF, jnp.float32),
            jnp.zeros((t, g, hg), jnp.float32))


def finalize_stats(acc, m, l, dtype) -> jnp.ndarray:
    """Normalize; fully-masked rows (padding) return zeros."""
    del m
    safe_l = jnp.where(l > 0.0, l, 1.0)
    out = acc / safe_l[..., None]
    out = jnp.where((l > 0.0)[..., None], out, 0.0)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# chunked stats (ring steps merge these across blocks)
# ---------------------------------------------------------------------------

def block_chunked_stats(q, k, v, q_seg, k_seg, q_pos, k_pos, *, scale: float,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, kv_chunk: int = 1024):
    """Online-softmax stats of q against one KV block, chunking the block's
    sequence dim for memory safety."""
    t, g, hg, _ = q.shape
    s_len = k.shape[0]
    dv = v.shape[-1]
    kv_chunk = min(kv_chunk, s_len)
    if s_len % kv_chunk != 0 or s_len == kv_chunk:
        return block_attention_stats(
            q, k, v, q_seg, k_seg, q_pos, k_pos, scale=scale, causal=causal,
            window=window, softcap=softcap)
    n_chunks = s_len // kv_chunk
    k_c = k.reshape(n_chunks, kv_chunk, *k.shape[1:])
    v_c = v.reshape(n_chunks, kv_chunk, *v.shape[1:])
    seg_c = k_seg.reshape(n_chunks, kv_chunk)
    pos_c = k_pos.reshape(n_chunks, kv_chunk)

    def body(carry, xs):
        kc, vc, sc, pc = xs
        stats = block_attention_stats(
            q, kc, vc, q_seg, sc, q_pos, pc, scale=scale, causal=causal,
            window=window, softcap=softcap)
        return merge_stats(carry, stats), None

    (acc, m, l), _ = jax.lax.scan(body, zero_stats(t, g, hg, dv),
                                  (k_c, v_c, seg_c, pos_c))
    return acc, m, l


# ---------------------------------------------------------------------------
# chunked (memory-safe) attention — the pure-jnp reference path
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, q_seg, k_seg, q_pos, k_pos, *, scale: float,
                  causal: bool = True, window: int = 0, softcap: float = 0.0,
                  kv_chunk: int = 1024, out_dtype=None) -> jnp.ndarray:
    """Flash-style chunked attention in pure jnp (lax.scan over KV chunks).

    Memory is O(T·kv_chunk) instead of O(T·S); HLO FLOPs match true
    attention cost, which keeps dry-run rooflines honest.
    """
    t, g, hg, _ = q.shape
    s_len = k.shape[0]
    dv = v.shape[-1]
    out_dtype = out_dtype or q.dtype
    kv_chunk = min(kv_chunk, s_len)
    if s_len % kv_chunk != 0:           # fall back to single block
        acc, m, l = block_attention_stats(
            q, k, v, q_seg, k_seg, q_pos, k_pos, scale=scale, causal=causal,
            window=window, softcap=softcap)
        return finalize_stats(acc, m, l, out_dtype)

    n_chunks = s_len // kv_chunk
    k_c = k.reshape(n_chunks, kv_chunk, *k.shape[1:])
    v_c = v.reshape(n_chunks, kv_chunk, *v.shape[1:])
    seg_c = k_seg.reshape(n_chunks, kv_chunk)
    pos_c = k_pos.reshape(n_chunks, kv_chunk)

    def body(carry, xs):
        kc, vc, sc, pc = xs
        stats = block_attention_stats(
            q, kc, vc, q_seg, sc, q_pos, pc, scale=scale, causal=causal,
            window=window, softcap=softcap)
        return merge_stats(carry, stats), None

    init = zero_stats(t, g, hg, dv)
    (acc, m, l), _ = jax.lax.scan(body, init, (k_c, v_c, seg_c, pos_c))
    return finalize_stats(acc, m, l, out_dtype)


# ---------------------------------------------------------------------------
# dense oracle (tests only — materializes [T,S])
# ---------------------------------------------------------------------------

def attention_dense_oracle(q, k, v, q_seg, k_seg, q_pos, k_pos, *, scale,
                           causal=True, window=0, softcap=0.0):
    s = jnp.einsum("tghd,sgd->gtsh", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = attention_mask(q_seg, k_seg, q_pos, k_pos, causal=causal,
                          window=window)
    s = jnp.where(mask[None, :, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=2)
    p = jnp.where(jnp.isnan(p), 0.0, p)                      # fully masked rows
    out = jnp.einsum("gtsh,sgd->tghd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
