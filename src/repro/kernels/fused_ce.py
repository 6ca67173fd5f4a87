"""Pallas fused SoftmaxCrossEntropy (ByteScale §7, Fig. 16).

BF16 logits never materialize in fp32 HBM: vocab panels stream through
VMEM; max / sum-exp / target-logit accumulate online in fp32 scratch.
Forward emits (nll, lse) per token; backward streams the same panels to
produce dlogits = (softmax − onehot)·g without re-reading fp32 logits.

Grid: (T blocks, V blocks), vocab innermost (scratch carries across).  The
vocabulary need not divide into panels: the last panel is ragged, and its
columns past V are masked out of the softmax and never written.  Per-token
values (labels, nll, lse, g) are [T, 1] columns, a layout the TPU tiles.
Final-logit softcapping (Gemma-2) composes: logits are pre-capped by the
caller; the kernel itself is linear in the logits panel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _panel(logits_ref, *, vocab, block_v):
    """The f32 logits panel with columns past the vocabulary at -inf, and
    each column's vocabulary index."""
    lg = logits_ref[...].astype(jnp.float32)            # [Bt, Bv]
    cols = pl.program_id(1) * block_v \
        + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    return jnp.where(cols < vocab, lg, NEG_INF), cols


def _fwd_kernel(logits_ref, labels_ref, nll_ref, lse_ref, m_ref, s_ref,
                t_ref, *, v_blocks, vocab, block_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    lg, cols = _panel(logits_ref, vocab=vocab, block_v=block_v)
    m_prev = m_ref[...]                                 # [Bt, 1]
    m_cur = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
    s_ref[...] = s_ref[...] * jnp.exp(m_prev - m_cur) \
        + jnp.sum(jnp.exp(lg - m_cur), axis=1, keepdims=True)
    m_ref[...] = m_cur
    # the target logit: exactly one panel holds each label's column
    t_ref[...] += jnp.sum(jnp.where(cols == labels_ref[...], lg, 0.0),
                          axis=1, keepdims=True)

    @pl.when(j == v_blocks - 1)
    def _done():
        lse = m_ref[...] + jnp.log(s_ref[...])
        lse_ref[...] = lse
        nll_ref[...] = lse - t_ref[...]


def _bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref, *,
                vocab, block_v):
    lg, cols = _panel(logits_ref, vocab=vocab, block_v=block_v)
    p = jnp.exp(lg - lse_ref[...])
    onehot = (cols == labels_ref[...]).astype(jnp.float32)
    dlogits_ref[...] = ((p - onehot) * g_ref[...]).astype(dlogits_ref.dtype)


def _grid(t, v, block_t, block_v):
    block_t, block_v = min(block_t, t), min(block_v, v)
    assert t % block_t == 0, (t, block_t)
    return block_t, block_v, (t // block_t, pl.cdiv(v, block_v))


def fused_ce_fwd(logits, labels, *, interpret, block_t=256, block_v=2048):
    """logits [T, V], labels [T] int32 -> (nll [T], lse [T]) f32."""
    t, v = logits.shape
    block_t, block_v, grid = _grid(t, v, block_t, block_v)
    tok = pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))
    panel = pl.BlockSpec((block_t, block_v), lambda i, j: (i, j))
    col = jax.ShapeDtypeStruct((t, 1), jnp.float32)
    kernel = functools.partial(_fwd_kernel, v_blocks=grid[1], vocab=v,
                               block_v=block_v)
    with jax.named_scope("fused_ce_fwd"):
        nll, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[panel, tok],
            out_specs=[tok, tok],
            out_shape=[col, col],
            scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)] * 3,
            name="fused_ce_fwd",
            interpret=interpret,
        )(logits, labels[:, None])
    return nll[:, 0], lse[:, 0]


def fused_ce_bwd(logits, labels, lse, g, *, interpret, block_t=256,
                 block_v=2048):
    """-> dlogits [T, V] in the logits dtype."""
    t, v = logits.shape
    block_t, block_v, grid = _grid(t, v, block_t, block_v)
    tok = pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))
    panel = pl.BlockSpec((block_t, block_v), lambda i, j: (i, j))
    with jax.named_scope("fused_ce_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, vocab=v, block_v=block_v),
            grid=grid,
            in_specs=[panel, tok, tok, tok],
            out_specs=panel,
            out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
            name="fused_ce_bwd",
            interpret=interpret,
        )(logits, labels[:, None], lse[:, None], g[:, None])
