"""Pallas TPU flash attention over packed segments (HDP's compute hot-spot).

Layout (ops.py transposes from the model's [T, G, Hg, D]):
    q    [G, Hg, T, Dk]
    k    [G, S, Dk]
    v    [G, S, Dv]
    q_seg/q_pos [T]; k_seg/k_pos [S]  (int32; segment 0 = padding)

The kernel reproduces core/attention.py's masking exactly (segment
equality + causal positions + sliding window + Gemma softcap), computing
online-softmax in fp32 in VMEM scratch.  Forward emits (out, lse) — lse is
stored for the backward kernels (dq, and dkv with inner q-accumulation).

BlockSpecs tile (Bq × Dk) query and (Bk × Dk/Dv) key/value panels into
VMEM; the kv axis is the innermost grid dimension so the (acc, m, l)
scratch carries across kv steps ("arbitrary" dimension semantics).  MXU
alignment: Bq/Bk default 256/512; head dims are already 64/128/256-aligned
for every assigned arch.

TPU tiling: a block's last two dims must be multiples of (8, 128) or span
the array.  So the kernels see query-side metadata and per-row softmax
stats as columns ([T, 1], one value per sublane row) and key-side metadata
as a row ([1, S], one value per lane); the [Bq, Bk] mask is their
broadcast.  The public entry points keep the flat [T] / [G, Hg, T] forms
and reshape at the boundary.  Every dot accumulates in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _col(x):
    """[..., T] -> [..., T, 1]: one value per query row."""
    return x[..., None]


def _row(x):
    """[S] -> [1, S]: one value per key lane."""
    return x[None, :]


def _mask(q_seg, k_seg, q_pos, k_pos, *, causal, window):
    """[Bq, Bk] boolean mask from query columns [Bq, 1] and key rows
    [1, Bk] of per-token metadata."""
    m = (q_seg == k_seg) & (q_seg > 0) & (k_seg > 0)
    if causal:
        m &= k_pos <= q_pos
    if window:
        m &= (q_pos - k_pos) < window
    return m


_NT = (((1,), (1,)), ((), ()))      # contract the last dims: a @ b.T
_TN = (((0,), (0,)), ((), ()))      # contract the first dims: a.T @ b


def _scores(q, k, scale, softcap):
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    if softcap:                                         # [Bq, Bk]
        s = softcap * jnp.tanh(s / softcap)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _online_update(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref,
                   acc_ref, m_ref, l_ref, *, scale, causal, window, softcap):
    """Fold one (Bq × Bk) panel into the running (acc, m, l) scratch."""
    q = q_ref[0, 0]                                     # [Bq, Dk]
    k = k_ref[0]                                        # [Bk, Dk]
    v = v_ref[0]                                        # [Bk, Dv]
    s = _scores(q, k, scale, softcap)
    mask = _mask(qs_ref[...], ks_ref[...], qp_ref[...], kp_ref[...],
                 causal=causal, window=window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # [Bq, 1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_cur


def _fwd_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref,
                out_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, window, softcap, kv_blocks):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _online_update(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref,
                   acc_ref, m_ref, l_ref, scale=scale, causal=causal,
                   window=window, softcap=softcap)

    @pl.when(j == kv_blocks - 1)
    def _done():
        l = l_ref[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        out = acc_ref[...] / safe_l
        out = jnp.where(l > 0, out, 0.0)
        out_ref[0, 0] = out.astype(out_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(safe_l), NEG_INF)
        lse_ref[0, 0] = lse


def _specs(block_q, block_k, dk, dv, *, kv_major=False):
    """BlockSpecs on the (G, Hg, q-block, kv-block) grid — or, with
    ``kv_major``, on (G, kv-block, Hg, q-block), the dkv kernel's grid whose
    accumulators live per KV block."""
    def at(f):
        return (lambda g, j, h, i: f(g, h, i, j)) if kv_major else f
    q_panel = at(lambda g, h, i, j: (g, h, i, 0))
    kv_panel = at(lambda g, h, i, j: (g, j, 0))
    return dict(
        q=pl.BlockSpec((1, 1, block_q, dk), q_panel),
        qv=pl.BlockSpec((1, 1, block_q, dv), q_panel),
        stat=pl.BlockSpec((1, 1, block_q, 1), q_panel),
        k=pl.BlockSpec((1, block_k, dk), kv_panel),
        v=pl.BlockSpec((1, block_k, dv), kv_panel),
        qmeta=pl.BlockSpec((block_q, 1), at(lambda g, h, i, j: (i, 0))),
        kmeta=pl.BlockSpec((1, block_k), at(lambda g, h, i, j: (0, j))),
    )


def _meta(q_seg, k_seg, q_pos, k_pos):
    return _col(q_seg), _row(k_seg), _col(q_pos), _row(k_pos)


def _blocks(t, s, block_q, block_k):
    block_q, block_k = min(block_q, t), min(block_k, s)
    assert t % block_q == 0 and s % block_k == 0, (t, s, block_q, block_k)
    return block_q, block_k


def flash_attention_fwd(q, k, v, q_seg, k_seg, q_pos, k_pos, *, scale,
                        interpret, causal=True, window=0, softcap=0.0,
                        block_q=256, block_k=512):
    """-> (out [G, Hg, T, Dv], lse [G, Hg, T] f32)."""
    g, hg, t, dk = q.shape
    s = k.shape[1]
    dv = v.shape[-1]
    block_q, block_k = _blocks(t, s, block_q, block_k)
    sp = _specs(block_q, block_k, dk, dv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, kv_blocks=s // block_k)
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(g, hg, t // block_q, s // block_k),
            in_specs=[sp["q"], sp["k"], sp["v"], sp["qmeta"], sp["kmeta"],
                      sp["qmeta"], sp["kmeta"]],
            out_specs=[sp["qv"], sp["stat"]],
            out_shape=[
                jax.ShapeDtypeStruct((g, hg, t, dv), q.dtype),
                jax.ShapeDtypeStruct((g, hg, t, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            name="flash_fwd",
            interpret=interpret,
        )(q, k, v, *_meta(q_seg, k_seg, q_pos, k_pos))
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# state-carrying forward (ring steps — kernels/ring_flash.py)
# ---------------------------------------------------------------------------

def _fwd_carry_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref,
                      acc_in_ref, m_in_ref, l_in_ref,
                      acc_out_ref, m_out_ref, l_out_ref,
                      acc_s, m_s, l_s, *,
                      scale, causal, window, softcap, kv_blocks):
    """Ring-step variant of ``_fwd_kernel``: instead of starting from empty
    stats and emitting a normalized output, the online-softmax state
    initializes from carry-in (acc, m, l) refs and the folded state is
    emitted unnormalized — partial stats accumulate across the g visiting
    KV blocks of a ring without a per-step renormalize/merge round-trip.
    Finalization (out = acc/l, lse = m + log l) happens once after the
    last ring step (kernels/ring_flash.py)."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = acc_in_ref[0, 0]
        m_s[...] = m_in_ref[0, 0]
        l_s[...] = l_in_ref[0, 0]

    _online_update(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref,
                   acc_s, m_s, l_s, scale=scale, causal=causal,
                   window=window, softcap=softcap)

    @pl.when(j == kv_blocks - 1)
    def _done():
        acc_out_ref[0, 0] = acc_s[...]
        m_out_ref[0, 0] = m_s[...]
        l_out_ref[0, 0] = l_s[...]


def flash_attention_fwd_carry(q, k, v, q_seg, k_seg, q_pos, k_pos,
                              acc, m, l, *, scale, interpret, causal=True,
                              window=0, softcap=0.0, block_q=256,
                              block_k=512):
    """One ring step: fold one KV block into carried online-softmax state.

    q [G, Hg, T, Dk]; k [G, S, Dk]; v [G, S, Dv];
    acc [G, Hg, T, Dv] f32, m/l [G, Hg, T] f32 (carry-in) -> same (carry-out).
    """
    g, hg, t, dk = q.shape
    s = k.shape[1]
    dv = v.shape[-1]
    block_q, block_k = _blocks(t, s, block_q, block_k)
    sp = _specs(block_q, block_k, dk, dv)
    kernel = functools.partial(
        _fwd_carry_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, kv_blocks=s // block_k)
    with jax.named_scope("flash_fwd_carry"):
        acc, m, l = pl.pallas_call(
            kernel,
            grid=(g, hg, t // block_q, s // block_k),
            in_specs=[sp["q"], sp["k"], sp["v"], sp["qmeta"], sp["kmeta"],
                      sp["qmeta"], sp["kmeta"], sp["qv"], sp["stat"],
                      sp["stat"]],
            out_specs=[sp["qv"], sp["stat"], sp["stat"]],
            out_shape=[
                jax.ShapeDtypeStruct((g, hg, t, dv), jnp.float32),
                jax.ShapeDtypeStruct((g, hg, t, 1), jnp.float32),
                jax.ShapeDtypeStruct((g, hg, t, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            name="flash_fwd_carry",
            interpret=interpret,
        )(q, k, v, *_meta(q_seg, k_seg, q_pos, k_pos), acc, _col(m),
          _col(l))
    return acc, m[..., 0], l[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_panel(q_ref, k_ref, v_ref, qs_ref, ks_ref, qp_ref, kp_ref, out_ref,
               lse_ref, do_ref, *, scale, causal, window, softcap):
    """Recompute one (Bq × Bk) panel in f32: returns (q, k, do, p, ds) with
    p the probabilities and ds the score gradients (softcap chain rule
    included)."""
    q = q_ref[0, 0].astype(jnp.float32)                 # [Bq, Dk]
    k = k_ref[0].astype(jnp.float32)                    # [Bk, Dk]
    v = v_ref[0].astype(jnp.float32)                    # [Bk, Dv]
    do = do_ref[0, 0].astype(jnp.float32)               # [Bq, Dv]
    out = out_ref[0, 0].astype(jnp.float32)
    delta = jnp.sum(do * out, axis=1, keepdims=True)    # [Bq, 1]

    s = jax.lax.dot_general(q, k, _NT) * scale          # [Bq, Bk]
    if softcap:
        t_ = jnp.tanh(s / softcap)
        s = softcap * t_
    mask = _mask(qs_ref[...], ks_ref[...], qp_ref[...], kp_ref[...],
                 causal=causal, window=window)
    p = jnp.exp(jnp.where(mask, s, NEG_INF) - lse_ref[0, 0])
    p = jnp.where(mask, p, 0.0)
    ds = p * (jax.lax.dot_general(do, v, _NT) - delta)
    if softcap:
        ds = ds * (1.0 - t_ * t_)
    return q, k, do, p, ds


def _bwd_dq_kernel(*refs, kv_blocks, **kw):
    dq_ref, acc_ref = refs[10:]
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, k, _, _, ds = _bwd_panel(*refs[:10], **kw)
    acc_ref[...] += jax.lax.dot(ds, k) * kw["scale"]

    @pl.when(j == kv_blocks - 1)
    def _done():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, q_blocks, hg, **kw):
    # grid: (G, kv_blocks, Hg, q_blocks) — dk/dv accumulate over (Hg, i)
    dk_ref, dv_ref, dk_acc, dv_acc = refs[10:]
    h = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when((h == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, _, do, p, ds = _bwd_panel(*refs[:10], **kw)
    dv_acc[...] += jax.lax.dot_general(p, do, _TN)
    dk_acc[...] += jax.lax.dot_general(ds, q, _TN) * kw["scale"]

    @pl.when((h == hg - 1) & (i == q_blocks - 1))
    def _done():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, q_seg, k_seg, q_pos, k_pos, out, lse, do, *,
                        scale, interpret, causal=True, window=0, softcap=0.0,
                        block_q=256, block_k=512):
    """-> (dq, dk, dv) in the dtypes of (q, k, v)."""
    g, hg, t, dk_dim = q.shape
    s = k.shape[1]
    dv_dim = v.shape[-1]
    block_q, block_k = _blocks(t, s, block_q, block_k)
    meta = _meta(q_seg, k_seg, q_pos, k_pos)
    args = (q, k, v, *meta, out, _col(lse), do)
    mask_kw = dict(scale=scale, causal=causal, window=window,
                   softcap=softcap)

    sp = _specs(block_q, block_k, dk_dim, dv_dim)
    with jax.named_scope("flash_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, kv_blocks=s // block_k,
                              **mask_kw),
            grid=(g, hg, t // block_q, s // block_k),
            in_specs=[sp["q"], sp["k"], sp["v"], sp["qmeta"], sp["kmeta"],
                      sp["qmeta"], sp["kmeta"], sp["qv"], sp["stat"],
                      sp["qv"]],
            out_specs=sp["q"],
            out_shape=jax.ShapeDtypeStruct((g, hg, t, dk_dim), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, dk_dim), jnp.float32)],
            name="flash_dq",
            interpret=interpret,
        )(*args)

    sp = _specs(block_q, block_k, dk_dim, dv_dim, kv_major=True)
    with jax.named_scope("flash_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, q_blocks=t // block_q, hg=hg,
                              **mask_kw),
            grid=(g, s // block_k, hg, t // block_q),
            in_specs=[sp["q"], sp["k"], sp["v"], sp["qmeta"], sp["kmeta"],
                      sp["qmeta"], sp["kmeta"], sp["qv"], sp["stat"],
                      sp["qv"]],
            out_specs=[sp["k"], sp["v"]],
            out_shape=[
                jax.ShapeDtypeStruct((g, s, dk_dim), k.dtype),
                jax.ShapeDtypeStruct((g, s, dv_dim), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, dk_dim), jnp.float32),
                pltpu.VMEM((block_k, dv_dim), jnp.float32),
            ],
            name="flash_dkv",
            interpret=interpret,
        )(*args)
    return dq, dk, dv
