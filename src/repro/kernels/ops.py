"""jit'd wrappers with custom VJPs around the Pallas kernels.

Every entry takes ``interpret``: True runs the kernel bodies through the
Pallas interpreter (the CPU backend has no Mosaic compiler), False compiles
them for the TPU.  Callers derive it with `interpret_on` from the mesh or
sharding the computation is placed on; on a TPU a kernel compiles or the
call fails — nothing falls back to the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, Sharding

from repro.kernels import flash_attention as FA
from repro.kernels import fused_ce as CE


def interpret_on(where) -> bool:
    """True iff every device of ``where`` — a Mesh, a Sharding or a
    sequence of devices — is a CPU device.  Read from the placement
    in hand, not from `jax.default_backend()`: a compile for a described
    TPU topology runs in a process whose default backend is the CPU."""
    if isinstance(where, Mesh):
        devices = where.devices.flat
    elif isinstance(where, Sharding):
        devices = where.device_set
    else:
        devices = where
    return all(d.platform == "cpu" for d in devices)


# ---------------------------------------------------------------------------
# flash attention (local/g=1 path), differentiable
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def flash_attention(q, k, v, q_seg, k_seg, q_pos, k_pos,
                    scale, causal, window, softcap, block_q, block_k,
                    interpret):
    """q [G, Hg, T, Dk], k/v [G, S, D*] -> out [G, Hg, T, Dv]."""
    out, _ = FA.flash_attention_fwd(
        q, k, v, q_seg, k_seg, q_pos, k_pos, scale=scale, causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out


def _fa_fwd(q, k, v, q_seg, k_seg, q_pos, k_pos, scale, causal, window,
            softcap, block_q, block_k, interpret):
    out, lse = FA.flash_attention_fwd(
        q, k, v, q_seg, k_seg, q_pos, k_pos, scale=scale, causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out, (q, k, v, q_seg, k_seg, q_pos, k_pos, out, lse)


def _fa_bwd(scale, causal, window, softcap, block_q, block_k, interpret,
            res, do):
    q, k, v, q_seg, k_seg, q_pos, k_pos, out, lse = res
    dq, dk, dv = FA.flash_attention_bwd(
        q, k, v, q_seg, k_seg, q_pos, k_pos, out, lse, do, scale=scale,
        causal=causal, window=window, softcap=softcap, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return dq, dk, dv, None, None, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# ring-flash attention (sharded CP path), differentiable
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_ring_flash(cfg):
    """Custom-VJP wrapper around the ring-flash engine
    (kernels/ring_flash.py) for one static ring configuration
    (`ring_flash.RingConfig` — hashable, so each distinct composition ×
    head-mode × mask-config builds exactly one differentiable callable,
    mirroring the per-composition executable cache).

    The returned function runs *inside* `core/ring.py`'s shard_map body:
    ``fn(q [C, hpl, D], kv [C, G, Dk(+Dv)], q_seg, k_seg, q_pos, k_pos,
    kgi) -> out [C, hpl, Dv]``.  Forward saves (out, lse) residuals; the
    backward rule runs the reverse ring (per-step dq contributions + dkv
    returned home) instead of differentiating through the Pallas calls.
    """
    from repro.kernels import ring_flash as RF

    @jax.custom_vjp
    def ring_flash(q, kv, q_seg, k_seg, q_pos, k_pos, kgi):
        out, _ = RF.ring_flash_fwd(cfg, q, kv, q_seg, k_seg, q_pos, k_pos,
                                   kgi)
        return out

    def _rf_fwd(q, kv, q_seg, k_seg, q_pos, k_pos, kgi):
        # record=False: under grad both the primal above and this rule
        # trace — only the primal lands the bytes-ledger comm record
        return RF.ring_flash_fwd(cfg, q, kv, q_seg, k_seg, q_pos, k_pos,
                                 kgi, record=False)

    def _rf_bwd(res, do):
        dq, dkv = RF.ring_flash_bwd(cfg, res, do)
        return dq, dkv, None, None, None, None, None

    ring_flash.defvjp(_rf_fwd, _rf_bwd)
    return ring_flash


# ---------------------------------------------------------------------------
# fused softmax cross-entropy, differentiable
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_softmax_xent(logits, labels, interpret):
    """logits [T, V] (bf16/f32), labels [T] int32 -> nll [T] fp32."""
    nll, _ = CE.fused_ce_fwd(logits, labels, interpret=interpret)
    return nll


def _ce_fwd(logits, labels, interpret):
    nll, lse = CE.fused_ce_fwd(logits, labels, interpret=interpret)
    return nll, (logits, labels, lse)


def _ce_bwd(interpret, res, g):
    logits, labels, lse = res
    dlogits = CE.fused_ce_bwd(logits, labels, lse, g.astype(jnp.float32),
                              interpret=interpret)
    return dlogits, None


fused_softmax_xent.defvjp(_ce_fwd, _ce_bwd)
