"""Token-level vocab-parallel cross-entropy (ByteScale §5.1 + §7).

Token-level loss: every token in the *global batch* contributes 1/denom,
where denom = total valid tokens across all micro-batches of the step.
This is what makes HDP's heterogeneous gradient accumulation bit-equivalent
to plain DP (paper Eq. 1–2): the trainer passes the same global `denom`
into every micro-batch's loss.

The reference path computes the log-sum-exp in fp32 over vocab-sharded
bf16 logits (Megatron VocabParallel style — XLA inserts the cross-model
max/sum all-reduces).  The fused Pallas kernel (kernels/fused_ce.py)
replaces the per-shard inner loop on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.transformer import logits_head
from repro.parallel.sharding import Runtime


def token_ce_from_logits(logits, labels, valid, denom, *, impl: str = "ref",
                         rt: Optional[Runtime] = None):
    """logits [T, V] (any float dtype), labels [T] int32, valid [T] bool.
    ``impl="pallas"`` runs the fused kernel on ``rt``'s mesh: a Mosaic
    kernel is not partitioned automatically, so each HDP rank runs it on
    its own tokens inside a shard_map (the vocabulary is whole per rank).

    Returns (loss, metrics).  loss = Σ_valid nll / denom.
    """
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops
        interpret = kernel_ops.interpret_on(rt.mesh)
        tok = P(rt.hdp_axes)
        nll = shard_map(
            lambda lg, lb: kernel_ops.fused_softmax_xent(lg, lb, interpret),
            mesh=rt.mesh, in_specs=(P(rt.hdp_axes, None), tok),
            out_specs=tok, check_vma=False)(logits, labels)
    else:
        lg = logits.astype(jnp.float32)
        m = jnp.max(lg, axis=-1, keepdims=True)
        lse = m + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1, keepdims=True))
        tgt = jnp.take_along_axis(lg, labels[:, None].astype(jnp.int32),
                                  axis=-1)
        nll = (lse - tgt)[:, 0]
    nll = jnp.where(valid, nll, 0.0)
    nll_sum = jnp.sum(nll)
    n_tok = jnp.sum(valid.astype(jnp.float32))
    return nll_sum / denom, {"nll_sum": nll_sum, "tokens": n_tok}


def token_ce_loss(params, cfg: ModelConfig, rt: Runtime, hidden, labels, seg,
                  denom):
    logits = logits_head(params, cfg, hidden)
    return token_ce_from_logits(logits, labels, seg > 0, denom,
                                impl="pallas" if rt.attn_impl == "pallas"
                                else "ref", rt=rt)
