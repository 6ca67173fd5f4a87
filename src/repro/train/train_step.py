"""Train / gradient-accumulation steps over HDP waves.

A *wave* is one SPMD micro-batch call: every HDP rank holds exactly C
tokens (packed + padded by the planner), and the wave's ring composition is
a static argument — each distinct composition is one compiled executable
(the TPU analogue of ByteScale's dynamic NCCL groups; see core/ring.py).

Token-level loss (paper Eq. 1–2): every wave divides by the same global
`denom` (total valid tokens in the global batch), so accumulating grads
over heterogeneous waves is bit-equivalent to plain DP.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import resolve_shardings
from repro.configs.base import ModelConfig
from repro.core.loss import token_ce_loss
from repro.models.transformer import forward_hidden, init_params
from repro.optim import adamw
from repro.parallel.sharding import Runtime, params_pspecs
from repro.parallel.zero1 import opt_state_pspecs


def loss_fn(params, cfg: ModelConfig, rt: Runtime, batch):
    hidden = forward_hidden(params, cfg, rt, batch)
    with jax.named_scope("loss"):
        return token_ce_loss(params, cfg, rt, hidden, batch["labels"],
                             batch["seg"], batch["denom"])


def make_train_step(cfg: ModelConfig, rt: Runtime, opt_cfg: adamw.AdamWConfig):
    """Fused single-wave step: grad + optimizer apply (used by the dry-run
    and by single-wave steps)."""

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, rt, batch), has_aux=True)(params)
        params, opt_state, om = adamw.apply_updates(params, grads, opt_state,
                                                    opt_cfg)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step


def make_accum_steps(cfg: ModelConfig, rt: Runtime,
                     opt_cfg: adamw.AdamWConfig, *,
                     numerics: bool = True, guard: bool = False):
    """(grad_step, apply_step) for multi-wave gradient accumulation.

    ``grad_step`` is re-jitted per ring composition (rt.with_composition);
    ``apply_step`` runs once per global batch.

    ``numerics`` fuses the in-graph health sentinels (obs/numerics.py:
    per-group grad/param/update norms + non-finite count) into the apply
    — one extra reduction tree, and the global grad norm it computes is
    fed INTO the optimizer so the step still pays exactly one global-norm
    reduction.  ``guard`` additionally makes the apply a no-op (params
    and opt state selected back to their old values, bit-exactly) when
    any grad element is non-finite; ``om["applied"]`` reports which
    branch won.  With finite grads the guard's ``where`` selects the new
    values, so guarded and unguarded steps are bit-identical.
    """
    from repro.obs import numerics as NU

    def grad_step(params, grad_accum, batch, rt_wave: Runtime):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, rt_wave, batch), has_aux=True)(params)
        grad_accum = jax.tree.map(jnp.add, grad_accum, grads)
        return grad_accum, {"loss": loss, **metrics}

    def apply_step(params, opt_state, grad_accum):
        gnorm = adamw.global_norm(grad_accum) if numerics or guard else None
        new_params, new_opt, om = adamw.apply_updates(
            params, grad_accum, opt_state, opt_cfg, gnorm=gnorm)
        if numerics or guard:
            sent = NU.sentinel_summary(grad_accum, params, new_params)
            ok = (sent["grad_nonfinite"] == 0)
            if guard:
                sel = lambda n, o: jnp.where(ok, n, o)   # noqa: E731
                new_params = jax.tree.map(sel, new_params, params)
                new_opt = jax.tree.map(sel, new_opt, opt_state)
            om = {**om, **sent,
                  "applied": (ok if guard
                              else jnp.ones((), jnp.bool_)).astype(jnp.int32)}
        return new_params, new_opt, om

    return grad_step, apply_step


def named_program(kind: str, composition, step_fn, rt_wave: Runtime):
    """``step_fn(params, grads, batch, rt_wave)`` as a function of
    (params, grads, batch) named ``<kind>_<composition>`` (``wave_1``,
    ``round_2_1_1``): jitted, its XLA module is ``jit_wave_1``, the name
    the compiled HLO and the profiler's trace carry."""
    def program(params, grad_accum, batch):
        return step_fn(params, grad_accum, batch, rt_wave)
    program.__name__ = program.__qualname__ = "_".join(
        [kind] + [str(int(c)) for c in composition])
    return program


# ---------------------------------------------------------------------------
# sharding-annotated jit wrappers (used by the launcher & dry-run)
# ---------------------------------------------------------------------------

def batch_pspecs(cfg: ModelConfig, rt: Runtime, batch_like) -> dict:
    hdp = rt.hdp_axes
    specs = {}
    for k, v in batch_like.items():
        if k == "denom":
            specs[k] = P()
        elif k == "embeds":
            specs[k] = P(hdp, None)
        elif k == "pos" and getattr(v, "ndim", 1) == 2:
            specs[k] = P(hdp, None)
        else:
            specs[k] = P(hdp)
    return specs


def pipeline_batch_pspecs(cfg: ModelConfig, rt: Runtime, batch_like) -> dict:
    """Specs for a stacked round of microbatches [M, ...]: the leading
    microbatch-stream dim is replicated (the pipeline scan consumes it one
    wave per slot); inner dims shard like a single wave's batch."""
    inner = {k: (jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                 if k != "denom" else v)
             for k, v in batch_like.items()}
    specs = batch_pspecs(cfg, rt, inner)
    return {k: (s if k == "denom" else P(None, *s))
            for k, s in specs.items()}


def _step_shardings(cfg: ModelConfig, rt: Runtime, fsdp: bool):
    params_like = jax.eval_shape(
        lambda k: init_params(k, cfg, rt), jax.random.PRNGKey(0))
    pspecs = params_pspecs(params_like, cfg, rt)
    if fsdp:
        from repro.parallel.zero1 import zero1_spec
        pspecs = jax.tree.map(
            lambda s, p: zero1_spec(s, p.shape, rt), pspecs, params_like)
    ospecs = opt_state_pspecs(pspecs, params_like, rt)
    return pspecs, ospecs


def jitted_train_step(cfg: ModelConfig, rt: Runtime,
                      opt_cfg: adamw.AdamWConfig, batch_like, *,
                      fsdp: bool = False, donate: bool = True):
    """jit(train_step) with explicit in/out shardings.  ``batch_like`` may be
    ShapeDtypeStructs (dry-run) or concrete arrays."""
    pspecs, ospecs = _step_shardings(cfg, rt, fsdp)
    bspecs = batch_pspecs(cfg, rt, batch_like)

    step = make_train_step(cfg, rt, opt_cfg)
    # resolve_shardings: bare PartitionSpecs in jit shardings only work on
    # jax >= 0.5 under set_mesh; NamedSharding works on every version
    return jax.jit(
        step,
        in_shardings=resolve_shardings((pspecs, ospecs, bspecs), rt.mesh),
        out_shardings=resolve_shardings((pspecs, ospecs, None), rt.mesh),
        donate_argnums=(0, 1) if donate else ())


def jitted_pipeline_train_step(cfg: ModelConfig, rt: Runtime,
                               opt_cfg: adamw.AdamWConfig, batch_like, *,
                               fsdp: bool = False, donate: bool = True):
    """Pipelined analogue of `jitted_train_step`: one fused round step over
    stacked microbatches [M, ...] on a stage × data × model mesh (stacked
    block params stage-sharded via params_pspecs)."""
    from repro.parallel.pipeline import (assert_pipeline_ready,
                                         make_pipeline_train_step)
    assert_pipeline_ready(cfg, rt)
    pspecs, ospecs = _step_shardings(cfg, rt, fsdp)
    bspecs = pipeline_batch_pspecs(cfg, rt, batch_like)

    step = make_pipeline_train_step(cfg, rt, opt_cfg)
    return jax.jit(
        step,
        in_shardings=resolve_shardings((pspecs, ospecs, bspecs), rt.mesh),
        out_shardings=resolve_shardings((pspecs, ospecs, None), rt.mesh),
        donate_argnums=(0, 1) if donate else ())
