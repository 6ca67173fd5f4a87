"""Generic decoder LM over packed token buffers, built from ModelConfig.

Layout: activations are flat packed buffers [T, d] with T sharded over the
HDP axis; every token carries (segment_id, position).  Layers are grouped
into pattern *periods* (e.g. Gemma-2 "lg", Jamba "mmmmgmmm") and scanned
with ``lax.scan`` over stacked per-period parameters — one period of HLO
regardless of depth, which keeps 512-device dry-run compiles tractable.

Mixer dispatch per layer code: 'g'/'l' (ring) attention — or MLA when
cfg.mla is set; 'm' Mamba; 'r' RWKV-6.  FFN per layer: dense MLP, MoE, or
RWKV channel-mix.  SSM mixers run inside shard_map over the HDP axes
(sequential chunk scans cannot be auto-partitioned over tokens) with the
model axis left in auto mode so XLA still shards heads/channels.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.compat import offload_policy
from repro.configs.base import ModelConfig
from repro.core import ring as R
from repro.obs import ledger
from repro.models import layers as L
from repro.models import mamba as MB
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rwkv6 as RW
from repro.parallel.sharding import Runtime


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, layout, dtype) -> dict:
    if cfg.mla is not None:
        return MLA.mla_init(key, cfg, dtype)
    d = cfg.d_model
    dk = cfg.resolved_head_dim
    g = cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "w_q": L.dense_init(ks[0], d, layout.h_pad * dk, dtype),
        "w_kv": (jax.random.normal(ks[1], (d, 2, g, dk), jnp.float32)
                 / math.sqrt(d)).astype(dtype),
        "w_o": L.dense_init(ks[2], layout.h_pad * dk, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dk,), jnp.float32)
        p["k_norm"] = jnp.zeros((dk,), jnp.float32)
    return p


def _mlp_init(key, cfg: ModelConfig, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_in": L.dense_init(ks[0], cfg.d_model, d_ff, dtype),
         "w_out": L.dense_init(ks[1], d_ff, cfg.d_model, dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = L.dense_init(ks[2], cfg.d_model, d_ff, dtype)
    return p


def _block_init(key, cfg: ModelConfig, layer_idx: int, layout, dtype) -> dict:
    code = cfg.layer_code(layer_idx)
    ks = jax.random.split(key, 4)
    p = {"norm1": L.rmsnorm_init(cfg.d_model),
         "norm2": L.rmsnorm_init(cfg.d_model)}
    if code in ("g", "l"):
        p["attn"] = _attn_init(ks[0], cfg, layout, dtype)
    elif code == "m":
        p["mamba"] = MB.mamba_init(ks[0], cfg, dtype)
    elif code == "r":
        p["time_mix"] = RW.rwkv_init(ks[0], cfg, dtype)
    else:
        raise ValueError(code)

    if code == "r":
        p["channel_mix"] = RW.channel_mix_init(ks[1], cfg, dtype)
    elif cfg.is_moe_layer(layer_idx):
        p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
    else:
        d_ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.dense_d_ff:
            d_ff = cfg.moe.dense_d_ff
        p["mlp"] = _mlp_init(ks[1], cfg, d_ff, dtype)

    if cfg.post_block_norm:
        p["postnorm1"] = L.rmsnorm_init(cfg.d_model)
        p["postnorm2"] = L.rmsnorm_init(cfg.d_model)
    return p


def head_layer_count(cfg: ModelConfig) -> int:
    """Leading layers kept outside the period scan (DeepSeek dense head)."""
    return cfg.moe.first_k_dense if cfg.moe is not None else 0


def init_params(key, cfg: ModelConfig, rt: Runtime) -> dict:
    dtype = L.activation_dtype(cfg)
    layout = rt.layout(cfg)
    period = len(cfg.layer_pattern)
    head_n = head_layer_count(cfg)
    scan_layers = cfg.num_layers - head_n
    assert scan_layers % period == 0, (cfg.name, scan_layers, period)
    n_periods = scan_layers // period

    keys = jax.random.split(key, cfg.num_layers + 3)
    params: dict = {}
    if cfg.frontend == "none":
        params["embed"] = L.embed_init(keys[-1], cfg.vocab_size, cfg.d_model,
                                       dtype)
    params["head_blocks"] = [
        _block_init(keys[i], cfg, i, layout, dtype) for i in range(head_n)]

    # stacked per-period-position params: leaf shape [n_periods, ...]
    def stack_position(j: int):
        per = [_block_init(keys[head_n + p * period + j], cfg,
                           head_n + p * period + j, layout, dtype)
               for p in range(n_periods)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    params["blocks"] = [stack_position(j) for j in range(period)]
    params["final_norm"] = L.rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[-2], cfg.d_model,
                                         cfg.vocab_size, dtype)
        if cfg.frontend != "none" and "embed" not in params:
            pass
    if cfg.tie_embeddings and "embed" not in params:
        # stub-frontend models with tied head still need the table
        params["embed"] = L.embed_init(keys[-1], cfg.vocab_size, cfg.d_model,
                                       dtype)
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _attention_block(bp, cfg: ModelConfig, rt: Runtime, x, seg, pos,
                     window: int, collect: Optional[list] = None):
    """``collect`` (serving): a list the block appends its per-token cache
    rows to — post-rotation (k, v), or the MLA latent kv — in exactly the
    layout `train/serve_step.py`'s decode cache stores per position, so a
    packed prefill can hand a populated cache to the decode path."""
    t = x.shape[0]
    pos_s = L.scalar_positions(cfg, pos)
    if cfg.mla is not None:
        q_eff, kv_eff = MLA.mla_qkv(bp, cfg, x, pos_s)
        if collect is not None:
            collect.append({"kv_lat": kv_eff})
        h_pad = rt.layout(cfg).h_pad
        if q_eff.shape[1] < h_pad:                       # pad heads to tp multiple
            q_eff = jnp.pad(q_eff, ((0, 0), (0, h_pad - q_eff.shape[1]), (0, 0)))
        out = R.ring_attention(
            q_eff, kv_eff, None, seg, seg, pos_s, pos_s,
            mesh=rt.mesh, hdp_axes=rt.hdp_axes, model_axis=rt.model_axis,
            composition=rt.composition, kv_sharded=False,
            kv_group_of_head=jnp.zeros((h_pad,), jnp.int32),
            scale=MLA.mla_scale(cfg), window=window,
            softcap=cfg.attn_softcap, kv_chunk=rt.kv_chunk,
            block_skip=rt.block_skip, attn_impl=rt.attn_impl,
            v_in_k=(0, cfg.mla.kv_lora_rank), unroll=rt.cost_unroll,
            block_q=rt.attn_block_q, block_k=rt.attn_block_k)
        out = out[:, :cfg.num_heads]                     # drop padded heads
        return MLA.mla_output(bp, cfg, out)

    layout = rt.layout(cfg)
    dk = cfg.resolved_head_dim
    q = (x @ bp["w_q"]).reshape(t, layout.h_pad, dk)
    kv = jnp.einsum("td,dsgk->tsgk", x, bp["w_kv"])      # [T, 2, G, Dk]
    k, v = kv[:, 0], kv[:, 1]
    if cfg.qk_norm:
        q = L.qk_head_norm(bp["q_norm"], q, cfg.norm_eps)
        k = L.qk_head_norm(bp["k_norm"], k, cfg.norm_eps)
    q, k = L.positional_rotate(cfg, q, k, pos, pos)
    if collect is not None:
        collect.append({"k": k, "v": v})
    out = R.ring_attention(
        q, k, v, seg, seg, pos_s, pos_s,
        mesh=rt.mesh, hdp_axes=rt.hdp_axes, model_axis=rt.model_axis,
        composition=rt.composition, kv_sharded=layout.kv_sharded,
        kv_group_of_head=(None if layout.kv_sharded
                          else layout.group_of_head()),
        scale=dk ** -0.5, window=window, softcap=cfg.attn_softcap,
        kv_chunk=rt.kv_chunk, block_skip=rt.block_skip,
        attn_impl=rt.attn_impl, unroll=rt.cost_unroll,
        block_q=rt.attn_block_q, block_k=rt.attn_block_k)
    if layout.pad_heads:
        out = out * layout.head_mask()[None, :, None].astype(out.dtype)
    return out.reshape(t, -1) @ bp["w_o"]


def _ssm_param_specs(which: str, model) -> dict:
    """Manual-TP shard_map in_specs for the SSM mixers (must match
    parallel/sharding.py's storage rules)."""
    col = P(None, model)
    row = P(model, None)
    if which == "time_mix":
        return {"mix_base": P(), "mix_a": P(), "mix_b": P(),
                "w_r": col, "w_k": col, "w_v": col, "w_g": col,
                "w_o": row, "decay_base": P(model), "decay_a": P(),
                "decay_b": col, "bonus_u": row,
                "ln_x": {"scale": P(model), "bias": P(model)}}
    if which == "channel_mix":
        return {"mix_k": P(), "w_k": col, "w_v": row}
    return {"w_in": P(None, None, model), "conv_w": col, "conv_b": P(model),
            "w_x": row, "dt_w": col, "dt_bias": P(model),
            "A_log": row, "D": P(model), "w_out": row}


def _ssm_block(bp, cfg: ModelConfig, rt: Runtime, x, seg, code: str,
               which: str):
    """Mamba / RWKV mixer (or RWKV channel-mix) under a fully-manual
    shard_map: tokens over the HDP axes, channels/heads over the model axis
    (Megatron-style TP with explicit row-parallel psums — XLA's CPU backend
    miscompiles grad-of-scan under auto axes, and manual collectives keep
    the roofline's collective schedule explicit anyway)."""
    comp = rt.composition
    multi = max(comp) > 1
    model = rt.model_axis
    tp = rt.tp

    def tp_reduce(a):
        return jax.lax.psum(a, model) if (model and tp > 1) else a

    def local(x_, seg_, bp_):
        k_taps = (cfg.mamba.d_conv - 1) if (code == "m" and cfg.mamba) else 1
        bx, bseg = R.shift_from_prev_rank(
            (x_[-k_taps:], seg_[-k_taps:]), hdp_axes=rt.hdp_axes,
            composition=comp) if multi else (
            jnp.zeros_like(x_[-k_taps:]), jnp.zeros_like(seg_[-k_taps:]))

        if which == "channel_mix":
            out, _ = RW.rwkv_channel_mix(bp_, cfg, x_, seg_, bx[-1], bseg[-1],
                                         tp_reduce=tp_reduce)
            return out
        if code == "m":
            exch = None
            if multi:
                exch = lambda h, a: R.distributed_state_scan(  # noqa: E731
                    a, h, hdp_axes=rt.hdp_axes, composition=comp)
            return MB.mamba_forward(bp_, cfg, x_, seg_, bx, bseg,
                                    state_exchange=exch, tp_reduce=tp_reduce)
        # rwkv time mix
        exch = None
        if multi:
            exch = lambda s, a: R.distributed_state_scan(      # noqa: E731
                a[..., None], s, hdp_axes=rt.hdp_axes, composition=comp)
        return RW.rwkv_time_mix(bp_, cfg, x_, seg_, bx[-1], bseg[-1],
                                state_exchange=exch, tp_reduce=tp_reduce)

    pspecs = _ssm_param_specs(which, model)
    fn = shard_map(
        local, mesh=rt.mesh,
        in_specs=(P(rt.hdp_axes, None), P(rt.hdp_axes), pspecs),
        out_specs=P(rt.hdp_axes, None),
        check_vma=False)
    return fn(x, seg, bp)


def _ffn_block(bp, cfg: ModelConfig, x):
    act = L.act_fn(cfg.act)
    h = x @ bp["w_in"]
    if cfg.gated_mlp:
        h = act(x @ bp["w_gate"]) * h
    else:
        h = act(h)
    return h @ bp["w_out"]


def _moe_block(bp, cfg: ModelConfig, rt: Runtime, x):
    """Per-HDP-rank routing semantics.  "manual" = shard_map expert
    parallelism (one [C,d] psum per layer — see models/moe_manual.py);
    "gather" = pjit/vmap baseline."""
    if rt.moe_impl == "manual" and cfg.moe.num_experts % max(rt.tp, 1) == 0:
        from repro.models.moe_manual import moe_forward_manual
        return moe_forward_manual(bp, cfg, rt, x)
    t, d = x.shape
    r = rt.hdp_size
    x3 = x.reshape(r, t // r, d)
    x3 = jax.lax.with_sharding_constraint(x3, P(rt.hdp_axes, None, None))
    y3 = jax.vmap(MOE.moe_forward, in_axes=(None, None, 0))(bp, cfg, x3)
    return y3.reshape(t, d)


def block_forward(bp, cfg: ModelConfig, rt: Runtime, x, seg, pos,
                  layer_idx: int, collect: Optional[list] = None):
    code = cfg.layer_code(layer_idx)
    window = cfg.window if code == "l" else 0
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if code in ("g", "l"):
        with jax.named_scope("attention"):
            h = _attention_block(bp["attn"], cfg, rt, h, seg, pos, window,
                                 collect=collect)
    elif code == "m":
        h = _ssm_block(bp["mamba"], cfg, rt, h, seg, code, "mamba")
    else:
        h = _ssm_block(bp["time_mix"], cfg, rt, h, seg, code, "time_mix")
    if cfg.post_block_norm:
        h = L.rmsnorm(bp["postnorm1"], h, cfg.norm_eps)
    x = x + h.astype(x.dtype)

    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    if code == "r":
        h = _ssm_block(bp["channel_mix"], cfg, rt, h, seg, code, "channel_mix")
    elif "moe" in bp:
        h = _moe_block(bp["moe"], cfg, rt, h)
    else:
        with jax.named_scope("mlp"):
            h = _ffn_block(bp["mlp"], cfg, h)
    if cfg.post_block_norm:
        h = L.rmsnorm(bp["postnorm2"], h, cfg.norm_eps)
    return x + h.astype(x.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x


def _offload_policy():
    # compat probes for a host memory space; backends without pinned_host
    # fall back to saving the same names on device (no transfer, same
    # recompute structure)
    return offload_policy(names=("resid",))


def _split_stacked(blocks, k: int):
    """Split stacked [n_periods, ...] block params at period k."""
    head = jax.tree.map(lambda a: a[:k], blocks)
    tail = jax.tree.map(lambda a: a[k:], blocks)
    return head, tail


def embed_frontend(params, cfg: ModelConfig, rt: Runtime, batch,
                   collect: Optional[list] = None) -> jnp.ndarray:
    """Token/embedding frontend + the un-scanned head blocks (DeepSeek
    dense head).  First-stage work under pipeline parallelism.
    ``collect``: per-head-block KV capture for serving (see
    `_attention_block`)."""
    seg, pos = batch["seg"], batch["pos"]
    if cfg.frontend == "none":
        x = embed_tokens(params, cfg, batch["tokens"])
    else:
        x = batch["embeds"]
        if cfg.embed_scale:
            x = x * math.sqrt(cfg.d_model)
    if cfg.pos_embed == "sinusoidal":
        x = x + L.sinusoidal_embedding(L.scalar_positions(cfg, pos),
                                       cfg.d_model).astype(x.dtype)
    x = jax.lax.with_sharding_constraint(x, P(rt.hdp_axes, None))

    for i, bp in enumerate(params["head_blocks"]):
        x = block_forward(bp, cfg, rt, x, seg, pos, i, collect=collect)
    return x


def apply_periods(blocks, cfg: ModelConfig, rt: Runtime, x, seg, pos):
    """Run a window of stacked layer periods over the residual stream.

    ``blocks``: tuple (per period position) of stacked [n, ...] params —
    the full stack for the plain forward, or one stage's contiguous slice
    under pipeline parallelism (parallel/pipeline.py vmaps this function
    over the stage axis).  Handles remat / offload / cost-unroll.
    """
    period = len(cfg.layer_pattern)
    head_n = head_layer_count(cfg)
    resid_spec = P(rt.hdp_axes, rt.model_axis if rt.seq_parallel else None)

    def period_body(x, bp_stack):
        x = checkpoint_name(x, "resid")
        for j in range(period):
            x = block_forward(bp_stack[j], cfg, rt, x, seg, pos, head_n + j)
            if rt.seq_parallel:
                # Megatron-style sequence parallelism: the residual stream
                # lives sharded over the model axis; GSPMD converts each
                # TP all-reduce into reduce-scatter + all-gather pairs
                x = jax.lax.with_sharding_constraint(x, resid_spec)
        x = jax.lax.with_sharding_constraint(x, resid_spec)
        return x, None

    blocks = tuple(blocks)
    n_periods = jax.tree.leaves(blocks)[0].shape[0]

    def run_scan(x, stacked, policy):
        body = period_body
        if rt.remat == "dots":
            # save matmul outputs inside the period: cheaper bwd recompute
            # at the cost of saved-dot memory (perf-iteration knob)
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if rt.remat != "none":
            body = jax.checkpoint(period_body, policy=policy,
                                  prevent_cse=False)
        if rt.cost_unroll:
            # cost-analysis lowering: python-unrolled periods (XLA counts
            # while-loop bodies only once — launch/dryrun.py)
            n = jax.tree.leaves(stacked)[0].shape[0]
            for i in range(n):
                x, _ = body(x, jax.tree.map(lambda a: a[i], stacked))
            return x
        # bytes ledger: the scan body traces once but executes once per
        # stacked period — scale trace-time comm records accordingly
        with ledger.comm_scale(jax.tree.leaves(stacked)[0].shape[0]):
            x, _ = jax.lax.scan(body, x, stacked)
        return x

    if rt.remat == "offload" and 0 < rt.offload_periods:
        k = min(rt.offload_periods, n_periods)
        if ledger.tally_active():
            # bytes ledger: each offloaded period ships its "resid" entry
            # ([T, d_model]) to host in the forward and back in the
            # backward — the execution-quantized side of Eq. 3's ratio
            moved = k * ledger.tree_bytes(x)
            ledger.record_comm("offload_d2h", moved)
            ledger.record_comm("offload_h2d", moved)
        head_stack, tail_stack = _split_stacked(blocks, k)
        x = run_scan(x, head_stack, _offload_policy())
        if k < n_periods:
            x = run_scan(x, tail_stack, None)
    else:
        x = run_scan(x, blocks, None)
    return x


def forward_hidden(params, cfg: ModelConfig, rt: Runtime, batch) -> jnp.ndarray:
    """batch: {"tokens" [T] | "embeds" [T,d], "seg" [T], "pos" [T] or [T,3]}
    -> final hidden [T, d]."""
    x = embed_frontend(params, cfg, rt, batch)
    x = apply_periods(params["blocks"], cfg, rt, x, batch["seg"],
                      batch["pos"])
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def logits_head(params, cfg: ModelConfig, hidden):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = hidden @ w.astype(hidden.dtype)
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits
