"""RWKV-6 "Finch" token/channel mixing with chunked WKV and HDP support.

The WKV-6 recurrence per head (size N), with data-dependent per-channel
decay w_t ∈ (0,1) and bonus u:
    y_t = r_t · (S_{t-1} + (u ⊙ k_t) ⊗ v_t)
    S_t = diag(w_t) · S_{t-1} + k_t ⊗ v_t

Chunked parallel form (chunk L, cum = cumsum(log w) rebased per chunk):
    inter:  y_t += (r_t ⊙ e^{cum_t}) · S_0
    intra:  scores[t,s] = Σ_i r_t[i] k_s[i] e^{cum_t[i] - cum_{s+1}[i]}  (s<t)
            + diagonal bonus (r_t ⊙ u ⊙ k_t) at s = t
    state:  S_L = diag(e^{cum_L}) S_0 + Σ_s (k_s ⊙ e^{cum_L - cum_{s+1}}) ⊗ v_s
Exponents are ≤ 0 for s < t so everything is bounded; per-chunk rebasing
keeps e^{cum} in range (chunk ≤ 128).

Packed segments: scores are masked by segment equality; the carried state is
neutralized across segment boundaries (A *= [chunk ends in same segment],
contributions from earlier segments are masked out of the state update).

Under HDP, a sequence sharded over a rank group composes the per-rank
(A = Π decay, b = local final state) summaries through
``core.ring.distributed_state_scan`` — see DESIGN.md §5 (the paper's
ring-attention does not apply to attention-free mixers; token-balanced
scheduling still does).

The per-rank sweep is pure jnp: `models/transformer.py` wraps it in
`jax.shard_map` over the HDP axes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

MIX_NAMES = ("r", "k", "v", "g", "w")


def rwkv_init(key, cfg: ModelConfig, dtype) -> dict:
    rs = cfg.rwkv
    d = cfg.d_model
    n_heads = d // rs.head_size
    ks = jax.random.split(key, 16)
    p = {
        # token-shift ddlerp: shared down-proj + per-target up-proj
        "mix_base": jnp.zeros((len(MIX_NAMES), d), jnp.float32) + 0.5,
        "mix_a": L.dense_init(ks[0], d, rs.mix_lora, dtype),
        "mix_b": (jax.random.normal(ks[1], (len(MIX_NAMES), rs.mix_lora, d),
                                    jnp.float32) * 0.01).astype(dtype),
        "w_r": L.dense_init(ks[2], d, d, dtype),
        "w_k": L.dense_init(ks[3], d, d, dtype),
        "w_v": L.dense_init(ks[4], d, d, dtype),
        "w_g": L.dense_init(ks[5], d, d, dtype),
        "w_o": L.dense_init(ks[6], d, d, dtype),
        # data-dependent decay LoRA: w = exp(-exp(decay_base + tanh(x A) B))
        "decay_base": jnp.full((d,), -6.0, jnp.float32),
        "decay_a": L.dense_init(ks[7], d, rs.decay_lora, dtype),
        "decay_b": (jax.random.normal(ks[8], (rs.decay_lora, d), jnp.float32)
                    * 0.01).astype(dtype),
        "bonus_u": jnp.zeros((n_heads, rs.head_size), jnp.float32),
        "ln_x": {"scale": jnp.ones((d,), jnp.float32),
                 "bias": jnp.zeros((d,), jnp.float32)},        # per-head groupnorm
    }
    return p


def channel_mix_init(key, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "mix_k": jnp.zeros((d,), jnp.float32) + 0.5,
        "w_k": L.dense_init(ks[0], d, cfg.d_ff, dtype),
        "w_v": L.dense_init(ks[1], cfg.d_ff, d, dtype),
    }


# ---------------------------------------------------------------------------
# token shift
# ---------------------------------------------------------------------------

def token_shift(x, seg, x_prev_boundary, seg_prev_boundary):
    """x [T, d]; returns x shifted by one token, zeros at segment starts.
    ``x_prev_boundary`` [d] / ``seg_prev_boundary`` [] come from the previous
    rank's last token (zeros / 0 when this rank starts a group)."""
    prev = jnp.concatenate([x_prev_boundary[None, :], x[:-1]], axis=0)
    seg_prev = jnp.concatenate([seg_prev_boundary[None], seg[:-1]])
    same = (seg == seg_prev) & (seg > 0)
    return jnp.where(same[:, None], prev, 0.0)


# ---------------------------------------------------------------------------
# WKV-6 chunked scan
# ---------------------------------------------------------------------------

def wkv6_chunked(r, k, v, logw, u, seg, *, head_size: int, chunk: int,
                 s0, carry_seg):
    """r/k/v [T, d], logw [T, d] (≤0), u [H, N]; seg [T].

    s0: incoming state [H, N, N]; carry_seg: scalar segment id the incoming
    state belongs to (0 = none).

    Returns (y [T, d], s_out [H, N, N], A_total [H, N], corr [T, H, N]):
      * A_total — total decay applied to s0 (zeroed by segment resets); the
        cross-rank composition coefficient.
      * corr — per-token coefficient such that the contribution of an
        *additional* incoming state h is ``y_t += corr_t · h`` (already
        masked to tokens whose segment continues from the buffer start).
        This makes the sweep linear in s0, so HDP rank groups run one local
        sweep, exchange O(H·N²) summaries, then add the correction
        (DESIGN.md §5).
    """
    t, d = r.shape
    n = head_size
    h = d // n
    chunk = min(chunk, t)
    assert t % chunk == 0
    nc = t // chunk

    def reshape(x):
        return x.reshape(nc, chunk, h, n)

    r_c, k_c, v_c = (reshape(a.astype(jnp.float32)) for a in (r, k, v))
    lw_c = reshape(logw.astype(jnp.float32))
    seg_c = seg.reshape(nc, chunk)

    def body(carry, xs):
        s, c_seg, a_tot = carry
        rc, kc, vc, lwc, sc = xs                                # [L,H,N], [L]
        valid = sc > 0
        lwc = jnp.where(valid[:, None, None], lwc, 0.0)         # pads don't decay
        cum = jnp.cumsum(lwc, axis=0)                           # inclusive [L,H,N]
        cum_ex = cum - lwc                                      # exclusive
        # segment bookkeeping
        same_as_carry = (sc == c_seg) & valid                   # may read s0
        any_valid = jnp.any(valid)
        last_idx = jnp.maximum(jnp.max(jnp.where(valid, jnp.arange(chunk), -1)), 0)
        last_seg = jnp.where(any_valid, sc[last_idx], c_seg)
        in_last = (sc == last_seg) & valid                      # feeds s_out
        # inter-chunk: y_t += (r ⊙ e^{cum_ex}) · S0   (and corr for later h_in)
        r_decay = rc * jnp.exp(jnp.clip(cum_ex, -30.0, 0.0))
        r_decay = jnp.where(same_as_carry[:, None, None], r_decay, 0.0)
        corr = r_decay * a_tot[None]                            # [L,H,N]
        y_inter = jnp.einsum("lhn,hnm->lhm", r_decay, s)
        # intra-chunk scores[t,s] = Σ_n r[t,n] k[s,n] e^{cum_ex[t]-cum[s]}
        q_t = rc * jnp.exp(jnp.clip(cum_ex, -30.0, 0.0))
        k_s = kc * jnp.exp(jnp.clip(-cum, -30.0, 30.0))
        scores = jnp.einsum("lhn,mhn->hlm", q_t, k_s)           # [H,L(t),L(s)]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
        seg_eq = (sc[:, None] == sc[None, :]) & valid[:, None] & valid[None, :]
        scores = jnp.where((tri & seg_eq)[None], scores, 0.0)
        diag = jnp.einsum("lhn,hn,lhn->lh", rc, u, kc)          # bonus at s=t
        diag = jnp.where(valid[:, None], diag, 0.0)
        y_intra = jnp.einsum("hlm,mhn->lhn", scores, vc)
        y_intra = y_intra + diag[..., None] * vc
        # state update
        a_chunk = jnp.exp(jnp.clip(cum[-1], -30.0, 0.0))        # [H,N]
        k_hat = kc * jnp.exp(jnp.clip(cum[-1][None] - cum, -30.0, 0.0))
        k_hat = jnp.where(in_last[:, None, None], k_hat, 0.0)
        s_new = jnp.einsum("lhn,lhm->hnm", k_hat, vc)
        keep_carry = (last_seg == c_seg).astype(jnp.float32)
        a_eff = a_chunk * keep_carry
        s = a_eff[..., None] * s + s_new
        a_tot = a_tot * a_eff
        c_seg = last_seg
        return (s, c_seg, a_tot), (y_inter + y_intra, corr)

    a0 = jnp.ones((h, n), jnp.float32)
    (s_out, _, a_total), (ys, corrs) = jax.lax.scan(
        body, (s0.astype(jnp.float32), carry_seg, a0),
        (r_c, k_c, v_c, lw_c, seg_c))
    return ys.reshape(t, d), s_out, a_total, corrs.reshape(t, h, n)


def rwkv_time_mix(params: dict, cfg: ModelConfig, x, seg, x_prev_boundary,
                  seg_prev_boundary, state_exchange=None, tp_reduce=None):
    """Full RWKV-6 time-mix block on a local token buffer.

    ``state_exchange(s_local, a_total) -> h_in`` performs the cross-rank
    (A, b) composition when the sequence is sharded over an HDP group
    (None => purely local, h_in = 0).  Returns out [T, d]."""
    rs = cfg.rwkv
    d = params["w_r"].shape[1]          # local (TP-sharded) width
    xp = token_shift(x, seg, x_prev_boundary, seg_prev_boundary)
    delta = xp - x
    mix_lora = jnp.tanh(x @ params["mix_a"])                    # [T, R]
    mixes = {}
    for i, name in enumerate(MIX_NAMES):
        lam = params["mix_base"][i] + mix_lora @ params["mix_b"][i]
        mixes[name] = x + lam * delta

    r = mixes["r"] @ params["w_r"]
    k = mixes["k"] @ params["w_k"]
    v = mixes["v"] @ params["w_v"]
    g = jax.nn.silu(mixes["g"] @ params["w_g"])
    logw = -jnp.exp(params["decay_base"]
                    + jnp.tanh(mixes["w"] @ params["decay_a"]) @ params["decay_b"])

    # carry_seg = previous rank's last segment: the cross-rank decay chain
    # A_total (and the h_in correction) stays alive only while that segment
    # continues into this rank's buffer.
    y, s_local, a_total, corr = wkv6_chunked(
        r, k, v, logw, params["bonus_u"], seg,
        head_size=rs.head_size, chunk=rs.chunk_size,
        s0=jnp.zeros((d // rs.head_size, rs.head_size, rs.head_size),
                     jnp.float32),
        carry_seg=seg_prev_boundary)

    if state_exchange is not None:
        h_in = state_exchange(s_local, a_total)                 # [H, N, N]
        y = y + jnp.einsum("thn,hnm->thm", corr,
                           h_in.astype(jnp.float32)).reshape(y.shape)

    # per-head group norm
    t = x.shape[0]
    n = rs.head_size
    yh = y.reshape(t, d // n, n)
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.var(yh, axis=-1, keepdims=True)
    yh = (yh - mu) * jax.lax.rsqrt(var + 1e-5)
    y = yh.reshape(t, d) * params["ln_x"]["scale"] + params["ln_x"]["bias"]

    out = (y.astype(x.dtype) * g) @ params["w_o"]
    if tp_reduce is not None:
        out = tp_reduce(out)            # row-parallel w_o partial sum
    return out


def rwkv_channel_mix(params: dict, cfg: ModelConfig, x, seg, x_prev_boundary,
                     seg_prev_boundary, tp_reduce=None):
    xp = token_shift(x, seg, x_prev_boundary, seg_prev_boundary)
    xk = x + params["mix_k"] * (xp - x)
    kk = jnp.square(jax.nn.relu(xk.astype(x.dtype) @ params["w_k"]))
    out = kk @ params["w_v"]
    if tp_reduce is not None:
        out = tp_reduce(out)
    return out, x[-1]


# ---------------------------------------------------------------------------
# sequential oracle (tests)
# ---------------------------------------------------------------------------

def wkv6_sequential(r, k, v, logw, u, seg, *, head_size: int, s0, carry_seg):
    """Token-by-token WKV-6 recurrence — the oracle for wkv6_chunked."""
    t, d = r.shape
    n = head_size
    h = d // n
    rs_ = r.reshape(t, h, n).astype(jnp.float32)
    ks_ = k.reshape(t, h, n).astype(jnp.float32)
    vs_ = v.reshape(t, h, n).astype(jnp.float32)
    ws_ = jnp.exp(logw.reshape(t, h, n).astype(jnp.float32))

    def body(carry, xs):
        s, c_seg = carry
        rt, kt, vt, wt, st = xs
        valid = st > 0
        s_use = jnp.where((st == c_seg) & valid, 1.0, 0.0) * s
        y = jnp.einsum("hn,hnm->hm", rt, s_use) \
            + jnp.einsum("hn,hn,hn,hm->hm", rt, u, kt, vt)
        y = jnp.where(valid, y.reshape(-1), 0.0).reshape(h, n)
        s_next = wt[..., None] * s_use + jnp.einsum("hn,hm->hnm", kt, vt)
        s = jnp.where(valid, s_next.reshape(-1), s.reshape(-1)).reshape(h, n, n)
        c_seg = jnp.where(valid, st, c_seg)
        return (s, c_seg), y

    (s_out, _), ys = jax.lax.scan(body, (s0.astype(jnp.float32), carry_seg),
                                  (rs_, ks_, vs_, ws_, seg))
    return ys.reshape(t, d), s_out


def rwkv_decode_step(params: dict, cfg: ModelConfig, x, state):
    """Single-token decode. x [B, d]; state dict with s [B,H,N,N], x_prev
    (time) [B, d], x_prev_cm [B, d]."""
    rs = cfg.rwkv
    d = cfg.d_model
    n = rs.head_size
    h = d // n
    xp = state["x_tm"]
    delta = xp - x
    mix_lora = jnp.tanh(x @ params["mix_a"])
    mixes = {name: x + (params["mix_base"][i] + mix_lora @ params["mix_b"][i]) * delta
             for i, name in enumerate(MIX_NAMES)}
    r = (mixes["r"] @ params["w_r"]).reshape(-1, h, n).astype(jnp.float32)
    k = (mixes["k"] @ params["w_k"]).reshape(-1, h, n).astype(jnp.float32)
    v = (mixes["v"] @ params["w_v"]).reshape(-1, h, n).astype(jnp.float32)
    g = jax.nn.silu(mixes["g"] @ params["w_g"])
    logw = -jnp.exp(params["decay_base"]
                    + jnp.tanh(mixes["w"] @ params["decay_a"]) @ params["decay_b"])
    w = jnp.exp(logw).reshape(-1, h, n)
    s = state["s"]
    y = jnp.einsum("bhn,bhnm->bhm", r, s) \
        + jnp.einsum("bhn,hn,bhn,bhm->bhm", r, params["bonus_u"], k, v)
    s = w[..., None] * s + jnp.einsum("bhn,bhm->bhnm", k, v)
    yh = y
    mu = jnp.mean(yh, axis=-1, keepdims=True)
    var = jnp.var(yh, axis=-1, keepdims=True)
    y = ((yh - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(x.shape[0], d)
    y = y * params["ln_x"]["scale"] + params["ln_x"]["bias"]
    out = (y.astype(x.dtype) * g) @ params["w_o"]
    return out, {"s": s, "x_tm": x}
