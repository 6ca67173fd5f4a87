"""Mamba-1 selective SSM (Jamba's mixer) with chunked scan + HDP support.

Recurrence per channel i, state dim N:
    a_t = exp(Δ_t · A)            (A = -exp(A_log) < 0, so a_t ∈ (0,1))
    h_t = a_t ⊙ h_{t-1} + (Δ_t x_t) ⊗ B_t
    y_t = h_t · C_t + D ⊙ x_t
Within a chunk we use an associative scan; chunks carry the state
sequentially.  Like RWKV (models/rwkv6.py) the sweep is linear in the
incoming state, so HDP rank groups exchange (A_total, h_local) summaries and
apply a correction pass — see DESIGN.md §5.

Segment handling: decay is forced to 0 at segment starts (history drop) and
to 1 on padding (transparent); the causal conv masks cross-segment taps.

The per-rank sweep is pure jnp: `models/transformer.py` wraps it in
`jax.shard_map` over the HDP axes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MambaSpec
from repro.models import layers as L


def mamba_dims(cfg: ModelConfig):
    ms = cfg.mamba or MambaSpec()
    d_in = ms.expand * cfg.d_model
    dt_rank = ms.dt_rank or -(-cfg.d_model // 16)
    return ms, d_in, dt_rank


def mamba_init(key, cfg: ModelConfig, dtype) -> dict:
    ms, d_in, dt_rank = mamba_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    # S4D-real initialization for A
    a_init = jnp.broadcast_to(jnp.arange(1, ms.d_state + 1, dtype=jnp.float32),
                              (d_in, ms.d_state))
    return {
        # [d, 2(x/z), d_in]: split before the TP-sharded dim (sharding.py)
        "w_in": L.dense_init(ks[0], d, 2 * d_in, dtype).reshape(d, 2, d_in),
        "conv_w": (jax.random.normal(ks[1], (ms.d_conv, d_in), jnp.float32)
                   / math.sqrt(ms.d_conv)).astype(dtype),
        "conv_b": jnp.zeros((d_in,), jnp.float32),
        "w_x": L.dense_init(ks[2], d_in, dt_rank + 2 * ms.d_state, dtype),
        "dt_w": L.dense_init(ks[3], dt_rank, d_in, jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((d_in,), 0.01, jnp.float32))),
        "A_log": jnp.log(a_init),
        "D": jnp.ones((d_in,), jnp.float32),
        "w_out": L.dense_init(ks[4], d_in, d, dtype),
    }


def _causal_conv(x, seg, conv_w, conv_b, boundary_x, boundary_seg):
    """Depthwise causal conv over time with segment masking.

    x [T, d_in]; boundary_x [K-1, d_in] = last K-1 rows of the previous rank
    (zeros at group starts); boundary_seg [K-1]."""
    k = conv_w.shape[0]
    xs = jnp.concatenate([boundary_x, x], axis=0)              # [T+K-1, d_in]
    segs = jnp.concatenate([boundary_seg, seg])
    t = x.shape[0]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(k):                                         # K is tiny (4)
        tap = xs[k - 1 - j: k - 1 - j + t]                     # x_{t-j}
        tap_seg = segs[k - 1 - j: k - 1 - j + t]
        same = (tap_seg == seg) & (seg > 0)
        out = out + jnp.where(same[:, None], tap, 0.0).astype(jnp.float32) \
            * conv_w[k - 1 - j]
    return out + conv_b


def mamba_ssm_chunked(dt, bx, b_in, c_out, a_log, seg, prev_last_seg, *,
                      chunk: int):
    """The selective scan.  dt [T, d_in], bx = Δ·x [T, d_in],
    b_in/c_out [T, N], a_log [d_in, N] (A = -exp(a_log)); ``prev_last_seg``
    is the previous rank's final segment id (0 at group starts) — the
    cross-rank decay chain A_total stays alive only while the segment
    continues from there.

    Returns (y [T, d_in], h_out [d_in, N], A_total [d_in, N]).
    Linear in the (zero) initial state; use ``mamba_correction`` to add an
    incoming cross-rank state's contribution.
    """
    t, d_in = dt.shape
    n = b_in.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0
    nc = t // chunk
    a_coef = -jnp.exp(a_log)                                   # [d_in, N]

    seg_prev = jnp.concatenate([prev_last_seg[None], seg[:-1]])
    keep = ((seg == seg_prev) & (seg > 0)).astype(jnp.float32)  # decay keeps history
    valid = (seg > 0).astype(jnp.float32)

    dt_c = dt.reshape(nc, chunk, d_in)
    bx_c = bx.reshape(nc, chunk, d_in)
    b_c = b_in.reshape(nc, chunk, n)
    c_c = c_out.reshape(nc, chunk, n)
    keep_c = keep.reshape(nc, chunk)
    valid_c = valid.reshape(nc, chunk)

    def body(h, xs):
        dtc, bxc, bc, cc, kc, vc = xs
        a = jnp.exp(dtc[..., None] * a_coef[None])             # [L, d_in, N]
        # pads transparent (a=1, b=0); segment starts drop history (a=0)
        a = jnp.where(vc[:, None, None] > 0, a * kc[:, None, None], 1.0)
        b = bxc[..., None] * bc[:, None, :]                    # [L, d_in, N]
        b = b * vc[:, None, None]

        def combine(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2

        cum_a, cum_b = jax.lax.associative_scan(combine, (a, b), axis=0)
        h_t = cum_a * h[None] + cum_b                          # [L, d_in, N]
        y = jnp.einsum("ldn,ln->ld", h_t, cc)
        return h_t[-1], (y, cum_a[-1])

    h0 = jnp.zeros((d_in, n), jnp.float32)
    h_out, (ys, a_chunks) = jax.lax.scan(
        body, h0, (dt_c, bx_c, b_c, c_c, keep_c, valid_c))
    a_total = jnp.prod(a_chunks, axis=0)
    return ys.reshape(t, d_in), h_out, a_total


def mamba_correction(dt, c_out, a_log, seg, prev_last_seg, h_in, *,
                     chunk: int):
    """y_t += C_t · (P_t ⊙ h_in) where P_t = decay from rank start to t
    (dies at the first segment boundary).  Recomputes decays chunkwise to
    avoid storing [T, d_in, N]."""
    t, d_in = dt.shape
    n = c_out.shape[-1]
    chunk = min(chunk, t)
    nc = t // chunk
    a_coef = -jnp.exp(a_log)
    seg_prev = jnp.concatenate([prev_last_seg[None], seg[:-1]])
    keep = ((seg == seg_prev) & (seg > 0)).astype(jnp.float32)
    valid = (seg > 0).astype(jnp.float32)

    dt_c = dt.reshape(nc, chunk, d_in)
    c_c = c_out.reshape(nc, chunk, n)
    keep_c = keep.reshape(nc, chunk)
    valid_c = valid.reshape(nc, chunk)

    def body(p, xs):
        dtc, cc, kc, vc = xs
        a = jnp.exp(dtc[..., None] * a_coef[None])
        a = jnp.where(vc[:, None, None] > 0, a * kc[:, None, None], 1.0)
        cum_a = jnp.cumprod(a, axis=0)                         # includes zeros
        p_t = cum_a * p[None]
        y = jnp.einsum("ldn,dn,ln->ld", p_t, h_in, cc)
        return p_t[-1], y

    p0 = jnp.ones((d_in, n), jnp.float32)
    _, ys = jax.lax.scan(body, p0, (dt_c, c_c, keep_c, valid_c))
    return ys.reshape(t, d_in)


def mamba_forward(params: dict, cfg: ModelConfig, x, seg, boundary_x,
                  boundary_seg, state_exchange=None, tp_reduce=None):
    """Full Mamba block on a local token buffer [T, d].  Under manual TP
    the channel dims are pre-sharded; `tp_reduce` sums the two row-parallel
    projections (w_x -> x_dbl, w_out -> out)."""
    ms, _, dt_rank = mamba_dims(cfg)
    d_in = params["w_in"].shape[-1]      # local (TP-sharded) width
    t = x.shape[0]
    xz = jnp.einsum("td,dkj->tkj", x, params["w_in"])          # [T, 2, d_in]
    x_p, z = xz[:, 0], xz[:, 1]
    # boundary rows (prev rank's last K-1 tokens) go through the same proj
    bxp = jnp.einsum("td,dj->tj", boundary_x, params["w_in"][:, 0])
    x_conv = _causal_conv(x_p, seg, params["conv_w"], params["conv_b"],
                          bxp, boundary_seg)
    x_conv = jax.nn.silu(x_conv).astype(x.dtype)

    x_dbl = x_conv @ params["w_x"]
    if tp_reduce is not None:
        x_dbl = tp_reduce(x_dbl)        # row-parallel (d_in contracted)
    dt_low = x_dbl[:, :dt_rank].astype(jnp.float32)
    b_in = x_dbl[:, dt_rank:dt_rank + ms.d_state].astype(jnp.float32)
    c_out = x_dbl[:, dt_rank + ms.d_state:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_low @ params["dt_w"] + params["dt_bias"])

    bx = dt * x_conv.astype(jnp.float32)
    prev_last_seg = boundary_seg[-1]
    y, h_local, a_total = mamba_ssm_chunked(
        dt, bx, b_in, c_out, params["A_log"], seg, prev_last_seg,
        chunk=ms.chunk_size)

    if state_exchange is not None:
        h_in = state_exchange(h_local, a_total)
        y = y + mamba_correction(dt, c_out, params["A_log"], seg,
                                 prev_last_seg, h_in, chunk=ms.chunk_size)

    y = y + params["D"] * x_conv.astype(jnp.float32)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ params["w_out"]
    if tp_reduce is not None:
        out = tp_reduce(out)
    return out


def mamba_decode_step(params: dict, cfg: ModelConfig, x, state):
    """Single-token decode. x [B, d]; state {conv: [B, K-1, d_in],
    h: [B, d_in, N]}."""
    ms, d_in, dt_rank = mamba_dims(cfg)
    b = x.shape[0]
    xz = jnp.einsum("bd,dkj->bkj", x, params["w_in"])          # [B, 2, d_in]
    x_p, z = xz[:, 0], xz[:, 1]
    conv_buf = jnp.concatenate([state["conv"], x_p[:, None, :]], axis=1)
    x_conv = jnp.einsum("bkd,kd->bd", conv_buf.astype(jnp.float32),
                        params["conv_w"].astype(jnp.float32)) + params["conv_b"]
    x_conv = jax.nn.silu(x_conv).astype(x.dtype)

    x_dbl = x_conv @ params["w_x"]
    dt_low = x_dbl[:, :dt_rank].astype(jnp.float32)
    b_in = x_dbl[:, dt_rank:dt_rank + ms.d_state].astype(jnp.float32)
    c_out = x_dbl[:, dt_rank + ms.d_state:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_low @ params["dt_w"] + params["dt_bias"])

    a = jnp.exp(dt[..., None] * (-jnp.exp(params["A_log"]))[None])
    h = a * state["h"] + (dt * x_conv.astype(jnp.float32))[..., None] \
        * b_in[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, c_out) + params["D"] * x_conv.astype(jnp.float32)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ params["w_out"]
    return out, {"conv": conv_buf[:, 1:], "h": h}


def mamba_sequential(dt, bx, b_in, c_out, a_log, seg, prev_last_seg, h0):
    """Token-by-token oracle for mamba_ssm_chunked (+ incoming state h0)."""
    a_coef = -jnp.exp(a_log)
    seg_prev = jnp.concatenate([prev_last_seg[None], seg[:-1]])
    keep = ((seg == seg_prev) & (seg > 0)).astype(jnp.float32)
    valid = (seg > 0).astype(jnp.float32)

    def body(h, xs):
        dtt, bxt, bt, ct, kt, vt = xs
        a = jnp.exp(dtt[:, None] * a_coef)
        a = jnp.where(vt > 0, a * kt, 1.0)
        bterm = (bxt[:, None] * bt[None, :]) * vt
        h = a * h + bterm
        y = jnp.einsum("dn,n->d", h, ct)
        return h, y

    h_out, ys = jax.lax.scan(body, h0, (dt, bx, b_in, c_out, keep, valid))
    return ys, h_out
