"""Ring-Flash attention engine: Pallas-backed distributed attention for
the CP hot path.

The g-rank zigzag rings of `core/ring.py` historically computed every ring
step with the jnp reference oracle; the Pallas flash kernel served only the
local (g = 1) path.  This module fuses the two: each ring step invokes the
state-carrying Pallas kernel (`flash_attention_fwd_carry`), which folds the
visiting KV block directly into carried online-softmax state (acc, m, l) —
no per-step renormalize + merge round-trip — and finalization (out = acc/l,
lse = m + log l) happens once after the last step.

Forward ring (per rank, inside shard_map):
    step 0 runs the local block; each subsequent step *first issues* the
    ``ppermute`` that fetches the next block, then launches the kernel on
    the block already in hand — the rotation has no data dependency on the
    kernel, so XLA overlaps comm with compute (double buffering); the final
    step is peeled so no dead rotation is issued.  The ring carries the same
    O(1) block metadata as the oracle path, so the block-skipping fast path
    (segments/causality/window pruning) is preserved: a skipped step costs
    one ``lax.cond`` branch, not an O(C²) kernel launch.

Backward ring ("reverse ring"): the KV blocks take the same tour.  At step
s the rank holds the block owned by rank (r - s) in its group and the
existing flash backward kernels emit that step's dq contribution (folded
into the local dq accumulator) plus dk/dv for the visiting block, which is
returned to its home rank in one hop via a reverse ``ppermute`` (rank j ->
j - s within the group).  The step loop is Python-unrolled (max(g) is a
small static), so the per-step reverse permutation stays static.

Layout notes: the engine transposes q/do into kernel layout ([G, Hg, C, D])
once per call, not once per ring step, and carries KV blocks untransposed so
the ring collective payload is unchanged from the oracle path.  The two head
modes of `core/ring.py` are both supported: sharded KV (q heads reshaped to
[G_local, Hg]) and replicated-KV gather (per-head KV gather under
``kv_group_of_head``, G = h_local, Hg = 1), including the MLA ``v_in_k``
latent overlap.

The public entry point is the `ring_flash` factory consumed by
`repro.kernels.ops.make_ring_flash` (the custom-VJP wrapper) and dispatched
from `core/ring.py` when ``attn_impl == "pallas"``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as att
from repro.core.ring import (_block_meta, _block_relevant, composition_tables,
                             ring_perm)
from repro.kernels import flash_attention as FA
from repro.obs import ledger

NEG_INF = FA.NEG_INF


# ---------------------------------------------------------------------------
# static ring configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RingConfig:
    """Hashable static configuration of one ring-flash executable (one per
    (composition, head-mode, mask-config, block-shape) — the same key
    granularity as the XLA ring-composition cache, and the lru_cache key
    of `ops.make_ring_flash`)."""

    hdp_axes: Tuple[str, ...]
    composition: Tuple[int, ...]
    kv_split: Tuple[int, int, int]            # (dk, v_off, dv)
    gather: bool
    scale: float
    interpret: bool                           # kernels/ops.interpret_on
    causal: bool = True
    window: int = 0
    softcap: float = 0.0
    block_q: int = 256
    block_k: int = 512
    block_skip: bool = True
    unroll: bool = False

    @property
    def steps(self) -> int:
        return max(self.composition) - 1

    @property
    def perm(self):
        return ring_perm(self.composition)


def _reverse_perm(cfg: RingConfig, s: int):
    """One-hop "send the visiting block's dkv home" permutation for step s:
    within a group of size g, rank j -> j - s (mod g).  Groups whose shift
    is a no-op at this step (singletons; s ≥ g implies a skipped step) are
    omitted — unlisted destinations receive zeros, matching their zero
    contribution."""
    perm = []
    start = 0
    for g in cfg.composition:
        if g > 1 and s % g != 0:
            for j in range(g):
                perm.append((start + j, start + (j - s) % g))
        start += g
    return perm


# ---------------------------------------------------------------------------
# layout helpers
# ---------------------------------------------------------------------------

def _to_kernel_q(cfg: RingConfig, x, g_kv: int):
    """[C, hpl, D] -> kernel layout [G, Hg, C, D] (sharded-KV mode groups
    heads; gather mode runs one KV row per q head)."""
    c, hpl, d = x.shape
    if cfg.gather:
        return jnp.transpose(x, (1, 0, 2))[:, None]          # [hpl, 1, C, D]
    assert hpl % g_kv == 0, (hpl, g_kv)
    return jnp.transpose(x.reshape(c, g_kv, hpl // g_kv, d), (1, 2, 0, 3))


def _from_kernel_out(x):
    """[G, Hg, C, Dv] -> [C, hpl, Dv] (both head modes)."""
    g, hg, c, dv = x.shape
    return jnp.transpose(x, (2, 0, 1, 3)).reshape(c, g * hg, dv)


def _split_kv(cfg: RingConfig, kv_blk, kgi):
    """Carried block [C, G_kv, Dk(+Dv)] -> kernel k [G, C, Dk], v [G, C, Dv]
    (per-head gather applied in gather mode)."""
    dk, v_off, dv = cfg.kv_split
    k_blk = kv_blk[..., :dk]
    v_blk = kv_blk[..., v_off:v_off + dv]
    if cfg.gather:
        k_blk = jnp.take(k_blk, kgi, axis=1)
        v_blk = jnp.take(v_blk, kgi, axis=1)
    return jnp.transpose(k_blk, (1, 0, 2)), jnp.transpose(v_blk, (1, 0, 2))


def _pack_dkv(cfg: RingConfig, dk_s, dv_s, kgi, g_kv: int):
    """Kernel-layout (dk [G, C, Dk], dv [G, C, Dv]) -> carried-block layout
    [C, G_kv, Dk(+Dv)] f32, un-gathering per-head contributions back onto
    their KV group and folding dv into the fused (or v_in_k overlapped)
    column range."""
    dk, v_off, dv = cfg.kv_split
    dk_c = jnp.transpose(dk_s, (1, 0, 2)).astype(jnp.float32)  # [C, G|hpl, Dk]
    dv_c = jnp.transpose(dv_s, (1, 0, 2)).astype(jnp.float32)
    c = dk_c.shape[0]
    if cfg.gather:                       # scatter-add heads -> KV groups
        dk_c = jnp.zeros((c, g_kv, dk), jnp.float32).at[:, kgi].add(dk_c)
        dv_c = jnp.zeros((c, g_kv, dv), jnp.float32).at[:, kgi].add(dv_c)
    width = max(dk, v_off + dv)
    out = jnp.zeros((c, g_kv, width), jnp.float32)
    out = out.at[..., :dk].add(dk_c)
    return out.at[..., v_off:v_off + dv].add(dv_c)


# ---------------------------------------------------------------------------
# forward ring
# ---------------------------------------------------------------------------

def _zero_stats(g, hg, c, dv):
    return (jnp.zeros((g, hg, c, dv), jnp.float32),
            jnp.full((g, hg, c), NEG_INF, jnp.float32),
            jnp.zeros((g, hg, c), jnp.float32))


def _liveness(cfg: RingConfig, q_seg, q_pos):
    """Build the ``live(s, meta_b)`` step gate (group membership + block
    relevance) — identical gating to the oracle ring so fwd and bwd skip
    exactly the same blocks."""
    sizes_tbl, _ = composition_tables(cfg.composition)
    my_g = jnp.take(sizes_tbl, jax.lax.axis_index(cfg.hdp_axes))
    q_meta = _block_meta(q_seg, q_pos)

    def live(s, meta_b):
        lv = s < my_g
        if cfg.block_skip:
            lv &= _block_relevant(q_meta, meta_b, causal=cfg.causal,
                                  window=cfg.window)
        return lv

    return live


def ring_flash_fwd(cfg: RingConfig, q, kv, q_seg, k_seg, q_pos, k_pos, kgi,
                   record: bool = True):
    """Forward ring.  Local shapes: q [C, hpl, D]; kv [C, G_kv, Dk(+Dv)];
    metadata [C].  Returns (out [C, hpl, Dv], residuals).

    ``record=False`` suppresses the bytes-ledger comm record: under
    differentiation the custom_vjp machinery traces BOTH the primal and
    the fwd rule (each calling this function), so only the primal call
    records (kernels/ops.py passes record=False from the fwd rule)."""
    dk, v_off, dv = cfg.kv_split
    g_kv = kv.shape[1]
    qt = _to_kernel_q(cfg, q, g_kv)                      # [G, Hg, C, D]
    g_dim, hg, c = qt.shape[0], qt.shape[1], qt.shape[2]
    live = _liveness(cfg, q_seg, q_pos)

    def step_kernel(stats, kv_b, seg_b, pos_b):
        kb, vb = _split_kv(cfg, kv_b, kgi)
        return FA.flash_attention_fwd_carry(
            qt, kb, vb, q_seg, seg_b, q_pos, pos_b, *stats,
            scale=cfg.scale, causal=cfg.causal, window=cfg.window,
            softcap=cfg.softcap, block_q=cfg.block_q, block_k=cfg.block_k,
            interpret=cfg.interpret)

    # step 0: local block (always relevant — contains our own diagonal)
    stats = step_kernel(_zero_stats(g_dim, hg, c, dv), kv, k_seg, k_pos)

    steps = cfg.steps
    if steps:
        rot = lambda x: jax.tree.map(                              # noqa: E731
            lambda a: jax.lax.ppermute(a, cfg.hdp_axes, cfg.perm), x)

        def step(blk, stats, s):
            kv_b, seg_b, pos_b, meta_b = blk
            return jax.lax.cond(
                live(s, meta_b),
                lambda st: step_kernel(st, kv_b, seg_b, pos_b),
                lambda st: st, stats)

        # the rotation fetching step 1's block is issued here, with step 0's
        # kernel still outstanding — no data dependency between them, so XLA
        # overlaps the collective with compute (double buffering); the same
        # holds inside the loop, and the final step is peeled so no dead
        # rotation is issued.
        blk_tree = (kv, k_seg, k_pos, _block_meta(k_seg, k_pos))
        if record and ledger.tally_active():
            # bytes ledger: `steps` forward rotations in total (pre-loop +
            # scan/unroll + peeled final), same carried tree as the oracle
            # ring — forward-trace accounting only, matching obs/ledger.py
            ledger.record_comm("ring", steps * len(cfg.perm)
                               * ledger.tree_bytes(blk_tree))
        blk = rot(blk_tree)
        if cfg.unroll:
            for s in range(1, steps):
                nxt = rot(blk)
                stats = step(blk, stats, jnp.int32(s))
                blk = nxt
        elif steps > 1:
            def body(carry, s):
                blk, stats = carry
                nxt = rot(blk)
                return (nxt, step(blk, stats, s)), None
            (blk, stats), _ = jax.lax.scan(body, (blk, stats),
                                           jnp.arange(1, steps))
        stats = step(blk, stats, jnp.int32(steps))

    acc, m, l = stats
    out_t = att.finalize_stats(acc, m, l, q.dtype)       # [G, Hg, C, Dv]
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), NEG_INF)
    out = _from_kernel_out(out_t)
    return out, (qt, kv, q_seg, k_seg, q_pos, k_pos, kgi, out_t, lse)


# ---------------------------------------------------------------------------
# backward (reverse) ring
# ---------------------------------------------------------------------------

def ring_flash_bwd(cfg: RingConfig, res, do):
    """Reverse ring: per-step dq contributions fold into the local dq; the
    visiting block's dkv returns home in one reverse-``ppermute`` hop."""
    qt, kv, q_seg, k_seg, q_pos, k_pos, kgi, out_t, lse = res
    g_kv = kv.shape[1]
    c, hpl = do.shape[0], do.shape[1]
    do_t = _to_kernel_q(cfg, do, g_kv)                   # [G, Hg, C, Dv]
    live = _liveness(cfg, q_seg, q_pos)

    def step_bwd(kv_b, seg_b, pos_b):
        kb, vb = _split_kv(cfg, kv_b, kgi)
        return FA.flash_attention_bwd(
            qt, kb, vb, q_seg, seg_b, q_pos, pos_b, out_t, lse, do_t,
            scale=cfg.scale, causal=cfg.causal, window=cfg.window,
            softcap=cfg.softcap, block_q=cfg.block_q, block_k=cfg.block_k,
            interpret=cfg.interpret)

    def zeros_bwd():
        dk, v_off, dv = cfg.kv_split
        g = hpl if cfg.gather else g_kv
        return (jnp.zeros(qt.shape, qt.dtype),
                jnp.zeros((g, c, dk), kv.dtype),
                jnp.zeros((g, c, dv), kv.dtype))

    dq_t = jnp.zeros(qt.shape, jnp.float32)
    dkv = jnp.zeros(kv.shape, jnp.float32)
    blk = (kv, k_seg, k_pos, _block_meta(k_seg, k_pos))
    # Python-unrolled: steps is a small static and each step's reverse
    # permutation differs (one hop home per step).
    for s in range(cfg.steps + 1):
        kv_b, seg_b, pos_b, meta_b = blk
        if s == 0:                       # local block: computed unconditionally
            dq_s, dk_s, dv_s = step_bwd(kv_b, seg_b, pos_b)
        else:
            dq_s, dk_s, dv_s = jax.lax.cond(
                live(jnp.int32(s), meta_b),
                lambda b=kv_b, sg=seg_b, ps=pos_b: step_bwd(b, sg, ps),
                zeros_bwd)
        dq_t = dq_t + dq_s.astype(jnp.float32)
        dkv_c = _pack_dkv(cfg, dk_s, dv_s, kgi, g_kv)
        if s:
            # non-empty for every 1 <= s <= steps: the max-size group
            # always shifts (s < g_max), smaller groups send zeros
            dkv_c = jax.lax.ppermute(dkv_c, cfg.hdp_axes,
                                     _reverse_perm(cfg, s))
        dkv = dkv + dkv_c
        if s < cfg.steps:
            blk = jax.tree.map(
                lambda a: jax.lax.ppermute(a, cfg.hdp_axes, cfg.perm), blk)

    dq = _from_kernel_out(dq_t).astype(qt.dtype)         # [C, hpl, D]
    return dq, dkv.astype(kv.dtype)
