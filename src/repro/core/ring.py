"""HDP dist-attention: subgroup ring attention on a static TPU mesh.

ByteScale's dynamic NCCL groups become **static ring compositions**: a
composition ``(96, 1, 1, ..., 1)`` (summing to the HDP axis size) describes
disjoint contiguous rank groups; each group of size g runs a g-step zigzag
ring; singleton groups do purely local attention with *zero* collective
traffic.  Each distinct composition compiles once (the XLA executable cache
plays the role of ByteScale's NCCL-group cache); the wave scheduler keeps the
set of live compositions small (powers of two + a few mixed leftovers).

Heterogeneous work inside one SPMD program: every rank knows its own group
size ``my_g`` (a traced lookup into the static composition table) and skips
ring steps ``s >= my_g`` through ``lax.cond`` — runtime-skipped compute, the
TPU analogue of "some ranks do less work".

The ring carries (k, v, k_seg, k_pos) plus O(1) block metadata (position and
segment ranges) that enables **block skipping**: a ring step whose incoming
KV block provably cannot attend to any local query (wrong segments, entirely
in the future, or beyond the sliding window) skips its O(C²) block compute.
This is a beyond-paper optimization enabled by carrying metadata with the
ring (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import attention as att
from repro.obs import ledger

AxisNames = Tuple[str, ...]


# ---------------------------------------------------------------------------
# compositions
# ---------------------------------------------------------------------------

def uniform_composition(hdp_size: int, group: int) -> Tuple[int, ...]:
    assert hdp_size % group == 0, (hdp_size, group)
    return (group,) * (hdp_size // group)


def composition_tables(composition: Sequence[int]):
    """Per-rank (group_size, group_start) arrays for a composition."""
    sizes, starts = [], []
    start = 0
    for g in composition:
        sizes += [g] * g
        starts += [start] * g
        start += g
    return jnp.array(sizes, jnp.int32), jnp.array(starts, jnp.int32)


def ring_perm(composition: Sequence[int]) -> list:
    """Union of intra-group rings; singleton groups send nothing."""
    perm = []
    start = 0
    for g in composition:
        if g > 1:
            for j in range(g):
                perm.append((start + j, start + (j + 1) % g))
        start += g
    return perm


def linear_rank(hdp_axes: AxisNames) -> jnp.ndarray:
    return jax.lax.axis_index(hdp_axes)


# ---------------------------------------------------------------------------
# block metadata for ring-step skipping
# ---------------------------------------------------------------------------

def _block_meta(seg, pos):
    """O(1) scalars describing a KV block: position/segment ranges over
    non-padding tokens."""
    valid = seg > 0
    big = jnp.int32(2**30)
    pos_min = jnp.min(jnp.where(valid, pos, big))
    pos_max = jnp.max(jnp.where(valid, pos, -1))
    seg_min = jnp.min(jnp.where(valid, seg, big))
    seg_max = jnp.max(jnp.where(valid, seg, -1))
    return jnp.stack([pos_min, pos_max, seg_min, seg_max])


def _block_relevant(q_meta, k_meta, *, causal: bool, window: int) -> jnp.ndarray:
    """Can ANY local query attend to ANY token of this KV block?"""
    q_pos_min, q_pos_max, q_seg_min, q_seg_max = (q_meta[i] for i in range(4))
    k_pos_min, k_pos_max, k_seg_min, k_seg_max = (k_meta[i] for i in range(4))
    ok = (k_seg_min <= q_seg_max) & (q_seg_min <= k_seg_max)   # segment ranges overlap
    ok &= k_seg_max >= 0                                       # block not all padding
    ok &= q_seg_max >= 0
    if causal:
        ok &= k_pos_min <= q_pos_max                           # not entirely in the future
    if window:
        ok &= k_pos_max > q_pos_min - window                   # not entirely out of window
    return ok


# ---------------------------------------------------------------------------
# ring attention (shard_map body)
# ---------------------------------------------------------------------------

def _ring_attention_local(q, kv, q_seg, k_seg, q_pos, k_pos, *,
                          hdp_axes: AxisNames,
                          composition: Tuple[int, ...],
                          kv_split: Tuple[int, int, int],    # (dk, v_off, dv)
                          kv_group_index,       # [hpl] int32 or None (kv sharded)
                          scale: float, causal: bool, window: int,
                          softcap: float, kv_chunk: int, block_skip: bool,
                          unroll: bool = False):
    """Per-rank body. Local shapes:
        q [C, hpl, D]; kv [C, G(_local), Dk+Dv] fused (or [C, G, Dk] when v
        is a prefix of k — the MLA latent ring ships 576 floats/token
        instead of the expanded 16×320).
    """
    dk, v_off, dv = kv_split
    if kv_group_index is not None:
        # replicated KV: gather the kv head for each local q head -> Hg=1
        kq = q[:, :, None, :]                                  # [C, hpl(=G), 1, D]
        gather = lambda a: jnp.take(a, kv_group_index, axis=1)  # noqa: E731
    else:
        g_local = kv.shape[1]
        hpg = q.shape[1] // g_local
        kq = q.reshape(q.shape[0], g_local, hpg, q.shape[2])   # [C, Gl, Hg, D]
        gather = lambda a: a                                    # noqa: E731

    c = q.shape[0]
    t, g_dim, hg = kq.shape[0], kq.shape[1], kq.shape[2]

    sizes_tbl, _ = composition_tables(composition)
    rank = linear_rank(hdp_axes)
    my_g = jnp.take(sizes_tbl, rank)
    steps = max(composition) - 1
    perm = ring_perm(composition)

    q_meta = _block_meta(q_seg, q_pos)

    def compute_block(kv_blk, seg_blk, pos_blk):
        k_blk = kv_blk[..., :dk]
        v_blk = kv_blk[..., v_off:v_off + dv]
        return att.block_chunked_stats(
            kq, gather(k_blk), gather(v_blk), q_seg, seg_blk, q_pos, pos_blk,
            scale=scale, causal=causal, window=window, softcap=softcap,
            kv_chunk=kv_chunk)

    # step 0: local block (always relevant — contains our own diagonal)
    stats = compute_block(kv, k_seg, k_pos)

    if steps == 0:
        return att.finalize_stats(*stats, q.dtype).reshape(c, -1, dv)

    k_meta = _block_meta(k_seg, k_pos)

    if ledger.tally_active():
        # bytes ledger: the carried block tree rotates once per ring step
        # over len(perm) edges — fleet bytes are static at trace time
        ledger.record_comm("ring", steps * len(perm) * ledger.tree_bytes(
            (kv, k_seg, k_pos, k_meta)))

    def body(carry, s):
        blk, stats = carry
        blk = jax.tree.map(
            lambda a: jax.lax.ppermute(a, hdp_axes, perm), blk)
        kv_b, seg_b, pos_b, meta_b = blk
        live = s < my_g
        if block_skip:
            live &= _block_relevant(q_meta, meta_b, causal=causal, window=window)
        new = jax.lax.cond(
            live,
            lambda: compute_block(kv_b, seg_b, pos_b),
            lambda: att.zero_stats(t, g_dim, hg, dv))
        return (blk, att.merge_stats(stats, new)), None

    init = ((kv, k_seg, k_pos, k_meta), stats)
    if unroll:
        # python-unrolled ring: every step appears in HLO (used by the
        # cost-analysis lowering — XLA counts while-loop bodies only once)
        carry = init
        for s in range(1, steps + 1):
            carry, _ = body(carry, jnp.int32(s))
        stats = carry[1]
    else:
        (_, stats), _ = jax.lax.scan(body, init, jnp.arange(1, steps + 1))
    out = att.finalize_stats(*stats, q.dtype)                  # [C, G, Hg, Dv]
    return out.reshape(c, -1, dv)                              # [C, hpl, Dv]


def ring_attention(q, k, v, q_seg, k_seg, q_pos, k_pos, *,
                   mesh, hdp_axes: AxisNames, model_axis: Optional[str],
                   composition: Tuple[int, ...], kv_sharded: bool,
                   kv_group_of_head=None,       # global [h_pad] (replicated case)
                   scale: float, causal: bool = True, window: int = 0,
                   softcap: float = 0.0, kv_chunk: int = 1024,
                   block_skip: bool = True, attn_impl: str = "ref",
                   v_in_k: Optional[Tuple[int, int]] = None,
                   unroll: bool = False,
                   block_q: int = 256, block_k: int = 512):
    """pjit-level entry point.

    Global shapes: q [T, h_pad, D] (heads sharded over `model_axis`),
    k/v [T, G, D/Dv] (G sharded over model iff kv_sharded else replicated),
    q_seg/k_seg/q_pos/k_pos [T] (or [T, 3] M-RoPE scalarized by caller).

    ``v_in_k=(offset, dv)`` declares that v is a slice of k (MLA latent:
    v = k[..., :512]); the ring then carries only k.  Otherwise k and v are
    fused into one carried tensor (same bytes, single collective).

    ``attn_impl`` selects the per-step compute backend: ``"ref"`` runs the
    jnp oracle ring (`_ring_attention_local`); ``"pallas"`` dispatches the
    whole ring to the fused ring-flash engine (kernels/ring_flash.py) —
    each step a state-carrying Pallas flash kernel with its own reverse
    ring for the backward pass; ``block_q``/``block_k`` are its tile
    shapes.  Both backends share the composition, ppermute schedule, and
    block-skipping metadata, so they are numerically interchangeable.
    """
    tp = mesh.shape[model_axis] if model_axis else 1
    hpl = q.shape[1] // tp
    use_group_gather = (not kv_sharded) and (kv_group_of_head is not None)

    if v_in_k is not None:
        v_off, dv = v_in_k
        kv = k
        kv_split = (k.shape[-1], v_off, dv)
    else:
        kv = jnp.concatenate([k, v], axis=-1)
        kv_split = (k.shape[-1], k.shape[-1], v.shape[-1])

    hdp_spec = P(hdp_axes)
    head_spec = P(hdp_axes, model_axis, None)
    kv_spec = P(hdp_axes, model_axis if kv_sharded else None, None)

    if attn_impl == "pallas":
        # lazy import: kernels/ring_flash imports this module's ring helpers
        from repro.kernels import ops as kernel_ops
        from repro.kernels.ring_flash import RingConfig
        ring_cfg = RingConfig(
            hdp_axes=hdp_axes, composition=composition, kv_split=kv_split,
            gather=use_group_gather, scale=scale, causal=causal,
            window=window, softcap=softcap, block_q=block_q,
            block_k=block_k, block_skip=block_skip, unroll=unroll,
            interpret=kernel_ops.interpret_on(mesh))
        ring_fn = kernel_ops.make_ring_flash(ring_cfg)

    def body(q_, kv_, qs_, ks_, qp_, kp_):
        if use_group_gather:
            m = jax.lax.axis_index(model_axis) if model_axis else 0
            kgi = jax.lax.dynamic_slice_in_dim(kv_group_of_head, m * hpl, hpl)
        else:
            kgi = None
        if attn_impl == "pallas":
            return ring_fn(q_, kv_, qs_, ks_, qp_, kp_,
                           kgi if kgi is not None
                           else jnp.zeros((1,), jnp.int32))
        return _ring_attention_local(
            q_, kv_, qs_, ks_, qp_, kp_,
            hdp_axes=hdp_axes, composition=composition, kv_split=kv_split,
            kv_group_index=kgi, scale=scale, causal=causal, window=window,
            softcap=softcap, kv_chunk=kv_chunk, block_skip=block_skip,
            unroll=unroll)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(head_spec, kv_spec, hdp_spec, hdp_spec, hdp_spec, hdp_spec),
        out_specs=head_spec,
        check_vma=False)
    return fn(q, kv, q_seg, k_seg, q_pos, k_pos)


def shift_from_prev_rank(x, *, hdp_axes: AxisNames,
                         composition: Tuple[int, ...]):
    """Bring each rank the value from its predecessor *within its group*
    (first rank of every group receives zeros).  Used for cross-rank token
    shift (RWKV) and sequential conv state (Mamba) under sequence sharding."""
    perm = []
    start = 0
    for g in composition:
        for j in range(g - 1):
            perm.append((start + j, start + j + 1))
        start += g
    if not perm:
        return jax.tree.map(jnp.zeros_like, x)
    return jax.tree.map(lambda a: jax.lax.ppermute(a, hdp_axes, perm), x)


# ---------------------------------------------------------------------------
# distributed chunk-state scan (RWKV / Mamba under HDP)
# ---------------------------------------------------------------------------

def distributed_state_scan(A_local, b_local, *, hdp_axes: AxisNames,
                           composition: Tuple[int, ...]):
    """Exclusive prefix of per-rank linear-recurrence summaries.

    Each rank reduces its local chunk sweep to ``h_out = A_local ⊙ h_in +
    b_local`` (elementwise/diagonal decay — true for both Mamba's selective
    SSM and RWKV-6's data-dependent decay).  Sequences sharded over a rank
    group need the incoming state ``h_in`` = exclusive prefix over the group.

    HDP adaptation (the paper covers attention only — see DESIGN.md §5): we
    all-gather the tiny (O(d·state)) per-rank summaries over the HDP axis and
    compute the masked group-prefix locally.  States are ~1 MB; the gather is
    negligible next to activations and keeps the schedule static.
    """
    sizes_tbl, starts_tbl = composition_tables(composition)
    rank = linear_rank(hdp_axes)
    my_start = jnp.take(starts_tbl, rank)

    def gather(x):
        return jax.lax.all_gather(x, hdp_axes, axis=0, tiled=False)

    A_all = gather(A_local)                                    # [R, ...]
    b_all = gather(b_local)
    n = A_all.shape[0]
    ranks = jnp.arange(n)
    # mask ranks outside my group or >= me; exclusive prefix in rank order
    in_prefix = (ranks >= my_start) & (ranks < rank)

    def step(h, i):
        a_i = A_all[i]
        b_i = b_all[i]
        take = in_prefix[i]
        h = jnp.where(take, a_i * h + b_i, h)
        return h, None

    h0 = jnp.zeros_like(b_local)
    h_in, _ = jax.lax.scan(step, h0, ranks)
    return h_in


# ---------------------------------------------------------------------------
# flash-decoding combine (sharded KV cache attention for serve steps)
# ---------------------------------------------------------------------------

def decode_attention_sharded(q, k_cache, v_cache, cache_len, *,
                             mesh, batch_axes: AxisNames, seq_axes: AxisNames,
                             scale: float, softcap: float = 0.0,
                             window: int = 0):
    """One-token attention against a KV cache sharded along its sequence dim.

    q        [B, G, Hg, D]        (B sharded over `batch_axes`, replicated
                                   over `seq_axes`)
    k_cache  [B, S, G, D]         (B over `batch_axes`, S over `seq_axes`)
    v_cache  [B, S, G, Dv]
    cache_len[B]                  valid prefix length per sequence
    Returns  [B, G, Hg, Dv]       (B over `batch_axes`).

    Each shard computes a partial online-softmax over its cache slice; the
    partials combine with a (max, sum, acc) psum over `seq_axes` — the
    TPU-native flash-decoding equivalent.  For global_batch=1 (long_500k)
    pass batch_axes=() and shard the cache sequence over every axis.
    """

    def body(q_, k_, v_, clen_):
        shard_idx = jax.lax.axis_index(seq_axes)
        base = shard_idx * k_.shape[1]
        pos = base + jnp.arange(k_.shape[1])                   # [S_local]
        valid = pos[None, :] < clen_[:, None]                  # [B, S_local]
        if window:
            valid &= pos[None, :] >= (clen_[:, None] - window)
        s = jnp.einsum("bghd,bsgd->bghs", q_.astype(jnp.float32),
                       k_.astype(jnp.float32)) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid[:, None, None, :], s, att.NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bghs,bsgd->bghd", p, v_.astype(jnp.float32))
        # combine across shards
        m = jax.lax.pmax(m_loc, seq_axes)
        w = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * w, seq_axes)
        acc = jax.lax.psum(acc * w[..., None], seq_axes)
        safe_l = jnp.where(l > 0, l, 1.0)
        out = jnp.where((l > 0)[..., None], acc / safe_l[..., None], 0.0)
        return out.astype(q_.dtype)

    b_ax = batch_axes if batch_axes else None
    q_spec = P(b_ax)
    cache_spec = P(b_ax, seq_axes, None, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, q_spec),
        out_specs=q_spec,
        check_vma=False)
    return fn(q, k_cache, v_cache, cache_len)
