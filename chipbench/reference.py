"""Plain float32 reference of the dense decoder's training step.

It imports nothing of the program.  From the seed it draws the same
random weights the program's initializer draws (the same key splits and
the same normal draws, kept in float32 where the program rounds them to
bfloat16), reads the same documents from `traffic.Traffic`, and trains
them with the same AdamW settings (`OPT`):

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * (1 + s1)
                q, k, v = h Wq, h Wk, h Wv;  rotate q, k by RoPE (halves)
                x += softmax(q k^T / sqrt(d_h), causal, same document) v Wo
                h = rmsnorm(x) * (1 + s2)
                x += (silu(h Wgate) * (h Win)) Wout
    logits = (rmsnorm(x) * (1 + sf)) Whead
    loss = sum over every document token of -log softmax(logits)[next token]
           / tokens in the global batch

The norm weights are stored as offsets from 1 (and decayed towards 1), as
the program stores them.  Every matmul runs at
``jax.default_matmul_precision("highest")``.  Documents are packed into
rows of ``row_len`` tokens; one jitted call takes one row per device, and
attention runs in query blocks under remat.  The float32 training state
(the weights, both AdamW moments, the gradient sum and each call's
gradient) is sharded over the devices, each leaf on its first dimension
that the device count divides (`leaf_spec`); the gradient call gathers
the weights it uses.  On one device that layout is the whole array.  So a
row of 8,192 tokens a chip fits beside a quarter of the state of a
four-chip cell's 906M-parameter share
(`chipbench/tests/test_chipbench_reference.py` compiles it for four v5e
chips).

``control=True`` computes every matmul from operands rounded to float8
(e4m3, scaled per tensor): the precision below the configuration's
bfloat16.  ``fault`` plants one of the faults the comparison must catch
(`FAULTS`).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the optimizer both sides run (the harness hands the same numbers to the
# program's AdamWConfig)
OPT = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
           grad_clip=1.0, warmup_steps=100, total_steps=10_000,
           min_lr_frac=0.1, schedule="cosine")

EMBED_STD = 0.02
FP8_MAX = 448.0
Q_BLOCK = 512

# half: half of the documents left out, the mean taken over the rest
# double: one row's loss and gradient counted twice (an answer altered
#   where it is produced)
# scale: the gradient twice what it should be and the loss right (a token
#   count that divides the gradient alone off by two)
FAULTS = ("half", "double", "scale")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def init_weights(key, c: dict) -> dict:
    """Float32 weights, one entry per layer, drawn as the program draws
    them: keys = split(PRNGKey(seed), L + 3); layer i from keys[i], the
    head from keys[-2], the embedding from keys[-1]."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    nl, vocab = c["num_hidden_layers"], c["vocab_size"]
    normal = partial(jax.random.normal, dtype=jnp.float32)
    keys = jax.random.split(key, nl + 3)
    w = {"embed": normal(keys[-1], (vocab, d)) * EMBED_STD,
         "final_norm": jnp.zeros((d,), jnp.float32)}
    if not c["tie_word_embeddings"]:
        w["head"] = normal(keys[-2], (d, vocab)) / math.sqrt(d)
    for i in range(nl):
        ks = jax.random.split(keys[i], 4)
        ka = jax.random.split(ks[0], 4)
        km = jax.random.split(ks[1], 3)
        w[f"layer{i}"] = {
            "norm1": jnp.zeros((d,), jnp.float32),
            "norm2": jnp.zeros((d,), jnp.float32),
            "wq": normal(ka[0], (d, h * dh)) / math.sqrt(d),
            "wkv": normal(ka[1], (d, 2, hkv, dh)) / math.sqrt(d),
            "wo": normal(ka[2], (h * dh, d)) / math.sqrt(h * dh),
            "win": normal(km[0], (d, ff)) / math.sqrt(d),
            "wout": normal(km[1], (ff, d)) / math.sqrt(ff),
            "wgate": normal(km[2], (d, ff)) / math.sqrt(d),
        }
    return w


def leaf_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; the gradient
    passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    xq = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(xq - x)


def _ein(spec, a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, seg, control):
    """q [R, H, dh], k/v [R, H, dh] -> [R, H, dh]; causal within a
    document."""
    r, h, dh = q.shape
    bq = min(Q_BLOCK, r)
    kidx = jnp.arange(r)

    @jax.checkpoint
    def block(args):
        i, qi = args
        qidx = i * bq + jnp.arange(bq)
        s = _ein("qhd,khd->hqk", qi, k, control) / math.sqrt(dh)
        mask = (seg[qidx][:, None] == seg[None, :]) \
            & (kidx[None, :] <= qidx[:, None])
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hqk,khd->qhd", p, v, control)

    out = jax.lax.map(block, (jnp.arange(r // bq),
                              q.reshape(r // bq, bq, h, dh)))
    return out.reshape(r, h, dh)


def row_nll(w, c: dict, tokens, labels, seg, pos, *, control=False):
    """Per-token negative log-likelihood of one packed row."""
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"]
    dh = c.get("head_dim") or d // h
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    r = tokens.shape[0]
    group = jnp.arange(h) // (h // hkv)           # kv head of each q head
    x = jnp.take(w["embed"], tokens, axis=0)

    @jax.checkpoint
    def layer(x, lw):
        a = _rmsnorm(x, lw["norm1"], eps)
        q = _ein("td,de->te", a, lw["wq"], control).reshape(r, h, dh)
        kv = _ein("td,dsgk->tsgk", a, lw["wkv"], control)
        k, v = kv[:, 0][:, group], kv[:, 1][:, group]
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, seg, control).reshape(r, h * dh)
        x = x + _ein("te,ed->td", o, lw["wo"], control)
        a = _rmsnorm(x, lw["norm2"], eps)
        f = jax.nn.silu(_ein("td,df->tf", a, lw["wgate"], control)) \
            * _ein("td,df->tf", a, lw["win"], control)
        return x + _ein("tf,fd->td", f, lw["wout"], control)

    for i in range(c["num_hidden_layers"]):
        x = layer(x, w[f"layer{i}"])
    x = _rmsnorm(x, w["final_norm"], eps)
    head = w["head"] if "head" in w else w["embed"].T
    logits = _ein("td,dv->tv", x, head, control)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.where(seg > 0, lse - tgt, 0.0)


# ---------------------------------------------------------------------------
# batches
# ---------------------------------------------------------------------------

def pack_rows(lengths: Sequence[int], row_len: int) -> List[List[int]]:
    """First-fit decreasing of document indices into rows."""
    rows: List[List[int]] = []
    free: List[int] = []
    for i in sorted(range(len(lengths)), key=lambda j: -lengths[j]):
        n = lengths[i]
        assert n <= row_len, (n, row_len)
        for r, f in enumerate(free):
            if f >= n:
                rows[r].append(i)
                free[r] -= n
                break
        else:
            rows.append([i])
            free.append(row_len - n)
    return rows


def step_rows(traffic, step: int, row_len: int, n_dev: int,
              fault: Optional[str] = None):
    """-> (row arrays {tokens, labels, seg, pos} [n_rows, row_len], denom,
    per-row weights).  Rows are padded to a multiple of ``n_dev``."""
    lengths = traffic.step_lengths(step)
    docs = list(range(len(lengths)))
    if fault == "half":
        keep, acc = [], 0
        for i in docs:
            if acc >= sum(lengths) // 2:
                break
            keep.append(i)
            acc += lengths[i]
        docs = keep
    rows = pack_rows([lengths[i] for i in docs], row_len)
    n_rows = -(-len(rows) // n_dev) * n_dev
    arr = {k: np.zeros((n_rows, row_len), np.int32)
           for k in ("tokens", "labels", "seg", "pos")}
    for r, members in enumerate(rows):
        cur = 0
        for j in members:
            i = docs[j]
            n = lengths[i]
            tok, lab = traffic.document(step, i)
            arr["tokens"][r, cur:cur + n] = tok
            arr["labels"][r, cur:cur + n] = lab
            arr["seg"][r, cur:cur + n] = i + 1
            arr["pos"][r, cur:cur + n] = np.arange(n)
            cur += n
    denom = float(sum(lengths[i] for i in docs))
    weight = np.ones(n_rows, np.float32)
    if fault == "double":
        weight[0] = 2.0
    return arr, denom, weight


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------

def _lr(step):
    o = OPT
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    decay = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 \
        * (1 + jnp.cos(jnp.pi * frac))
    return o["lr"] * warm * decay


def _adamw(w, g, m, v, step):
    o = OPT
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, o["grad_clip"] / (gnorm + 1e-12)), g)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    b1c = 1 - o["b1"] ** step
    b2c = 1 - o["b2"] ** step
    lr = _lr(step)
    w = jax.tree.map(
        lambda p, a, b: p - lr * ((a / b1c) / (jnp.sqrt(b / b2c) + o["eps"])
                                  + o["weight_decay"] * p), w, m, v)
    return w, m, v, g, gnorm


def leaf_spec(shape, n_dev: int) -> P:
    """A state leaf's layout over the ``rows`` axis: sharded on its first
    dimension that ``n_dev`` divides, replicated where none does."""
    for i, n in enumerate(shape):
        if n % n_dev == 0:
            return P(*(None,) * i, "rows")
    return P()


class Reference:
    """Trains the reference for a few steps on ``devices``; keeps the
    per-leaf norms the comparison reads."""

    def __init__(self, c: dict, traffic, devices, *, control=False,
                 fault: Optional[str] = None):
        assert fault is None or fault in FAULTS, fault
        self.c, self.traffic, self.fault = c, traffic, fault
        self.mesh = Mesh(np.array(devices), ("rows",))
        self.n_dev = len(devices)
        self.row_len = traffic.context
        rep = NamedSharding(self.mesh, P())
        self.rows_sharding = NamedSharding(self.mesh, P("rows"))
        init = partial(init_weights, c=c)
        shapes = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        # the weights, the AdamW moments and every gradient, each leaf
        # sharded over the devices (`leaf_spec`)
        self.state_sharding = sh = jax.tree.map(
            lambda x: NamedSharding(self.mesh, leaf_spec(x.shape, self.n_dev)),
            shapes)

        def loss(w, batch, weight, denom):
            nll = jax.vmap(lambda t, lb, s, p: row_nll(
                w, c, t, lb, s, p, control=control))(
                batch["tokens"], batch["labels"], batch["seg"], batch["pos"])
            out = jnp.sum(nll * weight[:, None]) / denom
            if fault == "scale":
                out = 2 * out - jax.lax.stop_gradient(out)
            return out

        with jax.default_matmul_precision("highest"):
            self._init = jax.jit(init, out_shardings=sh)
            self._grad = jax.jit(jax.value_and_grad(loss),
                                 out_shardings=(rep, sh))
            self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                                donate_argnums=(0,), out_shardings=sh)
            self._adamw = jax.jit(_adamw, donate_argnums=(0, 1, 2, 3),
                                  out_shardings=(sh, sh, sh, sh, rep))

    def init(self, seed: int):
        """-> (w, m, v): the weights drawn from ``seed`` and zero AdamW
        moments, laid out as `state_sharding` says."""
        with jax.default_matmul_precision("highest"):
            w = self._init(jax.random.PRNGKey(seed))
            # zeros_like keeps each leaf's sharding
            return (w, jax.tree.map(jnp.zeros_like, w),
                    jax.tree.map(jnp.zeros_like, w))

    def step(self, w, m, v, s: int):
        """Trains step ``s`` (from 0); donates ``w``, ``m`` and ``v``.  ->
        (w, m, v, loss, global norm of the gradient before clipping, the
        clipped gradient)."""
        with jax.default_matmul_precision("highest"):
            arr, denom, weight = step_rows(self.traffic, s, self.row_len,
                                           self.n_dev, self.fault)
            g, total = None, 0.0
            for r0 in range(0, arr["tokens"].shape[0], self.n_dev):
                batch = {k: jax.device_put(a[r0:r0 + self.n_dev],
                                           self.rows_sharding)
                         for k, a in arr.items()}
                wt = jax.device_put(weight[r0:r0 + self.n_dev],
                                    self.rows_sharding)
                lv, gi = self._grad(w, batch, wt, jnp.float32(denom))
                total += float(lv)
                g = gi if g is None else self._add(g, gi)
            w, m, v, gc, gn = self._adamw(w, g, m, v, jnp.float32(s + 1))
            return w, m, v, total, float(gn), gc

    def run(self, seed: int, steps: int) -> Dict:
        """-> {"loss": [per step], "gnorm": [per step, the global norm of
        the gradient before clipping], "grad1": {leaf: norm of the first
        clipped gradient}, "delta": {leaf: norm of the change after
        ``steps``}}."""
        w, m, v = self.init(seed)
        w0 = jax.device_get(w)              # on the host: HBM is full
        losses, gnorms, grad1 = [], [], None
        for s in range(steps):
            w, m, v, lv, gn, gc = self.step(w, m, v, s)
            losses.append(lv)
            gnorms.append(gn)
            if s == 0:
                grad1 = leaf_norms(gc)
            del gc
        del m, v
        w = jax.device_get(w)
        delta = host_delta_norms(w, w0)
        return {"loss": losses, "gnorm": gnorms, "grad1": grad1,
                "delta": delta}


def host_delta_norms(after, before) -> Dict[str, float]:
    flat_a = jax.tree_util.tree_flatten_with_path(after)[0]
    flat_b = jax.tree_util.tree_flatten(before)[0]
    return {leaf_name(p): float(np.linalg.norm(
        np.asarray(a, np.float32) - np.asarray(b, np.float32)))
        for (p, a), b in zip(flat_a, flat_b)}


def leaf_norms(tree) -> Dict[str, float]:
    """{leaf name: float32 norm}, fetched once."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.linalg.norm(x.reshape(-1)) for _, x in flat])
    return {leaf_name(p): float(n) for (p, _), n in zip(flat, norms)}
