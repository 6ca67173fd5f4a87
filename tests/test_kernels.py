"""Pallas kernel sweeps (shapes × dtypes) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _meta(rng, t, n_seq):
    bounds = sorted(rng.choice(np.arange(1, t), n_seq - 1, replace=False)) \
        if n_seq > 1 else []
    bounds = [0] + list(bounds) + [t]
    seg = np.zeros(t, np.int32)
    pos = np.zeros(t, np.int32)
    for i in range(len(bounds) - 1):
        a, b = bounds[i], bounds[i + 1]
        seg[a:b] = i + 1
        pos[a:b] = np.arange(b - a)
    return jnp.array(seg), jnp.array(pos)


@pytest.mark.parametrize("g,hg,t,s,dk,dv", [
    (1, 1, 64, 64, 32, 32),
    (2, 2, 64, 128, 64, 64),
    (2, 4, 128, 64, 32, 16),      # Dv != Dk (MLA-style)
    (4, 1, 64, 64, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(g, hg, t, s, dk, dv, dtype):
    rng = np.random.RandomState(g * 100 + hg)
    q = jnp.array(rng.randn(g, hg, t, dk), dtype)
    k = jnp.array(rng.randn(g, s, dk), dtype)
    v = jnp.array(rng.randn(g, s, dv), dtype)
    q_seg, q_pos = _meta(rng, t, 3)
    k_seg, k_pos = _meta(rng, s, 3)
    out = ops.flash_attention(q, k, v, q_seg, k_seg, q_pos, k_pos,
                              dk ** -0.5, True, 0, 0.0, 32, 32,
                              ops.interpret_on(q.sharding))
    oracle = ref.flash_attention_ref(q, k, v, q_seg, k_seg, q_pos, k_pos,
                                     scale=dk ** -0.5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0), (0, 30.0),
                                            (16, 50.0)])
def test_flash_attention_grads(window, softcap):
    rng = np.random.RandomState(0)
    g, hg, t, s, d = 2, 2, 64, 64, 32
    q = jnp.array(rng.randn(g, hg, t, d), jnp.float32)
    k = jnp.array(rng.randn(g, s, d), jnp.float32)
    v = jnp.array(rng.randn(g, s, d), jnp.float32)
    q_seg, q_pos = _meta(rng, t, 2)
    k_seg, k_pos = _meta(rng, s, 2)

    interpret = ops.interpret_on(q.sharding)

    def f(q, k, v):
        return (ops.flash_attention(q, k, v, q_seg, k_seg, q_pos, k_pos,
                                    0.2, True, window, softcap, 32, 32,
                                    interpret) ** 2).sum()

    def fr(q, k, v):
        o = ref.flash_attention_ref(q, k, v, q_seg, k_seg, q_pos, k_pos,
                                    scale=0.2, window=window, softcap=softcap)
        return (o.astype(jnp.float32) ** 2).sum()

    gk = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                                   rtol=1e-3)


@pytest.mark.parametrize("t,v", [(64, 512), (128, 1024), (32, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ce_sweep(t, v, dtype):
    rng = np.random.RandomState(t)
    logits = jnp.array(rng.randn(t, v) * 3, dtype)
    labels = jnp.array(rng.randint(0, v, t), jnp.int32)
    interpret = ops.interpret_on(logits.sharding)
    nll = ops.fused_softmax_xent(logits, labels, interpret)
    nll_r, _ = ref.fused_ce_ref(logits, labels)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(nll), np.asarray(nll_r), atol=tol,
                               rtol=tol)
    g = jnp.array(rng.randn(t), jnp.float32)
    d1 = jax.grad(lambda lg: (ops.fused_softmax_xent(lg, labels, interpret)
                              * g).sum())(logits)
    d2 = ref.fused_ce_grad_ref(logits, labels, g)
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("v,block_v", [(2500, 2048), (1000, 384)])
def test_fused_ce_ragged_vocab(v, block_v):
    """A vocabulary that does not divide into panels (Llama's 128,256 does
    not divide by 2048): the ragged last panel is masked out of the forward
    softmax and its columns of dlogits are written.  Reference: the jnp
    token-level loss of core/loss.py and its gradient."""
    from repro.core.loss import token_ce_from_logits
    from repro.kernels import fused_ce as CE
    rng = np.random.RandomState(v)
    t = 32
    logits = jnp.array(rng.randn(t, v) * 3, jnp.float32)
    labels = jnp.array(rng.randint(0, v, t), jnp.int32)
    labels = labels.at[0].set(v - 1)            # a label in the ragged tail
    valid = jnp.ones(t, bool)
    interpret = ops.interpret_on(logits.sharding)

    def ref_loss(lg):
        return token_ce_from_logits(lg, labels, valid, float(t))[0]

    nll, lse = CE.fused_ce_fwd(logits, labels, interpret=interpret,
                               block_v=block_v)
    np.testing.assert_allclose(float(nll.sum()) / t, float(ref_loss(logits)),
                               rtol=1e-5)
    d_ref = np.asarray(jax.grad(ref_loss)(logits))
    g = jnp.full((t,), 1.0 / t, jnp.float32)
    d_kernel = CE.fused_ce_bwd(logits, labels, lse, g, interpret=interpret,
                               block_v=block_v)
    np.testing.assert_allclose(np.asarray(d_kernel), d_ref, atol=1e-6,
                               rtol=1e-4)
    # the production wrapper (default panels) through jax.grad
    d_ops = jax.grad(lambda lg: (ops.fused_softmax_xent(lg, labels, interpret)
                                 * g).sum())(logits)
    np.testing.assert_allclose(np.asarray(d_ops), d_ref, atol=1e-6,
                               rtol=1e-4)
