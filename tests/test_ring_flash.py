"""Ring-flash engine parity: the Pallas-backed ring (attn_impl="pallas")
vs the jnp oracle ring, fwd + bwd, across compositions (g ∈ {1, 2, 4} and
mixed), packed segments, zigzag layout, sliding window, Gemma softcap and
both head modes — interpret mode, 8 CPU devices (subprocesses, so the
device-count flag never leaks into the smoke tests)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_ring_flash_single_device_matches_oracle(rt1):
    """g = 1 fast path through the engine (no subprocess): fwd + grads."""
    from repro.core.ring import ring_attention

    mesh = rt1.mesh
    T, H, G, D = 32, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (T, G, D), jnp.float32)
    v = jax.random.normal(ks[2], (T, G, D), jnp.float32)
    seg = jnp.array([1] * 20 + [2] * 8 + [0] * 4)
    pos = jnp.concatenate([jnp.arange(20), jnp.arange(8),
                           jnp.zeros(4, jnp.int32)])

    def f(impl, q, k, v):
        o = ring_attention(q, k, v, seg, seg, pos, pos, mesh=mesh,
                           hdp_axes=rt1.hdp_axes, model_axis=rt1.model_axis,
                           composition=(1,), kv_sharded=True, scale=0.3,
                           window=7, softcap=20.0, attn_impl=impl)
        return (o.astype(jnp.float32) ** 2).sum()

    l_ref, g_ref = jax.value_and_grad(
        lambda q, k, v: f("ref", q, k, v), argnums=(0, 1, 2))(q, k, v)
    l_pal, g_pal = jax.value_and_grad(
        lambda q, k, v: f("pallas", q, k, v), argnums=(0, 1, 2))(q, k, v)
    assert float(abs(l_ref - l_pal)) < 1e-3 * float(abs(l_ref))
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=3e-4)


RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.ring import ring_attention

mesh = compat.make_mesh((4,2), ("data","model"))
compat.set_mesh(mesh)
C, R = 16, 4; T = C*R
H, G, D = 4, 2, 8
ks = jax.random.split(jax.random.PRNGKey(1), 3)
q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
k = jax.random.normal(ks[1], (T, G, D), jnp.float32)
v = jax.random.normal(ks[2], (T, G, D), jnp.float32)
# packed layout: two sequences + padding, shuffled across ranks
seg = np.zeros(T, np.int32); pos = np.zeros(T, np.int32)
order = np.random.RandomState(0).permutation(T)
toks = [(1,i) for i in range(28)] + [(2,i) for i in range(32)] + [(0,0)]*4
for slot, (s_,p_) in zip(order, toks): seg[slot], pos[slot] = s_, p_
seg = jnp.array(seg); pos = jnp.array(pos)

def check(comp, seg, pos, window, softcap, tag):
    def f(impl, q, k, v):
        o = ring_attention(q, k, v, seg, seg, pos, pos, mesh=mesh,
                           hdp_axes=("data",), model_axis="model",
                           composition=comp, kv_sharded=True, scale=0.3,
                           window=window, softcap=softcap, attn_impl=impl,
                           kv_chunk=8)
        return (o.astype(jnp.float32)**2).sum(), o
    vg = lambda impl: jax.jit(jax.value_and_grad(
        lambda q,k,v: f(impl,q,k,v), argnums=(0,1,2), has_aux=True))(q,k,v)
    (l1, o1), g1 = vg("ref")
    (l2, o2), g2 = vg("pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=2e-5, err_msg=tag+" out")
    for nm, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   rtol=3e-4, err_msg=tag+" d"+nm)

# g in {1, 2, 4} + a mixed composition, with window/softcap variants
check((4,),        seg, pos, 0, 0.0,  "g4")
check((4,),        seg, pos, 9, 25.0, "g4_win_cap")
check((2,2),       seg, pos, 0, 0.0,  "g2")
check((2,2),       seg, pos, 9, 25.0, "g2_win_cap")
check((1,1,1,1),   seg, pos, 9, 25.0, "g1_win_cap")
check((2,1,1),     seg, pos, 0, 0.0,  "mixed")
check((2,1,1),     seg, pos, 9, 25.0, "mixed_win_cap")

# zigzag layout: planner-style symmetric chunk pairs (Fig. 14), one
# 32-token sequence per 2-rank group, composition (2,2)
from repro.data.packing import zigzag_chunks
zseg = np.zeros(T, np.int32); zpos = np.zeros(T, np.int32)
for grp, sid in ((0, 1), (1, 2)):        # group index -> segment id
    for j, lo, hi in zigzag_chunks(32, 2):
        r = 2*grp + j
        zseg[r*C : r*C+8] = sid; zpos[r*C : r*C+8] = np.arange(*lo)
        zseg[r*C+8 : r*C+16] = sid; zpos[r*C+8 : r*C+16] = np.arange(*hi)
check((2,2), jnp.array(zseg), jnp.array(zpos), 0, 0.0,  "zigzag")
check((2,2), jnp.array(zseg), jnp.array(zpos), 9, 25.0, "zigzag_win_cap")
print("RINGFLASH_OK")
"""


GATHER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.ring import ring_attention

mesh = compat.make_mesh((4,2), ("data","model"))
compat.set_mesh(mesh)
C, R = 16, 4; T = C*R
H, G, D = 4, 2, 8
ks = jax.random.split(jax.random.PRNGKey(1), 3)
q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
k = jax.random.normal(ks[1], (T, G, D), jnp.float32)
v = jax.random.normal(ks[2], (T, G, D), jnp.float32)
seg = jnp.array(np.repeat([1,2], 32)); pos = jnp.array(np.tile(np.arange(32), 2))

# replicated-KV gather mode (GQA kv_group_of_head)
kgi = jnp.array([0, 0, 1, 1], jnp.int32)
for comp in [(2,2), (2,1,1)]:
    def f(impl, q, k, v):
        o = ring_attention(q, k, v, seg, seg, pos, pos, mesh=mesh,
                           hdp_axes=("data",), model_axis="model",
                           composition=comp, kv_sharded=False,
                           kv_group_of_head=kgi, scale=0.3, attn_impl=impl,
                           kv_chunk=8)
        return (o.astype(jnp.float32)**2).sum()
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda q,k,v: f("ref",q,k,v), argnums=(0,1,2)))(q,k,v)
    l2, g2 = jax.jit(jax.value_and_grad(
        lambda q,k,v: f("pallas",q,k,v), argnums=(0,1,2)))(q,k,v)
    assert abs(l1 - l2) < 1e-3 * abs(l1), (comp, l1, l2)
    for nm, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                                   rtol=3e-4, err_msg=f"{comp} d{nm}")

# MLA v_in_k: latent kv [T, 1, Dk] with v = k[..., :dv]
kl = jax.random.normal(ks[1], (T, 1, D), jnp.float32)
kgi1 = jnp.zeros((H,), jnp.int32)
def f(impl, q, kl):
    o = ring_attention(q, kl, None, seg, seg, pos, pos, mesh=mesh,
                       hdp_axes=("data",), model_axis="model",
                       composition=(2,2), kv_sharded=False,
                       kv_group_of_head=kgi1, scale=0.3, attn_impl=impl,
                       v_in_k=(0, 6), kv_chunk=8)
    return (o.astype(jnp.float32)**2).sum()
l1, g1 = jax.jit(jax.value_and_grad(
    lambda q,kl: f("ref",q,kl), argnums=(0,1)))(q,kl)
l2, g2 = jax.jit(jax.value_and_grad(
    lambda q,kl: f("pallas",q,kl), argnums=(0,1)))(q,kl)
assert abs(l1 - l2) < 1e-3 * abs(l1), (l1, l2)
for nm, a, b in zip(["q","kl"], g1, g2):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4,
                               rtol=3e-4, err_msg="v_in_k d"+nm)
print("GATHER_OK")
"""


MODEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses as dc
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat
from repro.configs.registry import get_config
from repro.parallel.sharding import Runtime, params_pspecs, shardings_from_pspecs
from repro.models.transformer import init_params, forward_hidden
from repro.core.loss import token_ce_loss

# model-level loss + grad parity on a Gemma-style config (softcap + local
# window layers), pallas ring engine vs jnp oracle ring, composition (2,2)
cfg = dc.replace(get_config("gemma2-9b").reduced(), window=9)
mesh = compat.make_mesh((4,2), ("data","model"))
compat.set_mesh(mesh)
def make_rt(impl):
    return Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                   composition=(2,2), remat="none", kv_chunk=16,
                   attn_impl=impl)
rt = make_rt("ref")
params = init_params(jax.random.PRNGKey(0), cfg, rt)
T = 64
rng = np.random.RandomState(0)
batch = {"tokens": jnp.array(rng.randint(0, cfg.vocab_size, T)),
         "labels": jnp.array(rng.randint(0, cfg.vocab_size, T)),
         "seg": jnp.array(np.repeat([1,2], 32)),
         "pos": jnp.array(np.tile(np.arange(32), 2)),
         "denom": jnp.float32(64.0)}
pspecs = params_pspecs(params, cfg, rt)
params = jax.device_put(params, shardings_from_pspecs(pspecs, mesh))
bspecs = {k: (P() if k == "denom" else P(("data",))) for k in batch}
batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
         for k, v in batch.items()}
in_sh = compat.resolve_shardings((pspecs, bspecs), mesh)

def loss(rt_):
    def f(p, b):
        h = forward_hidden(p, cfg, rt_, b)
        l, _ = token_ce_loss(p, cfg, rt_, h, b["labels"], b["seg"], b["denom"])
        return l
    return f

l_ref, g_ref = jax.jit(jax.value_and_grad(loss(make_rt("ref"))),
                       in_shardings=in_sh)(params, batch)
l_pal, g_pal = jax.jit(jax.value_and_grad(loss(make_rt("pallas"))),
                       in_shardings=in_sh)(params, batch)
# bf16 activations: bf16-scale tolerances (same as the HDP grad test)
np.testing.assert_allclose(float(l_ref), float(l_pal), rtol=2e-2)
for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2, rtol=3e-2)
print("MODEL_OK")
"""


@pytest.mark.parametrize("name,script,marker", [
    ("ring", RING_SCRIPT, "RINGFLASH_OK"),
    ("gather", GATHER_SCRIPT, "GATHER_OK"),
    ("model", MODEL_SCRIPT, "MODEL_OK"),
])
def test_ring_flash_distributed(name, script, marker):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert marker in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
