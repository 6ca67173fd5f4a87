"""Multi-device correctness (ring attention, HDP gradients) — run in
subprocesses so the 8-device XLA flag never leaks into the smoke tests."""
import subprocess
import sys

import pytest

RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core.ring import ring_attention
from repro.core.attention import attention_dense_oracle

mesh = compat.make_mesh((4,2), ("data","model"))
compat.set_mesh(mesh)
C, R = 16, 4; T = C*R
H, G, D = 4, 2, 8
ks = jax.random.split(jax.random.PRNGKey(1), 4)
q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
k = jax.random.normal(ks[1], (T, G, D), jnp.float32)
v = jax.random.normal(ks[2], (T, G, D), jnp.float32)
seg = np.zeros(T, np.int32); pos = np.zeros(T, np.int32)
order = np.random.RandomState(0).permutation(T)
toks = [(1,i) for i in range(28)] + [(2,i) for i in range(32)] + [(0,0)]*4
for slot, (s_,p_) in zip(order, toks): seg[slot], pos[slot] = s_, p_
seg = jnp.array(seg); pos = jnp.array(pos)
for comp in [(4,), (2,2), (1,1,1,1), (2,1,1)]:
    out = jax.jit(lambda q,k,v,s,p: ring_attention(
        q,k,v,s,s,p,p, mesh=mesh, hdp_axes=("data",), model_axis="model",
        composition=comp, kv_sharded=True, scale=0.3, kv_chunk=8))(q,k,v,seg,pos)
    ranks = np.repeat(np.arange(R), C)
    sizes, starts, st_ = [], [], 0
    for g_ in comp:
        sizes += [g_]*g_; starts += [st_]*g_; st_ += g_
    qg = q.reshape(T, G, H//G, D)
    oracle = np.zeros((T, G, H//G, D), np.float32)
    for r in range(R):
        grp = (ranks >= starts[r]) & (ranks < starts[r]+sizes[r])
        mine = ranks == r
        o = attention_dense_oracle(qg[mine], k[grp], v[grp], seg[mine],
                                   seg[grp], pos[mine], pos[grp], scale=0.3)
        oracle[mine] = np.array(o)
    np.testing.assert_allclose(np.array(out).reshape(T,G,H//G,D), oracle,
                               atol=2e-5, rtol=2e-5)
print("RING_OK")
"""

GRAD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.configs.registry import get_config
from repro.parallel.sharding import Runtime, params_pspecs
from repro.models.transformer import init_params, forward_hidden
from repro.core.loss import token_ce_loss

# sharded ring-grad == single-device grad (HDP distribution is exact)
cfg = get_config("llama3.2-3b").reduced()
mesh = compat.make_mesh((4,2), ("data","model"))
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
             composition=(2,2), remat="none", kv_chunk=16)
params = init_params(jax.random.PRNGKey(0), cfg, rt)
T = 64
rng = np.random.RandomState(0)
batch = {"tokens": jnp.array(rng.randint(0, cfg.vocab_size, T)),
         "labels": jnp.array(rng.randint(0, cfg.vocab_size, T)),
         "seg": jnp.array(np.repeat([1,2], 32)),
         "pos": jnp.array(np.tile(np.arange(32), 2)),
         "denom": jnp.float32(64.0)}

def loss(p, b):
    h = forward_hidden(p, cfg, rt, b)
    l, _ = token_ce_loss(p, cfg, rt, h, b["labels"], b["seg"], b["denom"])
    return l

pspecs = params_pspecs(params, cfg, rt)
from jax.sharding import NamedSharding
from repro.parallel.sharding import shardings_from_pspecs
params = jax.device_put(params, shardings_from_pspecs(pspecs, mesh))
bspecs = {k: (P() if k == "denom" else P(("data",))) for k in batch}
batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
         for k, v in batch.items()}
in_sh = compat.resolve_shardings((pspecs, bspecs), mesh)
g_sharded = jax.jit(jax.grad(loss), in_shardings=in_sh)(params, batch)

rt1 = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
              composition=(1,1,1,1), remat="none", kv_chunk=16)
# composition (1,1,1,1) with each 32-token sequence on 2 ranks would split
# segments across singleton groups — instead compare against composition
# (4,) ring over everything (same math, different schedule)
rt4 = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
              composition=(4,), remat="none", kv_chunk=16)
def loss4(p, b):
    h = forward_hidden(p, cfg, rt4, b)
    l, _ = token_ce_loss(p, cfg, rt4, h, b["labels"], b["seg"], b["denom"])
    return l
g_ring4 = jax.jit(jax.grad(loss4), in_shardings=in_sh)(params, batch)
for a, b in zip(jax.tree.leaves(g_sharded), jax.tree.leaves(g_ring4)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2, rtol=3e-2)
print("GRAD_OK")
"""


@pytest.mark.parametrize("name,script,marker", [
    ("ring", RING_SCRIPT, "RING_OK"),
    ("grad", GRAD_SCRIPT, "GRAD_OK"),
])
def test_distributed(name, script, marker):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert marker in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
