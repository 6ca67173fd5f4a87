"""Compile rehearsals for a described TPU v5e (no chip needed).

Interpret mode cannot see a misaligned tile, a VMEM overrun or a step that
does not fit in HBM; the TPU compiler, run here for a described `v5e:2x2`
topology, can.  Each test compiles in this process — the fixture describes
the topology only once a test of this file has started — and runs nothing:
a pass is a compile, never a chip run.

Covered: the flash kernels at llama3.2-3b attention widths, the fused CE at
its published 128,256 vocabulary, the ring-flash engine on a 4-chip mesh,
and the one-chip config's training step, whose memory must fit one chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.registry import ONE_CHIP
from repro.kernels import flash_attention as FA
from repro.kernels import fused_ce as CE
from repro.kernels import ops

GiB = 2 ** 30
# the HBM one v5e chip gives a program: the compiler refuses a step with
# "Used X of 15.75G hbm" beyond it
V5E_HBM = 15.75 * GiB

# llama3.2-3b attention widths: 8 KV groups of 3 query heads, head_dim 128
G, HG, T, D = 8, 3, 4096, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


def _mesh(topo, n):
    return compat.make_mesh((n, 1), ("data", "model"),
                            devices=topo.devices[:n])


def _compile(fn, *args, mesh=None):
    """Lower and compile ``fn`` for the described chips; fails with the
    TPU compiler's own error."""
    if mesh is None:
        return jax.jit(fn).lower(*args).compile()
    with compat.use_mesh(mesh):
        return jax.jit(fn).lower(*args).compile()


def _spec(mesh, shape, dtype, pspec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, pspec))


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "kernel not compiled"


def test_flash_kernels_compile(topo):
    mesh = _mesh(topo, 1)
    interpret = ops.interpret_on(mesh)
    assert not interpret
    q = _spec(mesh, (G, HG, T, D), jnp.bfloat16)
    kv = _spec(mesh, (G, T, D), jnp.bfloat16)
    meta = _spec(mesh, (T,), jnp.int32)
    stat = _spec(mesh, (G, HG, T), jnp.float32)
    acc = _spec(mesh, (G, HG, T, D), jnp.float32)
    kw = dict(scale=D ** -0.5, interpret=interpret)

    _assert_mosaic(_compile(
        lambda q, k, v, s, p: FA.flash_attention_fwd(
            q, k, v, s, s, p, p, **kw), q, kv, kv, meta, meta))
    _assert_mosaic(_compile(
        lambda q, k, v, s, p, a, m, l: FA.flash_attention_fwd_carry(
            q, k, v, s, s, p, p, a, m, l, **kw),
        q, kv, kv, meta, meta, acc, stat, stat))
    _assert_mosaic(_compile(
        lambda q, k, v, s, p, o, lse, do: FA.flash_attention_bwd(
            q, k, v, s, s, p, p, o, lse, do, window=1024, softcap=50.0,
            **kw), q, kv, kv, meta, meta, q, stat, q))


def test_fused_ce_compiles_at_published_vocab(topo):
    mesh = _mesh(topo, 1)
    interpret = ops.interpret_on(mesh)
    t, v = 4096, 128_256                    # 128,256 % 2048 != 0: ragged
    logits = _spec(mesh, (t, v), jnp.bfloat16)
    col = _spec(mesh, (t,), jnp.float32)
    labels = _spec(mesh, (t,), jnp.int32)
    _assert_mosaic(_compile(
        lambda lg, lb: CE.fused_ce_fwd(lg, lb, interpret=interpret),
        logits, labels))
    _assert_mosaic(_compile(
        lambda lg, lb, lse, g: CE.fused_ce_bwd(lg, lb, lse, g,
                                               interpret=interpret),
        logits, labels, col, col))


def test_ring_flash_engine_compiles_on_four_chips(topo):
    from repro.core.ring import ring_attention
    mesh = _mesh(topo, 4)
    c = 2048                                  # tokens per rank
    q = _spec(mesh, (4 * c, G * HG, D), jnp.bfloat16,
              P("data", "model", None))
    kv = _spec(mesh, (4 * c, G, D), jnp.bfloat16, P("data", "model", None))
    meta = _spec(mesh, (4 * c,), jnp.int32, P("data"))

    def loss(q, k, v, seg, pos):
        out = ring_attention(q, k, v, seg, seg, pos, pos, mesh=mesh,
                             hdp_axes=("data",), model_axis="model",
                             composition=(4,), kv_sharded=True,
                             scale=D ** -0.5, attn_impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        q, kv, kv, meta, meta, mesh=mesh)
    _assert_mosaic(compiled)
    assert "collective-permute" in compiled.as_text()


def _bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _footprint(compiled, resident=0):
    """Peak device bytes of one executable: its arguments, the outputs
    that do not reuse a donated argument, its temporaries, and what stays
    resident beside it."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes + resident)


@pytest.mark.parametrize("chips,impl,composition", [
    (1, "ref", (1,)),
    (1, "pallas", (1,)),
    (4, "pallas", (4,)),              # the chip_smoke --four-chips ring
])
def test_one_chip_config_step_fits(topo, chips, impl, composition):
    """The trainer's per-wave grad step (accumulator donated) plus the
    resident AdamW state fits one chip at the config's CAPACITY, and so
    does the optimizer apply (accumulator donated)."""
    from repro.models.transformer import init_params
    from repro.optim import adamw
    from repro.parallel.sharding import Runtime
    from repro.train.train_step import make_accum_steps, named_program
    mod = ONE_CHIP["llama3.2-3b-1chip"]
    cfg = mod.CONFIG
    mesh = _mesh(topo, chips)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 attn_impl=impl)
    params = jax.eval_shape(lambda k: init_params(k, cfg, rt),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        params)
    grads = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=rep),
        params)
    opt = jax.eval_shape(adamw.init_state, params)
    tokens = mod.CAPACITY * chips
    batch = {k: _spec(mesh, (tokens,), jnp.int32, P("data"))
             for k in ("tokens", "labels", "seg", "pos")}
    batch["denom"] = _spec(mesh, (), jnp.float32)
    grad_step, apply_step = make_accum_steps(cfg, rt, adamw.AdamWConfig(),
                                             guard=True)
    wave = named_program("wave", composition, grad_step,
                         rt.with_composition(composition))
    with compat.use_mesh(mesh):
        step = jax.jit(wave, donate_argnums=(1,)).lower(params, grads,
                                                        batch).compile()
        apply = jax.jit(apply_step, donate_argnums=(2,)).lower(
            params, opt, grads).compile()
    # the names the profiler's trace shows: the wave's module and each
    # kernel's custom call
    hlo = step.as_text()
    assert f"jit_{wave.__name__}" in hlo
    if impl == "pallas":
        _assert_mosaic(step)
        for kernel in ("flash_fwd_carry", "flash_dq", "flash_dkv",
                       "fused_ce_fwd", "fused_ce_bwd"):
            assert kernel in hlo, kernel
    assert _footprint(step, resident=_bytes(opt)) <= V5E_HBM
    assert _footprint(apply) <= V5E_HBM
