"""Compile rehearsals for a described TPU v5e (no chip needed).

Interpret mode cannot see a misaligned tile, a VMEM overrun or a step that
does not fit in HBM; the TPU compiler, run here for a described `v5e:2x2`
topology, can.  Each test compiles in this process — the fixture describes
the topology only once a test of this file has started — and runs nothing:
a pass is a compile, never a chip run.

Covered: the flash kernels at llama3.2-3b attention widths, the fused CE at
its published 128,256 vocabulary, the ring-flash engine on a 4-chip mesh,
the one-chip config's training step, whose memory must fit one chip, and
the remat policy "auto" picks for waves of the benchmark's deepseek-llm-7b
slice and of a full-depth Qwen2.5-0.5B.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
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
        # an earlier test of this worker may have left a CPU mesh as the
        # global mesh: the described chips' arrays must not resolve there,
        # so the file runs under the empty mesh
        with jax.set_mesh(Mesh(np.empty((), dtype=object), ())):
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


def _wave_args(cfg, mesh, rt, tokens):
    """Shapes of the trainer's per-wave grad step arguments for ``tokens``
    tokens -> (params, gradient accumulator, batch, AdamW state)."""
    from repro.models.transformer import init_params
    from repro.optim import adamw
    params = jax.eval_shape(lambda k: init_params(k, cfg, rt),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        params)
    grads = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=rep),
        params)
    batch = {k: _spec(mesh, (tokens,), jnp.int32, P("data"))
             for k in ("tokens", "labels", "seg", "pos")}
    batch["denom"] = _spec(mesh, (), jnp.float32)
    return params, grads, batch, jax.eval_shape(adamw.init_state, params)


def _compile_wave(cfg, mesh, rt, composition, tokens):
    """Compile the trainer's per-wave grad step (accumulator donated) and
    the optimizer apply (accumulator donated) for ``tokens`` tokens ->
    (wave, apply, the AdamW state's shape tree)."""
    from repro.optim import adamw
    from repro.train.train_step import make_accum_steps, named_program
    params, grads, batch, opt = _wave_args(cfg, mesh, rt, tokens)
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
    assert f"jit_{wave.__name__}" in step.as_text()
    return step, apply, opt


@pytest.mark.parametrize("chips,impl,composition", [
    (1, "ref", (1,)),
    (1, "pallas", (1,)),
    (4, "pallas", (4,)),              # the chip_smoke --four-chips ring
])
def test_one_chip_config_step_fits(topo, chips, impl, composition):
    """The trainer's per-wave grad step (accumulator donated) plus the
    resident AdamW state fits one chip at the config's CAPACITY, and so
    does the optimizer apply (accumulator donated)."""
    from repro.parallel.sharding import Runtime
    mod = ONE_CHIP["llama3.2-3b-1chip"]
    mesh = _mesh(topo, chips)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 attn_impl=impl, remat="full")
    step, apply, opt = _compile_wave(mod.CONFIG, mesh, rt, composition,
                                        mod.CAPACITY * chips)
    if impl == "pallas":
        _assert_mosaic(step)
        hlo = step.as_text()
        for kernel in ("flash_fwd_carry", "flash_dq", "flash_dkv",
                       "fused_ce_fwd", "fused_ce_bwd"):
            assert kernel in hlo, kernel
    assert _footprint(step, resident=_bytes(opt)) <= V5E_HBM
    assert _footprint(apply) <= V5E_HBM


def _benchmark_config(name):
    """The benchmark's configuration file as the program's ModelConfig
    (its published config.json keys; a dense SwiGLU decoder)."""
    path = Path(__file__).resolve().parents[1] / "chipbench" / "configs"
    c = json.loads((path / f"{name}.json").read_text())
    return ModelConfig(
        name=name, family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"])


# Qwen2.5-0.5B (huggingface.co/Qwen/Qwen2.5-0.5B, config.json) at full
# depth: a whole small model that one chip trains alone.  Its q/k/v biases
# are left out (2,688 values a layer).
QWEN25_05B = ModelConfig(
    name="qwen2.5-0.5b", family="dense", num_layers=24, d_model=896,
    num_heads=14, num_kv_heads=2, head_dim=64, d_ff=4864,
    vocab_size=151_936, rope_theta=1e6, norm_eps=1e-6, tie_embeddings=True)

# memory_stats()["bytes_limit"] of one v5e chip, as the chip reports it
V5E_LIMIT = 16_909_336_064


@pytest.mark.parametrize("model,impl,tokens,want", [
    # the benchmark cell's wave: 2 of 30 layers, no pipeline
    ("deepseek-llm-7b-1chip", "pallas", 2048, "none"),
    # the jnp attention keeps its chunk scan's scores and probabilities
    ("deepseek-llm-7b-1chip", "ref", 2048, "full"),
    # a document of 8,192 tokens on one chip (c_mult 4)
    ("deepseek-llm-7b-1chip", "pallas", 8192, "full"),
    ("qwen2.5-0.5b", "pallas", 2048, "none"),
    ("qwen2.5-0.5b", "pallas", 4096, "full"),
])
def test_auto_remat_on_v5e(topo, monkeypatch, model, impl, tokens, want):
    """"auto" resolves a wave on a v5e from the compiler's memory count
    of its "none" variant plus the resident AdamW state, the trainer's
    own path (`_Program.resolve`): "none" where that fits the chip's
    ``bytes_limit``, and "full" where it does not, whose program then
    fits."""
    from repro.optim import adamw
    from repro.parallel.sharding import Runtime
    from repro.train.train_step import make_accum_steps, named_program
    from repro.train.trainer import _Program
    cfg = (QWEN25_05B if model == "qwen2.5-0.5b"
           else _benchmark_config(model))
    mesh = _mesh(topo, 1)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 attn_impl=impl)
    assert rt.remat == "auto"
    args = _wave_args(cfg, mesh, rt, tokens)
    resident = _bytes(args[3])
    grad_step, _ = make_accum_steps(cfg, rt, adamw.AdamWConfig(),
                                    guard=True)

    def jit_of(remat):
        return jax.jit(named_program("wave", (1,), grad_step,
                                     dataclasses.replace(rt, remat=remat)),
                       donate_argnums=(1,))
    prog = _Program("wave_1", jit_of, rt.remat, None, lambda: resident,
                    pipelined=False, offload=False)
    monkeypatch.setattr(compat, "device_memory_stats",
                        lambda device=None: {"bytes_limit": V5E_LIMIT})
    with compat.use_mesh(mesh):
        assert prog.resolve(*args[:3]) == want
        if want == "none":
            assert prog.peak_bytes <= V5E_LIMIT
            return
        # the "none" program does not fit, or the compiler refused it
        assert prog.peak_bytes is None or prog.peak_bytes > V5E_LIMIT
        full = jit_of("full").lower(*args[:3]).compile()
    assert _footprint(full, resident=resident) <= V5E_LIMIT
