"""Pipeline-parallel execution subsystem (parallel/pipeline.py).

Host-side: stage partitioning, round grouping and the analytic pipelined
schedule.  Subprocess (8 CPU devices, same pattern as test_distributed):
the acceptance criterion — a pipelined train step (num_stages=2) on a
stage x data x model mesh produces per-step loss matching the
num_stages=1 path on the same plan within bf16-accumulation tolerance,
with matching accumulated gradients, and the trainer's pipelined executor
trains end-to-end.
"""
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.hdp import Piece, StepPlan, Wave
from repro.core.planner import PlanSpec, plan
from repro.parallel.pipeline import (assert_pipeline_ready, num_scan_periods,
                                     pipeline_rounds,
                                     pipeline_schedule_stats, round_key,
                                     stage_stacked)

CFG = get_config("llama-7b")
SPEC = PlanSpec.for_config(CFG, capacity=8192, hdp=8, use_offload=False)


# ---------------------------------------------------------------------------
# host-side
# ---------------------------------------------------------------------------

def _wave(comp, c_mult=1, cost=1.0, offload=0.0):
    hdp = sum(comp)
    return Wave(composition=tuple(comp), slots=[[] for _ in range(hdp)],
                costs=[cost] * hdp, c_mult=c_mult, offload_ratio=offload)


def test_pipeline_rounds_groups_globally_by_key():
    waves = [_wave((2, 2)), _wave((1, 1, 1, 1)), _wave((2, 2)),
             _wave((2, 2), c_mult=2), _wave((1, 1, 1, 1))]
    p = StepPlan(waves=waves, denom=1, capacity=8192)
    rounds = pipeline_rounds(p)
    assert [r.wave_ids for r in rounds] == [[0, 2], [1, 4], [3]]
    assert rounds[0].composition == (2, 2) and rounds[0].c_mult == 1
    assert rounds[2].c_mult == 2
    # key includes offload class
    assert round_key(_wave((2, 2), offload=0.5)) != round_key(_wave((2, 2)))


def test_pipeline_rounds_max_waves_chunks_long_rounds():
    """Round-size capping (ROADMAP PP follow-up): rounds longer than
    max_waves split into chunks, bounding in-flight activation memory at
    max_waves microbatches per flush."""
    waves = [_wave((2, 2)) for _ in range(7)] + [_wave((4,))] * 2
    p = StepPlan(waves=waves, denom=1, capacity=8192)
    rounds = pipeline_rounds(p, max_waves=3)
    assert [r.wave_ids for r in rounds] == [[0, 1, 2], [3, 4, 5], [6],
                                            [7, 8]]
    assert all(len(r.wave_ids) <= 3 for r in rounds)
    assert all(r.composition == (2, 2) for r in rounds[:3])
    assert rounds[3].composition == (4,)
    # uncapped (default) behaviour unchanged
    assert [r.wave_ids for r in pipeline_rounds(p)] == \
        [[0, 1, 2, 3, 4, 5, 6], [7, 8]]
    # capping can only add flushes: the pipelined makespan never improves
    s_un = pipeline_schedule_stats(p, num_stages=4)
    s_cap = pipeline_schedule_stats(p, num_stages=4, max_round_waves=3)
    assert s_cap["makespan_pipeline"] >= s_un["makespan_pipeline"]
    assert s_cap["n_rounds"] == 4 and s_un["n_rounds"] == 2


def test_pipeline_schedule_stats_reduces_to_lockstep_at_one_stage():
    lengths = [16384] * 6 + [512] * 300
    p = plan(lengths, SPEC)
    st = pipeline_schedule_stats(p, num_stages=1)
    # S=1: slot max == per-wave max -> makespan equals the plan's lockstep
    assert st["makespan_pipeline"] == pytest.approx(
        p.stats["makespan_lockstep"])
    assert st["bubble_frac_pipeline"] == pytest.approx(
        p.stats["bubble_frac_lockstep"], abs=1e-9)


def test_pipeline_schedule_flush_grows_with_depth():
    lengths = [512] * 600
    p = plan(lengths, SPEC)
    bubbles = [pipeline_schedule_stats(p, s)["bubble_frac_pipeline"]
               for s in (1, 2, 4, 8)]
    assert bubbles == sorted(bubbles), bubbles   # deeper -> more flush

def test_stage_stacked_splits_periods_contiguously():
    import jax.numpy as jnp
    blocks = ({"w": jnp.arange(12.0).reshape(6, 2)},)
    st = stage_stacked(blocks, 3)
    assert st[0]["w"].shape == (3, 2, 2)
    np.testing.assert_array_equal(np.asarray(st[0]["w"][1]),
                                  np.asarray(blocks[0]["w"][2:4]))


def test_assert_pipeline_ready_rejects_bad_splits():
    from repro.parallel.sharding import single_device_runtime
    rt1 = single_device_runtime()
    with pytest.raises(ValueError, match="num_stages > 1"):
        assert_pipeline_ready(CFG, rt1)


def test_num_scan_periods_matches_layer_stack():
    cfg = get_config("llama3.2-3b").reduced()
    assert num_scan_periods(cfg) == cfg.num_layers // len(cfg.layer_pattern)


# ---------------------------------------------------------------------------
# 8-device subprocess: the acceptance criterion
# ---------------------------------------------------------------------------

PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset, WaveMaterializer
from repro.launch.mesh import hdp_axes_of, make_pipeline_mesh
from repro.models.transformer import init_params
from repro.parallel.pipeline import (make_pipeline_grad_step, pipeline_loss_fn,
                                     pipeline_rounds)
from repro.parallel.sharding import Runtime
from repro.train.train_step import loss_fn, make_accum_steps
from repro.optim.adamw import AdamWConfig

cfg = get_config("llama3.2-3b").reduced()
mesh = make_pipeline_mesh(2, 2, 2)          # stage x data x model = 8 devices
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=hdp_axes_of(mesh), model_axis="model",
             stage_axis="stage", remat="none", kv_chunk=64)
params = init_params(jax.random.PRNGKey(0), cfg, rt)

DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
ds = SyntheticDataset(DIST, cfg.vocab_size, tokens_per_step=4096, context=1024)
sched = GlobalScheduler(ds, cfg, capacity=512, hdp=2, mode="pp",
                        strategy="balance", use_offload=False, num_stages=2)
plan = sched.plan_step(0)
loader = WaveMaterializer(ds, cfg, 512)
denom = float(plan.denom)
rounds = pipeline_rounds(plan)

# per-step loss: pipelined (num_stages=2) vs per-wave non-PP path
total_pp = total_ref = 0.0
grads_round0 = None
for ri, rd in enumerate(rounds):
    loaded = [loader.materialize(0, plan.waves[i]) for i in rd.wave_ids]
    stacked = {k: jnp.asarray(np.stack([lw.batch[k] for lw in loaded]))
               for k in loaded[0].batch}
    stacked["denom"] = jnp.float32(denom)
    rt_round = rt.with_composition(rd.composition)
    loss_pp, _ = jax.jit(
        lambda p, b: pipeline_loss_fn(p, cfg, rt_round, b))(params, stacked)
    total_pp += float(loss_pp)
    rt_ref = Runtime(mesh=mesh, hdp_axes=rt.hdp_axes, model_axis="model",
                     composition=rd.composition, remat="none", kv_chunk=64)
    for lw in loaded:
        b = {k: jnp.asarray(v) for k, v in lw.batch.items()}
        b["denom"] = jnp.float32(denom)
        lr, _ = jax.jit(lambda p, bb: loss_fn(p, cfg, rt_ref, bb))(params, b)
        total_ref += float(lr)
    if ri == 0:
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        gs = make_pipeline_grad_step(cfg, rt)
        g_pp, _ = jax.jit(lambda p, g, b: gs(p, g, b, rt_round))(
            params, g0, stacked)
        grad_step, _ = make_accum_steps(cfg, rt, AdamWConfig())
        g_ref = g0
        for lw in loaded:
            b = {k: jnp.asarray(v) for k, v in lw.batch.items()}
            b["denom"] = jnp.float32(denom)
            g_ref, _ = jax.jit(
                lambda p, g, bb: grad_step(p, g, bb, rt_ref))(params, g_ref, b)
        errs = [float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).max()
                      / max(np.abs(np.asarray(b, np.float32)).max(), 1e-6))
                for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref))]
        assert max(errs) < 5e-2, ("grad mismatch", max(errs))

np.testing.assert_allclose(total_pp, total_ref, rtol=2e-2)
print("PP_PARITY_OK", total_pp, total_ref)
"""

# a tiny pipelined trainer: 2 stages x 2 HDP ranks x 2-way model axis
TRAINER_SETUP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.launch.mesh import hdp_axes_of, make_pipeline_mesh
from repro.obs import get_tracer
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig

cfg = get_config("llama3.2-3b").reduced()
mesh = make_pipeline_mesh(2, 2, 2)
compat.set_mesh(mesh)
rt = Runtime(mesh=mesh, hdp_axes=hdp_axes_of(mesh), model_axis="model",
             stage_axis="stage", remat="none", kv_chunk=64)
DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
ds = SyntheticDataset(DIST, cfg.vocab_size, tokens_per_step=4096, context=1024)
sched = GlobalScheduler(ds, cfg, capacity=512, hdp=2, mode="pp",
                        strategy="balance", use_offload=False, num_stages=2)
tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
             sched, TrainerConfig(capacity=512, mode="pp"))
"""

TRAINER_SCRIPT = TRAINER_SETUP + r"""
get_tracer().enabled = True         # the round path's step spans
for rec in tr.run(3):
    assert np.isfinite(rec["loss"]), rec
    assert rec["rounds"] >= 1 and 0.0 <= rec["bubble_frac_pipeline"] < 1.0
assert tr.history[-1]["loss"] < tr.history[0]["loss"], tr.history
names = {e["name"] for e in get_tracer().snapshot()}
assert {"train_step", "plan", "grads_init", "materialize", "h2d", "round",
        "compile", "wave_dispatch", "wave_wait", "wave_post", "apply",
        "apply_dispatch", "apply_fetch", "step_end"} <= names, names
assert "wave" not in names, names
print("PP_TRAINER_OK")
"""


@pytest.mark.parametrize("name,script,marker", [
    ("parity", PARITY_SCRIPT, "PP_PARITY_OK"),
    ("trainer", TRAINER_SCRIPT, "PP_TRAINER_OK"),
])
def test_pipeline_distributed(name, script, marker):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert marker in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
