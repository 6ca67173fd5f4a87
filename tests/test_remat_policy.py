"""The remat policy of a wave program: "auto" keeps a wave's activations
when the compiler's memory count of its "none" program, with the state
resident beside it, fits the device's ``bytes_limit``, and
rematerialises otherwise; every explicit policy passes through; both
policies compute the same loss and gradients."""
import gc

import jax
import numpy as np
import pytest

from repro import compat
from repro.configs.registry import get_config
from repro.core.offload import resolve_remat
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import (Tracer, get_metrics, get_recorder, get_tracer,
                       set_tracer)
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import single_device_runtime
from repro.train.trainer import Trainer, TrainerConfig, _Wave

LIMIT = 16 * 10 ** 9


@pytest.mark.parametrize("remat,limit,need,kw,want,weighed", [
    ("auto", LIMIT, LIMIT, {}, "none", True),       # fits to the byte
    ("auto", LIMIT, LIMIT + 1, {}, "full", True),
    ("auto", LIMIT, None, {}, "full", True),        # the compiler refused it
    ("auto", None, 1, {}, "full", False),           # no memory_stats (CPU)
    ("auto", LIMIT, 1, {"pipelined": True}, "full", False),
    ("auto", LIMIT, 1, {"offload": True}, "full", False),
    ("none", 1, LIMIT, {}, "none", False),
    ("full", LIMIT, 1, {}, "full", False),
    ("dots", LIMIT, 1, {}, "dots", False),
    ("offload", LIMIT, 1, {"offload": True}, "offload", False),
    ("none", None, 1, {"pipelined": True, "offload": True}, "none", False),
])
def test_resolve_remat(remat, limit, need, kw, want, weighed):
    calls = []

    def peak():
        calls.append(1)
        return need
    assert resolve_remat(remat, limit, peak, **kw) == want
    # the "none" program is compiled to be weighed only where "auto"
    # has nothing else to go by
    assert bool(calls) == weighed


@pytest.fixture(scope="module")
def trainer():
    cfg = get_config("llama3.2-3b").reduced()
    rt = single_device_runtime()                 # remat "auto"
    with compat.use_mesh(rt.mesh):
        ds = SyntheticDataset(LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5,
                                                 256),
                              cfg.vocab_size, tokens_per_step=512,
                              context=256)
        sched = GlobalScheduler(ds, cfg, capacity=256, hdp=1,
                                use_offload=False)
        tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=4), sched,
                     TrainerConfig(capacity=256, calibrate=False))
        yield tr
        tr.sched.stop()
    del tr
    gc.collect()                 # free its device buffers before the next file


def _wave(tr, limit):
    """Build the step's first wave afresh and run it with the device
    reporting ``limit`` as its ``bytes_limit`` (None: no memory_stats)
    -> (program, compile span args, manifest runtime, counter deltas,
    loss, gradients)."""
    prev = get_tracer()
    tracer = set_tracer(Tracer(enabled=True))
    mx = get_metrics()
    n0 = {p: mx.counter(f"trainer.remat.{p}").value for p in ("none", "full")}
    stats = {} if limit is None else {"bytes_limit": limit}
    try:
        with compat.use_mesh(tr.rt.mesh), \
                pytest.MonkeyPatch.context() as mp:
            mp.setattr(compat, "device_memory_stats",
                       lambda device=None: stats)
            tr._exec_cache.clear()
            plan = tr.sched.plan_step(0)
            # drained: no producer thread outlives the test
            lw = list(tr.loader.iter_step(0, plan))[0]
            batch = tr._to_device(lw.batch, 0, float(plan.denom))
            zero = jax.tree.map(lambda p: np.zeros(p.shape, np.float32),
                                tr.params)
            fn, fresh = tr._wave_fn(lw.composition, lw.c_mult,
                                    lw.offload_ratio)
            assert fresh
            w = _Wave("wave", 0, fn, fresh, plan.waves[:1], lw.composition,
                      lw.c_mult, lw.offload_ratio)
            grads, _, (done,) = tr._dispatch(tracer, mx, 0, None, w, zero,
                                             batch, last=True)
            loss = done.loss
    finally:
        set_tracer(prev)
    span = [e for e in tracer.snapshot()
            if e["ph"] == "X" and e["name"] == "compile"][-1]
    runtime = get_recorder().meta["run_manifest"]["runtime"]
    counted = {p: mx.counter(f"trainer.remat.{p}").value - n0[p]
               for p in ("none", "full")}
    return fn, span["args"], runtime, counted, loss, grads


@pytest.fixture(scope="module")
def waves(trainer):
    """The same wave under a device with no memory_stats (the CPU), with
    room to spare, with exactly the room "none" needs, and one byte
    less."""
    out = {"cpu": _wave(trainer, None), "room": _wave(trainer, 10 ** 12)}
    peak = out["room"][0].peak_bytes
    out["exact"] = _wave(trainer, peak)
    out["short"] = _wave(trainer, peak - 1)
    return out


@pytest.mark.parametrize("case,want,weighed", [
    ("cpu", "full", False), ("room", "none", True),
    ("exact", "none", True), ("short", "full", True),
])
def test_trainer_resolves_auto(trainer, waves, case, want, weighed):
    """"auto" resolves at the program's first call, counts the policy
    once, and the compile span and the manifest name it."""
    fn, args, runtime, counted, _, _ = waves[case]
    peak = waves["room"][0].peak_bytes
    assert trainer.rt.remat == "auto"
    assert fn.remat == want
    assert fn.peak_bytes == (peak if weighed else None)
    assert counted == {"none": int(want == "none"),
                       "full": int(want == "full")}
    assert args["remat"] == want
    assert args["remat_peak_bytes"] == fn.peak_bytes
    # the manifest publishes the resolved policy, never "auto"
    assert runtime["remat"] == want
    assert runtime["remat_programs"] == {fn.name: want}
    # the "none" program holds more than its arguments and the AdamW state
    opt = sum(x.nbytes for x in jax.tree.leaves(trainer.opt_state))
    assert peak > opt


def test_trainer_remat_none_matches_full(waves):
    """The "none" and "full" programs of one wave agree to bf16
    tolerance: remat changes what is stored, not what is computed."""
    _, _, _, _, loss_full, g_full = waves["cpu"]
    _, _, _, _, loss_none, g_none = waves["room"]
    np.testing.assert_allclose(loss_none, loss_full, rtol=1e-2)
    for a, b in zip(jax.tree.leaves(g_none), jax.tree.leaves(g_full)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(b).max()) + 1e-8)
