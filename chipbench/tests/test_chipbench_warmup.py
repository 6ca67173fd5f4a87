"""Warm-up covers each cell: the steps that set-up runs (the compared
steps) compile every composition and buffer size that the planner emits
in the window, for any seed."""
import json

import pytest

from model import model_config
from run import CHECK_STEPS
from tinycell import BENCH
from traffic import Traffic, load_mix

WINDOW_STEPS = 40
CELLS = json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]


def _keys(plan):
    return {(tuple(w.composition), w.c_mult, round(w.offload_ratio, 2))
            for w in plan.waves}


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7, 2 ** 31 + 99])
def test_window_meets_only_warmed_compositions(seed):
    from repro.data.loader import GlobalScheduler
    for cell in CELLS:
        cfg = model_config(cell["config"])
        tr = Traffic(load_mix(cell["traffic"]), cfg.vocab_size, seed)
        sched = GlobalScheduler(tr, cfg, capacity=tr.capacity,
                                hdp=cell["chips"], strategy=tr.strategy,
                                use_offload=False)
        warmed = set()
        try:
            for step in range(CHECK_STEPS + WINDOW_STEPS):
                keys = _keys(sched.plan_step(step))
                if step < CHECK_STEPS:
                    warmed |= keys
                else:
                    assert keys <= warmed, (cell["name"], step,
                                            keys - warmed)
                # the trainer hands its compiled keys back after every step
                sched.service.warm_keys(sorted(warmed))
        finally:
            sched.stop()
