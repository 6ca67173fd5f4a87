"""A warm wave's loss is fetched one wave late: the trainer dispatches
wave i+1 before it blocks on wave i, so the chip runs i+1 while the host
prepares i+2.  A fresh program and the step's last wave fetch their own
loss at once.  Every consumer of a wave's loss and time still sees each
wave once, in wave order, before `Trainer.train_step` returns."""
import dataclasses
import json
import subprocess
import sys
import time

import pytest

from repro import compat
from repro.configs.registry import get_config
from repro.ctrl.worker import make_telemetry_record
from repro.data.distribution import LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.obs import Tracer, get_metrics, get_tracer, set_tracer
from repro.obs.anomaly import AnomalyConfig, AnomalyDetector
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import single_device_runtime
from repro.train.trainer import Trainer, TrainerConfig
from test_pipeline import TRAINER_SETUP

FETCH = ("trainer.wave_fetch.deferred", "trainer.wave_fetch.immediate")

# the pipelined trainer of tests/test_pipeline.py, in rounds of at most
# two waves, run until a step has two rounds or more and compiles none;
# prints that step's record, spans and fetch counters
ROUND_SCRIPT = TRAINER_SETUP + r"""
import dataclasses, json
from repro.obs import get_metrics
FETCH = ("trainer.wave_fetch.deferred", "trainer.wave_fetch.immediate")
tr.tcfg = dataclasses.replace(tr.tcfg, max_round_waves=2)
tracer, mx = get_tracer(), get_metrics()
tracer.enabled = True
for _ in range(8):
    before = [mx.counter(n).value for n in FETCH]
    tracer.clear()
    rec = tr.train_step()
    events = [{"name": e["name"], "ts": e["ts"], "dur": e["dur"],
               "args": {k: e["args"][k] for k in ("idx", "fresh")
                        if k in e["args"]}}
              for e in tracer.snapshot() if e["ph"] == "X"]
    if rec["rounds"] >= 2 and not any(
            e["args"]["fresh"] for e in events if e["name"] == "round"):
        break
print("ROUND_STEP " + json.dumps({
    "n": rec["rounds"], "events": events,
    "fetch": [mx.counter(n).value - b for n, b in zip(FETCH, before)]}))
"""


@pytest.fixture(scope="module")
def run():
    """A tiny traced trainer whose telemetry hook logs every dispatch
    (step, measured seconds, host wall, the wave findings then), and its
    first step's spans: the step that compiles."""
    cfg = get_config("llama3.2-3b").reduced()
    rt = single_device_runtime(remat="none")
    prev = get_tracer()
    tracer = set_tracer(Tracer(enabled=True))
    with compat.use_mesh(rt.mesh):
        ds = SyntheticDataset(LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5,
                                                 256),
                              cfg.vocab_size, tokens_per_step=1024,
                              context=512)
        sched = GlobalScheduler(ds, cfg, capacity=256, hdp=1,
                                use_offload=False)
        tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=8), sched,
                     TrainerConfig(capacity=256, calibrate=False))
        log = []
        tr.telemetry_fn = lambda waves, measured, fresh, wall_s: log.append(
            (tr.step, measured, wall_s, list(tr.last_wave_findings)))
        first = _step(tr, tracer)
        yield tr, tracer, log, first
        tr.sched.stop()
    set_tracer(prev)


def _step(tr, tracer):
    """One traced step -> (its record, {span name: [spans in order]})."""
    tracer.clear()
    rec = tr.train_step()
    return rec, _spans(e for e in tracer.snapshot() if e["ph"] == "X")


def _spans(events):
    spans = {}
    for e in sorted(events, key=lambda e: e["ts"]):
        spans.setdefault(e["name"], []).append(e)
    return spans


@pytest.fixture(scope="module")
def round_step():
    """A warm step of the pipelined rounds loop -> (its rounds, spans,
    the fetch counters' increments)."""
    r = subprocess.run([sys.executable, "-c", ROUND_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ, "PYTHONPATH": "src"})
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("ROUND_STEP ")]
    assert lines, r.stdout[-2000:] + r.stderr[-2000:]
    out = json.loads(lines[-1][len("ROUND_STEP "):])
    return out["n"], _spans(out["events"]), out["fetch"]


def _end(e):
    return e["ts"] + e["dur"]


def _inside(e, outer):
    return outer["ts"] <= e["ts"] and _end(e) <= _end(outer)


def test_fresh_program_fetches_its_own_loss_at_once(run):
    _, _, _, (rec, spans) = run
    fresh = [w for w in spans["wave"] if w["args"]["fresh"]]
    assert fresh and len(spans["compile"]) == len(fresh)
    for w, c in zip(fresh, spans["compile"]):
        i = w["args"]["idx"]
        assert _inside(c, w) and c["args"]["idx"] == i
        (d,) = [e for e in spans["wave_dispatch"] if e["args"]["idx"] == i]
        (f,) = [e for e in spans["wave_wait"] if e["args"]["idx"] == i]
        assert _inside(d, c) and _inside(f, c) and _end(d) <= f["ts"]
        # the wave before, if still unread, is fetched before the compile
        for e in spans["wave_wait"]:
            if _inside(e, w) and e is not f:
                assert e["args"]["idx"] == i - 1 and _end(e) <= c["ts"]


@pytest.mark.parametrize("unit", ["wave", "round"])
def test_warm_wave_is_fetched_one_wave_late(unit, request):
    """Both loops, the single-wave one and the pipelined rounds, share
    the late fetch."""
    if unit == "wave":
        tr, tracer, _, _ = request.getfixturevalue("run")
        mx = get_metrics()
        before = [mx.counter(n).value for n in FETCH]
        rec, spans = _step(tr, tracer)
        n = rec["waves"]
        fetch = [mx.counter(k).value - b for k, b in zip(FETCH, before)]
    else:
        n, spans, fetch = request.getfixturevalue("round_step")
    assert n >= 2 and not any(w["args"]["fresh"] for w in spans[unit])
    assert fetch == [n - 1, 1]
    # every wave's loss is fetched exactly once, and observed in order
    assert [e["args"]["idx"] for e in spans["wave_wait"]] == list(range(n))
    assert [e["args"]["idx"] for e in spans["wave_post"]] == list(range(n))
    disp, wait = spans["wave_dispatch"], spans["wave_wait"]
    for i in range(n - 1):
        # wave i+1 is on its way before the host blocks on wave i ...
        assert disp[i + 1]["ts"] < _end(wait[i]), i
        # ... and wave i's late fetch sits in wave i+1's span
        assert _inside(wait[i], spans[unit][i + 1])
        assert _end(disp[i + 1]) <= wait[i]["ts"]
    assert _inside(wait[-1], spans[unit][-1])
    assert _end(wait[-1]) <= spans["apply_dispatch"][0]["ts"]


def test_wave_times_fit_in_the_step(run):
    tr, tracer, log, _ = run
    step = tr.step
    rec, _ = _step(tr, tracer)
    times = [(m, w) for s, m, w, _ in log if s == step]
    assert len(times) == rec["waves"]
    assert all(m > 0 and m == w for m, w in times), times
    assert sum(m for m, _ in times) <= rec["wall_s"]


def test_host_stall_stays_out_of_the_wave_time(run, monkeypatch):
    """A host stall while a wave runs (here: the next wave's materialize)
    is chip idle, not that wave's time: every wave's ``wall_s`` leaves it
    out, so the anomaly detector, fed the streamed records, sees it as a
    ``wave_gap`` after the record-to-record cadence of warm steps."""
    tr, tracer, _, _ = run
    stall, k = 0.5, 2
    recs = []
    monkeypatch.setattr(
        tr, "telemetry_fn",
        lambda waves, measured, fresh, wall_s: recs.append(
            make_telemetry_record([0], measured, fresh, step=tr.step,
                                  wall_s=wall_s)))
    det = AnomalyDetector(1, AnomalyConfig(gap_floor_s=stall / 4))

    def gaps():
        return [a for r in recs for a in det.ingest_wave(0, r)
                if a.kind == "wave_gap"]
    for _ in range(3):                   # warm cadence: no stall
        del recs[:]
        _step(tr, tracer)
        assert gaps() == []
    iter_step = tr.loader.iter_step

    def stalled(step, plan):
        for i, lw in enumerate(iter_step(step, plan)):
            if i == k:
                time.sleep(stall)
            yield lw
    monkeypatch.setattr(tr.loader, "iter_step", stalled)
    del recs[:]
    rec, spans = _step(tr, tracer)
    assert rec["waves"] > k and not any(r["fresh"] for r in recs)
    (mat,) = [e for e in spans["materialize"] if e["args"]["idx"] == k]
    assert mat["dur"] >= stall * 1e6
    # wave k-1 ran through the stall, and its time leaves the stall out
    assert all(r["wall_s"] < stall / 2 for r in recs), recs
    (gap,) = gaps()
    assert gap.value > stall * 0.8


def test_nan_fault_finding_lands_in_its_wave(run, monkeypatch, tmp_path):
    tr, tracer, log, _ = run
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    step, k = tr.step, 1
    tcfg = tr.tcfg
    tr.tcfg = dataclasses.replace(tcfg, nan_fault={"step": step, "wave": k})
    try:
        rec, _ = _step(tr, tracer)
    finally:
        tr.tcfg = tcfg
    assert rec["waves"] > k + 1          # wave k's fetch was a late one
    # the telemetry of wave k, streamed mid-step, carries its finding
    seen = [f for s, _, _, f in log if s == step]
    assert len(seen) == rec["waves"]
    for i, found in enumerate(seen):
        assert [(f["reason"], f["wave"]) for f in found] \
            == ([("nonfinite_loss", k)] if i == k else [])
    assert any(f["reason"] == "nonfinite_loss" and f["step"] == step
               and f["wave"] == k for f in tr.numerics.findings)
