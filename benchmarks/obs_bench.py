"""Observability overhead + trace-validity + cluster-analytics benchmark
→ ``BENCH_obs.json``.

Four measurements:

* **Tracing overhead.**  Cost of the span layer PLUS the bytes ledger
  PLUS the in-graph numerics sentinels on the hot path, as a fraction
  of an untraced CPU training step: (events-per-step x per-span cost +
  ledger-records-per-step x per-record cost + the A/B'd fused-sentinel
  apply delta) / median clean (no-compile) untraced step wall.
  The ledger leg runs on the real trainer too — the traced leg turns
  the ledger on beside the tracer, so record counts come from the same
  traced steps.  Gate
  (CI): combined overhead < 2% of a step AND the traced leg produced
  ledger records.  The disabled paths must stay effectively free (span:
  one attribute check returning a shared no-op singleton; ledger trace
  sites: one `tally_active` thread-local read — both per-call costs
  are reported).

* **Trace validity on 8 devices.**  A subprocess (host platform forced
  to 8 CPU devices, same re-exec trick as kernel_bench) runs an hdp=4
  trainer for two steps and a serve engine through a few requests with
  tracing on, exports the Chrome ``trace_event`` JSON, and validates it
  with `repro.obs.validate_chrome_trace`: required keys on every event,
  strict nesting per (pid, tid) lane, one "wave" span per dispatched
  wave, and at least one request's prefill→decode lifecycle.

* **Cluster analytics (obs/analyze + obs/anomaly).**  Two real
  control-plane runs (controller + 2 worker subprocesses, hdp=4), each
  exporting per-process traces into ``obs_out/``:

  - a CLEAN run — the merged cross-process trace must validate, every
    (step x lane) time attribution must close within 5% of its step
    wall, goodput must read above 0, and the online anomaly detector must
    emit ZERO advisories (false-positive gate — numerics advisories
    count too);
  - an injected ``slow_ranks={1: 3.0}`` straggler run — a straggler
    advisory for rank 1 must fire from the MID-step telemetry stream
    within a bounded number of fleet waves, and its recorded
    ``rank_speed_after`` must show `SchedulerService` already
    de-weighted the slow rank when it fired.

* **Numerics observatory (obs/numerics + obs/replay).**  Two drills:

  - guarded continuation, single process — a clean run vs the same run
    with ``nan_fault`` poisoning one wave: pre-fault losses bit-equal,
    the fault step's optimizer apply is skipped (``applied == 0``) and
    a flight-recorder dump fires, the next step's loss is finite AND
    bit-equal to a reference that never executed the fault step at all
    (the guard's no-op is bitwise invisible);
  - an injected-NaN control-plane run — the controller's numerics
    channel must fire an advisory from the streamed findings, a worker
    must leave a provenance-bearing flight-recorder dump, and a
    ``python -m repro.obs.replay <dump> --json`` subprocess must
    reproduce the fault signature bit-exactly (exit 0) while the run
    itself continues to a finite loss.

Run: ``python -m benchmarks.obs_bench [--skip-validate]
[--skip-cluster] [--out PATH]``
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

SNAPSHOT_PATH = "BENCH_obs.json"
OVERHEAD_GATE = 0.02
ATTR_GATE = 0.05                   # |compute+dispatch+bubble+stall - 1|
DETECT_WAVES_GATE = 12             # straggler advisory within this many
                                   # finalized fleet waves
OBS_DIR = os.environ.get("REPRO_OBS_DIR", "obs_out")
_CHILD_FLAG = "--validate-child"
_CLUSTER_FLAG = "--cluster-child"


def _mk_trainer(sched_async: bool = False, **tkw):
    from repro import compat
    from repro.configs.registry import get_config
    from repro.data.distribution import LengthDistribution
    from repro.data.loader import GlobalScheduler, SyntheticDataset
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import single_device_runtime
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config("llama3.2-3b").reduced()
    rt = single_device_runtime(remat="none")
    compat.set_mesh(rt.mesh)
    dist = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
    ds = SyntheticDataset(dist, cfg.vocab_size, tokens_per_step=2048,
                          context=1024)
    sched = GlobalScheduler(ds, cfg, capacity=256, hdp=1,
                            use_offload=False, sched_async=sched_async)
    return Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=64),
                   sched, TrainerConfig(capacity=256,
                                        sched_async=sched_async, **tkw))


def tracing_overhead(steps: int = 5) -> dict:
    """Span-layer cost per step as a fraction of an untraced CPU step.

    Whole-step A/B walls cannot resolve a 2% effect on this workload:
    steps differ in wave count and compile events by 2x+, and CI
    machine load adds more.  The gated number is therefore composed
    from robust pieces -- (events recorded per step, measured on the
    real trainer) x (per-span cost, tight loop) / (median untraced
    step wall, compile-polluted samples discarded).  The raw A/B step
    medians ride along as informational fields only.
    """
    import numpy as np

    from repro.obs import Tracer, get_metrics, get_tracer, set_tracer

    tr = _mk_trainer()
    for _ in range(4):                 # pay the common jit compiles up front
        tr.train_step()

    miss = get_metrics().counter("trainer.compile_miss")

    def measure(n):
        """Median clean-step wall + number of steps actually run; a step
        that compiled (``trainer.compile_miss`` advanced) is not clean."""
        clean, dirty = [], []
        ran = 0
        for _ in range(2 * n):
            m0 = miss.value
            t0 = time.perf_counter()
            tr.train_step()
            dt = time.perf_counter() - t0
            ran += 1
            (clean if miss.value == m0 else dirty).append(dt)
            if len(clean) >= n:
                break
        return float(np.median(clean or dirty)), ran

    from repro.obs import ledger as ledger_mod
    prev = get_tracer()
    tracer = Tracer(enabled=True)
    ledger_was = ledger_mod.ledger_enabled()
    try:
        set_tracer(tracer)
        ledger_mod.set_ledger_enabled(True)
        on, ran_on = measure(steps)
        n_events = len(tracer.snapshot())
        tracer.enabled = False
        ledger_mod.set_ledger_enabled(ledger_was)
        off, _ = measure(steps)

        # tight-loop per-span cost, enabled and (the default) disabled
        n_loop = 20_000
        tracer.enabled = True
        tracer.clear()
        t0 = time.perf_counter()
        for _ in range(n_loop):
            with tracer.span("bench", i=0):
                pass
        span_s = (time.perf_counter() - t0) / n_loop
        tracer.enabled = False
        t0 = time.perf_counter()
        for _ in range(n_loop):
            with tracer.span("bench", i=0):
                pass
        span_off_s = (time.perf_counter() - t0) / n_loop

        # bytes-ledger cost.  The ledger was on for the traced leg
        # above, so the record count comes from the real trainer; its
        # per-record host cost comes from a tight loop on a standalone
        # Ledger, and the disabled trace-site guard (`tally_active`,
        # one thread-local read) is priced like the disabled span.
        ledger_records = tr.ledger.summary()["n"] if tr.ledger else 0
        led = ledger_mod.Ledger(tr.cfg, capacity=256, hdp=1,
                                max_records=64)
        n_rec = 5_000
        t0 = time.perf_counter()
        for i in range(n_rec):
            led.record_dispatch(step=0, idx=i, kind="wave",
                                composition=(2, 1, 1), c_mult=1,
                                offload_ratio=0.0,
                                measured={"ring": 1.0})
        rec_s = (time.perf_counter() - t0) / n_rec
        t0 = time.perf_counter()
        for _ in range(n_loop):
            ledger_mod.tally_active()
        tally_off_s = (time.perf_counter() - t0) / n_loop
    finally:
        set_tracer(prev)
        ledger_mod.set_ledger_enabled(ledger_was)

    # numerics-sentinel cost (obs/numerics.py).  The fused in-graph
    # summary rides the once-per-step optimizer apply; A/B the jitted
    # apply (sentinels + guard vs plain) on the trainer's real trees and
    # charge the per-call delta against the same untraced step wall.
    # Conservative: the step wall above already PAID the sentinels (the
    # trainer runs with the guard on), so the composed fraction double
    # counts them rather than hiding them.
    import jax
    import jax.numpy as jnp

    from repro.train.train_step import make_accum_steps
    _, apply_plain = make_accum_steps(tr.cfg, tr.rt, tr.opt_cfg,
                                      numerics=False)
    _, apply_sent = make_accum_steps(tr.cfg, tr.rt, tr.opt_cfg,
                                     guard=True)
    ap, asn = jax.jit(apply_plain), jax.jit(apply_sent)
    g = jax.tree.map(jnp.zeros_like, tr.params)

    def med_apply(f, n=30):
        jax.block_until_ready(f(tr.params, tr.opt_state, g))  # compile
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(f(tr.params, tr.opt_state, g))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    sentinel_s = max(0.0, med_apply(asn) - med_apply(ap))

    events_per_step = n_events / max(ran_on, 1)
    records_per_step = ledger_records / max(ran_on, 1)
    span_frac = events_per_step * span_s / off if off > 0 else 0.0
    ledger_frac = records_per_step * rec_s / off if off > 0 else 0.0
    sentinel_frac = sentinel_s / off if off > 0 else 0.0
    frac = span_frac + ledger_frac + sentinel_frac
    return {"step_ms_traced": round(on * 1e3, 3),      # informational
            "step_ms_untraced": round(off * 1e3, 3),
            "events_per_step": round(events_per_step, 1),
            "span_cost_us": round(span_s * 1e6, 3),
            "span_cost_us_disabled": round(span_off_s * 1e6, 4),
            "ledger_records": ledger_records,
            "ledger_rec_cost_us": round(rec_s * 1e6, 3),
            "ledger_frac": round(ledger_frac, 7),
            "tally_cost_us_disabled": round(tally_off_s * 1e6, 4),
            "sentinel_cost_us": round(sentinel_s * 1e6, 3),
            "sentinel_frac": round(sentinel_frac, 7),
            "overhead_frac": round(frac, 7),
            "events_recorded": n_events,
            "steps": steps, "gate": OVERHEAD_GATE,
            "gate_ok": bool(frac < OVERHEAD_GATE and ledger_records > 0)}


def guard_continuation() -> dict:
    """Guarded-continuation drill (single process): a clean 4-step run
    vs the same run with ``nan_fault`` poisoning step 2 / wave 0.

    Gates: pre-fault losses bit-equal (the sentinels and the guard's
    finite-path `where` are bitwise invisible); the fault step reports a
    non-finite loss, skips the apply (``applied == 0``) and leaves a
    flight-recorder dump; the post-skip step is finite AND bit-equal to
    a reference that rewound to the pre-fault state and never executed
    the fault step at all — i.e. the guarded skip is exactly a no-op.
    """
    import math

    fault = {"step": 2, "wave": 0}
    a = _mk_trainer(calibrate=False)
    la = [a.train_step()["loss"], a.train_step()["loss"]]
    p2, o2 = a.params, a.opt_state      # state ENTERING the fault step
    la += [a.train_step()["loss"], a.train_step()["loss"]]

    b = _mk_trainer(calibrate=False, nan_fault=fault)
    lb, applied = [], []
    for _ in range(4):
        lb.append(b.train_step()["loss"])
        applied.append(int(b.last_numerics["applied"]))

    # skip-parity reference: rewind the clean trainer to the pre-fault
    # state and jump the step cursor past the fault — what the guarded
    # run's step 3 must reproduce bit-exactly
    a.params, a.opt_state = p2, o2
    a.step = 3
    skip3 = a.train_step()["loss"]

    pre = lb[:2] == la[:2]
    parity = lb[3] == skip3
    ok = bool(pre and not math.isfinite(lb[2]) and math.isfinite(lb[3])
              and applied == [1, 1, 0, 1] and parity
              and b._numerics_dumps >= 1)

    def safe(ls):
        return [l if math.isfinite(l) else None for l in ls]
    return {"losses_clean": safe(la), "losses_fault": safe(lb),
            "applied": applied, "prefault_bitexact": bool(pre),
            "fault_step_nonfinite": not math.isfinite(lb[2]),
            "postfault_finite": math.isfinite(lb[3]),
            "skip_parity_bitexact": bool(parity),
            "fault_dumps": b._numerics_dumps, "gate_ok": ok}


# -- 8-device trace validation (subprocess) -----------------------------
def _validate_child(trace_out: str) -> None:
    """Runs inside the forced-8-device subprocess: trace an hdp=4 trainer
    and a serve engine, export, validate, print one JSON summary line."""
    import jax
    import numpy as np

    from repro import compat
    from repro.configs.registry import get_config
    from repro.data.distribution import LengthDistribution
    from repro.data.loader import GlobalScheduler, SyntheticDataset
    from repro.models.transformer import init_params
    from repro.obs import get_tracer, validate_chrome_trace
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import Runtime, single_device_runtime
    from repro.serve import ServeConfig, ServeEngine
    from repro.train.trainer import Trainer, TrainerConfig

    assert len(jax.devices()) >= 8, jax.devices()
    tracer = get_tracer()
    tracer.enabled = True
    cfg = get_config("llama3.2-3b").reduced()
    mesh = compat.make_mesh((4, 2), ("data", "model"))
    compat.set_mesh(mesh)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 remat="none", kv_chunk=64)
    dist = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
    ds = SyntheticDataset(dist, cfg.vocab_size, tokens_per_step=2048,
                          context=1024)
    sched = GlobalScheduler(ds, cfg, capacity=256, hdp=4,
                            use_offload=False)
    tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=8), sched,
                 TrainerConfig(capacity=256))
    n_waves = 0
    for _ in range(2):
        rec = tr.train_step()
        n_waves += rec["waves"]

    # serve leg: a few requests through prefill -> decode on this host
    rt1 = single_device_runtime(remat="none")
    compat.set_mesh(rt1.mesh)
    params = init_params(jax.random.PRNGKey(0), cfg, rt1)
    eng = ServeEngine(params, cfg, rt1,
                      ServeConfig(max_slots=2, max_context=64,
                                  prefill_capacity=64))
    rng = np.random.RandomState(0)
    for _ in range(3):
        eng.submit(rng.randint(1, cfg.vocab_size, 8), 4)
    finished = eng.drain()

    doc = tracer.to_chrome(trace_out)
    ok, problems = validate_chrome_trace(
        doc, require_names=("plan", "materialize", "wave", "apply",
                            "admit", "prefill", "decode"))
    wave_spans = sum(1 for e in doc["traceEvents"]
                     if e.get("ph") == "X" and e["name"] == "wave")
    if wave_spans != n_waves:
        ok = False
        problems.append(f"{n_waves} waves dispatched but {wave_spans} "
                        f"'wave' spans recorded")
    print(json.dumps({"ok": ok, "problems": problems[:8],
                      "n_events": len(doc["traceEvents"]),
                      "n_wave_spans": wave_spans,
                      "devices": len(jax.devices()),
                      "serve_finished": len(finished)}))


def trace_validation(trace_out: str = None) -> dict:
    if trace_out is None:
        trace_out = os.path.join(OBS_DIR, "trace_obs_bench.json")
    os.makedirs(os.path.dirname(trace_out) or ".", exist_ok=True)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.pop("REPRO_TRACE", None)       # child enables programmatically
    r = subprocess.run([sys.executable, "-m", "benchmarks.obs_bench",
                        _CHILD_FLAG, "--trace-out", trace_out],
                       capture_output=True, text=True, timeout=1800,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-800:])
    return json.loads(r.stdout.strip().splitlines()[-1])


# -- cluster analytics: merged traces + attribution + anomaly gates -----
def _cluster_child(trace_dir: str, slow: bool, nan: bool = False) -> None:
    """Runs in its own process: a 2-worker hdp=4 control-plane run with
    tracing on in every process (workers export on exit via
    $REPRO_TRACE_DIR), optionally with the 3x fault-injection clock on
    rank 1 or the NaN numerics drill on step 2.  Prints one JSON line:
    advisories, detector summary, final rank speeds, per-step losses."""
    import math
    os.makedirs(trace_dir, exist_ok=True)
    os.environ["REPRO_TRACE"] = "1"          # workers inherit
    os.environ["REPRO_TRACE_DIR"] = trace_dir
    if nan:
        # workers' flight-recorder dumps (the numerics monitor fires one
        # on the non-finite step) land next to the traces
        os.environ["REPRO_OBS_DIR"] = trace_dir
    from repro.configs.registry import get_config
    from repro.core.planner import PlanSpec
    from repro.ctrl.controller import Controller, ControllerConfig
    from repro.data.distribution import LengthDistribution
    from repro.data.loader import SyntheticDataset
    from repro.launch.cluster import LocalCluster
    from repro.obs import configure as obs_configure, get_tracer

    obs_configure(trace=True, trace_process="controller")
    cfg = get_config("llama3.2-3b").reduced()
    dist = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
    ds = SyntheticDataset(dist, cfg.vocab_size, tokens_per_step=2048,
                          context=1024)
    spec = PlanSpec.for_config(cfg, capacity=256, hdp=4,
                               use_offload=False)
    nan_kw = dict(nan_fault={"step": 2, "wave": 0},
                  ckpt_dir=os.path.join(trace_dir, "ckpt"),
                  ckpt_every=1) if nan else {}
    ctl = Controller(ds, cfg, spec, ControllerConfig(
        num_workers=2, steps=4, calibrate=True,
        heartbeat_interval=0.05,     # stream per-wave telemetry mid-step
        slow_ranks={1: 3.0} if slow else None,
        runtime_kw={"remat": "none", "kv_chunk": 64},
        opt_kw={"lr": 1e-3}, **nan_kw))
    cluster = LocalCluster(ctl)
    cluster.start()
    try:
        hist = cluster.run()
    finally:
        cluster.shutdown()
    get_tracer().to_chrome(os.path.join(
        trace_dir, f"trace_controller_{os.getpid()}.json"))
    print(json.dumps({
        "advisories": ctl.advisories,
        "anomaly": ctl.anomaly.summary() if ctl.anomaly else None,
        "telemetry": {str(k): v
                      for k, v in ctl.telemetry_summary().items()},
        "rank_speed": [round(float(s), 4)
                       for s in ctl.calib.rank_speed()],
        "losses": [r["loss"] if math.isfinite(r["loss"]) else None
                   for r in hist]}))


def _run_cluster_child(trace_dir: str, slow: bool,
                       nan: bool = False) -> dict:
    env = dict(os.environ)
    env.pop("REPRO_TRACE", None)       # child enables programmatically
    env.pop("REPRO_TRACE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = ([sys.executable, "-m", "benchmarks.obs_bench", _CLUSTER_FLAG,
            "--trace-dir", trace_dir] + (["--slow"] if slow else [])
           + (["--nan"] if nan else []))
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=1800, env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-1200:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def _merge_dir(trace_dir: str):
    """Merge + validate + attribute + read the goodput of every
    per-process trace a cluster child left in ``trace_dir``."""
    from repro.obs import validate_chrome_trace
    from repro.obs.analyze import attribute_steps, goodput, merge_traces
    paths = sorted(p for p in
                   glob.glob(os.path.join(trace_dir, "trace_*.json"))
                   if "merged" not in os.path.basename(p))
    merged = merge_traces(paths)
    with open(os.path.join(trace_dir, "trace_merged.json"), "w") as f:
        json.dump(merged, f)
        f.write("\n")
    ok, problems = validate_chrome_trace(merged)
    attribution = attribute_steps(merged)
    return (paths, merged, ok, problems, attribution,
            goodput(merged, attribution))


def cluster_analysis(base_dir: str = None) -> dict:
    base_dir = base_dir or OBS_DIR
    out = {}

    # -- clean run: trace pipeline + zero-false-positive gate ----------
    clean_dir = os.path.join(base_dir, "cluster_clean")
    clean = _run_cluster_child(clean_dir, slow=False)
    paths, _merged, ok, problems, attribution, good = _merge_dir(clean_dir)
    worst = max((abs(r["check"] - 1.0) for r in attribution),
                default=None)
    lanes = len({(r["pid"], r["tid"]) for r in attribution})
    n_fp = len(clean["advisories"])
    n_num = len([a for a in clean["advisories"]
                 if a.get("kind") == "numerics"])
    clean_ok = bool(ok and n_fp == 0 and worst is not None
                    and worst <= ATTR_GATE and lanes >= 3
                    and (good.get("goodput") or 0) > 0)
    out["clean"] = {
        "n_processes": len(paths), "trace_valid": ok,
        "problems": problems[:4], "lanes": lanes,
        "attr_worst": round(worst, 5) if worst is not None else None,
        "attr_gate": ATTR_GATE, "false_positives": n_fp,
        "numerics_advisories": n_num,     # subset of false_positives
        "goodput": good.get("goodput"),
        "steps": good.get("n_steps"),
        "anomaly": clean["anomaly"], "gate_ok": clean_ok}

    # -- injected straggler: bounded-wave mid-step detection gate ------
    slow_dir = os.path.join(base_dir, "cluster_straggler")
    slow = _run_cluster_child(slow_dir, slow=True)
    strag = [a for a in slow["advisories"]
             if a["kind"] == "straggler" and a.get("rank") == 1]
    applied = [a for a in strag if a.get("applied")
               and a.get("rank_speed_after")]
    detect_waves = min((a["waves_seen"] for a in strag), default=None)
    shifted = False
    if applied:
        sp = applied[0]["rank_speed_after"]
        shifted = sp[1] < min(s for i, s in enumerate(sp) if i != 1)
    slow_ok = bool(strag and applied and shifted
                   and detect_waves is not None
                   and detect_waves <= DETECT_WAVES_GATE)
    out["straggler"] = {
        "advisories": len(slow["advisories"]),
        "straggler_advisories": len(strag),
        "detect_waves": detect_waves,
        "detect_gate": DETECT_WAVES_GATE,
        "applied_mid_step": bool(applied), "speed_shifted": shifted,
        "rank_speed_after": applied[0]["rank_speed_after"]
        if applied else None,
        "final_rank_speed": slow["rank_speed"],
        "anomaly": slow["anomaly"], "gate_ok": slow_ok}
    out["gate_ok"] = bool(clean_ok and slow_ok)

    # human-readable artifact for CI upload: the full dashboard over the
    # clean run's merged trace plus the straggler run's advisories
    from repro.obs.report import render_report
    with open(os.path.join(base_dir, "cluster_report.txt"), "w") as f:
        f.write(render_report(attribution=attribution, goodput=good,
                              advisories=slow["advisories"],
                              title="obs_bench cluster analysis"))
        f.write("\n")
    return out


def numerics_cluster(base_dir: str = None) -> dict:
    """Injected-NaN control-plane drill: the full observe -> dump ->
    replay loop on a real 2-worker run.

    Gates: the controller's numerics channel fired an advisory; a worker
    left a provenance-bearing flight-recorder dump (``run_manifest`` in
    meta + a ``step_provenance`` record with ``applied == 0``); a
    ``python -m repro.obs.replay <dump> --json`` subprocess reproduced
    the fault signature and wave losses bit-exactly (exit 0, ``ok``);
    and the run itself continued past the skipped step to a finite
    final loss."""
    base_dir = base_dir or OBS_DIR
    nan_dir = os.path.join(base_dir, "cluster_numerics")
    res = _run_cluster_child(nan_dir, slow=False, nan=True)
    advs = [a for a in res["advisories"] if a.get("kind") == "numerics"]

    # provenance-bearing dump from a worker (controller advisory dumps
    # carry no run_manifest and are skipped)
    dump, sig = None, None
    for p in sorted(glob.glob(os.path.join(nan_dir, "flightrec_*.json"))):
        with open(p) as f:
            doc = json.load(f)
        provs = [e for e in doc.get("events", [])
                 if e.get("kind") == "step_provenance"
                 and not e.get("applied", 1)]
        if provs and (doc.get("meta") or {}).get("run_manifest"):
            dump, sig = p, provs[-1]
            break

    replay = None
    if dump is not None:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)   # replay forces its own device count
        env["REPRO_OBS_DIR"] = os.path.join(nan_dir, "replay_obs")
        r = subprocess.run([sys.executable, "-m", "repro.obs.replay",
                            dump, "--json"], capture_output=True,
                           text=True, timeout=1800, env=env)
        for line in r.stdout.splitlines():
            if line.startswith("REPLAY_JSON "):
                replay = json.loads(line[len("REPLAY_JSON "):])
        if replay is None:
            replay = {"ok": False, "error": r.stderr[-400:]}
        replay["returncode"] = r.returncode

    losses = res.get("losses") or []
    continued = bool(len(losses) == 4 and losses[2] is None
                     and losses[3] is not None)
    ok = bool(advs and dump is not None and replay is not None
              and replay.get("ok") and replay["returncode"] == 0
              and continued)
    return {"numerics_advisories": len(advs),
            "fault_step": sig.get("step") if sig else None,
            "grad_nonfinite": (sig.get("sentinels") or {})
            .get("grad_nonfinite") if sig else None,
            "dump": os.path.basename(dump) if dump else None,
            "losses": losses, "continued_finite": continued,
            "replay": {k: replay.get(k) for k in
                       ("ok", "plan_hash_ok", "signature_ok",
                        "losses_exact", "sentinels_exact",
                        "restored_ckpt", "returncode", "error")
                       if k in replay} if replay else None,
            "gate_ok": ok}


# -- snapshot / harness wiring ------------------------------------------
def snapshot(path: str = SNAPSHOT_PATH, skip_validate: bool = False,
             skip_cluster: bool = False, steps: int = 5) -> dict:
    snap = {"overhead": tracing_overhead(steps=steps),
            "numerics_guard": guard_continuation()}
    if not skip_validate:
        snap["trace_8dev"] = trace_validation()
    if not skip_cluster:
        snap["cluster"] = cluster_analysis()
        snap["numerics"] = numerics_cluster()
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    return snap


def rows_from(snap: dict) -> list:
    ov = snap["overhead"]
    rows = [("obs.tracing_overhead", ov["step_ms_traced"] * 1e3,
             f"overhead_frac={ov['overhead_frac']}")]
    ng = snap.get("numerics_guard")
    if ng:
        rows.append(("obs.numerics_guard", 0.0,
                     f"applied={''.join(map(str, ng['applied']))} "
                     f"skip_parity={ng['skip_parity_bitexact']} "
                     f"dumps={ng['fault_dumps']}"))
    tv = snap.get("trace_8dev")
    if tv:
        rows.append(("obs.trace_8dev_valid", 0.0,
                     f"ok={tv['ok']} events={tv['n_events']}"))
    cl = snap.get("cluster")
    if cl:
        rows.append(("obs.cluster_clean", 0.0,
                     f"fp={cl['clean']['false_positives']} "
                     f"attr_worst={cl['clean']['attr_worst']} "
                     f"goodput={cl['clean']['goodput']}"))
        rows.append(("obs.cluster_straggler",
                     float(cl["straggler"]["detect_waves"] or -1),
                     f"applied={cl['straggler']['applied_mid_step']} "
                     f"shifted={cl['straggler']['speed_shifted']}"))
    nm = snap.get("numerics")
    if nm:
        rp = nm.get("replay") or {}
        rows.append(("obs.numerics_replay", 0.0,
                     f"advisories={nm['numerics_advisories']} "
                     f"replay_ok={rp.get('ok')} "
                     f"continued={nm['continued_finite']}"))
    return rows


def run() -> list:
    return rows_from(snapshot())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=SNAPSHOT_PATH)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--skip-validate", action="store_true",
                    help="no 8-device trace-validity subprocess")
    ap.add_argument("--skip-cluster", action="store_true",
                    help="no cluster-analytics control-plane runs")
    ap.add_argument(_CHILD_FLAG, action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument(_CLUSTER_FLAG, action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--slow", action="store_true",
                    help=argparse.SUPPRESS)   # cluster child: straggler
    ap.add_argument("--nan", action="store_true",
                    help=argparse.SUPPRESS)   # cluster child: NaN drill
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()
    if args.validate_child:
        _validate_child(args.trace_out
                        or os.path.join(OBS_DIR, "trace_obs_bench.json"))
        return
    if args.cluster_child:
        _cluster_child(args.trace_dir
                       or os.path.join(OBS_DIR, "cluster"), args.slow,
                       nan=args.nan)
        return
    snap = snapshot(args.out, skip_validate=args.skip_validate,
                    skip_cluster=args.skip_cluster, steps=args.steps)
    print(json.dumps(snap, indent=1, sort_keys=True))
    if not snap["overhead"]["gate_ok"]:
        raise SystemExit(
            f"tracing overhead {snap['overhead']['overhead_frac']:.3%} "
            f"exceeds the {OVERHEAD_GATE:.0%} gate")
    if not snap["numerics_guard"]["gate_ok"]:
        raise SystemExit(
            f"numerics guard gate failed: {snap['numerics_guard']}")
    tv = snap.get("trace_8dev")
    if tv is not None and not tv["ok"]:
        raise SystemExit(f"8-device trace invalid: {tv['problems']}")
    cl = snap.get("cluster")
    if cl is not None and not cl["gate_ok"]:
        raise SystemExit(
            f"cluster analytics gate failed: "
            f"clean={cl['clean']['gate_ok']} "
            f"straggler={cl['straggler']['gate_ok']}")
    nm = snap.get("numerics")
    if nm is not None and not nm["gate_ok"]:
        raise SystemExit(
            f"numerics replay gate failed: "
            f"advisories={nm['numerics_advisories']} "
            f"dump={nm['dump']} replay={nm['replay']} "
            f"continued={nm['continued_finite']}")


if __name__ == "__main__":
    main()
