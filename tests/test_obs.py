"""Observability layer: Chrome-trace schema + nesting, the zero-overhead
disabled path, metrics thread-safety (including the scheduler's async
planner thread), flight-recorder dumps on worker death, telemetry
timestamping and the controller's drop accounting."""
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.planner import PlanSpec
from repro.data.distribution import LengthDistribution
from repro.data.loader import SyntheticDataset
from repro.obs import (MetricsRegistry, Tracer, get_metrics, get_recorder,
                       get_tracer, monotime, render_report,
                       validate_chrome_trace)
from repro.obs.recorder import FlightRecorder
from repro.obs.trace import _NULL_SPAN
from repro.sched.service import SchedulerService

DIST = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
CFG = get_config("llama3.2-3b").reduced()


@pytest.fixture(autouse=True)
def _clean_obs():
    """Obs state is process-global; keep each test hermetic."""
    was_enabled = get_tracer().enabled
    get_metrics().reset()
    get_tracer().clear()
    get_recorder().clear()
    yield
    get_metrics().reset()
    get_metrics().configure_sink(None)
    get_tracer().clear()
    get_tracer().enabled = was_enabled
    get_recorder().clear()


def _mk_service(async_plan=False, hdp=4):
    ds = SyntheticDataset(DIST, CFG.vocab_size, tokens_per_step=4096,
                          context=2048)
    spec = PlanSpec.for_config(CFG, capacity=512, hdp=hdp,
                               use_offload=False)
    return SchedulerService(ds, spec, lookahead=2, async_plan=async_plan)


# -- tracing ------------------------------------------------------------
def test_trace_schema_and_nesting(tmp_path):
    t = Tracer(enabled=True, process="test", pid=7)
    t.set_thread_name("main-thread")
    with t.span("outer", step=0):
        with t.span("inner", idx=1):
            pass
        t.instant("marker", note="hello")
    with t.span("second"):
        pass

    def other():
        with t.span("other-thread-span"):
            pass
    th = threading.Thread(target=other)
    th.start()
    th.join()

    path = tmp_path / "trace.json"
    doc = t.to_chrome(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] == doc["traceEvents"]
    ok, problems = validate_chrome_trace(
        doc, require_names=("outer", "inner", "marker",
                            "other-thread-span"))
    assert ok, problems
    evs = doc["traceEvents"]
    # every non-meta event carries the Chrome-required keys
    for e in evs:
        for k in ("name", "ph", "ts", "pid", "tid"):
            assert k in e, e
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert xs["outer"]["pid"] == 7
    assert xs["outer"]["args"]["step"] == 0
    # inner nests strictly inside outer on the same lane
    assert xs["inner"]["ts"] >= xs["outer"]["ts"]
    assert (xs["inner"]["ts"] + xs["inner"]["dur"]
            <= xs["outer"]["ts"] + xs["outer"]["dur"] + 1e-6)
    # metadata rows name the process lane; wall anchor present
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    assert any(m["name"] == "thread_name"
               and m["args"]["name"] == "main-thread" for m in metas)
    assert "wall_anchor" in doc["otherData"]


def test_validator_rejects_partial_overlap():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "pid": 0,
         "tid": 0},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "pid": 0,
         "tid": 0}]}
    ok, problems = validate_chrome_trace(bad)
    assert not ok
    assert any("overlaps" in p for p in problems)
    ok, problems = validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "ts": 0, "pid": 0, "tid": 0}]})
    assert not ok                      # missing name, missing dur


def test_disabled_tracing_allocates_nothing():
    t = Tracer(enabled=False)
    s1 = t.span("hot-path", step=1)
    s2 = t.span("other")
    assert s1 is s2 is _NULL_SPAN      # one shared no-op object
    with s1:
        s1.set("k", "v")               # all no-ops
    t.instant("marker")
    assert t.snapshot() == []          # nothing recorded
    t.enabled = True
    assert t.span("now-real") is not _NULL_SPAN


# -- metrics ------------------------------------------------------------
def test_metrics_concurrent_updates_exact():
    reg = MetricsRegistry()
    N, T = 1000, 8

    def work(i):
        for _ in range(N):
            reg.counter("shared").inc()
            reg.histogram("lat").observe(1e-3 * (i + 1))
        reg.gauge("speed").set([1.0, 2.0, float(i)])

    threads = [threading.Thread(target=work, args=(i,)) for i in range(T)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = reg.snapshot()
    assert snap["shared"] == N * T     # no lost increments
    assert snap["lat.count"] == N * T
    assert len(snap["speed"]) == 3


def test_metrics_jsonl_export(tmp_path):
    reg = MetricsRegistry()
    sink = tmp_path / "metrics.jsonl"
    reg.configure_sink(str(sink))
    reg.counter("steps").inc()
    reg.export_step(0)
    reg.counter("steps").inc()
    reg.export_step(1)
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [ln["step"] for ln in lines] == [0, 1]
    assert lines[1]["steps"] == 2
    for ln in lines:                   # clock-unification contract
        assert "t_mono" in ln and "t_wall" in ln


def test_histogram_quantile_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("d")
    for v in np.linspace(1e-3, 0.5, 200):
        h.observe(float(v))
    assert 1e-3 <= h.quantile(0.5) <= 0.5 * 4
    assert h.summary()["count"] == 200


def test_histogram_quantile_interpolates():
    """Within-bucket interpolation: uniform samples filling one log2
    bucket recover exact percentiles (rank-linear between the edges),
    and estimates clamp to the observed [min, max]."""
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    vals = np.linspace(1.0, 2.0, 1000, endpoint=False)  # one bucket [1,2)
    for v in vals:
        h.observe(float(v))
    for q in (0.1, 0.25, 0.5, 0.9):
        exact = float(np.percentile(vals, q * 100))
        assert abs(h.quantile(q) - exact) < 0.02, (q, h.quantile(q), exact)
    assert h.quantile(0.0) >= h.min
    assert h.quantile(1.0) <= h.max
    # degenerate distribution answers exactly via the clamp
    h2 = reg.histogram("const")
    for _ in range(10):
        h2.observe(0.125)
    assert h2.quantile(0.5) == 0.125
    # snapshot exports the interpolated p50/p99 alongside the summary
    snap = reg.snapshot()
    assert abs(snap["lat.p50"] - 1.5) < 0.02
    assert snap["const.p99"] == 0.125


def test_async_planner_thread_writes_metrics():
    """The planner daemon thread and the consumer thread hit the global
    registry concurrently; counts stay exact and reads never throw."""
    svc = _mk_service(async_plan=True)
    try:
        stop = threading.Event()
        errs = []

        def poll():
            while not stop.is_set():
                try:
                    get_metrics().snapshot()
                except Exception as e:      # pragma: no cover
                    errs.append(e)
        th = threading.Thread(target=poll)
        th.start()
        for t in range(6):
            svc.plan_step(t)
        stop.set()
        th.join()
        assert not errs
        snap = get_metrics().snapshot()
        assert snap.get("sched.windows_planned", 0) >= 3
    finally:
        svc.stop()


# -- flight recorder ----------------------------------------------------
def test_recorder_dump_contents(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    rec = FlightRecorder(capacity=4, process="unit")
    for i in range(6):                 # ring keeps only the last 4
        rec.record("tick", i=i)
    get_metrics().counter("x").inc(3)
    path = rec.dump("unit_test")
    assert path and os.path.exists(path)
    doc = json.loads(open(path).read())
    assert doc["reason"] == "unit_test"
    assert [e["i"] for e in doc["events"]] == [2, 3, 4, 5]
    for e in doc["events"]:
        assert "t_mono" in e and "t_wall" in e
    assert doc["metrics"]["x"] == 3
    # dump never raises, even into an unwritable location
    assert rec.dump("bad", path="/nonexistent-dir/nope/x.json") == ""


def _stub_worker(address):
    """Protocol-complete worker (no compute): step_done per plan, ready
    after reconfig — enough to drive the controller's elastic path."""
    from repro.ctrl.rpc import connect
    chan = connect(address)
    chan.send({"type": "hello"})
    cfg = chan.recv()
    assert cfg["type"] == "config"
    ranks = cfg["ranks"]
    chan.send({"type": "ready", "step": cfg.get("resume_step", 0)})
    try:
        while True:
            msg = chan.recv()
            if msg["type"] == "plan":
                tel = [{"ranks": ranks, "times": [1e-3] * len(ranks),
                        "exact": True, "fresh": False,
                        "t_mono": monotime(), "t_wall": time.time(),
                        "step": msg["step"]}
                       for _ in msg["plan"].waves]
                chan.send({"type": "step_done", "step": msg["step"],
                           "loss": 0.0, "grad_norm": 0.0, "keys": [],
                           "telemetry": tel})
            elif msg["type"] == "reconfig":
                ranks = msg["ranks"]
                chan.send({"type": "ready", "step": msg["resume_step"]})
            elif msg["type"] == "shutdown":
                chan.send({"type": "bye"})
                return
    except (EOFError, OSError):
        pass
    finally:
        chan.close()


def _mk_controller(num_workers=2, steps=4, **kw):
    from repro.ctrl.controller import Controller, ControllerConfig
    ds = SyntheticDataset(DIST, CFG.vocab_size, tokens_per_step=2048,
                          context=1024)
    spec = PlanSpec.for_config(CFG, capacity=256, hdp=4,
                               use_offload=False)
    return Controller(ds, CFG, spec, ControllerConfig(
        num_workers=num_workers, steps=steps, lookahead=1,
        heartbeat_interval=0.05, **kw))


def test_flight_recorder_dump_on_worker_kill(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    ctl = _mk_controller(num_workers=2, steps=4)
    addr = ctl.serve()
    threads = [threading.Thread(target=_stub_worker, args=(addr,),
                                daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    ctl.wait_for_workers()
    killed = []

    def on_step(c, rec):
        if not killed:                  # kill worker 0 after step one
            killed.append(True)
            c.handles[0].chan.close()

    hist = ctl.run(on_step=on_step)
    assert hist[-1]["step"] == 4
    assert hist[-1]["workers"] == 1     # finished on the survivor
    dumps = glob.glob(str(tmp_path / "flightrec_membership_change_*.json"))
    assert dumps, "worker death must write a flight record"
    doc = json.loads(open(dumps[0]).read())
    assert doc["reason"] == "membership_change"
    kinds = {e["kind"] for e in doc["events"]}
    assert "dispatch" in kinds          # the ring saw the lead-up
    assert "membership_change" in kinds
    snap = get_metrics().snapshot()
    assert snap.get("ctrl.recoveries") == 1
    assert snap.get("ctrl.waves_streamed", 0) == 0  # stubs don't stream
    for t in threads:
        t.join(timeout=10.0)


# -- telemetry records --------------------------------------------------
def test_make_telemetry_record_timestamps():
    from repro.ctrl.worker import make_telemetry_record
    lo = monotime()
    rec = make_telemetry_record([2, 3], 0.25, False, step=7)
    hi = monotime()
    assert rec["ranks"] == [2, 3]
    assert rec["times"] == [0.25, 0.25]    # wall attributed to all owned
    assert rec["exact"] is False
    assert rec["step"] == 7
    assert lo <= rec["t_mono"] <= hi       # same monotonic timeline
    assert abs(rec["t_wall"] - time.time()) < 60.0
    # vector measurement: per-rank clock, sliced to the owned ranks
    vec = make_telemetry_record([1, 2], np.asarray([9.0, 0.1, 0.2, 9.0]),
                                True)
    assert vec["exact"] is True
    assert vec["times"] == [0.1, 0.2]
    assert vec["fresh"] is True
    assert "step" not in vec


def test_ingest_counts_dropped_telemetry(caplog):
    ctl = _mk_controller(num_workers=2, steps=1)
    try:
        plan, _ = ctl.service.get_step(0)
        n = len(plan.waves)
        rec = {"ranks": [0, 1], "times": [1e-3, 2e-3], "exact": True,
               "fresh": False}
        rec2 = {"ranks": [2, 3], "times": [1e-3, 5e-3], "exact": True,
                "fresh": False}
        dones = {"a": {"keys": [], "telemetry": [dict(rec)] * n},
                 "b": {"keys": [], "telemetry": [dict(rec2)] * (n + 2)}}
        with caplog.at_level("WARNING", logger="repro.ctrl"):
            ctl._ingest_telemetry(0, plan, dones)
        snap = get_metrics().snapshot()
        assert snap.get("ctrl.telemetry_dropped") == 2
        assert any("dropping 2" in r.message for r in caplog.records)
        # straggler gap histogram saw every aligned dispatch
        assert snap.get("ctrl.wave_gap_s.count") == n
        assert snap["ctrl.wave_gap_s.max"] == pytest.approx(4e-3)
        # aligned telemetry counts nothing
        get_metrics().reset()
        dones["b"]["telemetry"] = dones["b"]["telemetry"][:n]
        ctl._ingest_telemetry(1, plan, dones)
        assert "ctrl.telemetry_dropped" not in get_metrics().snapshot()
    finally:
        ctl.stop()


# -- report -------------------------------------------------------------
def test_report_renders_sections():
    get_metrics().counter("trainer.compile_hit").inc(9)
    get_metrics().counter("trainer.compile_miss").inc()
    txt = render_report(
        history=[{"wall_s": 0.5, "waves": 3, "bubble_frac": 0.1},
                 {"wall_s": 0.6, "waves": 4, "bubble_frac": 0.2}],
        metrics=get_metrics(),
        calib={"scale": 2.0, "model_gap": 0.05, "speed": [0.9, 1.1],
               "n_observed": 12},
        serve_records=[{"t_submit": 0.0, "t_first": 0.2, "t_done": 1.0}])
    for needle in ("step loop", "cost model", "compile cache",
                   "serving", "TTFT", "90.00%"):
        assert needle in txt, txt
    assert render_report() == "== observability report ==\n  (no data)"


# -- cluster analytics: trace merge / attribution / goodput --------------
def _span(tr, name, t0, t1, **args):
    tr.complete(name, t0, t1, tid=1, **args)


def test_merge_traces_skewed_anchors():
    """Two tracers whose monotonic epochs differ by ~83 minutes (raw ts
    wildly out of order) merge onto one wall timeline: wall ordering is
    preserved and the merged doc validates."""
    from repro.obs.analyze import merge_traces
    ta = Tracer(enabled=True, process="ctrl", pid=1)
    tb = Tracer(enabled=True, process="worker", pid=1)   # pid collision
    base = monotime()
    _span(ta, "ctrl_step", base, base + 0.10, step=0)
    _span(tb, "wave", base + 0.02, base + 0.05, step=0, idx=0)
    # simulate a different monotonic epoch in process B: shift its clock
    # AND its anchor together, so wall times are unchanged
    skew_us = 5000.0 * 1e6
    tb._anchor_mono += 5000.0
    for e in tb._events:
        e["ts"] += skew_us
    da, db = ta.to_chrome(), tb.to_chrome()
    assert db["traceEvents"][-1]["ts"] > da["traceEvents"][-1]["ts"] + 1e9
    merged = merge_traces([da, db])
    ok, problems = validate_chrome_trace(
        merged, require_names=("ctrl_step", "wave"))
    assert ok, problems
    xs = {e["name"]: e for e in merged["traceEvents"] if e["ph"] == "X"}
    # wall order restored: the wave starts 20ms into ctrl_step
    assert xs["ctrl_step"]["ts"] == pytest.approx(0.0, abs=1.0)
    assert xs["wave"]["ts"] == pytest.approx(0.02 * 1e6, abs=2e3)
    # the pid collision was remapped to distinct lanes
    assert xs["ctrl_step"]["pid"] != xs["wave"]["pid"]
    assert merged["otherData"]["merged_from"] == 2


def test_attribution_sums_to_window():
    """compute + dispatch + bubble + stall == step window, with nested
    compiles moved out of compute and the controller's ctrl_step
    wrapper peeled."""
    from repro.obs.analyze import attribute_steps
    tr = Tracer(enabled=True, process="worker", pid=3)
    b = monotime()
    _span(tr, "plan", b + 0.00, b + 0.10, step=0)
    _span(tr, "materialize", b + 0.10, b + 0.15, step=0)
    _span(tr, "wave", b + 0.15, b + 0.45, step=0, idx=0)
    _span(tr, "compile", b + 0.20, b + 0.40, step=0)    # nested in wave
    # [0.45, 0.55] uncovered between waves -> bubble
    _span(tr, "wave", b + 0.55, b + 0.85, step=0, idx=1)
    _span(tr, "apply", b + 0.85, b + 0.95, step=0)

    tc = Tracer(enabled=True, process="controller", pid=9)
    _span(tc, "ctrl_step", b + 0.00, b + 1.00, step=0)  # wrapper
    _span(tc, "plan", b + 0.10, b + 0.30, step=0)

    from repro.obs.analyze import merge_traces
    recs = attribute_steps(merge_traces([tr.to_chrome(), tc.to_chrome()]))
    by_proc = {r["process"]: r for r in recs}
    w = by_proc["worker"]
    assert w["window_s"] == pytest.approx(0.95, rel=1e-3)
    assert w["compute_s"] == pytest.approx(0.40, rel=1e-3)  # waves - compile
    assert w["stall_s"] == pytest.approx(0.20, rel=1e-3)    # the compile
    assert w["dispatch_s"] == pytest.approx(0.25, rel=1e-3)
    assert w["bubble_s"] == pytest.approx(0.10, rel=1e-3)
    assert w["n_waves"] == 2
    c = by_proc["controller"]
    assert c["window_s"] == pytest.approx(1.00, rel=1e-3)   # wrapper peeled
    assert c["dispatch_s"] == pytest.approx(0.20, rel=1e-3)
    for r in recs:
        assert abs(r["check"] - 1.0) < 1e-6, r


def test_goodput_counts_final_steps_over_extent():
    """Final-step wall over the trace extent: a replayed step counts at
    its widest window once; the stretch no step covers is lost."""
    from repro.obs.analyze import goodput
    tr = Tracer(enabled=True, process="worker", pid=3)
    b = monotime()
    _span(tr, "train_step", b + 0.0, b + 1.0, step=0)
    _span(tr, "wave", b + 0.1, b + 0.9, step=0, idx=0)
    _span(tr, "recover", b + 1.0, b + 1.5)             # no step: lost
    _span(tr, "train_step", b + 1.5, b + 2.0, step=1)
    _span(tr, "wave", b + 1.6, b + 1.9, step=1, idx=0)
    out = goodput(tr.to_chrome())
    assert out["n_steps"] == 2
    assert out["useful_s"] == pytest.approx(1.5, rel=1e-3)
    assert out["total_s"] == pytest.approx(2.0, rel=1e-3)
    assert out["goodput"] == pytest.approx(0.75, abs=1e-3)
    assert "mfu" not in out
    # a trace with no step-stamped span degrades explicitly
    empty = Tracer(enabled=True, process="x", pid=1)
    _span(empty, "plan", b, b + 0.1)
    assert goodput(empty.to_chrome()) == {"n_steps": 0, "goodput": None}


def test_analyze_cli_merges_and_reports(tmp_path, capsys):
    from repro.obs.analyze import main as analyze_main
    b = monotime()
    ta = Tracer(enabled=True, process="controller", pid=1)
    _span(ta, "ctrl_step", b, b + 0.2, step=0)
    tb = Tracer(enabled=True, process="worker", pid=1)
    _span(tb, "wave", b + 0.01, b + 0.15, step=0, idx=0,
          composition=[1, 1, 1, 1], fresh=False)
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    ta.to_chrome(str(p1))
    tb.to_chrome(str(p2))
    out_path = tmp_path / "merged.json"
    rc = analyze_main([str(p1), str(p2), "--out", str(out_path),
                       "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["valid"] is True
    assert doc["goodput"]["n_steps"] == 1 and doc["goodput"]["goodput"] > 0
    assert {r["process"] for r in doc["attribution"]} == \
        {"controller", "worker"}
    merged = json.loads(out_path.read_text())
    assert merged["otherData"]["merged_from"] == 2


# -- online anomaly detection -------------------------------------------
def _wave_rec(ranks, times, step, fresh=False, t_mono=None):
    return {"ranks": list(ranks), "times": list(times), "exact": True,
            "fresh": fresh,
            "t_mono": monotime() if t_mono is None else t_mono,
            "t_wall": time.time(), "step": step}


def test_anomaly_clean_stream_is_silent():
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    rng = np.random.RandomState(0)
    advs = []
    for step in range(3):
        for _ in range(4):
            t = 0.1 * (1.0 + 0.03 * rng.randn(4))
            advs += det.ingest_wave(0, _wave_rec([0, 1], t[:2], step))
            advs += det.ingest_wave(1, _wave_rec([2, 3], t[2:], step))
    assert advs == []
    s = det.summary()
    assert s["waves_seen"] == 12
    assert s["advisories"] == {}
    assert all(abs(r - 1.0) < 0.2 for r in s["rank_ratio_ewma"])


def test_anomaly_straggler_fires_bounded_and_cools_down():
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    advs = []
    for i in range(10):
        t = [0.1, 0.3, 0.1, 0.1]       # rank 1 runs 3x slow
        advs += det.ingest_wave(0, _wave_rec([0, 1], t[:2], 0))
        advs += det.ingest_wave(1, _wave_rec([2, 3], t[2:], 0))
    strag = [a for a in advs if a.kind == "straggler"]
    assert strag, "3x straggler must be detected"
    a = strag[0]
    assert a.rank == 1
    assert a.slowdown == pytest.approx(3.0, rel=0.1)
    assert a.waves_seen <= 5            # detection latency in waves
    assert a.severity >= det.cfg.z_thresh
    # cooldown: 10 waves < cooldown_waves -> exactly one advisory
    assert len(strag) == 1


def test_anomaly_fresh_records_are_ignored():
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    advs = []
    for _ in range(8):
        advs += det.ingest_wave(0, _wave_rec([0, 1], [0.1, 9.9], 0,
                                             fresh=True))
        advs += det.ingest_wave(1, _wave_rec([2, 3], [0.1, 0.1], 0,
                                             fresh=True))
    assert advs == []
    assert det.summary()["waves_seen"] == 0


def test_anomaly_partial_joins_never_finalize():
    """One worker's records alone (ranks 0..1 of hdp=4) must not fake a
    fleet wave — medians over half the ranks double-count dispatches."""
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    for step in range(8):
        det.ingest_wave(0, _wave_rec([0, 1], [0.1, 0.9], step))
    s = det.summary()
    assert s["waves_seen"] == 0
    assert s["pending_joins"] <= det.cfg.max_pending_steps + 1


def test_anomaly_wave_gap_and_heartbeat():
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    t0 = 100.0
    advs = []
    for i in range(6):                  # steady 0.2s dispatch cadence
        advs += det.ingest_wave(0, _wave_rec([0, 1], [0.1, 0.1], 0,
                                             t_mono=t0 + 0.2 * i))
    assert advs == []
    advs = det.ingest_wave(0, _wave_rec([0, 1], [0.1, 0.1], 0,
                                        t_mono=t0 + 0.2 * 5 + 5.0))
    assert [a.kind for a in advs] == ["wave_gap"]
    assert advs[0].worker == 0
    # value is the dispatch IDLE: the 5.0s gap minus the arriving
    # wave's own 0.1s wall — a long wave alone must not trip this
    assert advs[0].value == pytest.approx(4.9, rel=1e-6)

    # heartbeat silence: cadence 0.05s, then a 2s hole
    hb = []
    for i in range(5):
        hb += det.ingest_heartbeat(1, t0 + 0.05 * i, 0.05)
    assert hb == []
    hb = det.ingest_heartbeat(1, t0 + 0.05 * 4 + 2.0, 0.05)
    assert [a.kind for a in hb] == ["heartbeat"]
    assert hb[0].severity > det.cfg.hb_factor


def test_anomaly_long_warm_wave_is_not_a_gap():
    # HDP wave walls legitimately vary with composition: a warm packed
    # [4] wave costs ~4x a [1,1,1,1] wave.  The cadence jump it causes
    # is compute, not a dispatch stall — the detector subtracts the
    # arriving wave's own wall, so this must stay silent.
    from repro.obs.anomaly import AnomalyDetector
    det = AnomalyDetector(4)
    t, advs = 100.0, []
    for i in range(6):                  # short waves: 0.5s wall, 0.6s gap
        t += 0.6
        advs += det.ingest_wave(0, _wave_rec([0, 1], [0.5, 0.5], 0,
                                             t_mono=t))
    t += 12.5                           # packed wave: 12.4s of compute
    advs += det.ingest_wave(0, _wave_rec([0, 1], [12.4, 12.4], 0,
                                         t_mono=t))
    assert advs == []
    t += 12.4                           # idle 12.3s >> walls: DOES fire
    advs += det.ingest_wave(0, _wave_rec([0, 1], [0.1, 0.1], 0,
                                         t_mono=t))
    assert [a.kind for a in advs] == ["wave_gap"]


def test_advisory_shifts_scheduler_mid_step(tmp_path, monkeypatch):
    """The full controller-side loop, no cluster: streamed frames from
    two (fake) worker handles drive the detector, the straggler advisory
    applies to the calibrator and `SchedulerService.rank_speed` BEFORE
    any step_done calibration ran."""
    import types
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))  # severe advisory
    ctl = _mk_controller(num_workers=2, steps=1)        # may dump
    try:
        h0 = types.SimpleNamespace(wid=0)
        h1 = types.SimpleNamespace(wid=1)
        assert ctl.service.rank_speed is None      # nothing calibrated yet
        for _ in range(6):
            ctl._on_worker_frame(h0, {"telemetry": [
                _wave_rec([0, 1], [0.1, 0.3], 0)]})
            ctl._on_worker_frame(h1, {"telemetry": [
                _wave_rec([2, 3], [0.1, 0.1], 0)]})
        strag = [a for a in ctl.advisories if a["kind"] == "straggler"]
        assert strag and strag[0]["rank"] == 1
        assert strag[0]["applied"] is True
        sp = strag[0]["rank_speed_after"]
        assert sp[1] < min(s for i, s in enumerate(sp) if i != 1)
        # the service consumes the advisory speeds for future planning
        speed = ctl.service.rank_speed
        assert speed is not None
        assert speed[1] < min(np.delete(np.asarray(speed), 1))
        snap = get_metrics().snapshot()
        assert snap.get("anomaly.advisories", 0) >= 1
        assert snap.get("anomaly.straggler", 0) >= 1
        assert snap.get("calib.advisories_applied", 0) >= 1
        # the severe advisory (z >> anomaly_dump_z) triggered a bounded
        # flight-recorder dump, and the ring logged the advisory record
        dumps = glob.glob(str(tmp_path / "flightrec_advisory_*.json"))
        assert dumps, "severe advisory must dump a flight record"
        doc = json.loads(open(dumps[0]).read())
        advs = [e for e in doc["events"] if e["kind"] == "advisory"]
        assert advs and advs[0]["advisory_kind"] == "straggler"
        assert advs[0]["rank_speed_after"][1] < 1.0
    finally:
        ctl.stop()


def test_anomaly_detection_disabled_is_inert():
    import types
    ctl = _mk_controller(num_workers=2, steps=1, anomaly_detect=False)
    try:
        assert ctl.anomaly is None
        ctl._on_worker_frame(types.SimpleNamespace(wid=0), {
            "telemetry": [_wave_rec([0, 1], [0.1, 9.9], 0)]})
        assert ctl.advisories == []
    finally:
        ctl.stop()


def test_controller_telemetry_summary():
    ctl = _mk_controller(num_workers=2, steps=2)
    addr = ctl.serve()
    threads = [threading.Thread(target=_stub_worker, args=(addr,),
                                daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    ctl.wait_for_workers()
    hist = ctl.run()
    assert hist[-1]["step"] == 2
    ts = ctl.telemetry_summary()
    assert sorted(ts) == [0, 1]
    owned = sorted(r for w in ts.values() for r in w["ranks"])
    assert owned == [0, 1, 2, 3]
    for w in ts.values():
        for key in ("alive", "streamed", "buffered", "dropped",
                    "last_step", "progress"):
            assert key in w
        assert w["dropped"] == 0
    for t in threads:
        t.join(timeout=10.0)
