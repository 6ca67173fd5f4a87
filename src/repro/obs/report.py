"""Text dashboard: one screen answering "where did the step's time go".

`render_report` folds whatever the caller has — trainer history records,
the metrics snapshot, an `OnlineCalibrator` summary, serve request
telemetry — into a fixed-width text report with the quantities §6.1's
production loop watches:

* makespan / step wall statistics and the waves-per-step shape;
* per-wave straggler gap (max-min of per-rank wall times, from the
  controller's streamed telemetry);
* modeled-vs-measured cost gap — how far Eq. 2/Eq. 3 predictions are
  from the measured wall, after the calibrator's global scale;
* pipeline bubble fraction (planned and pipelined);
* compile-cache hit rate (the NCCL-group-cache analogue);
* serving TTFT p50/p99, end-to-end latency and queue depth;
* the analysis layer (obs/analyze.py, obs/anomaly.py): the per-step
  time-attribution table (compute/dispatch/bubble/stall), goodput, the
  controller's advisory log and the per-worker telemetry-stream
  summary.

Histogram-backed lines (dispatch wall p50/p99) read the bucketed
`Histogram.quantile` values straight off the metrics snapshot — no
raw-sample lists are ever needed.

Sections with no data are omitted, so the same function serves the
single-process trainer, the controller and the serve router.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def _fmt_s(v: float) -> str:
    if v >= 1.0:
        return f"{v:8.3f}s "
    return f"{v * 1e3:8.3f}ms"


def _fmt_b(v: float) -> str:
    for unit in ("B ", "KB", "MB", "GB"):
        if abs(v) < 1024.0:
            return f"{v:9.2f}{unit}"
        v /= 1024.0
    return f"{v:9.2f}TB"


def _pct(q, xs):
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) \
        else 0.0


def _line(k: str, v: str) -> str:
    return f"  {k:<34}{v}"


def render_report(history: Optional[List[Dict]] = None,
                  metrics: Optional[Dict] = None,
                  calib: Optional[Dict] = None,
                  serve_records: Optional[Sequence[Dict]] = None,
                  attribution: Optional[List[Dict]] = None,
                  goodput: Optional[Dict] = None,
                  advisories: Optional[Sequence[Dict]] = None,
                  telemetry: Optional[Dict] = None,
                  comm: Optional[Dict] = None,
                  title: str = "observability report") -> str:
    """Build the dashboard.  ``metrics`` is a `MetricsRegistry.snapshot()`
    dict (a live registry is accepted too); ``calib`` is
    `OnlineCalibrator.summary()`; ``serve_records`` is a list of request
    telemetry dicts (`Request.telemetry()` / controller request_log);
    ``attribution`` / ``goodput`` come from `obs.analyze.attribute_steps`
    / `obs.analyze.goodput`; ``advisories`` is the controller's
    advisory log and ``telemetry`` its `telemetry_summary()`; ``comm``
    is the bytes-ledger audit (`obs.analyze.comm_summary`, or a ledger/
    controller `summary()`/`ledger_summary()` dict)."""
    if metrics is not None and hasattr(metrics, "snapshot"):
        metrics = metrics.snapshot()
    m = metrics or {}
    out: List[str] = [f"== {title} =="]

    if history:
        walls = [r["wall_s"] for r in history if "wall_s" in r]
        waves = [r["waves"] for r in history if "waves" in r]
        out.append("-- step loop --")
        out.append(_line("steps", str(len(history))))
        if walls:
            out.append(_line("step wall p50 / p99",
                             f"{_fmt_s(_pct(50, walls))} /"
                             f"{_fmt_s(_pct(99, walls))}"))
            out.append(_line("makespan (sum of step walls)",
                             _fmt_s(float(np.sum(walls)))))
        if waves:
            out.append(_line("waves per step (mean / max)",
                             f"{np.mean(waves):6.1f} / {max(waves)}"))
        bub = [r["bubble_frac"] for r in history if "bubble_frac" in r]
        if bub:
            out.append(_line("planned bubble fraction (mean)",
                             f"{np.mean(bub):8.4f}"))
        pbub = [r["bubble_frac_pipeline"] for r in history
                if "bubble_frac_pipeline" in r]
        if pbub:
            out.append(_line("pipeline bubble fraction (mean)",
                             f"{np.mean(pbub):8.4f}"))
    dp50 = m.get("trainer.dispatch_s.p50")
    if dp50 is not None:
        out.append(_line("dispatch wall p50 / p99 (hist)",
                         f"{_fmt_s(dp50)} /"
                         f"{_fmt_s(m.get('trainer.dispatch_s.p99', 0.0))}"))

    if attribution:
        out.append("-- time attribution (step x lane) --")
        out.append(_line("step  lane",
                         "window    comp%  disp%  bubb%  stall%"))
        for r in attribution[:24]:
            w = max(r["window_s"], 1e-12)
            out.append(_line(
                f"{r['step']:>4d}  {r['process'][:24]:<24}",
                f"{_fmt_s(r['window_s'])} "
                f"{r['compute_s'] / w * 100:6.1f} "
                f"{r['dispatch_s'] / w * 100:6.1f} "
                f"{r['bubble_s'] / w * 100:6.1f} "
                f"{r['stall_s'] / w * 100:6.1f}"))
        if len(attribution) > 24:
            out.append(_line("...", f"({len(attribution) - 24} more)"))

    if goodput and goodput.get("goodput") is not None:
        out.append("-- goodput --")
        out.append(_line("goodput (final-step / total wall)",
                         f"{goodput['goodput'] * 100:7.2f}%"))
        out.append(_line("useful / total",
                         f"{_fmt_s(goodput['useful_s'])} /"
                         f"{_fmt_s(goodput['total_s'])}"))

    if comm and (comm.get("n_dispatch") or comm.get("n")):
        out.append("-- comm / memory (bytes ledger: predicted vs "
                   "measured) --")
        out.append(_line("dispatches audited",
                         str(comm.get("n_dispatch", comm.get("n")))))
        out.append(_line("predicted / measured comm total",
                         f"{_fmt_b(comm.get('pred_total', 0.0))} /"
                         f"{_fmt_b(comm.get('meas_total', 0.0))}"))
        if comm.get("comm_residual") is not None:
            out.append(_line("comm residual |pred-meas|/max",
                             f"{comm['comm_residual'] * 100:7.2f}%"))
        for kind, resid in sorted((comm.get("residual") or {}).items()):
            out.append(_line(f"  residual [{kind}]",
                             f"{resid * 100:7.2f}%"))
        if comm.get("hbm_pred_peak") or comm.get("hbm_meas_peak"):
            out.append(_line("HBM peak predicted / sampled",
                             f"{_fmt_b(comm.get('hbm_pred_peak', 0.0))} /"
                             f"{_fmt_b(comm.get('hbm_meas_peak', 0.0))}"))
        for kind, v in sorted((comm.get("step_bytes") or {}).items()):
            out.append(_line(f"per-step [{kind}]", _fmt_b(float(v))))

    gap_mean = m.get("ctrl.wave_gap_s.mean")
    gap_max = m.get("ctrl.wave_gap_s.max")
    if gap_mean is not None:
        out.append("-- stragglers (per-wave rank gap) --")
        out.append(_line("wave max-min gap (mean / max)",
                         f"{_fmt_s(gap_mean)} /{_fmt_s(gap_max or 0.0)}"))
    streamed = m.get("ctrl.waves_streamed")
    if streamed:
        out.append(_line("per-wave records streamed",
                         str(int(streamed))))
    dropped = m.get("ctrl.telemetry_dropped")
    if dropped:
        out.append(_line("telemetry records DROPPED", str(int(dropped))))

    if advisories:
        out.append("-- anomaly advisories --")
        for a in list(advisories)[-8:]:
            who = f"rank {a['rank']}" if a.get("rank") is not None \
                else f"worker {a.get('worker')}"
            out.append(_line(
                f"[{a['kind']}] step {a.get('step')} {who}",
                f"sev {a.get('severity', 0):6.1f}  "
                f"{a.get('detail', '')[:40]}"))
        if len(advisories) > 8:
            out.append(_line("...", f"({len(advisories) - 8} earlier)"))

    if telemetry:
        out.append("-- telemetry stream (per worker) --")
        for wid, t in sorted(telemetry.items()):
            alive = "up" if t.get("alive") else "DEAD"
            out.append(_line(
                f"worker {wid} [{alive}] ranks {t.get('ranks')}",
                f"streamed {t.get('streamed', 0):5d}  "
                f"dropped {t.get('dropped', 0):3d}  "
                f"last step {t.get('last_step')}"))

    if calib:
        out.append("-- cost model (Eq. 2 / Eq. 3) vs measurement --")
        if calib.get("scale") is not None:
            out.append(_line("measured/modeled scale (median)",
                             f"{calib['scale']:8.4f}"))
        if calib.get("model_gap") is not None:
            out.append(_line("modeled-vs-measured gap (median)",
                             f"{calib['model_gap'] * 100:7.2f}%"))
        sp = calib.get("speed")
        if sp:
            out.append(_line("rank speed (min / max)",
                             f"{min(sp):6.3f} / {max(sp):6.3f}"))
        if calib.get("bytes_residual") is not None:
            out.append(_line("comm bytes residual (ledger EMA)",
                             f"{calib['bytes_residual'] * 100:7.2f}%  "
                             f"({calib.get('bytes_n', 0)} dispatches)"))
        if calib.get("n_observed") is not None:
            out.append(_line("observations", str(calib["n_observed"])))

    miss = m.get("trainer.compile_miss", 0)
    hit = m.get("trainer.compile_hit", 0)
    if miss or hit:
        out.append("-- compile cache --")
        out.append(_line("hit rate",
                         f"{hit / max(hit + miss, 1) * 100:7.2f}%  "
                         f"({int(hit)} hit / {int(miss)} miss)"))
    smiss = m.get("serve.compile_miss", 0)
    shit = m.get("serve.compile_hit", 0)
    if smiss or shit:
        out.append(_line("serve prefill hit rate",
                         f"{shit / max(shit + smiss, 1) * 100:7.2f}%  "
                         f"({int(shit)} hit / {int(smiss)} miss)"))

    if serve_records:
        ttft = [r["t_first"] - r["t_submit"] for r in serve_records
                if r.get("t_first") is not None
                and r.get("t_submit") is not None]
        e2e = [r["t_done"] - r["t_submit"] for r in serve_records
               if r.get("t_done") is not None
               and r.get("t_submit") is not None]
        out.append("-- serving --")
        out.append(_line("requests", str(len(serve_records))))
        if ttft:
            out.append(_line("TTFT p50 / p99",
                             f"{_fmt_s(_pct(50, ttft))} /"
                             f"{_fmt_s(_pct(99, ttft))}"))
        if e2e:
            out.append(_line("latency p50 / p99",
                             f"{_fmt_s(_pct(50, e2e))} /"
                             f"{_fmt_s(_pct(99, e2e))}"))
        qd = m.get("serve.queue_depth")
        if qd is not None:
            out.append(_line("queue depth (last)", str(int(qd))))
        dw = m.get("serve.decode_waves")
        pw = m.get("serve.prefill_waves")
        if dw is not None or pw is not None:
            out.append(_line("prefill / decode waves",
                             f"{int(pw or 0)} / {int(dw or 0)}"))

    if len(out) == 1:
        out.append("  (no data)")
    return "\n".join(out)
