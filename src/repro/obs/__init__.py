"""Unified observability layer (zero-dependency): tracing, metrics,
flight recorder, report.

One import surface for every instrumented module::

    from repro.obs import get_metrics, get_recorder, get_tracer, monotime

    with get_tracer().span("wave", step=t, composition=comp):
        ...
    get_metrics().counter("trainer.waves").inc()
    get_recorder().record("dispatch", step=t)

All three are process-global singletons.  Tracing is DISABLED by default
(`span()` is a shared no-op singleton — nothing allocates); metrics and
the flight-recorder ring are always on and cost one lock acquisition per
update.  `configure()` is the one knob surface:

    obs.configure(trace=True, trace_process="worker3", trace_pid=3,
                  metrics_path="metrics.jsonl")

Environment: ``REPRO_TRACE=1`` enables tracing at import (the knob
subprocess workers inherit), ``REPRO_OBS_DIR`` sets where flight-recorder
dumps land (default ``obs_out/``), ``REPRO_TRACE_DIR`` makes ctrl worker
agents export their Chrome trace there on exit (one file per process —
the input set for ``python -m repro.obs.analyze``).
"""
from __future__ import annotations

from typing import Optional

from repro.obs.analyze import (attribute_steps, comm_summary, goodput,
                               merge_traces)
from repro.obs.anomaly import Advisory, AnomalyConfig, AnomalyDetector
from repro.obs.ledger import Ledger, ledger_enabled, set_ledger_enabled
from repro.obs.metrics import MetricsRegistry, get_metrics
from repro.obs.numerics import (NONFINITE_SEVERITY, MonitorConfig,
                                NumericsMonitor, StepProvenance,
                                nonfinite_signature, plan_fingerprint)
from repro.obs.recorder import FlightRecorder, get_recorder
from repro.obs.report import render_report
from repro.obs.trace import (Tracer, get_tracer, monotime, set_tracer,
                             validate_chrome_trace)

__all__ = [
    "MetricsRegistry", "FlightRecorder", "Tracer", "Ledger",
    "Advisory", "AnomalyConfig", "AnomalyDetector",
    "MonitorConfig", "NumericsMonitor", "StepProvenance",
    "NONFINITE_SEVERITY", "plan_fingerprint", "nonfinite_signature",
    "get_metrics", "get_recorder", "get_tracer", "set_tracer",
    "monotime", "render_report", "validate_chrome_trace", "configure",
    "merge_traces", "attribute_steps", "goodput", "comm_summary",
    "ledger_enabled", "set_ledger_enabled",
]


def configure(trace: Optional[bool] = None,
              trace_process: Optional[str] = None,
              trace_pid: Optional[int] = None,
              metrics_path: Optional[str] = None,
              ledger: Optional[bool] = None) -> None:
    """Adjust the process-global observability state in one call; every
    argument left ``None`` keeps its current setting."""
    t = get_tracer()
    if trace is not None:
        t.enabled = bool(trace)
    if ledger is not None:
        set_ledger_enabled(ledger)
    if trace_process is not None:
        t.process = trace_process
        t.set_process_name(t.pid if trace_pid is None else int(trace_pid),
                           trace_process)
    if trace_pid is not None:
        t.pid = int(trace_pid)
    if metrics_path is not None:
        get_metrics().configure_sink(metrics_path or None)
