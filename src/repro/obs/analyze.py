"""Cluster analytics: merge per-process traces, attribute step time,
and report goodput.

PR 7 built the collection side — spans, metrics, streamed telemetry —
and every Chrome trace carries an ``otherData.wall_anchor`` pair
(monotonic µs, wall s) taken at tracer construction.  This module is
the consumer:

* `merge_traces` joins the controller's and N workers' trace files into
  ONE cluster timeline.  Monotonic clocks share no epoch across
  processes, so each doc's events are re-based through its wall anchor:
  ``wall(ev) = wall_s + (ts_us - mono_us) / 1e6``, then shifted onto a
  common zero.  Colliding pids are renumbered (each source keeps its
  lane structure) and the merged doc passes `validate_chrome_trace`.

* `attribute_steps` decomposes each (step × lane) window into
  **compute** (wave/round spans minus nested compiles), **dispatch**
  (the trainer's other host spans: plan / grads_init / materialize /
  h2d / wave_post / apply / step_end / checkpoint), **bubble** (uncovered time
  between the first and last compute span — the wave-gap the planner's
  makespan model calls bubble) and **stall** (compile time + uncovered
  time outside the compute envelope).  The four buckets sum to the
  window by construction — the invariant the obs bench gates at 5%
  against the measured step wall.

* `goodput` divides the final-step wall — each step index once, a
  step replayed after elastic recovery was wasted work — by the whole
  trace extent, recoveries and re-plans included.

CLI::

    python -m repro.obs.analyze trace_*.json [--metrics metrics.jsonl]
        [--out merged.json] [--json]
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from repro.obs.trace import validate_chrome_trace

#: Span-name taxonomy (train/trainer.py, sched/service.py,
#: ctrl/controller.py) -> attribution bucket.
COMPUTE_SPANS = ("wave", "round")
DISPATCH_SPANS = ("plan", "grads_init", "materialize", "h2d", "wave_post",
                  "apply", "step_end", "checkpoint",
                  "plan_window", "materialize_ahead", "plan_pool")
STALL_SPANS = ("compile", "await_step")


# ---------------------------------------------------------------------------
# trace merging
# ---------------------------------------------------------------------------

def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _anchor_of(doc: dict) -> Tuple[float, float]:
    a = (doc.get("otherData") or {}).get("wall_anchor") or {}
    if "mono_us" not in a or "wall_s" not in a:
        raise ValueError(
            "trace has no otherData.wall_anchor — cannot align it onto "
            "the cluster timeline (re-export with repro.obs.Tracer)")
    return float(a["mono_us"]), float(a["wall_s"])


def merge_traces(docs: Sequence, validate: bool = False) -> dict:
    """One cluster-wide Chrome trace from per-process trace docs (or
    file paths).  Every event's ``ts`` is re-based onto a shared
    wall-clock timeline (µs since the earliest event across all docs)
    via each doc's ``wall_anchor``; pids colliding across docs are
    renumbered so each process keeps a distinct lane.  With
    ``validate=True`` the merged doc is schema-checked and a failure
    raises ``ValueError``."""
    docs = [load_trace(d) if isinstance(d, str) else d for d in docs]
    if not docs:
        raise ValueError("merge_traces needs at least one trace doc")
    rebased: List[Tuple[dict, List[dict]]] = []   # (doc, wall-us events)
    for doc in docs:
        mono_us, wall_s = _anchor_of(doc)
        evs = []
        for e in doc.get("traceEvents", []):
            e = dict(e)
            if e.get("ph") != "M":     # meta rows stay pinned at ts 0
                e["ts"] = wall_s * 1e6 + (float(e["ts"]) - mono_us)
            evs.append(e)
        rebased.append((doc, evs))

    # common zero: the earliest non-meta event on the shared wall line
    starts = [e["ts"] for _, evs in rebased for e in evs
              if e.get("ph") != "M"]
    t0 = min(starts) if starts else 0.0

    used_pids: set = set()
    merged: List[dict] = []
    sources: List[dict] = []
    for doc, evs in rebased:
        pids = sorted({e["pid"] for e in evs})
        remap: Dict[int, int] = {}
        for pid in pids:
            new = pid
            while new in used_pids:
                new += 1               # next free lane, order-preserving
            remap[pid] = new
            used_pids.add(new)
        for e in evs:
            e["pid"] = remap[e["pid"]]
            if e.get("ph") != "M":
                e["ts"] = e["ts"] - t0
            merged.append(e)
        od = doc.get("otherData") or {}
        sources.append({"process": od.get("process"),
                        "pid_map": {str(k): v for k, v in remap.items()},
                        "dropped_events": od.get("dropped_events", 0)})
    merged.sort(key=lambda e: (0 if e.get("ph") == "M" else 1,
                               float(e.get("ts", 0.0))))
    out = {"traceEvents": merged, "displayTimeUnit": "ms",
           "otherData": {"clock": "wall-aligned",
                         "merged_from": len(docs),
                         "wall_anchor": {"mono_us": 0.0,
                                         "wall_s": t0 / 1e6},
                         "sources": sources}}
    if validate:
        ok, problems = validate_chrome_trace(out)
        if not ok:
            raise ValueError(f"merged trace invalid: {problems[:4]}")
    return out


# ---------------------------------------------------------------------------
# time attribution
# ---------------------------------------------------------------------------

def _proc_names(doc: dict) -> Dict[int, str]:
    return {e["pid"]: e["args"]["name"]
            for e in doc.get("traceEvents", [])
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and e.get("args", {}).get("name")}


def _step_spans(doc: dict) -> Dict[Tuple[int, int], List[dict]]:
    """(pid, tid) -> "X" spans carrying an ``args.step`` stamp.  Only
    the busiest step-stamped tid per pid is kept — the step loop lane —
    so planner-thread lookahead spans (stamped with FUTURE steps they
    plan ahead for) don't smear into the executing step's window."""
    lanes: Dict[Tuple[int, int], List[dict]] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "X" and "step" in (e.get("args") or {}):
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    best: Dict[int, Tuple[int, int]] = {}
    for (pid, tid), evs in lanes.items():
        if pid not in best or len(evs) > len(lanes[best[pid]]):
            best[pid] = (pid, tid)
    return {k: lanes[k] for k in best.values()}


def _subtract_covered(window: Tuple[float, float],
                      tops: List[Tuple[float, float]]) -> List[
                          Tuple[float, float]]:
    """Uncovered sub-intervals of ``window`` given sorted disjoint
    top-level span intervals."""
    gaps = []
    cur = window[0]
    for t0, t1 in tops:
        if t0 > cur:
            gaps.append((cur, min(t0, window[1])))
        cur = max(cur, t1)
    if cur < window[1]:
        gaps.append((cur, window[1]))
    return [(a, b) for a, b in gaps if b > a]


def _overlap(a: Tuple[float, float], b: Tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_steps(doc: dict) -> List[dict]:
    """Per (step × lane) wall-time decomposition.  Returns one record
    per step per process lane carrying step-stamped spans, with
    ``compute_s + dispatch_s + bubble_s + stall_s == window_s`` (exact
    up to float rounding — ``check`` reports the ratio).  A lane whose
    step window is a single enclosing span (the controller's
    ``ctrl_step``, the trainer's ``train_step``) is peeled: the wrapper
    defines the window and its children are attributed."""
    names = _proc_names(doc)
    recs: List[dict] = []
    for (pid, tid), evs in sorted(_step_spans(doc).items()):
        by_step: Dict[int, List[dict]] = {}
        for e in evs:
            by_step.setdefault(int(e["args"]["step"]), []).append(e)
        for step, spans in sorted(by_step.items()):
            iv = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]),
                   e["name"]) for e in spans]
            w0, w1 = min(t0 for t0, _, _ in iv), max(t1 for _, t1, _ in iv)
            # peel a wrapper span covering the whole window (ctrl_step)
            wrappers = [x for x in iv
                        if x[0] <= w0 + 1e-6 and x[1] >= w1 - 1e-6]
            inner = [x for x in iv if x not in wrappers] or wrappers[-1:]
            # top-level selection: sort (start asc, dur desc); a span
            # contained in the previous top-level span is nested
            inner.sort(key=lambda x: (x[0], -(x[1] - x[0])))
            tops: List[Tuple[float, float, str]] = []
            nested: List[Tuple[float, float, str]] = []
            for t0, t1, name in inner:
                if tops and t1 <= tops[-1][1] + 1e-6 \
                        and t0 >= tops[-1][0] - 1e-6:
                    nested.append((t0, t1, name))
                else:
                    tops.append((t0, t1, name))
            compute = dispatch = stall = 0.0
            n_waves = 0
            for t0, t1, name in tops:
                dur = t1 - t0
                if name in COMPUTE_SPANS:
                    n_waves += 1
                    compile_s = sum(min(t1, n1) - max(t0, n0)
                                    for n0, n1, nm in nested
                                    if nm in STALL_SPANS
                                    and n0 >= t0 - 1e-6 and n1 <= t1 + 1e-6)
                    compile_s = min(max(compile_s, 0.0), dur)
                    compute += dur - compile_s
                    stall += compile_s
                elif name in STALL_SPANS:
                    stall += dur
                else:                  # plan/materialize/apply/... and
                    dispatch += dur    # any future span name
            # uncovered time: inside the compute envelope it's bubble
            # (wave-gap), outside it's stall
            env = None
            cts = [(t0, t1) for t0, t1, nm in tops if nm in COMPUTE_SPANS]
            if cts:
                env = (min(t0 for t0, _ in cts), max(t1 for _, t1 in cts))
            gaps = _subtract_covered((w0, w1),
                                     [(t0, t1) for t0, t1, _ in tops])
            bubble = 0.0
            for g in gaps:
                if env is not None:
                    b = _overlap(g, env)
                    bubble += b
                    stall += (g[1] - g[0]) - b
                else:
                    stall += g[1] - g[0]
            window = (w1 - w0) / 1e6
            parts = [compute / 1e6, dispatch / 1e6, bubble / 1e6,
                     stall / 1e6]
            recs.append({
                "step": step, "pid": pid, "tid": tid,
                "process": names.get(pid, f"pid{pid}"),
                "t0_us": w0, "window_s": window,
                "compute_s": parts[0], "dispatch_s": parts[1],
                "bubble_s": parts[2], "stall_s": parts[3],
                "n_waves": n_waves,
                "check": sum(parts) / window if window > 0 else 1.0})
    return recs


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------

def goodput(doc: dict, attribution: Optional[List[dict]] = None) -> dict:
    """Final-step wall over the trace extent.  Each step index counts
    once, at its widest (step × lane) window from `attribute_steps` —
    a step replayed after elastic recovery was wasted work — against
    the extent of every span in the trace, recoveries and re-plans
    included."""
    if attribution is None:
        attribution = attribute_steps(doc)
    step_windows: Dict[int, float] = {}
    for r in attribution:
        cur = step_windows.get(r["step"], 0.0)
        step_windows[r["step"]] = max(cur, r["window_s"])
    if not step_windows:
        return {"n_steps": 0, "goodput": None}
    xs = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
          for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    extent_s = max((max(t1 for _, t1 in xs) - min(t0 for t0, _ in xs))
                   / 1e6, 1e-9)
    useful = sum(step_windows.values())
    return {"n_steps": len(step_windows),
            "goodput": round(min(useful / extent_s, 1.0), 4),
            "useful_s": round(useful, 6),
            "total_s": round(extent_s, 6)}


# ---------------------------------------------------------------------------
# comm / memory (bytes-ledger stamps)
# ---------------------------------------------------------------------------

def comm_summary(doc: dict) -> dict:
    """Aggregate the bytes-ledger stamps off wave/round spans
    (``args.bytes_pred`` / ``args.bytes_meas`` — `obs.ledger` records the
    trainer lands per dispatch) into the predicted-vs-measured comm
    audit: per-kind fleet byte totals, relative residuals, and per-step
    totals.  Waves are SPMD — every worker lane stamps the same fleet
    record — so one lane per (step, idx) counts, final occurrence wins
    (elastic replays overwrite)."""
    from repro.obs import ledger

    spans: Dict[Tuple[int, int], dict] = {}
    for e in doc.get("traceEvents", []):
        a = e.get("args") or {}
        if e.get("ph") != "X" or e["name"] not in COMPUTE_SPANS \
                or "bytes_pred" not in a:
            continue
        key = (int(a.get("step", -1)), int(a.get("idx", 0)))
        prev = spans.get(key)
        if prev is None or float(e["ts"]) > prev["ts"]:
            spans[key] = {"ts": float(e["ts"]),
                          "pred": a["bytes_pred"],
                          "meas": a.get("bytes_meas")}
    if not spans:
        return {"n_dispatch": 0}
    totals = ledger.new_totals()
    by_step: Dict[int, Dict[str, float]] = {}
    for (step, _), s in spans.items():
        rec = {"pred": s["pred"]}
        if s["meas"]:
            rec["meas"] = s["meas"]
        ledger.merge_record(totals, rec)
        agg = by_step.setdefault(step, {"pred": 0.0, "meas": 0.0})
        agg["pred"] += sum(float(v) for v in s["pred"].values())
        if s["meas"]:
            agg["meas"] += sum(float(v) for v in s["meas"].values())
    out = ledger.totals_summary(totals)
    out["n_dispatch"] = out.pop("n")
    out["per_step"] = [{"step": s, "pred_bytes": round(v["pred"]),
                        "meas_bytes": round(v["meas"])}
                       for s, v in sorted(by_step.items())]
    return out


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _load_metrics_jsonl(path: str) -> Optional[dict]:
    last = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                last = json.loads(line)
    return last


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.analyze",
        description="Merge per-process Chrome traces into one cluster "
                    "timeline, attribute step time, report goodput.")
    ap.add_argument("traces", nargs="+", help="trace_*.json files")
    ap.add_argument("--metrics", default=None,
                    help="per-step metrics JSONL (launcher --metrics-out)"
                         "; the last record joins the report")
    ap.add_argument("--out", default=None,
                    help="write the merged Chrome trace here")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary instead of the report")
    ap.add_argument("--comm", action="store_true",
                    help="include the bytes-ledger comm/memory audit "
                         "(predicted vs measured bytes per collective "
                         "kind, off the wave spans' ledger stamps)")
    args = ap.parse_args(argv)

    merged = merge_traces(args.traces)
    ok, problems = validate_chrome_trace(merged)
    attribution = attribute_steps(merged)
    good = goodput(merged, attribution)
    comm = comm_summary(merged) if args.comm else None
    if args.out:
        with open(args.out, "w") as f:
            json.dump(merged, f)
            f.write("\n")
    metrics = _load_metrics_jsonl(args.metrics) if args.metrics else None
    if args.json:
        out = {"valid": ok, "problems": problems[:8],
               "n_events": len(merged["traceEvents"]),
               "attribution": attribution, "goodput": good}
        if comm is not None:
            out["comm"] = comm
        print(json.dumps(out, indent=1, sort_keys=True))
    else:
        from repro.obs.report import render_report
        print(render_report(metrics=metrics, attribution=attribution,
                            goodput=good, comm=comm,
                            title="cluster analysis "
                            f"({len(args.traces)} trace(s), "
                            f"valid={ok})"))
        if not ok:
            print("  trace problems:", *problems[:4], sep="\n    ")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
