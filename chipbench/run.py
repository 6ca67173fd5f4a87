#!/usr/bin/env python3
"""ByteScale training benchmark on the chip.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One cell of `BENCHMARK.json` per run: its configuration
(`chipbench/configs/<config>.json`), its traffic mix
(`chipbench/traffic/<traffic>.json`) and its chips.  Set-up builds the
trainer from the seed, drives it through the first steps that the
comparison checks (they compile every program the window uses), then the
window runs `Trainer.train_step` for ``--seconds`` seconds.  After the
window the trainer is freed and the plain float32 reference
(`reference.py`) trains the same first steps; `compare.py` decides
``correct``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, each read by `chipbench/metrics/<metric>.py` from the
profiler's trace of the window, the program's spans and counters.  The
last line of stdout is one JSON object; the numbers compared stand last
on stderr and under ``checks``.  Without a TPU, or with fewer chips than
the cell asks for, the run exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

CHECK_STEPS = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _profile_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0        # the program's own spans suffice
    return o


def load_cell(name: str, root: Path = ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    return bench, cells[name]


def cell_metrics(bench: dict, cell: dict, kind: str):
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def setup_cache(root: Path = ROOT) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout, set
    before JAX starts, so every run after a cell's first finds its
    programs there."""
    cache = root / "chipbench" / ".jax_cache"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_at_hand(want: int):
    """The first ``want`` TPU chips, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX reports {devs[0].platform!r}",
              file=sys.stderr)
        return None
    if len(devs) < want:
        print(f"chipbench: the cell needs {want} chips; found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:want]


class CompileCounter:
    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE:
            self.n += 1


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_window(trainer, traffic, seconds: float):
    """Steps until ``seconds`` have passed; each ends in the apply's host
    fetch.  -> (per-step [start, end] on the host clock, per-step
    non-padding tokens)."""
    from jax.profiler import TraceAnnotation
    spans, tokens = [], []
    t0 = time.perf_counter()
    while True:
        step = trainer.step
        with TraceAnnotation("chipbench_step"):
            ts = time.perf_counter()
            trainer.train_step()
            te = time.perf_counter()
        spans.append((ts, te))
        tokens.append(sum(traffic.step_lengths(step)))
        if te - t0 >= seconds:
            return t0, spans, tokens


def main(argv=None, *, root: Path = ROOT, devices=None, plant=None) -> int:
    """``devices`` and ``plant`` are for tests: the devices to run on in
    place of the chips, and a function that breaks the built trainer."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell = load_cell(args.workload, root)
    setup_cache(root)

    import jax
    if devices is None:
        devices = chips_at_hand(int(cell["chips"]))
        if devices is None:
            return 2

    import compare
    import flops
    import program
    import reference
    from model import load_config, model_config
    from peaks import peaks
    from traffic import Traffic, load_mix

    kind = devices[0].device_kind
    peak = peaks(kind) if devices[0].platform == "tpu" else None
    limits = compare.load_limits(cell["name"], root / "chipbench")
    c = load_config(cell["config"], root / "chipbench")
    cfg = model_config(cell["config"], root / "chipbench")
    mix = load_mix(cell["traffic"], root / "chipbench")
    traffic = Traffic(mix, c["vocab_size"], args.seed)
    compiles = CompileCounter()

    t_build = time.perf_counter()
    trainer = program.build(cfg, traffic, devices, args.seed)
    if plant is not None:
        plant(trainer)
    t_steps = time.perf_counter()
    prog = program.readings(trainer, CHECK_STEPS)
    print(f"set-up: start {t_build - T_START:.1f}s, build "
          f"{t_steps - t_build:.1f}s, first {CHECK_STEPS} steps "
          f"{time.perf_counter() - t_steps:.1f}s; compiles {compiles.n}",
          file=sys.stderr)
    print(f"first steps: loss {prog['loss']} compositions "
          f"{[sorted(set(r['compositions'])) for r in prog['records']]}",
          file=sys.stderr, flush=True)

    from repro import obs
    ctx = {"config": c, "peak": peak, "chips": len(devices)}
    trace_dir = root / "chipbench" / ".trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.configure(trace=True)
        obs.get_tracer().clear()
        jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
    n_compiles = compiles.n
    setup_s = time.perf_counter() - T_START
    t0, spans, tokens = run_window(trainer, traffic, args.seconds)
    window_s = spans[-1][1] - t0
    ctx["compiles_in_window"] = compiles.n - n_compiles
    if args.trace:
        jax.profiler.stop_trace()
        obs.configure(trace=False)
        ctx["spans"] = obs.get_tracer().snapshot()
    mem_peak = peak_memory(devices) if devices[0].platform == "tpu" else 0
    first_step = trainer.step - len(spans)
    ctx.update(step_tokens=tokens,
               steps=list(range(first_step, trainer.step)))
    step_lengths = [traffic.step_lengths(s) for s in ctx["steps"]]
    ctx["step_lengths"] = step_lengths

    trainer.sched.stop()
    del trainer
    gc.collect()

    result = {"attempted": len(spans), "failed": 0}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if args.trace:
        import devtrace
        xspace = devtrace.find(trace_dir).read_bytes()
        trace = devtrace.read(xspace, c["vocab_size"], len(devices),
                              [s for s, _ in spans], ctx["spans"])
        ctx["trace"] = trace
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        metrics = per_layer(bench, cell, ctx, root)
        result["breakdown"] = trace.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        n_tok = float(sum(tokens))
        mflops = sum(flops.model_flops(c, ls) for ls in step_lengths)
        values = {"tokens_per_s": n_tok / window_s, "setup_s": setup_s,
                  "mfu": (100.0 * mflops / (len(devices)
                                            * peak["bf16_flops"]
                                            * window_s)) if peak else None}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, cell, "end_to_end")}
    walls = [round(e - s, 4) for s, e in spans]
    print(f"window: {len(spans)} steps in {window_s:.3f}s, step walls {walls}"
          f", compiles in window {ctx['compiles_in_window']}",
          file=sys.stderr, flush=True)

    t_ref = time.perf_counter()
    ref = reference.Reference(c, traffic, devices).run(args.seed, CHECK_STEPS)
    print(f"reference: {time.perf_counter() - t_ref:.1f}s", file=sys.stderr)
    values = compare.gaps(prog, ref)
    correct, checks = compare.verdict(values, limits)
    print(f"loss program {prog['loss']} reference {ref['loss']}; gradient "
          f"norm before clipping program {prog['gnorm']} reference "
          f"{ref['gnorm']}; worst leaves {values['worst_leaf']}",
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    result.update(correct=correct, metrics=metrics, device=device,
                  checks=checks)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


def per_layer(bench: dict, cell: dict, ctx: dict, root: Path = ROOT):
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read leaves its metric out."""
    metrics = {}
    for m in cell_metrics(bench, cell, "per_layer"):
        val = load_reader(m["name"], root)(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return metrics


def load_reader(name: str, root: Path = ROOT):
    """``chipbench/metrics/<name>.py``'s ``read(ctx)``."""
    import importlib.util
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


if __name__ == "__main__":
    sys.exit(main())
