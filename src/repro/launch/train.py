"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

On the CPU it runs reduced configs end to end (``--reduced``); on a TPU
host the same entry point takes ``--mesh HxM`` over the host's chips and a
one-chip-share config such as ``llama3.2-3b-1chip``.  `make_trainer` is
the single-process path; ``chip_smoke.py`` drives it too.
"""
from __future__ import annotations

import argparse
import os

from repro import compat
from repro.configs.registry import get_config
from repro.data.distribution import DISTRIBUTIONS, LengthDistribution
from repro.data.loader import GlobalScheduler, SyntheticDataset
from repro.launch.mesh import make_local_mesh
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import Runtime
from repro.train.trainer import Trainer, TrainerConfig


def _resolve_config(args):
    """Shared by the single-process and --ctrl paths: the model config
    (with the --reduced clamps applied to args in place) plus the
    synthetic dataset for the requested distribution."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        args.capacity = min(args.capacity, 512)
        args.tokens_per_step = min(args.tokens_per_step, 8192)
        args.context = min(args.context, 2048)
    dist = DISTRIBUTIONS.get(args.dataset) or \
        LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
    ds = SyntheticDataset(dist, cfg.vocab_size, args.tokens_per_step,
                          args.context)
    return cfg, ds


def make_trainer(args) -> Trainer:
    """The single-process path: config, ``--mesh HxM`` mesh over the
    devices at hand (installed as the ambient mesh), scheduler and
    trainer.  The caller iterates ``trainer.run(n)`` and must call
    ``trainer.sched.stop()`` when done."""
    cfg, ds = _resolve_config(args)
    mesh = make_local_mesh(args.mesh)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model")
    compat.set_mesh(rt.mesh)
    sched = GlobalScheduler(ds, cfg, capacity=args.capacity,
                            hdp=rt.hdp_size, strategy=args.strategy,
                            use_offload=False, lookahead=args.lookahead,
                            sched_async=args.sched_async)
    return Trainer(cfg, rt,
                   AdamWConfig(lr=args.lr, total_steps=args.steps),
                   sched, TrainerConfig(capacity=args.capacity,
                                        ckpt_dir=args.ckpt_dir,
                                        strategy=args.strategy,
                                        attn_impl=args.attn_impl,
                                        max_round_waves=args.max_round_waves,
                                        sched_async=args.sched_async))


def _run_ctrl(args):
    """Distributed control plane: controller here, workers spawned as
    local subprocesses (launch/cluster.py).  Returns the controller so
    the exit path can render advisories / telemetry."""
    from repro.core.planner import PlanSpec
    from repro.ctrl.controller import Controller, ControllerConfig
    from repro.launch.cluster import LocalCluster

    cfg, ds = _resolve_config(args)
    dims = tuple(int(x) for x in args.mesh.split("x"))
    hdp, tp = (dims[0], dims[1]) if len(dims) >= 2 else (dims[0], 1)
    spec = PlanSpec.for_config(cfg, capacity=args.capacity, hdp=hdp,
                               strategy=args.strategy, use_offload=False)
    ctl = Controller(ds, cfg, spec, ControllerConfig(
        num_workers=args.num_workers, steps=args.steps,
        lookahead=args.lookahead, async_plan=args.sched_async,
        ship_buffers=args.ship_buffers, ckpt_dir=args.ckpt_dir, tp=tp,
        heartbeat_interval=args.heartbeat_interval,
        heartbeat_timeout=args.heartbeat_timeout,
        max_round_waves=args.max_round_waves,
        anomaly_detect=not args.no_anomaly,
        runtime_kw={"remat": "none"}, opt_kw={"lr": args.lr}))
    cluster = LocalCluster(ctl)
    addr = cluster.start()
    print(f"controller at {addr}; {args.num_workers} workers x "
          f"{hdp}x{tp} mesh", flush=True)
    try:
        cluster.run(on_step=lambda _c, r: print(
            f"step {r['step']:4d} loss {r['loss']:.4f} "
            f"waves {r['waves']} hdp {r['hdp']} "
            f"workers {r['workers']}", flush=True))
    finally:
        cluster.shutdown()
    return ctl


def _analyze_trace_dir(trace_dir):
    """Merge every per-process trace in ``trace_dir`` onto the cluster
    timeline (workers export there on exit via $REPRO_TRACE_DIR; the
    controller's own trace is written just before this runs) and return
    (attribution records, goodput dict) — or (None, None) when there is
    nothing to merge."""
    import glob
    import json

    from repro.obs.analyze import attribute_steps, goodput, merge_traces
    paths = sorted(p for p in
                   glob.glob(os.path.join(trace_dir, "trace_*.json"))
                   if "merged" not in os.path.basename(p))
    if not paths:
        return None, None
    merged = merge_traces(paths)
    out = os.path.join(trace_dir, "trace_merged.json")
    with open(out, "w") as f:
        json.dump(merged, f)
        f.write("\n")
    print(f"merged cluster trace ({len(paths)} processes) -> {out}",
          flush=True)
    attribution = attribute_steps(merged)
    return attribution, goodput(merged, attribution)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--capacity", type=int, default=8192)
    ap.add_argument("--tokens-per-step", type=int, default=65_536)
    ap.add_argument("--context", type=int, default=32_768)
    ap.add_argument("--dataset", default="github",
                    choices=list(DISTRIBUTIONS) + ["tiny"])
    ap.add_argument("--strategy", default="balance",
                    choices=["static", "naive", "balance"])
    ap.add_argument("--mesh", default="1x1",
                    help="HxM: H-way HDP x M-way tensor parallel over all "
                         "local devices (H*M must equal their count); "
                         "--ctrl: each worker's geometry")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--attn-impl", default=None, choices=["ref", "pallas"],
                    help="attention backend: jnp oracle ring (ref) or the "
                         "Pallas ring-flash engine (pallas; compiled on a "
                         "TPU, interpreted on CPU devices)")
    ap.add_argument("--max-round-waves", type=int, default=0,
                    help="pipelined executor: cap waves per round (0 = "
                         "uncapped) to bound in-flight activation memory")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="scheduler service: jointly plan windows of K "
                         "upcoming steps (cross-step balance + compile-"
                         "cache-aware compositions; 1 = per-step windows, "
                         "still template-harmonized)")
    ap.add_argument("--sched-async", action="store_true",
                    help="plan + materialize upcoming steps on a planner "
                         "thread while the current step executes")
    ap.add_argument("--ctrl", action="store_true",
                    help="distributed control plane: run the controller "
                         "in this process and spawn --num-workers worker "
                         "agents as subprocesses (repro.ctrl); the mesh "
                         "arg gives each worker's hdp x model geometry")
    ap.add_argument("--num-workers", type=int, default=2,
                    help="worker agent processes (--ctrl); must divide "
                         "the HDP axis")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5,
                    help="worker->controller heartbeat cadence, seconds")
    ap.add_argument("--heartbeat-timeout", type=float, default=60.0,
                    help="declare a silent worker dead after this many "
                         "seconds (crashes are caught instantly via EOF)")
    ap.add_argument("--ship-buffers", action="store_true",
                    help="controller materializes wave buffers and ships "
                         "them with the plan (paper's remote dataloader); "
                         "default: workers build buffers from metadata")
    ap.add_argument("--trace", action="store_true",
                    help="enable span tracing (repro.obs; worker "
                         "subprocesses inherit via REPRO_TRACE=1)")
    ap.add_argument("--trace-out", default=None,
                    help="export the Chrome trace_event JSON here on "
                         "exit (open in https://ui.perfetto.dev); "
                         "implies --trace")
    ap.add_argument("--trace-dir", default=None,
                    help="cluster tracing (--ctrl): every process "
                         "exports its trace into this directory on exit "
                         "(workers via REPRO_TRACE_DIR) and the launcher "
                         "merges them onto one wall-clock timeline "
                         "(trace_merged.json) with time attribution and "
                         "goodput in the --report; implies --trace and "
                         "the bytes ledger")
    ap.add_argument("--no-anomaly", action="store_true",
                    help="disable the controller's online anomaly "
                         "detector (straggler / wave-gap / throughput "
                         "advisories over the streamed telemetry)")
    ap.add_argument("--metrics-out", default=None,
                    help="append one JSONL metrics record per step here")
    ap.add_argument("--report", action="store_true",
                    help="print the observability dashboard on exit")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    compat.enable_compile_cache()

    from repro.obs import (configure as obs_configure, get_metrics,
                           get_recorder, get_tracer, render_report)
    if args.trace or args.trace_out or args.trace_dir:
        obs_configure(trace=True, trace_process="main")
        os.environ["REPRO_TRACE"] = "1"     # --ctrl workers inherit
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        os.environ["REPRO_TRACE_DIR"] = args.trace_dir
        # the wave spans carry the bytes ledger's stamps for
        # `python -m repro.obs.analyze <dir>/trace_*.json --comm`
        obs_configure(ledger=True)
        os.environ["REPRO_LEDGER"] = "1"    # --ctrl workers inherit
    if args.metrics_out:
        obs_configure(metrics_path=args.metrics_out)
    get_recorder().install_excepthook()

    if args.ctrl:
        ctl = None
        try:
            ctl = _run_ctrl(args)
            return
        finally:
            if args.trace_out:
                get_tracer().to_chrome(args.trace_out)
                print(f"trace -> {args.trace_out}", flush=True)
            attribution = good = None
            if args.trace_dir:
                # Workers already exported on shutdown; add ours, merge.
                get_tracer().to_chrome(os.path.join(
                    args.trace_dir, f"trace_controller_{os.getpid()}.json"))
                attribution, good = _analyze_trace_dir(args.trace_dir)
            if args.report:
                print(render_report(
                    metrics=get_metrics(),
                    calib=ctl.calib.summary() if ctl is not None else None,
                    attribution=attribution, goodput=good,
                    advisories=ctl.advisories if ctl is not None else None,
                    telemetry=ctl.telemetry_summary()
                    if ctl is not None else None,
                    title="controller"), flush=True)

    trainer = make_trainer(args)
    if args.ckpt_dir and trainer.resume_if_possible():
        print(f"resumed at step {trainer.step}")
    try:
        for rec in trainer.run(args.steps - trainer.step):
            print(f"step {rec['step']:4d} loss {rec['loss']:.4f} "
                  f"waves {rec['waves']} wall {rec['wall_s']:.1f}s",
                  flush=True)
    finally:
        trainer.sched.stop()  # the planner thread must not outlive the loop
        if args.trace_out:
            get_tracer().to_chrome(args.trace_out)
            print(f"trace -> {args.trace_out}", flush=True)
        if args.report:
            calib = trainer.calib.summary() \
                if getattr(trainer, "calib", None) is not None else None
            print(render_report(history=trainer.history,
                                metrics=get_metrics(), calib=calib,
                                title=f"train {args.arch}"), flush=True)


if __name__ == "__main__":
    main()
