#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python chipbench/calibrate.py --workload <name> --seeds 1 2 ... \
        [--control-seeds 7 8 9] [--out <file.jsonl>]

In one process, so that set-up and compilation are paid once: for each
of ``--seeds``, the program's first steps (built and driven as a
benchmark run builds and drives them) against the float32 reference: the
lower readings.  For each of ``--control-seeds``, the reference against
itself computed in float8 (the control) and against itself with each
planted fault of `reference.FAULTS`: the upper readings.  Each line also
carries the leaf that set each per-leaf number and both sides' gradient
norms before clipping (``grad_clip`` is 1).  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main(argv=None, *, root=run.ROOT, devices=None) -> int:
    """``root`` and ``devices`` are for tests, as in `run.main`."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench, cell = run.load_cell(args.workload, root)
    run.setup_cache(root)
    if devices is None:
        devices = run.chips_at_hand(int(cell["chips"]))
        if devices is None:
            return 2

    import compare
    import program
    import reference
    from model import load_config, model_config
    from traffic import Traffic, load_mix

    base = root / "chipbench"
    c = load_config(cell["config"], base)
    cfg = model_config(cell["config"], base)
    mix = load_mix(cell["traffic"], base)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in args.seeds:
        t = time.perf_counter()
        traffic = Traffic(mix, c["vocab_size"], seed)
        trainer = program.build(cfg, traffic, devices, seed)
        prog = program.readings(trainer, run.CHECK_STEPS)
        prog.pop("records")
        trainer.sched.stop()
        del trainer
        gc.collect()
        ref = reference.Reference(c, traffic, devices).run(seed,
                                                           run.CHECK_STEPS)
        emit({"workload": cell["name"], "kind": "program", "seed": seed,
              **compare.gaps(prog, ref), "loss": prog["loss"],
              "ref_loss": ref["loss"], "gnorm": prog["gnorm"],
              "ref_gnorm": ref["gnorm"],
              "seconds": round(time.perf_counter() - t, 1)})

    for seed in args.control_seeds:
        traffic = Traffic(mix, c["vocab_size"], seed)
        ref = reference.Reference(c, traffic, devices).run(seed,
                                                           run.CHECK_STEPS)
        for kind in ("control",) + reference.FAULTS:
            t = time.perf_counter()
            other = reference.Reference(
                c, traffic, devices, control=kind == "control",
                fault=None if kind == "control" else kind).run(
                    seed, run.CHECK_STEPS)
            emit({"workload": cell["name"], "kind": kind, "seed": seed,
                  **compare.gaps(other, ref), "loss": other["loss"],
                  "ref_loss": ref["loss"], "gnorm": other["gnorm"],
                  "ref_gnorm": ref["gnorm"],
                  "seconds": round(time.perf_counter() - t, 1)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
