#!/usr/bin/env python3
"""Chip smoke test: the HDP training path on a TPU, end to end, in one
process, through the launcher's own `make_trainer`.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the 4-chip host, HDP = 4

Default phase (one chip): 3 training steps of ``llama3.2-3b-1chip`` (the
published widths, 4 layers, a quarter of the vocabulary; random weights
from seed 0) with the jnp attention oracle (``--attn-impl ref``), then 3
steps from the same seed and data with the compiled Pallas kernels
(``pallas``).  Fails if a loss is not finite, if the first loss is more
than 0.5 from the loss of random logits at this init (ln V + σ²/2, with
σ = 0.02·√d_model the logits' standard deviation; ln V alone would be
0.61 too low), or if the first losses of ref and pallas differ by more
than 1e-2 relative.

``--four-chips`` runs only this phase: a 4×1 mesh, the heavy-tailed
``github`` mix with context up to 4× the per-rank capacity so that rings
of g ∈ {2, 4} form, 3 steps under ``--strategy balance`` and the same
steps under ``static``, with the Pallas kernels (the jnp oracle ring
needs 28 GiB per chip at g = 4; tests/test_tpu_compile.py).  The Eq. 2
token-level loss does not depend on the plan, so the first losses must
agree to bf16 tolerance.

Each step prints its loss, wall time (host clock around work that ends in
a device sync), the allocator's peak HBM and the compile count.  These
are smoke readings, not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``; without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import compat  # noqa: E402
from repro.configs.registry import ONE_CHIP  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.models.layers import EMBED_INIT_STD  # noqa: E402

ARCH = "llama3.2-3b-1chip"
STEPS = 3
BF16_RTOL = 1e-2
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def _compile_counter():
    import jax
    count = [0]

    def listen(event, _secs, **_kw):
        if event == BACKEND_COMPILE:
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def _peak_hbm(devices) -> int:
    return max(compat.device_memory_stats(d).get("peak_bytes_in_use", 0)
               for d in devices)


def run_phase(label: str, argv, compiles, devices):
    """STEPS steps through the launcher's single-process path; returns
    the per-step records."""
    args = train.build_parser().parse_args(argv + ["--steps", str(STEPS)])
    print(f"[{label}] {' '.join(argv)}", flush=True)
    trainer = train.make_trainer(args)
    recs = []
    seen = compiles[0]
    try:
        for rec in trainer.run(STEPS):
            comps = sorted({"+".join(map(str, c))
                            for c in rec["compositions"]})
            # a step that met a new wave shape compiled it inside its wall
            fresh, seen = compiles[0] > seen, compiles[0]
            wall = f"{rec['wall_s']:.3f}s" if rec["step"] >= 2 \
                else "(compiles)"
            if rec["step"] >= 2 and fresh:
                wall += " (incl. compiles)"
            print(f"[{label}] step {rec['step']} loss {rec['loss']:.6f} "
                  f"wall {wall} waves {rec['waves']} "
                  f"compositions {comps} "
                  f"peak_hbm {_peak_hbm(devices) / 2**30:.2f} GiB "
                  f"compiles {compiles[0]}", flush=True)
            if not math.isfinite(rec["loss"]):
                raise SmokeFailure(f"{label}: non-finite loss at step "
                                   f"{rec['step']}")
            recs.append(rec)
    finally:
        trainer.sched.stop()
        del trainer
        gc.collect()          # free this phase's params and optimizer state
    return recs


def _agree(label, a, b, rtol=BF16_RTOL):
    rel = abs(a - b) / abs(b)
    print(f"{label}: {a:.6f} vs {b:.6f} (rel {rel:.2e}, limit {rtol:g})",
          flush=True)
    if not rel <= rtol:
        raise SmokeFailure(f"{label} differ by {rel:.2e} > {rtol:g}")


def one_chip(compiles, devices):
    mod = ONE_CHIP[ARCH]
    cap = mod.CAPACITY
    base = ["--arch", ARCH, "--mesh", "1x1", "--capacity", str(cap),
            "--context", str(cap), "--tokens-per-step", str(8 * cap),
            "--dataset", "github"]
    ref = run_phase("ref", base + ["--attn-impl", "ref"], compiles, devices)
    pal = run_phase("pallas", base + ["--attn-impl", "pallas"], compiles,
                    devices)
    cfg = mod.CONFIG
    sigma2 = cfg.d_model * EMBED_INIT_STD ** 2
    expect = math.log(cfg.vocab_size) + sigma2 / 2
    print(f"expected step-1 loss ln({cfg.vocab_size}) + {sigma2 / 2:.3f} = "
          f"{expect:.3f}", flush=True)
    for label, recs in (("ref", ref), ("pallas", pal)):
        if abs(recs[0]["loss"] - expect) > 0.5:
            raise SmokeFailure(f"{label}: step-1 loss {recs[0]['loss']:.4f} "
                               f"is more than 0.5 from {expect:.4f}")
    _agree("step-1 loss pallas vs ref", pal[0]["loss"], ref[0]["loss"])


def four_chips(compiles, devices):
    cap = ONE_CHIP[ARCH].CAPACITY
    base = ["--arch", ARCH, "--mesh", "4x1", "--capacity", str(cap),
            "--context", str(4 * cap), "--tokens-per-step", str(16 * cap),
            "--dataset", "github", "--attn-impl", "pallas"]
    runs = {s: run_phase(s, base + ["--strategy", s], compiles, devices)
            for s in ("balance", "static")}
    rings = {max(c) for s in runs for r in runs[s] for c in r["compositions"]}
    print(f"ring sizes that ran: {sorted(rings)}", flush=True)
    if not rings & {2, 4}:
        raise SmokeFailure("no ring of g >= 2 formed")
    _agree("step-1 loss balance vs static", runs["balance"][0]["loss"],
           runs["static"][0]["loss"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip HDP phase (balance vs static)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"platform {d0.platform} device_kind {d0.device_kind} "
          f"count {len(devices)}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX reports platform "
              f"{d0.platform!r}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"chip_smoke: this phase needs {want} chip(s); found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {compat.enable_compile_cache()}", flush=True)
    mod = ONE_CHIP[ARCH]
    print(f"config {ARCH}: {mod.CONFIG}", flush=True)
    for key, why in mod.REDUCED.items():
        print(f"  reduced {key}: {why}", flush=True)
    print(f"  capacity {mod.CAPACITY} tokens per wave and rank", flush=True)

    compiles = _compile_counter()
    try:
        (four_chips if args.four_chips else one_chip)(compiles, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
