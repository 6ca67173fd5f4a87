"""Comm-bytes benchmark → ``BENCH_comm.json``: HDP vs static-CP total
communication, plus the instrumented predicted-vs-measured residual gate.

Two legs:

* **Analytic pricing.**  The bytes ledger's plan-level model
  (`obs.ledger.plan_comm_bytes`) prices one bimodal batch (the Insight-1
  mix from benchmarks/pipeline_bubble.py: a few 4x-capacity longs in a
  sea of shorts) under the HDP balance planner and under static CP
  (every wave at the full fixed composition).  ByteScale's core comm
  claim is that HDP "eliminates redundant communication for short
  sequences": short sequences in singleton groups move ZERO ring bytes,
  while static CP shards everything and pays the full ring every layer.
  Gate (CI): ``hdp_bytes < static_cp_bytes`` strictly.

* **Instrumented residual.**  A subprocess (host platform forced to 8
  CPU devices) runs a real hdp=8 trainer for two steps with the ledger
  on and reports `Ledger.comm_residual()` — the relative gap between
  the analytic per-dispatch predictions and the trace-time measured
  byte tallies stamped by core/ring.py / kernels/ring_flash.py.  Gate
  (CI): residual <= 10% (exact 0 on the jnp oracle ring; the bound
  leaves room for backends whose payload layout differs).

Run: ``python -m benchmarks.comm_bench [--skip-instrumented] [--out P]``
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

SNAPSHOT_PATH = "BENCH_comm.json"
RESIDUAL_GATE = 0.10
_CHILD_FLAG = "--instr-child"


# -- analytic leg -------------------------------------------------------
def analytic_comm() -> dict:
    from benchmarks.pipeline_bubble import CAPACITY, HDP, bimodal_lengths
    from repro.configs.registry import get_config
    from repro.core.planner import PlanSpec, plan as plan_batch
    from repro.obs import ledger

    cfg = get_config("llama-7b")
    spec = PlanSpec.for_config(cfg, capacity=CAPACITY, hdp=HDP,
                               use_offload=False)
    lens = bimodal_lengths()
    t0 = time.perf_counter()
    plans = {name: plan_batch(lens, spec.replace(strategy=s))
             for name, s in (("hdp", "balance"), ("static_cp", "static"))}
    priced = {name: ledger.plan_comm_bytes(p, cfg)
              for name, p in plans.items()}
    wall_ms = (time.perf_counter() - t0) * 1e3
    hdp_b = priced["hdp"]["total"]
    static_b = priced["static_cp"]["total"]
    return {"batch": {"n_seqs": len(lens), "tokens": int(sum(lens)),
                      "hdp": HDP, "capacity": CAPACITY},
            "hdp_bytes": hdp_b, "static_cp_bytes": static_b,
            "hdp_ring_bytes": priced["hdp"]["ring"],
            "static_cp_ring_bytes": priced["static_cp"]["ring"],
            "saving_frac": round(1.0 - hdp_b / static_b, 4)
            if static_b > 0 else None,
            "n_waves": {k: len(p.waves) for k, p in plans.items()},
            "wall_ms": round(wall_ms, 2),
            "gate_ok": bool(hdp_b < static_b)}


# -- instrumented leg (8-device subprocess) -----------------------------
def _instr_child() -> None:
    """Runs inside the forced-8-device subprocess: two hdp=8 training
    steps with the bytes ledger on, then one JSON line with the
    ledger's predicted/measured totals and residual."""
    import jax

    from repro import compat
    from repro.configs.registry import get_config
    from repro.data.distribution import LengthDistribution
    from repro.data.loader import GlobalScheduler, SyntheticDataset
    from repro.obs import set_ledger_enabled
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import Runtime
    from repro.train.trainer import Trainer, TrainerConfig

    assert len(jax.devices()) >= 8, jax.devices()
    set_ledger_enabled(True)
    cfg = get_config("llama3.2-3b").reduced()
    mesh = compat.make_mesh((8, 1), ("data", "model"))
    compat.set_mesh(mesh)
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 remat="none", kv_chunk=64)
    dist = LengthDistribution("tiny", 4.5, 0.8, 0.1, 1.5, 256)
    ds = SyntheticDataset(dist, cfg.vocab_size, tokens_per_step=4096,
                          context=1024)
    sched = GlobalScheduler(ds, cfg, capacity=256, hdp=8,
                            use_offload=False)
    tr = Trainer(cfg, rt, AdamWConfig(lr=1e-3, total_steps=8), sched,
                 TrainerConfig(capacity=256, attn_impl="ref"))
    for _ in range(2):
        tr.train_step()
    s = tr.ledger.summary()
    ring_dispatches = sum(1 for r in tr.ledger.recent(256)
                          if r["pred"]["ring"] > 0)
    print(json.dumps({"residual": s["comm_residual"],
                      "pred_total": s["pred_total"],
                      "meas_total": s["meas_total"],
                      "n_records": s["n"],
                      "ring_dispatches": ring_dispatches,
                      "step_bytes": s.get("step_bytes"),
                      "devices": len(jax.devices())}))


def instrumented_residual() -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.pop("REPRO_LEDGER", None)      # child enables programmatically
    r = subprocess.run([sys.executable, "-m", "benchmarks.comm_bench",
                        _CHILD_FLAG],
                       capture_output=True, text=True, timeout=1800,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-800:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["gate"] = RESIDUAL_GATE
    # a run where no dispatch moved ring bytes audits nothing — require
    # real ring traffic under the residual gate
    out["gate_ok"] = bool(out["residual"] <= RESIDUAL_GATE
                          and out["ring_dispatches"] > 0
                          and out["meas_total"] > 0)
    return out


# -- snapshot / harness wiring ------------------------------------------
def snapshot(path: str = SNAPSHOT_PATH,
             skip_instrumented: bool = False) -> dict:
    snap = {"analytic": analytic_comm()}
    gate = snap["analytic"]["gate_ok"]
    if not skip_instrumented:
        snap["instrumented"] = instrumented_residual()
        gate = gate and snap["instrumented"]["gate_ok"]
    snap["hdp_bytes"] = snap["analytic"]["hdp_bytes"]
    snap["static_cp_bytes"] = snap["analytic"]["static_cp_bytes"]
    snap["residual"] = snap.get("instrumented", {}).get("residual")
    snap["gate_ok"] = bool(gate)
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    return snap


def rows_from(snap: dict) -> list:
    an = snap["analytic"]
    rows = [("comm.hdp_vs_static_bytes", an["wall_ms"] * 1e3,
             f"hdp={an['hdp_bytes']:.3e} static={an['static_cp_bytes']:.3e}"
             f" saving={an['saving_frac']} ok={an['gate_ok']}")]
    ins = snap.get("instrumented")
    if ins:
        rows.append(("comm.pred_vs_meas_residual", 0.0,
                     f"residual={ins['residual']:.4f} "
                     f"n={ins['n_records']} ok={ins['gate_ok']}"))
    return rows


def run() -> list:
    return rows_from(snapshot())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=SNAPSHOT_PATH)
    ap.add_argument("--skip-instrumented", action="store_true",
                    help="analytic pricing only (no 8-device subprocess)")
    ap.add_argument(_CHILD_FLAG, action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.instr_child:
        _instr_child()
        return
    snap = snapshot(args.out, skip_instrumented=args.skip_instrumented)
    print(json.dumps(snap, indent=1, sort_keys=True))
    if not snap["analytic"]["gate_ok"]:
        raise SystemExit(
            f"HDP comm bytes {snap['hdp_bytes']:.3e} not below static-CP "
            f"{snap['static_cp_bytes']:.3e}")
    ins = snap.get("instrumented")
    if ins is not None and not ins["gate_ok"]:
        raise SystemExit(
            f"predicted-vs-measured residual {ins['residual']:.4f} "
            f"exceeds the {RESIDUAL_GATE:.0%} gate "
            f"(ring_dispatches={ins['ring_dispatches']})")


if __name__ == "__main__":
    main()
