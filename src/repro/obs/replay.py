"""Deterministic step replay from flight-recorder dumps.

``python -m repro.obs.replay <flightrec dump.json>`` rebuilds the run a
dump came from and re-executes the recorded step(s) single-process:

* the **run manifest** (recorder meta, published by the Trainer) gives
  the model config, PlanSpec, synthetic-dataset cursor, optimizer config
  and runtime geometry — everything is rebuilt through the
  ``obs.numerics`` ``*_from_dict`` inverses;
* each step's **StepProvenance** record pins the executed plan
  (``plan_hash``), the scheduler snapshot the window was planned from
  (``sched_prov``), the wave losses, the fused sentinel summary and the
  newest checkpoint the step started from (``ckpt_step``);
* the **ReplayScheduler** replans each step deterministically from its
  recorded ``sched_prov`` (a throwaway SchedulerService restored to the
  exact pre-window state) and asserts the fingerprint matches — replay
  never guesses at scheduling state, it replays it;
* params/optimizer restore from the referenced checkpoint (params at
  checkpoint step M are exactly the state entering step M), the steps
  M..N re-execute through the real Trainer (including any recorded
  ``nan_fault`` injection and the ``numerics_guard`` setting), and the
  replayed wave losses / sentinels / non-finite signature are compared
  bit-for-bit against the recorded ones.

``--bisect-wave`` additionally re-executes the target step one wave at a
time from the restored params (zero accumulator each time), isolating
the first wave whose gradients go non-finite and the sequence ids it
carried.

Exit status 0 iff the plan fingerprints, the non-finite signature and
the wave losses all reproduce exactly.

Heavy imports (jax, repro.*) happen inside functions: the device count
must be forced via XLA_FLAGS *before* the jax backend initializes, and
``main`` only knows the needed count after reading the dump's manifest.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, List, Optional


def _feq(a, b) -> bool:
    """Bit-comparable float equality where NaN == NaN (any NaN payload
    collapses to one bucket — JSON did that already)."""
    a, b = float(a), float(b)
    if math.isnan(a) and math.isnan(b):
        return True
    return a == b


def load_dump(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def provenance_by_step(doc: dict) -> Dict[int, dict]:
    """step -> newest step_provenance record in the dump's ring (a test
    process may run several trainers against one ring; last wins, which
    matches the manifest — ``set_meta`` also keeps the newest)."""
    out: Dict[int, dict] = {}
    for ev in doc.get("events", []):
        if ev.get("kind") == "step_provenance":
            out[int(ev["step"])] = ev
    return out


def pick_target(provs: Dict[int, dict], step: Optional[int]) -> int:
    if step is not None:
        if step not in provs:
            raise SystemExit(f"no step_provenance record for step {step}; "
                             f"dump covers {sorted(provs)}")
        return step
    if not provs:
        raise SystemExit("dump has no step_provenance records")
    bad = [s for s, p in provs.items() if int(p.get("applied", 1)) == 0]
    return max(bad) if bad else max(provs)


# ---------------------------------------------------------------------------
# scheduler facade
# ---------------------------------------------------------------------------

class ReplayScheduler:
    """GlobalScheduler-shaped facade that replans steps from recorded
    ``sched_prov`` snapshots.  Each window gets a throwaway
    SchedulerService restored to the exact pre-window state the recorded
    run planned from, and ``_plan_one_window`` is driven directly at the
    recorded ``t0`` — never ``plan_step`` from zero, which would replan
    (and re-mutate load/templates through) every earlier window.

    Deliberately has no ``service`` attribute: the Trainer's warm-keys /
    data_state hooks are live-run machinery and must not touch replay.
    """

    def __init__(self, ds, spec, provs: Dict[int, dict]):
        self.ds = ds
        self.spec = spec
        self._provs = provs
        self._plans: Dict[int, object] = {}
        self.mismatches: List[dict] = []

    @property
    def hdp(self) -> int:
        return self.spec.hdp

    @property
    def capacity(self) -> int:
        return self.spec.capacity

    def update_rank_speed(self, speed) -> None:
        pass      # replay never recalibrates: plans come from the record

    def plan_step(self, step: int):
        from repro.obs.numerics import plan_fingerprint
        from repro.sched.service import SchedulerService
        if step not in self._plans:
            rec = self._provs.get(step)
            sp = rec.get("sched_prov") if rec else None
            if sp is None:
                # no snapshot (very old dump): best-effort cold plan of
                # just this step's window via the fast-forward path
                svc = SchedulerService(self.ds, self.spec, lookahead=1)
                self._plans[step] = svc.plan_step(step)
            else:
                svc = SchedulerService(self.ds,
                                       self.spec.replace(hdp=int(sp["hdp"])),
                                       lookahead=int(sp["k"]))
                svc.load_state({"hdp": sp["hdp"],
                                "rank_speed": sp["rank_speed"],
                                "load": sp["load"],
                                "templates": sp["templates"],
                                "coeffs": sp["coeffs"]})
                plans = svc._plan_one_window(
                    int(sp["t0"]), transient=bool(sp.get("transient")))
                self._plans.update(plans)
        plan = self._plans[step]
        rec = self._provs.get(step)
        if rec and rec.get("plan_hash"):
            got = plan_fingerprint(plan)
            if got != rec["plan_hash"]:
                self.mismatches.append({"step": step,
                                        "want": rec["plan_hash"],
                                        "got": got})
        return plan


# ---------------------------------------------------------------------------
# replay driver
# ---------------------------------------------------------------------------

def _pick_start(provs: Dict[int, dict], target: int, ckpt_dir: Optional[str]):
    """(start step M, ckpt manager or None): the newest valid checkpoint
    M <= target such that every step in [M, target] has provenance;
    fresh-init (M=0) is the fallback when the record reaches back to 0."""
    from repro.ckpt.checkpoint import CheckpointManager

    def covered(m: int) -> bool:
        return all(t in provs for t in range(m, target + 1))

    cm = None
    if ckpt_dir and os.path.isdir(ckpt_dir):
        cm = CheckpointManager(ckpt_dir)
        for s in sorted(cm.steps(), reverse=True):
            if s <= target and covered(s) \
                    and cm._verified_manifest(s) is not None:
                return s, cm
    if covered(0):
        return 0, None
    raise SystemExit(
        f"cannot reach step {target}: no usable checkpoint under "
        f"{ckpt_dir!r} and provenance does not cover 0..{target} "
        f"(have {sorted(provs)})")


def _build_trainer(man: dict, provs: Dict[int, dict]):
    import jax  # noqa: F401  (backend init happens here, after XLA_FLAGS)
    from repro import compat
    from repro.launch.mesh import make_pipeline_mesh
    from repro.obs import numerics as NU
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import Runtime
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = NU.model_from_dict(man["model"])
    spec = NU.spec_from_dict(man["spec"])
    ds = NU.dataset_from_dict(man["dataset"])
    if ds is None:
        raise SystemExit("manifest has no dataset cursor — cannot replay")
    rkw = man["runtime"]
    hdp, tp, stages = int(rkw["hdp"]), int(rkw["tp"]), int(rkw["num_stages"])
    extra = dict(remat=rkw["remat"], kv_chunk=int(rkw["kv_chunk"]),
                 attn_impl=rkw["attn_impl"],
                 seq_parallel=bool(rkw["seq_parallel"]))
    if stages > 1:
        mesh = make_pipeline_mesh(stages, hdp, tp)
        rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                     stage_axis="stage", **extra)
    else:
        mesh = compat.make_mesh((hdp, tp), ("data", "model"))
        rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                     **extra)
    compat.set_mesh(mesh)
    tman = man["trainer"]
    tcfg = TrainerConfig(
        capacity=int(tman["capacity"]), mode=tman["mode"],
        strategy=tman["strategy"], ckpt_dir=None, ckpt_save=False,
        max_round_waves=int(tman.get("max_round_waves") or 0),
        attn_impl=tman.get("attn_impl"), calibrate=False,
        numerics_guard=bool(tman.get("numerics_guard", True)),
        nan_fault=tman.get("nan_fault"))
    sched = ReplayScheduler(ds, spec, provs)
    return Trainer(cfg, rt, AdamWConfig(**man["opt"]), sched, tcfg,
                   seed=int(man.get("seed", 0)))


def _compare(rec: dict, rep: dict) -> dict:
    from repro.obs.numerics import nonfinite_signature
    want_l = [float(x) for x in rec.get("wave_losses") or []]
    got_l = [float(x) for x in rep.get("wave_losses") or []]
    losses_exact = len(want_l) == len(got_l) \
        and all(_feq(a, b) for a, b in zip(want_l, got_l))
    diffs = [abs(a - b) for a, b in zip(want_l, got_l)
             if math.isfinite(a) and math.isfinite(b)]
    ws, gs = rec.get("sentinels") or {}, rep.get("sentinels") or {}
    sent_exact = set(ws) == set(gs) \
        and all(_feq(ws[k], gs[k]) for k in ws)
    rels = [abs(float(ws[k]) - float(gs[k]))
            / max(abs(float(ws[k])), 1e-12)
            for k in set(ws) & set(gs)
            if math.isfinite(float(ws[k])) and math.isfinite(float(gs[k]))]
    sig_w = nonfinite_signature(rec)
    sig_g = nonfinite_signature(rep)
    return {"step": int(rec["step"]),
            "signature_ok": sig_w == sig_g,
            "losses_exact": losses_exact,
            "sentinels_exact": sent_exact,
            "max_loss_diff": max(diffs) if diffs else 0.0,
            "max_sentinel_rel": max(rels) if rels else 0.0,
            "recorded_signature": sig_w, "replayed_signature": sig_g}


def _bisect_wave(tr, plan, step: int) -> List[dict]:
    """Re-execute the step's waves one at a time from the params that
    entered the step (zero accumulator each time): per-wave loss +
    non-finite grad count isolates the first offending wave and the
    sequence ids it carried.  Non-PP plans only (a pipelined round is
    one executable — wave isolation has no meaning there)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.obs.numerics import count_nonfinite
    out: List[dict] = []
    denom = float(plan.denom)
    for i, lw in enumerate(tr.loader.iter_step(step, plan)):
        nf = tr.tcfg.nan_fault
        hit = bool(nf) and int(nf.get("step", -1)) == step \
            and int(nf.get("wave", 0)) == i
        batch = {k: jnp.asarray(v) for k, v in lw.batch.items()}
        batch["denom"] = jnp.float32(float("nan") if hit else denom)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            tr.params)
        fn, _ = tr._wave_fn(lw.composition, lw.c_mult, lw.offload_ratio)
        g, m = fn(tr.params, zero, batch)
        seqs = sorted({p.seq_id for rank in plan.waves[i].slots
                       for p in rank})
        out.append({"wave": i, "loss": float(m["loss"]),
                    "grad_nonfinite": int(np.asarray(
                        jax.device_get(count_nonfinite(g)))),
                    "nan_fault_injected": hit, "seq_ids": seqs})
    return out


def run_replay(dump_path: str, step: Optional[int] = None,
               ckpt_dir: Optional[str] = None,
               bisect: bool = False) -> dict:
    """The full replay (call only after XLA_FLAGS is settled — `main`
    handles that): returns the comparison report dict."""
    from repro.obs import get_recorder
    doc = load_dump(dump_path)
    man = (doc.get("meta") or {}).get("run_manifest")
    if not man:
        raise SystemExit("dump carries no run_manifest (meta) — was it "
                         "written by a pre-numerics recorder?")
    provs = provenance_by_step(doc)
    target = pick_target(provs, step)
    start, cm = _pick_start(provs, target,
                            ckpt_dir or man["trainer"].get("ckpt_dir"))
    tr = _build_trainer(man, provs)
    if cm is not None and start > 0:
        params, opt, dstate = cm.restore(start, tr.params, tr.opt_state)
        tr.params, tr.opt_state = params, opt
        tr.step = start
    n0 = len(get_recorder().events())
    params_at_target = tr.params
    for t in range(start, target + 1):
        params_at_target = tr.params       # params ENTERING step t
        tr.train_step()
    replayed = {int(e["step"]): e for e in get_recorder().events()[n0:]
                if e.get("kind") == "step_provenance"}
    steps = [_compare(provs[t], replayed[t])
             for t in range(start, target + 1)]
    tgt = steps[-1]
    hash_ok = not tr.sched.mismatches
    report = {
        "dump": dump_path, "target": target, "start": start,
        "restored_ckpt": start if cm is not None and start > 0 else None,
        "plan_hash_ok": hash_ok,
        "plan_mismatches": tr.sched.mismatches,
        "signature_ok": all(s["signature_ok"] for s in steps),
        "losses_exact": all(s["losses_exact"] for s in steps),
        "sentinels_exact": all(s["sentinels_exact"] for s in steps),
        "steps": steps, "target_step": tgt,
        "ok": bool(hash_ok and all(s["signature_ok"] for s in steps)
                   and all(s["losses_exact"] for s in steps)),
    }
    if bisect:
        saved, tr.params = tr.params, params_at_target
        try:
            plan = tr.sched.plan_step(target)
            waves = _bisect_wave(tr, plan, target)
        finally:
            tr.params = saved
        bad = [w["wave"] for w in waves if w["grad_nonfinite"] > 0
               or not math.isfinite(w["loss"])]
        report["bisect"] = {"waves": waves,
                            "first_bad_wave": bad[0] if bad else None}
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.replay",
        description="Deterministically re-execute recorded steps from a "
                    "flight-recorder dump and diff them against the "
                    "recorded wave losses / sentinels.")
    ap.add_argument("dump", help="flightrec_*.json written by the recorder")
    ap.add_argument("--step", type=int, default=None,
                    help="step to replay (default: last guarded/non-finite "
                         "step in the dump, else the newest recorded step)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: the manifest's)")
    ap.add_argument("--bisect-wave", action="store_true",
                    help="re-run the target step wave-by-wave to isolate "
                         "the first wave with non-finite grads")
    ap.add_argument("--json", action="store_true",
                    help="print only the machine-readable REPLAY line")
    args = ap.parse_args(argv)

    # the backend needs hdp*tp*stages host devices, and XLA_FLAGS is read
    # exactly once at backend init — force it before any jax import
    doc = load_dump(args.dump)
    man = (doc.get("meta") or {}).get("run_manifest") or {}
    rkw = man.get("runtime") or {}
    need = int(rkw.get("hdp", 1)) * int(rkw.get("tp", 1)) \
        * int(rkw.get("num_stages", 1))
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " if flags else "") \
            + f"--xla_force_host_platform_device_count={need}"

    report = run_replay(args.dump, step=args.step, ckpt_dir=args.ckpt_dir,
                        bisect=args.bisect_wave)
    if not args.json:
        t = report["target_step"]
        print(f"replayed steps {report['start']}..{report['target']} "
              f"(ckpt: {report['restored_ckpt']})")
        print(f"  plan hash    : {'ok' if report['plan_hash_ok'] else 'MISMATCH'}")
        print(f"  signature    : {'ok' if report['signature_ok'] else 'MISMATCH'}"
              f"  {t['recorded_signature']}")
        print(f"  wave losses  : "
              f"{'bit-exact' if report['losses_exact'] else 'DIFFER'}"
              f" (max finite diff {t['max_loss_diff']:.3g})")
        print(f"  sentinels    : "
              f"{'bit-exact' if report['sentinels_exact'] else 'differ'}"
              f" (max rel {t['max_sentinel_rel']:.3g})")
        if report.get("bisect") is not None:
            for w in report["bisect"]["waves"]:
                mark = " <-- first bad" \
                    if w["wave"] == report["bisect"]["first_bad_wave"] else ""
                print(f"    wave {w['wave']}: loss={w['loss']!r} "
                      f"nonfinite={w['grad_nonfinite']} "
                      f"seqs={w['seq_ids']}{mark}")
        print("REPLAY " + ("OK" if report["ok"] else "FAIL"))
    print("REPLAY_JSON " + json.dumps(
        {k: v for k, v in report.items() if k != "steps"},
        sort_keys=True, default=str))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
