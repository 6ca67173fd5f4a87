"""Numerics observatory: in-graph health sentinels, an online monitor,
and per-step provenance records for deterministic replay.

Three layers (ByteScale §6.1's production story — when a 12K-GPU run
goes numerically wrong you need to *see* it the same step and *re-run*
it on a laptop):

* **Sentinels** — pure in-graph reductions fused into the optimizer
  apply (train/train_step.py): global + per-layer-group grad/param/
  update norms and a non-finite element count.  One extra reduction
  tree, zero extra host syncs — the trainer fetches the whole summary
  in the same ``device_get`` that used to fetch ``grad_norm`` alone.

* **NumericsMonitor** — host-side online detector: absolute triggers
  on any non-finite loss/grad (severity ``NONFINITE_SEVERITY``, far
  above every dump threshold) plus EWMA z-score spike detection on
  loss and grad-norm.  Findings are plain JSON-safe dicts that ride
  the flight-recorder ring, streamed telemetry and ``step_done``
  frames into obs/anomaly.py's ``numerics`` channel.

* **Provenance** — ``plan_fingerprint`` hashes the executable content
  of a StepPlan; ``model_to_dict`` / ``spec_to_dict`` /
  ``dataset_to_dict`` (+ inverses) serialize everything
  ``repro.obs.replay`` needs to rebuild a run: the model config, the
  PlanSpec, the synthetic-dataset cursor (the dataset is a pure
  function of ``(dist, vocab, tokens_per_step, context, seed, step)``
  — no mutable iterator state to lose), the optimizer config and the
  runtime essentials.  A per-step ``StepProvenance`` record lands in
  the recorder ring so any dump carries its own reproduction recipe.

jax is imported lazily inside the in-graph helpers: ``repro.obs`` is
imported by controller-only processes that never touch the device.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# Finite stand-in for "infinitely bad": JSON round-trips it, and it sits
# far above the controller's dump threshold (anomaly_dump_z = 6).
NONFINITE_SEVERITY = 1000.0


# ---------------------------------------------------------------------------
# in-graph sentinels (traced; jax imported lazily)
# ---------------------------------------------------------------------------

def count_nonfinite(tree):
    """Total non-finite elements across every inexact leaf (int32 scalar)."""
    import jax
    import jax.numpy as jnp
    tot = jnp.zeros((), jnp.int32)
    for x in jax.tree.leaves(tree):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
            tot = tot + jnp.sum(~jnp.isfinite(x)).astype(jnp.int32)
    return tot


def group_norms(tree, prefix: str) -> Dict[str, Any]:
    """Per-top-level-group global norms: the params pytree's top level
    (embed / blocks / head_blocks / final_norm / lm_head) is the natural
    "layer group" granularity — fine enough to localize a blow-up, coarse
    enough to stay one reduction tree."""
    import jax
    from repro.optim.adamw import global_norm
    if not isinstance(tree, dict):
        return {prefix: global_norm(tree)}
    return {f"{prefix}/{k}": global_norm(v) for k, v in tree.items()
            if jax.tree.leaves(v)}   # leafless groups have no norm


def sentinel_summary(grads, params=None, new_params=None) -> Dict[str, Any]:
    """The fused in-graph summary (all jnp scalars, still traced):
    per-group grad norms + non-finite count, and — when the applied
    params are supplied — per-group param and update norms."""
    import jax
    import jax.numpy as jnp
    out: Dict[str, Any] = {}
    out.update(group_norms(grads, "gnorm"))
    out["grad_nonfinite"] = count_nonfinite(grads)
    if new_params is not None:
        out.update(group_norms(new_params, "pnorm"))
        if params is not None:
            diff = jax.tree.map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_params, params)
            out.update(group_norms(diff, "unorm"))
    return out


# ---------------------------------------------------------------------------
# plan fingerprint
# ---------------------------------------------------------------------------

def plan_fingerprint(plan) -> str:
    """sha256 over the executable content of a StepPlan: capacity, denom
    and per wave (composition, c_mult, offload_ratio, per-rank slot
    pieces).  Everything that determines the dispatched batches and jit
    keys; nothing advisory (stats / cost estimates are excluded)."""
    doc = {
        "capacity": int(plan.capacity),
        "denom": int(plan.denom),
        "waves": [
            {
                "comp": [int(g) for g in w.composition],
                "c_mult": int(w.c_mult),
                "off": float(w.offload_ratio),
                "slots": [[[int(p.seq_id), int(p.start), int(p.end)]
                           for p in rank] for rank in w.slots],
            }
            for w in plan.waves
        ],
    }
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# config / spec / dataset serialization (run manifest <-> replay)
# ---------------------------------------------------------------------------

def model_to_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def model_from_dict(d: dict):
    from repro.configs.base import (MLASpec, MambaSpec, ModelConfig, MoESpec,
                                    RWKVSpec)
    d = dict(d)
    if d.get("mrope_sections") is not None:
        d["mrope_sections"] = tuple(d["mrope_sections"])
    for key, cls in (("moe", MoESpec), ("mla", MLASpec),
                     ("rwkv", RWKVSpec), ("mamba", MambaSpec)):
        sub = d.get(key)
        if sub is not None and not isinstance(sub, cls):
            sub = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in sub.items()}
            d[key] = cls(**sub)
    return ModelConfig(**d)


def spec_to_dict(spec) -> dict:
    """PlanSpec -> JSON-safe dict (coeffs/comm flattened, rank_speed
    listified — replay re-applies the recorded per-window rank_speed from
    ``sched_prov`` anyway)."""
    c = spec.coeffs
    return {
        "capacity": int(spec.capacity),
        "hdp": int(spec.hdp),
        "coeffs": [float(c.a1), float(c.b1), float(c.g),
                   float(c.a2), float(c.b2)],
        "num_layers": int(spec.num_layers),
        "strategy": spec.strategy,
        "mode": spec.mode,
        "num_stages": int(spec.num_stages),
        "use_offload": bool(spec.use_offload),
        "balance_d": bool(spec.balance_d),
        "quadratic": bool(spec.quadratic),
        "zigzag": bool(spec.zigzag),
        "comm": None if spec.comm is None else dataclasses.asdict(spec.comm),
        "rank_speed": None if spec.rank_speed is None
        else [float(s) for s in spec.rank_speed],
        "cp_degree": spec.cp_degree,
        "pp_width": spec.pp_width,
        "n_periods": spec.n_periods,
        "snap_widths": bool(spec.snap_widths),
        "n_buckets": int(spec.n_buckets),
        "delta": spec.delta,
    }


def spec_from_dict(d: dict):
    from repro.core.hdp import CommModel
    from repro.core.offload import CostCoeffs
    from repro.core.planner import PlanSpec
    d = dict(d)
    d["coeffs"] = CostCoeffs(*[float(x) for x in d["coeffs"]])
    if d.get("comm") is not None:
        d["comm"] = CommModel(**d["comm"])
    return PlanSpec(**d)


def dataset_to_dict(ds) -> Optional[dict]:
    """SyntheticDataset cursor: with these five fields + a step index the
    dataset is bit-reconstructible (lengths via a per-step seeded rng,
    tokens via a pure hash) — this *is* the "dataset cursor" of the
    provenance record."""
    if ds is None or not hasattr(ds, "tokens_per_step"):
        return None
    dist = ds.dist
    dd = dataclasses.asdict(dist) if dataclasses.is_dataclass(dist) else dist
    return {"dist": dd, "vocab_size": int(ds.vocab),
            "tokens_per_step": int(ds.tokens_per_step),
            "context": int(ds.context), "seed": int(ds.seed)}


def dataset_from_dict(d: dict):
    from repro.data.distribution import LengthDistribution
    from repro.data.loader import SyntheticDataset
    dist = d["dist"]
    if isinstance(dist, dict):
        dist = LengthDistribution(**dist)
    return SyntheticDataset(dist, d["vocab_size"], d["tokens_per_step"],
                            d["context"], seed=d["seed"])


# ---------------------------------------------------------------------------
# per-step provenance record
# ---------------------------------------------------------------------------

@dataclass
class StepProvenance:
    """Compact per-step reproduction recipe (one ring slot per step).

    ``plan_hash`` pins the executed plan; ``sched_prov`` carries the
    scheduler/calibrator state the window was planned FROM (stamped by
    sched/service.py at plan time); ``ckpt_step`` names the newest
    checkpoint whose params are the state this step started from."""
    step: int
    plan_hash: str
    denom: int
    n_waves: int
    wave_losses: List[float] = field(default_factory=list)
    sentinels: Dict[str, float] = field(default_factory=dict)
    applied: int = 1
    ckpt_step: Optional[int] = None
    sched_prov: Optional[dict] = None
    n_seqs: Optional[int] = None
    nan_fault: Optional[dict] = None

    def to_record(self) -> dict:
        return dataclasses.asdict(self)


def nonfinite_signature(prov: dict) -> dict:
    """The bit-comparable non-finite signature of a recorded step: exact
    integer non-finite grad count, whether the apply ran, and which wave
    losses were non-finite.  Replay must reproduce this exactly."""
    sent = prov.get("sentinels") or {}
    losses = prov.get("wave_losses") or []
    return {
        "grad_nonfinite": int(sent.get("grad_nonfinite", 0)),
        "applied": int(prov.get("applied", 1)),
        "nonfinite_waves": [i for i, l in enumerate(losses)
                            if not math.isfinite(float(l))],
    }


# ---------------------------------------------------------------------------
# online monitor (host-side, numpy-free)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MonitorConfig:
    warmup: int = 5               # steps of history before z-tests fire
    z_thresh: float = 6.0         # upward spike threshold (conservative:
                                  # clean runs must stay silent)
    ema: float = 0.3
    sigma_floor_frac: float = 0.05  # sigma floored at frac * |mean|
    cooldown: int = 8             # steps between repeated spike findings


class _Ewma:
    __slots__ = ("a", "mean", "var", "n")

    def __init__(self, a: float):
        self.a, self.mean, self.var, self.n = a, 0.0, 0.0, 0

    def update(self, x: float) -> None:
        self.n += 1
        if self.n == 1:
            self.mean = x
            return
        d = x - self.mean
        self.mean += self.a * d
        self.var = (1 - self.a) * (self.var + self.a * d * d)

    def std(self) -> float:
        return math.sqrt(max(self.var, 0.0))


def _safe(x) -> Optional[float]:
    """Float for JSON/pickle transport: non-finite -> None (the repr goes
    into ``detail`` instead, so strict JSON consumers stay happy)."""
    x = float(x)
    return x if math.isfinite(x) else None


class NumericsMonitor:
    """Online numerics detector for one trainer.

    ``observe_wave`` runs on every fetched wave loss, in wave order,
    before the step returns (free: the trainer reads that float anyway,
    one wave late while the next wave runs); ``observe_step`` runs on the
    fused sentinel summary after the apply.  Both return finding dicts
    (possibly empty) shaped for obs/anomaly.py's numerics channel."""

    def __init__(self, cfg: Optional[MonitorConfig] = None):
        self.cfg = cfg or MonitorConfig()
        self._sig = {"loss": _Ewma(self.cfg.ema),
                     "grad_norm": _Ewma(self.cfg.ema)}
        self._last_fire: Dict[str, int] = {}
        self.findings: List[dict] = []
        self.trips = 0            # non-finite (severe) findings

    # -- helpers ----------------------------------------------------------

    def _mk(self, reason: str, step: int, *, wave=None, value=None,
            baseline=None, severity=0.0, detail="") -> dict:
        f = {"kind": "numerics", "reason": reason, "step": int(step),
             "wave": wave, "value": _safe(value) if value is not None
             else None, "baseline": _safe(baseline) if baseline is not None
             else None, "severity": float(severity), "detail": detail}
        self.findings.append(f)
        if severity >= NONFINITE_SEVERITY:
            self.trips += 1
        return f

    def _spike(self, name: str, step: int, x: float) -> List[dict]:
        ew = self._sig[name]
        out: List[dict] = []
        if ew.n >= self.cfg.warmup:
            floor = self.cfg.sigma_floor_frac * max(abs(ew.mean), 1e-12)
            sd = max(ew.std(), floor)
            z = (x - ew.mean) / sd
            cooled = step - self._last_fire.get(name, -10**9) \
                >= self.cfg.cooldown
            if z >= self.cfg.z_thresh and cooled:   # upward spikes only
                self._last_fire[name] = step
                out.append(self._mk(
                    f"{name}_spike", step, value=x, baseline=ew.mean,
                    severity=float(z),
                    detail=f"{name}={x:.6g} vs ewma {ew.mean:.6g} "
                           f"(z={z:.1f})"))
        ew.update(x)
        return out

    # -- observation points ----------------------------------------------

    def observe_wave(self, step: int, wave: int, loss: float) -> List[dict]:
        if not math.isfinite(loss):
            return [self._mk("nonfinite_loss", step, wave=int(wave),
                             severity=NONFINITE_SEVERITY,
                             detail=f"wave {wave} loss={loss!r}")]
        return []

    def observe_step(self, step: int, loss: float,
                     sentinels: Dict[str, float]) -> List[dict]:
        out: List[dict] = []
        nonf = int(sentinels.get("grad_nonfinite", 0))
        if nonf > 0:
            out.append(self._mk(
                "nonfinite_grads", step, value=nonf,
                severity=NONFINITE_SEVERITY,
                detail=f"{nonf} non-finite grad elements"))
        gn = sentinels.get("grad_norm")
        if math.isfinite(loss):
            out.extend(self._spike("loss", step, float(loss)))
        if gn is not None and math.isfinite(float(gn)):
            out.extend(self._spike("grad_norm", step, float(gn)))
        return out
