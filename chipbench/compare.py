"""The comparison that decides ``correct``.

Four numbers, each against a limit of its own (`chipbench/limits/`,
one file per cell, with the readings each limit was set from):

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the compared steps;
* ``gnorm_gap``: the largest relative gap between the program's and the
  reference's global gradient norm before clipping over the compared
  steps (clipping hides a gradient's scale from the numbers below);
* ``grad_gap``: over the leaves (one weight tensor per layer), the largest
  gap between the norm of the program's first gradient as AdamW got it
  and the reference's, over the larger of that leaf's reference norm and
  the median leaf's;
* ``update_gap``: the same for the norm of the weights' change after the
  compared steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a leaf whose gradient is nought to
  rounding moves under AdamW by round-off alone).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
NAMES = ("loss_gap", "gnorm_gap", "grad_gap", "update_gap")
QUIET_LEAF = 1e-3


def load_limits(workload: str, root: Path = HERE) -> Dict[str, float]:
    """A limit of ``null`` marks a number that has no readings on the chip
    yet to set its limit from: it is printed and not compared."""
    data = json.loads((root / "limits" / f"{workload}.json").read_text())
    return {k: None if data[k]["limit"] is None else float(data[k]["limit"])
            for k in NAMES}


def _worst_norm_gap(prog: Dict[str, float], ref: Dict[str, float],
                    leaves) -> Tuple[float, str]:
    """-> (the worst leaf's gap, that leaf)."""
    if set(prog) != set(ref):
        raise KeyError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = float(np.median([ref[k] for k in leaves]))
    return max((abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30), k)
               for k in leaves)


def _worst_rel(prog, ref) -> float:
    return float(max(abs(a - b) / abs(b) for a, b in zip(prog, ref)))


def gaps(prog: Dict, ref: Dict) -> Dict:
    """prog/ref: {"loss": [...], "gnorm": [...], "grad1": {leaf: norm},
    "delta": {...}} -> the four numbers, and under ``worst_leaf`` the leaf
    that set each per-leaf number."""
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    moving = [k for k in g if g[k] >= QUIET_LEAF * med]
    grad, grad_leaf = _worst_norm_gap(prog["grad1"], g, list(g))
    upd, upd_leaf = _worst_norm_gap(prog["delta"], ref["delta"], moving)
    return {"loss_gap": _worst_rel(prog["loss"], ref["loss"]),
            "gnorm_gap": _worst_rel(prog["gnorm"], ref["gnorm"]),
            "grad_gap": float(grad), "update_gap": float(upd),
            "worst_leaf": {"grad_gap": grad_leaf, "update_gap": upd_leaf}}


def verdict(values: Dict[str, float], limits: Dict[str, float]):
    """-> (correct, {name: {"value", "limit"}})."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    ok = all(np.isfinite(values[k]) and values[k] <= limits[k]
             for k in NAMES if limits[k] is not None)
    return bool(ok), checks
