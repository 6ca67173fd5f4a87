"""The system under test, built from the program's public classes.

`build` makes one Trainer for a cell: the configuration's ModelConfig, a
``<chips>x1`` HDP mesh over the chips at hand, the Pallas attention and
loss kernels, the mix's capacity and planner strategy, and the seed for
both the weights and the documents.  `readings` drives that same object
through its first steps with `Trainer.train_step` (the call the window
times) and takes from its AdamW state what the comparison needs.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference import OPT, leaf_name

# the program's parameter tree -> the reference's leaf names; stacked
# block leaves [layers, ...] split into one leaf per layer
_BLOCK_LEAVES = {"attn.w_q": "wq", "attn.w_kv": "wkv", "attn.w_o": "wo",
                 "mlp.w_in": "win", "mlp.w_out": "wout",
                 "mlp.w_gate": "wgate", "norm1.scale": "norm1",
                 "norm2.scale": "norm2"}
_TOP_LEAVES = {"embed": "embed", "lm_head": "head",
               "final_norm.scale": "final_norm"}


def build(cfg, traffic, devices, seed: int):
    from repro import compat
    from repro.data.loader import GlobalScheduler
    from repro.optim.adamw import AdamWConfig
    from repro.parallel.sharding import Runtime
    from repro.train.trainer import Trainer, TrainerConfig
    chips = len(devices)
    mesh = compat.make_mesh((chips, 1), ("data", "model"), devices=devices)
    compat.set_mesh(mesh)
    # the Pallas kernels: the jnp oracle ring does not fit a chip at g = 4
    rt = Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                 attn_impl="pallas")
    sched = GlobalScheduler(traffic, cfg, capacity=traffic.capacity,
                            hdp=chips, strategy=traffic.strategy,
                            use_offload=False)
    # calibrate=False: plans follow the data alone, so every seed runs the
    # same plans and no measured speed re-plans into a new composition
    tcfg = TrainerConfig(capacity=traffic.capacity,
                         strategy=traffic.strategy, attn_impl="pallas",
                         calibrate=False)
    return Trainer(cfg, rt, AdamWConfig(**OPT), sched, tcfg, seed=seed)


def _leaves(tree):
    """[(reference leaf name, array or per-layer stack)] of a program
    tree; stacked leaves carry a leading layer axis."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = leaf_name(path)
        if name in _TOP_LEAVES:
            out.append((_TOP_LEAVES[name], x, False))
            continue
        head, _, rest = name.partition(".")       # blocks.0.attn.w_q
        period, _, leaf = rest.partition(".")
        if head == "blocks" and period == "0" and leaf in _BLOCK_LEAVES:
            out.append((_BLOCK_LEAVES[leaf], x, True))
        else:
            raise KeyError(f"program leaf {name!r} has no reference leaf")
    return out


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """{reference leaf name: norm} of a program tree, per layer."""
    out = {}
    entries = _leaves(tree)
    norms = jax.device_get([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                         axis=tuple(range(1, x.ndim)) if stacked else None))
        for _, x, stacked in entries])
    for (name, _, stacked), n in zip(entries, norms):
        if stacked:
            for i, ni in enumerate(np.atleast_1d(n)):
                out[f"layer{i}.{name}"] = float(ni) * scale
        else:
            out[name] = float(n) * scale
    return out


def host_delta_norms(after, before) -> Dict[str, float]:
    """Per-leaf norms of ``after - before`` for two host copies of the
    program's float32 master weights."""
    out = {}
    for (name, a, stacked), (_, b, _) in zip(_leaves(after),
                                             _leaves(before)):
        d = np.asarray(a, np.float32) - np.asarray(b, np.float32)
        if stacked:
            for i in range(d.shape[0]):
                out[f"layer{i}.{name}"] = float(np.linalg.norm(d[i]))
        else:
            out[name] = float(np.linalg.norm(d))
    return out


def readings(trainer, steps: int) -> Dict:
    """Drive ``trainer`` through its first ``steps`` steps and read the
    numbers the comparison holds against the reference: each step's loss
    and global gradient norm before clipping, the per-leaf norm of the first gradient as AdamW got it (its first
    moment after one step over 1 - b1), and the per-leaf norm of the
    master weights' change after ``steps``."""
    master0 = jax.device_get(trainer.opt_state["master"])
    losses: List[float] = []
    gnorms: List[float] = []
    recs = []
    grad1 = None
    for s in range(steps):
        rec = trainer.train_step()
        recs.append(rec)
        losses.append(float(rec["loss"]))
        gnorms.append(float(rec["grad_norm"]))
        if s == 0:
            grad1 = leaf_norms(trainer.opt_state["m"], 1.0 / (1 - OPT["b1"]))
    delta = host_delta_norms(jax.device_get(trainer.opt_state["master"]),
                             master0)
    return {"loss": losses, "gnorm": gnorms, "grad1": grad1, "delta": delta,
            "records": recs}
