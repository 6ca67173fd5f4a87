"""Mesh runtime + parameter sharding rules.

The production mesh axes are ("pod",) ("stage",) "data", "model"
(launch/mesh.py).  The **HDP axis is ("pod","data") combined** —
ByteScale's d_hdp = d_dp·d_cp as a single token axis; "model" is 16-way
tensor parallelism; an optional "stage" axis carries pipeline parallelism
(parallel/pipeline.py): the stacked per-period block parameters shard
their leading [n_periods] dim over it, so stage s stores exactly its
contiguous window of n_periods/num_stages periods (embed / head / norms
stay stage-replicated — only first/last stage ever computes with them).

Parameter sharding is rule-based (MaxText-style): ordered (predicate ->
spec) rules matched against the parameter's path, applied with
``jax.tree_util.tree_map_with_path``.  ZeRO-1 lives in parallel/zero1.py.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig
from repro.models.layers import gqa_layout


@dataclass(frozen=True)
class Runtime:
    """Everything the model/train code needs to know about distribution."""
    mesh: Mesh
    hdp_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"
    stage_axis: Optional[str] = None  # pipeline axis (parallel/pipeline.py)
    composition: Tuple[int, ...] = (1,)
    attn_impl: str = "ref"            # ref (jnp oracle ring) | pallas
                                      # (fused ring-flash engine)
    attn_block_q: int = 256           # Pallas flash tile shapes (clamped to
    attn_block_k: int = 512           # the local chunk when it is smaller)
    remat: str = "auto"               # auto | none | full | dots | offload:
                                      # the trainer resolves "auto" per wave
                                      # program from the compiler's memory
                                      # count (core/offload.resolve_remat);
                                      # unresolved it runs as "full"
    offload_periods: int = 0          # leading layer-periods whose residuals offload
    kv_chunk: int = 1024
    block_skip: bool = True
    cost_unroll: bool = False         # cost-analysis lowering: unroll ring steps + period loop
    seq_parallel: bool = False        # shard the residual stream over model (SP)
    moe_impl: str = "gather"          # gather (pjit) | manual (shard_map EP)

    @property
    def tp(self) -> int:
        return int(self.mesh.shape[self.model_axis]) if self.model_axis else 1

    @property
    def hdp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.hdp_axes]))

    @property
    def num_stages(self) -> int:
        return (int(self.mesh.shape[self.stage_axis])
                if self.stage_axis else 1)

    def with_composition(self, comp: Tuple[int, ...]) -> "Runtime":
        return dataclasses.replace(self, composition=tuple(comp))

    def layout(self, cfg: ModelConfig):
        return gqa_layout(cfg.num_heads, cfg.num_kv_heads, self.tp)


def single_device_runtime(**kw) -> Runtime:
    """CPU smoke-test runtime: a 1×1 mesh."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return Runtime(mesh=mesh, hdp_axes=("data",), model_axis="model",
                   composition=(1,), **kw)


# ---------------------------------------------------------------------------
# parameter sharding rules
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def param_spec(path, leaf, *, model: str, kv_sharded: bool) -> P:
    """The sharding rule table.  `path` is the pytree path, `leaf` the array.

    Conventions (see models/*.py):
      * col-parallel (output dim on model): w_q, w_in, w_gate, ffn up-projs,
        rwkv r/k/v/g projections, decay_b, dt_w, lm_head
      * row-parallel (input dim on model, psum output): w_o, w_out
      * expert-parallel: 3-D [E, ...] tensors shard dim 0
      * vocab: embedding shards the vocab dim
    """
    name = _path_str(path)
    last = name.rsplit("/", 1)[-1]
    nd = leaf.ndim

    if last == "embed":
        return P(model, None)
    if last == "lm_head":
        return P(None, model)

    if "mamba" in name:
        if last == "w_in":                    # [d, 2, d_in]
            return P(None, None, model)
        if last == "conv_w":                  # [K, d_in]
            return P(None, model)
        if last in ("conv_b", "dt_bias", "D"):
            return P(model)
        if last == "A_log":                   # [d_in, N]
            return P(model, None)
        if last == "w_x":                     # [d_in, r+2N] row-parallel
            return P(model, None)
        if last == "dt_w":                    # [r, d_in]
            return P(None, model)
        if last == "w_out":                   # [d_in, d]
            return P(model, None)
        return P()

    if "time_mix" in name:
        if last in ("w_r", "w_k", "w_v", "w_g"):
            return P(None, model)
        if last == "w_o":
            return P(model, None)
        if last == "decay_b":                 # [R, d]
            return P(None, model)
        if last == "decay_base":
            return P(model)
        if last == "bonus_u":                 # [H, N]
            return P(model, None)
        if last in ("scale", "bias"):         # ln_x [d]
            return P(model)
        return P()                            # mix loras: replicated

    if "channel_mix" in name:
        if last == "w_k":                     # [d, d_ff]
            return P(None, model)
        if last == "w_v":                     # [d_ff, d]
            return P(model, None)
        return P()

    if "moe" in name:
        if nd == 3:                           # expert-parallel [E, ...]
            return P(model, None, None)
        if last in ("shared_in", "shared_gate"):
            return P(None, model)
        if last == "shared_out":
            return P(model, None)
        return P()                            # router

    if last == "w_kv":                        # [d, 2, G, Dk]
        return P(None, None, model if kv_sharded else None, None)
    if last in ("w_uk", "w_uv"):              # MLA absorbed projections [H,...]
        return P(model, None, None)
    if last in ("w_q", "w_in", "w_gate"):
        return P(None, model)
    if last in ("w_o", "w_out"):
        return P(model, None)
    # norms, biases, loras, w_dkv (shared latent), router: replicated
    return P()


def params_pspecs(params, cfg: ModelConfig, rt: Runtime):
    """Pytree of PartitionSpec matching `params` (stacked layer dims get a
    leading None automatically: the rule sees the per-layer shape)."""
    layout = rt.layout(cfg)
    model = rt.model_axis

    stage = rt.stage_axis if rt.num_stages > 1 else None

    def rule(path, leaf):
        name = _path_str(path)
        stacked = name.split("/", 1)[0] == "blocks"
        # stacked block params carry a leading [n_periods] dim; under
        # pipeline parallelism that dim shards over the stage axis (stage
        # s holds its contiguous periods window — parallel/pipeline.py)
        if stacked:
            if stage is not None:
                assert leaf.shape[0] % rt.num_stages == 0, (
                    leaf.shape, rt.num_stages,
                    "scan periods must divide evenly into pipeline stages")
            sub = jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
            spec = param_spec(path, sub, model=model,
                              kv_sharded=layout.kv_sharded)
            return P(stage, *spec)
        return param_spec(path, leaf, model=model,
                          kv_sharded=layout.kv_sharded)

    return jax.tree_util.tree_map_with_path(rule, params)


def shardings_from_pspecs(pspecs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
