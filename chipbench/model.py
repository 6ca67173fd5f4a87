"""A configuration file of `chipbench/configs/` as the program's ModelConfig.

The files hold the model's published `config.json` keys (with the keys
cut to one chip's share listed under ``reduced``).  Only dense decoders
with RMSNorm, RoPE, SwiGLU and untied or tied heads map onto the
program's generic decoder, and the mapping refuses anything else rather
than run a different model under the published name.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

DENSE_TYPES = ("llama", "mistral")


def load_config(name: str, base: Path = HERE) -> dict:
    return json.loads((base / "configs" / f"{name}.json").read_text())


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def check_dense(c: dict) -> None:
    bad = []
    if c["model_type"] not in DENSE_TYPES:
        bad.append(f"model_type {c['model_type']!r}")
    if c.get("hidden_act") != "silu":
        bad.append(f"hidden_act {c.get('hidden_act')!r}")
    if c.get("attention_bias"):
        bad.append("attention_bias")
    if c.get("sliding_window"):
        bad.append("sliding_window")
    if c.get("rope_scaling"):
        bad.append("rope_scaling")
    if bad:
        raise ValueError(f"not a plain dense decoder: {', '.join(bad)}")


def model_config(name: str, base: Path = HERE):
    """-> repro ModelConfig for the configuration file ``name``."""
    from repro.configs.base import ModelConfig
    c = load_config(name, base)
    check_dense(c)
    return ModelConfig(
        name=name, family="dense",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=head_dim(c),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        act="silu", gated_mlp=True, pos_embed="rope",
        dtype=c["torch_dtype"])
