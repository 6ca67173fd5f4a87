"""Operations and bytes the algorithm needs, from a configuration file's
sizes and the documents' lengths.  Recomputation is never counted.

Per token a dense decoder's matmuls need 6 x (non-embedding matmul
weights + the head over the vocabulary slice) operations, forward and
backward; causal attention over a document of L tokens adds 6 L^2 H d_h
per layer (forward QK^T and PV over half the L x L scores, twice that
backward).
"""
from __future__ import annotations

from typing import Iterable


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], c.get("head_dim") or d // h


def matmul_weights(c: dict) -> int:
    """Non-embedding matmul weights, the head included."""
    d, h, hkv, dh = _dims(c)
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d \
        + 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def attention_flops(c: dict, lengths: Iterable[int]) -> float:
    """Causal attention of whole documents, forward and backward, all
    layers (what the flash kernels need)."""
    _, h, _, dh = _dims(c)
    return float(sum(6.0 * n * n * h * dh for n in lengths)) \
        * c["num_hidden_layers"]


def attention_bytes(c: dict, lengths: Iterable[int]) -> float:
    """bf16 bytes the flash kernels must move at least: q, k, v and o
    forward; q, k, v, o, do in and dq, dk, dv out backward."""
    _, h, hkv, dh = _dims(c)
    per_token = (2 * h + 2 * hkv) * dh + (4 * h + 4 * hkv) * dh
    return 2.0 * per_token * sum(lengths) * c["num_hidden_layers"]


def model_flops(c: dict, lengths: Iterable[int]) -> float:
    lengths = list(lengths)
    return 6.0 * matmul_weights(c) * sum(lengths) \
        + attention_flops(c, lengths)


def ce_flops(c: dict, tokens: int) -> float:
    """Softmax cross-entropy over the logits: max, exp, sum forward and
    exp, subtract, scale backward, about 8 operations per logit."""
    return 8.0 * tokens * c["vocab_size"]


def ce_bytes(c: dict, tokens: int) -> float:
    """bf16 logits read forward, read again and dlogits written
    backward."""
    return 6.0 * tokens * c["vocab_size"]
