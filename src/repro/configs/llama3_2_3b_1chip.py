"""Llama-3.2-3B cut to one TPU v5e chip's share (16 GB of HBM).

Source: `llama3_2_3b.CONFIG`, the published config.json of
meta-llama/Llama-3.2-3B.  Every width is kept as published: d_model 3072,
24 query / 8 KV heads of 128, d_ff 8192 (SwiGLU), RoPE θ 5e5, RMSNorm
ε 1e-5, tied embeddings.

Deployment it stands for: a 28-layer model trained over a pipeline of
seven 4-layer stages, where the first and last stage each split the tied
vocabulary four ways.  This chip holds one stage's 4 layers and a quarter
of the vocabulary.  With random weights the traffic draws its token ids
from the slice, and the loss is over the slice.

CAPACITY (tokens per wave) was chosen by rehearsal: the largest wave whose
`grad_step` compile for a described v5e, plus the resident bf16 params,
the f32 AdamW master/m/v and the f32 gradient accumulator, fits in the
chip's 15.75 GiB (tests/test_tpu_compile.py holds it there).  On one chip
the context is at most the capacity: a longer sequence spills into
`c_mult` waves that multiply the per-rank buffer.
"""
import dataclasses

from repro.configs.llama3_2_3b import CONFIG as PUBLISHED

CONFIG = dataclasses.replace(
    PUBLISHED,
    name="llama3.2-3b-1chip",
    num_layers=4,
    vocab_size=32_128,
)

REDUCED = {
    "num_layers": "28 -> 4: one pipeline stage of seven; the other 24 "
                  "layers lie on further stages",
    "vocab_size": "128256 -> 32128: a quarter of the vocabulary (32064) "
                  "rounded up to a multiple of 128, as one of four chips "
                  "that share the tied embedding/head would hold it",
}

CAPACITY = 2048
