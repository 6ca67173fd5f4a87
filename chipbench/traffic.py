"""The one traffic generator: reads a mix file of `chipbench/traffic/`.

A mix file is data only::

    {"mix": {"lognorm_mean", "lognorm_sigma", "tail_frac", "tail_alpha",
             "tail_scale"},          # document lengths: lognormal body
                                     # with a Pareto tail
     "min_len", "max_len",           # every length is clipped to these
     "tokens_per_step",              # tokens of one global batch
     "capacity",                     # tokens per wave and chip
     "strategy",                     # HDP planner: balance | naive | static
     "pool_steps", "pool_seed"}      # the batches every seed shares

Every seed trains on the same batches in the same order: ``pool_steps``
global batches drawn once from the mix with ``pool_seed``, over and
over.  The run's seed orders the documents inside
each batch and fills them with token ids.  So two seeds do the same work
step by step, and differ in what the tokens are.

`Traffic` is what the program's scheduler and loader read a dataset
through: ``step_lengths(step)`` and ``tokens(step, seq_id, start, end)``,
where ``seq_id`` indexes the step's length list.  Token ids are a hash of
(seed, step, document, position), so the reference reads the same
documents without touching the program.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent
_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def load_mix(name: str, base: Path = HERE) -> dict:
    return json.loads((base / "traffic" / f"{name}.json").read_text())


def sample_lengths(mix: dict, rng: np.random.Generator, n: int,
                   lo: int, hi: int) -> np.ndarray:
    body = rng.lognormal(mix["lognorm_mean"], mix["lognorm_sigma"], size=n)
    tail = mix["tail_scale"] * (1.0 + rng.pareto(mix["tail_alpha"], size=n))
    is_tail = rng.random(n) < mix["tail_frac"]
    return np.clip(np.where(is_tail, tail, body), lo, hi).astype(np.int64)


def sample_batch(mix: dict, rng: np.random.Generator, total: int,
                 lo: int, hi: int) -> List[int]:
    """Documents until ``total`` tokens; the last one is cut to fit (never
    below ``lo``)."""
    out: List[int] = []
    acc = 0
    while acc < total:
        ln = int(sample_lengths(mix, rng, 1, lo, hi)[0])
        ln = max(min(ln, total - acc), lo)
        out.append(ln)
        acc += ln
    return out


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 arrays."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


@dataclass
class Traffic:
    mix: dict
    vocab: int
    seed: int

    def __post_init__(self):
        m = self.mix
        self.tokens_per_step = int(m["tokens_per_step"])
        self.context = int(m["max_len"])
        self.capacity = int(m["capacity"])
        self.strategy = m["strategy"]
        rng = np.random.default_rng(int(m["pool_seed"]))
        self.pool = [sample_batch(m["mix"], rng, self.tokens_per_step,
                                  int(m["min_len"]), self.context)
                     for _ in range(int(m["pool_steps"]))]
        self._key = _mix64(np.array([self.seed & int(_M64)], np.uint64))[0]
        self._cache: dict = {}

    def _rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng([self.seed & 0xFFFFFFFF,
                                      self.seed >> 32, *salt])

    def step_lengths(self, step: int) -> List[int]:
        if step not in self._cache:
            batch = self.pool[step % len(self.pool)]
            perm = self._rng(step).permutation(len(batch))
            self._cache[step] = [int(batch[i]) for i in perm]
        return list(self._cache[step])

    def tokens(self, step: int, seq_id: int, start: int,
               end: int) -> np.ndarray:
        """Token ids of document ``seq_id`` of ``step`` at [start, end)."""
        with np.errstate(over="ignore"):
            base = _mix64(self._key ^ _mix64(np.array(
                [(step << 24) + seq_id], np.uint64)))[0]
            idx = np.arange(start, end, dtype=np.uint64)
            h = _mix64(base + idx * np.uint64(0x9E3779B97F4A7C15))
        return (h % np.uint64(self.vocab)).astype(np.int32)

    def document(self, step: int, seq_id: int):
        """(tokens, next-token labels) of one whole document."""
        n = self.step_lengths(step)[seq_id]
        return self.tokens(step, seq_id, 0, n), self.tokens(step, seq_id,
                                                            1, n + 1)
