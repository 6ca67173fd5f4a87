"""Reduce the profiler's trace of the window to the numbers the per-layer
readers take.

The trace is the `.xplane.pb` that `jax.profiler` writes.  Each chip is a
plane ``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per HLO
instruction executed, named by the instruction's text
(``%name = <shape> <opcode>(...)``), with start and duration in ns on the
trace's clock.  The host plane holds the harness's ``chipbench_step``
annotations on the same clock, which tie the program's own spans (taken
on `time.perf_counter`) to the device's timeline.

* busy: the union of a chip's op intervals inside the traced window
  (ops nest: a ``while`` covers its body), averaged over the chips;
* kernel time: the summed durations of the Pallas custom calls of one
  kind (`kernel_kind`);
* idle gaps: the stretches between busy intervals, each labelled by the
  program span the host was in at the gap's middle.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")
_INSTR = re.compile(r"^(%[^ ]+) = ")
CONTAINERS = ("while", "conditional", "call")
MIN_GAP_NS = 20_000


def opcode(name: str) -> str:
    m = _OPCODE.search(name)
    return m.group(1) if m else name.split(" ", 1)[0]


def instruction(name: str) -> str:
    m = _INSTR.match(name)
    return m.group(1) if m else name[:60]


def kernel_kind(name: str, vocab: int) -> Optional[str]:
    """Which Pallas kernel a ``tpu_custom_call`` event is, from its
    operands: the flash kernels take the key metadata as an ``s32[1,S]``
    row; the fused cross-entropy takes ``[T, vocab]`` logits.  None for
    every other op."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    if "s32[1," in name:
        return "flash"
    if f",{vocab}]" in name:
        return "fused_ce"
    return "other_kernel"


def union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals of an [n, 2] array."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


@dataclass
class ChipOps:
    """One chip's ops: for each, its short label (``%instr opcode``), its
    opcode, its kernel kind (or None) and its [start, end) in ns."""
    labels: List[str]
    ops: List[str]
    kinds: List[Optional[str]]
    iv: np.ndarray

    @classmethod
    def of(cls, events, vocab: int) -> "ChipOps":
        labels, ops, kinds, iv = [], [], [], []
        for e in events:
            op = opcode(e.name)
            labels.append(f"{instruction(e.name)} {op}")
            ops.append(op)
            kinds.append(kernel_kind(e.name, vocab))
            iv.append((e.start_ns, e.start_ns + e.duration_ns))
        return cls(labels, ops, kinds,
                   np.asarray(iv, np.float64).reshape(-1, 2))


@dataclass
class Trace:
    chips: List[ChipOps]
    window: Tuple[float, float]    # ns on the trace clock
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        per = [length(clip(union(c.iv), *self.window)) for c in self.chips]
        return float(np.mean(per)) / 1e9

    def _inside(self, chip: ChipOps):
        """Indices of the chip's ops inside the window, containers (whose
        time is their body's) left out."""
        w0, w1 = self.window
        return [i for i, (s, e) in enumerate(chip.iv)
                if s >= w0 and e <= w1 and chip.ops[i] not in CONTAINERS]

    def kernel_s(self, kind: str) -> float:
        """Device seconds of one kernel kind, summed over chips."""
        return sum(c.iv[i, 1] - c.iv[i, 0] for c in self.chips
                   for i in self._inside(c) if c.kinds[i] == kind) / 1e9

    def device_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time (seconds per chip)."""
        agg: Dict[str, float] = {}
        for c in self.chips:
            for i in self._inside(c):
                key = c.labels[i] + (f" [{c.kinds[i]}]" if c.kinds[i]
                                     else "")
                agg[key] = agg.get(key, 0.0) + c.iv[i, 1] - c.iv[i, 0]
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / len(self.chips)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle stretches of chip 0, labelled by the program
        span the host was in."""
        busy = clip(union(self.chips[0].iv), *self.window)
        edges = [self.window[0]] + list(busy.ravel()) + [self.window[1]]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                if e - s >= MIN_GAP_NS]
        out = [[self.label(0.5 * (s + e)), (e - s) / 1e9] for s, e in gaps]
        return sorted(out, key=lambda g: -g[1])[:n]

    def label(self, t: float) -> str:
        """The innermost host span covering ``t``."""
        best = None
        for name, s, e in self.host_spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "harness"

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}


def find(trace_dir: Path) -> Path:
    return next(Path(trace_dir).rglob("*.xplane.pb"))


def read(xspace: bytes, vocab: int, n_chips: int,
         step_starts: Sequence[float], spans: Sequence[dict]) -> Trace:
    """Reduce a serialized XSpace (the bytes of an .xplane.pb) for the
    first ``n_chips`` chips.
    ``step_starts``: the host clock (s) at the start of each traced step;
    ``spans``: the program's spans (Chrome events, µs on that clock)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    chips: Dict[int, ChipOps] = {}
    marks: List[Tuple[float, float]] = []
    for plane in pd.planes:
        m = re.match(r"/device:(TPU|CPU):(\d+)$", plane.name)
        if m and int(m.group(2)) < n_chips:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[int(m.group(2))] = ChipOps.of(line.events, vocab)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "chipbench_step":
                        marks.append((e.start_ns, e.start_ns + e.duration_ns))
    marks.sort()
    if not marks:
        raise ValueError("trace has no chipbench_step annotations")
    window = (marks[0][0], marks[-1][1])
    offset = float(np.median([m[0] - t * 1e9
                              for m, t in zip(marks, step_starts)])) \
        if len(step_starts) == len(marks) else None
    host = []
    if offset is not None:
        for ev in spans:
            if ev.get("ph") == "X":
                s = ev["ts"] * 1e3 + offset
                host.append((ev["name"], s, s + ev["dur"] * 1e3))
    if not chips:
        raise ValueError("trace has no device planes")
    return Trace(chips=[chips[i] for i in sorted(chips)], window=window,
                 host_spans=host)
