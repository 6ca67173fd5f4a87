"""Mesh construction: the local H×M mesh of ``--mesh HxM`` over the
devices at hand, and the production meshes the dry-run lowers onto.

Local:      (H, M) = ("data", "model") over exactly `jax.devices()`.

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips.
Pipelined:  a leading "stage" axis carved out of the data dimension —
(4, 4, 16) = ("stage", "data", "model") keeps 256 chips with 4 pipeline
stages × 4-way HDP × 16-way TP (the hdp × model × stage mesh of
parallel/pipeline.py).

The HDP axis is every non-"model", non-"stage" axis combined (d_hdp =
32 multi-pod / 16 single-pod at dry-run scale; arbitrary in production).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro import compat

NON_HDP_AXES = ("model", "stage")


def make_local_mesh(spec: str):
    """``--mesh HxM``: H-way HDP × M-way tensor parallelism over every
    device `jax.devices()` reports.  Fails unless H·M is that count."""
    dims = tuple(int(x) for x in spec.lower().split("x"))
    devices = jax.devices()
    if len(dims) != 2 or dims[0] * dims[1] != len(devices):
        raise ValueError(
            f"--mesh {spec} needs H×M = {len(devices)} devices "
            f"({devices[0].platform} x{len(devices)} present)")
    return compat.make_mesh(dims, ("data", "model"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False, num_stages: int = 1):
    if num_stages > 1:
        assert 16 % num_stages == 0, (num_stages, "must divide the data dim")
        shape: Tuple[int, ...] = (num_stages, 16 // num_stages, 16)
        axes: Tuple[str, ...] = ("stage", "data", "model")
        if multi_pod:
            shape = (2,) + shape
            axes = ("pod",) + axes
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_pipeline_mesh(num_stages: int, hdp: int, tp: int = 1):
    """Small-scale pipelined mesh (examples / CPU tests): stage × data ×
    model over num_stages · hdp · tp devices."""
    return compat.make_mesh((num_stages, hdp, tp), ("stage", "data", "model"))


def hdp_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a not in NON_HDP_AXES)


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)
