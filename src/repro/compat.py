"""The single import point for the JAX runtime surfaces the code shares:
meshes, the ambient mesh, jit shardings, host-offload memory and allocator
statistics.  The repo targets one JAX release (0.9); this module keeps the
call sites uniform and lazy.

Probing is lazy where it would initialize the backend (the dry-run sets
XLA_FLAGS before first device use; importing this module must never touch
device state).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


# ---------------------------------------------------------------------------
# mesh construction / ambient mesh
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis Auto: the code places arrays with
    NamedShardings and sharding constraints, never explicit-axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def set_mesh(mesh: Mesh) -> Mesh:
    """Install ``mesh`` as the ambient mesh for bare-PartitionSpec
    resolution (`with_sharding_constraint(x, P(...))` etc.)."""
    jax.set_mesh(mesh)
    return mesh


def use_mesh(mesh: Mesh):
    """Scoped variant of `set_mesh`: a context that restores the previous
    ambient mesh on exit."""
    return jax.set_mesh(mesh)


def resolve_shardings(tree, mesh: Mesh):
    """PartitionSpec pytree -> NamedSharding pytree for `jax.jit`, so the
    shardings do not depend on an ambient mesh.  None leaves (unspecified
    shardings) and existing Sharding objects pass through."""
    def leaf(s):
        return NamedSharding(mesh, s) if isinstance(s, PartitionSpec) else s
    return jax.tree.map(leaf, tree,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache lives in ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache key).  Called by entry
    points only, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# host-offload memory probing
# ---------------------------------------------------------------------------

def memory_kinds() -> set:
    """Memory kinds exposed by the local devices (initializes the backend —
    call lazily, never at import time)."""
    try:
        return {m.kind for d in jax.local_devices()
                for m in d.addressable_memories()}
    except Exception:
        return set()


def host_offload_memory_kind() -> Optional[str]:
    """The memory kind residuals offload to, or None when the backend has
    no distinct host memory space (offloading to device memory would be a
    no-op, so we report unsupported)."""
    return "pinned_host" if "pinned_host" in memory_kinds() else None


def offload_supported() -> bool:
    return host_offload_memory_kind() is not None


def device_memory_stats(device=None) -> dict:
    """``device.memory_stats()`` with graceful degradation: backends that
    expose no allocator stats (CPU returns None) yield ``{}`` instead of
    crashing — the bytes ledger's measured HBM watermark simply stays
    absent there (obs/ledger.py).

    Initializes the backend when ``device`` is None — call lazily, never
    at import time (same discipline as `memory_kinds`)."""
    try:
        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
        return dict(stats) if stats else {}
    except Exception:
        return {}


def offload_policy(names: Sequence[str] = ("resid",)):
    """Remat policy offloading ``names`` to host memory (ByteScale Eq. 3's
    execution side).  Degrades to saving the same names on device when the
    backend lacks a host memory space — same recompute structure, no
    transfer, so plans stay executable everywhere."""
    cp = jax.checkpoint_policies
    kind = host_offload_memory_kind()
    if kind is not None:
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src="device", offload_dst=kind)
    return cp.save_only_these_names(*names)


# ---------------------------------------------------------------------------
# feature registry (conftest skip-with-reason support)
# ---------------------------------------------------------------------------

# only the probes whose answer differs between the CPU and the TPU backend
_FEATURES = {
    "host_offload": lambda: (offload_supported(),
                             "no pinned_host memory on this backend"),
    "memory_stats": lambda: (bool(device_memory_stats()),
                             "backend exposes no allocator stats"),
}


def feature_status(name: str) -> Tuple[bool, str]:
    """(supported, reason-if-not) for a named backend feature.  Unknown
    names report unsupported so tests skip loudly rather than crash."""
    probe = _FEATURES.get(name)
    if probe is None:
        return False, f"unknown JAX feature {name!r}"
    return probe()
