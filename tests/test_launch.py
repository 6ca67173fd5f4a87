"""Launcher surfaces: ``--mesh HxM`` over the local devices, the shared
single-process path (`make_trainer`, also driven by chip_smoke.py), the
compile-cache location, and the interpret-mode choice of the kernels."""
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compat
from repro.kernels import ops
from repro.launch import train
from repro.launch.mesh import make_local_mesh

ROOT = Path(__file__).resolve().parents[1]


def test_local_mesh_takes_exactly_the_local_devices():
    n = len(jax.devices())
    mesh = make_local_mesh(f"{n}x1")
    assert dict(mesh.shape) == {"data": n, "model": 1}
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(f"{n + 1}x1")
    with pytest.raises(ValueError):
        make_local_mesh(f"{n}x1x1")


def test_make_trainer_runs_the_single_process_path(rt1):
    n = len(jax.devices())
    args = train.build_parser().parse_args([
        "--arch", "llama3.2-3b-1chip", "--reduced", "--mesh", f"{n}x1",
        "--capacity", "128", "--context", "256", "--tokens-per-step",
        str(512 * n), "--steps", "1", "--dataset", "tiny"])
    trainer = train.make_trainer(args)
    try:
        rec = next(trainer.run(1))
    finally:
        trainer.sched.stop()
        compat.set_mesh(rt1.mesh)
    assert trainer.cfg.vocab_size == 512 and trainer.rt.hdp_size == n
    assert math.isfinite(rec["loss"])
    assert all(sum(c) == n for c in rec["compositions"])
    assert rec["waves"] == len(rec["compositions"])


_CACHE_PROBE = """
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {src!r})
from repro import compat
print(compat.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """$JAX_COMPILATION_CACHE_DIR is left to JAX and written there;
    without it the cache is <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    want = str(ROOT / ".jax_cache")
    if env_dir:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    returned, configured = out.stdout.split()[:2]
    assert returned == configured == want
    assert any(p.name.startswith("jit__lambda")
               for p in Path(want).iterdir()), "nothing written there"


def test_interpret_is_read_from_the_placement():
    class Tpu:
        platform = "tpu"
    cpu = jax.devices()[0]
    mesh = make_local_mesh(f"{len(jax.devices())}x1")
    assert ops.interpret_on(mesh)
    assert ops.interpret_on(jax.sharding.SingleDeviceSharding(cpu))
    assert ops.interpret_on([cpu])
    assert not ops.interpret_on([Tpu()])
    assert not ops.interpret_on([cpu, Tpu()])
