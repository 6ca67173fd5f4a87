"""CPU tests of the chip benchmark: the harness's modules are importable
by name, `tiny_root` lays out a checkout with one tiny cell, and every
test hands JAX's process-wide state back as it found it."""
import os
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(HERE), str(BENCH), str(BENCH.parent / "src")]

from tinycell import write_tiny_root  # noqa: E402

CACHE_CONFIG = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def restore_jax_globals():
    """A benchmark run installs its mesh and its compile cache for the
    whole process; tests of other files that later share this worker
    expect neither, so put back what the test found."""
    from jax.experimental.compilation_cache import compilation_cache
    mesh = jax.set_mesh(jax.make_mesh((1,), ("_",),
                                      devices=jax.devices()[:1]))
    mesh.__exit__(None, None, None)     # remembers the mesh in effect
    config = {k: getattr(jax.config, k) for k in CACHE_CONFIG}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    mesh.__exit__(None, None, None)
    for k, v in config.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    compilation_cache.reset_cache()


@pytest.fixture
def tiny_root(tmp_path):
    write_tiny_root(tmp_path)
    return tmp_path
