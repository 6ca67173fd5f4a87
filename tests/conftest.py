"""Test-tier bootstrap.

* Smoke tests see ONE device (the dry-run sets its own 512-device flag in a
  separate process; distributed tests spawn subprocesses with their own
  XLA_FLAGS).  CI may export XLA_FLAGS=--xla_force_host_platform_device_count=8
  — the smoke tests only ever use device 0, so that is harmless.
* Tests that need a backend feature the CPU lacks skip with a reason (via
  `repro.compat.feature_status`) instead of hard-erroring: mark them
  ``@pytest.mark.jax_feature("host_offload")`` etc.
"""
import os

os.environ.setdefault("XLA_FLAGS", "")

import jax  # noqa: E402
import pytest  # noqa: E402

from repro import compat  # noqa: E402
from repro.parallel.sharding import single_device_runtime  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "jax_feature(name): skip when the backend lacks the feature "
        "(names: host_offload, memory_stats)")


def pytest_runtest_setup(item):
    for mark in item.iter_markers("jax_feature"):
        if not mark.args:
            pytest.fail("@pytest.mark.jax_feature requires a feature name, "
                        "e.g. jax_feature('host_offload')")
        name = mark.args[0]
        ok, why = compat.feature_status(name)
        if not ok:
            pytest.skip(f"jax {jax.__version__} lacks {name!r}: {why}")


@pytest.fixture(scope="session")
def rt1():
    rt = single_device_runtime(remat="none")
    compat.set_mesh(rt.mesh)
    return rt
