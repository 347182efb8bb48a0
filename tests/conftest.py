"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE jax is imported
anywhere, so sharding/mesh tests run without TPU hardware (the driver
separately dry-runs the multi-chip path). Mirrors the reference's approach of
running its full cluster test suite in-process (reference: test/pilosa.go:390
MustRunCluster).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: boots multi-process clusters / exceeds the tier-1 time "
        "budget (excluded by the default -m 'not slow' run; "
        "make test-spmd-mesh runs them)")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
