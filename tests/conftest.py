"""Test configuration.

Mirrors the reference's CPU-CI strategy (SURVEY.md §4): multi-device tests run
on a virtual 8-device CPU platform (the Gloo-backend analog), so the full
sharding/collective surface is exercised without TPU hardware.  Must set the
XLA flags before jax initialises its backends.
"""

import os

# Tests run on the CPU whatever the machine holds: hermetic and multi-device.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
