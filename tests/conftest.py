"""Test harness config.

Runs the whole suite on the XLA CPU backend with 8 virtual devices so that
mesh/sharding/collective logic is exercised without TPU hardware — the
strategy SURVEY.md §4 calls for (the reference's closest analog is the
fake_cpu_device CustomDevice plugin, ref: paddle/phi/backends/custom/
fake_cpu_device.h + test/custom_runtime/).

Env vars must be set before the first jax import, hence this file's top.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: a TPU host would select the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
