"""Test rig: multi-device without a cluster.

The reference's check suite simulates multi-node by launching N slave JVMs
on localhost (SURVEY.md section 4). Here we simulate a TPU pod with 8
virtual CPU devices (xla_force_host_platform_device_count) and enable x64
so DOUBLE/LONG operands are exact for differential comparison.

Must run before any jax import, hence module-level env mutation in
conftest (pytest imports conftest first).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def n_devices():
    return jax.device_count()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
