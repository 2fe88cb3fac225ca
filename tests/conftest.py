"""Test harness: run JAX on a virtual 8-device CPU platform.

The reference tests multi-GPU data parallelism with a real in-process
P2PManager over k GPUs (test_gradient_based_solver.cpp:201-217) and leaves
multi-node untested. Here the same gap is closed portably: XLA's host
platform is split into 8 virtual devices so mesh/psum/pjit paths run as a
real 8-way SPMD program on CPU.

Platform forcing: the suite is defined on the CPU platform whatever the
launching shell selects. Backends initialize lazily, so setting
jax.config *before any jax computation* (conftest import time) wins.
XLA_FLAGS must likewise be set before the CPU client is created.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

assert jax.devices()[0].platform == "cpu", "tests must run on the CPU platform"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end tests (full-size models)")


@pytest.fixture
def rng():
    return np.random.RandomState(1701)
