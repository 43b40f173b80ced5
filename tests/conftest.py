"""Test harness configuration.

Forces the jax CPU backend with 8 virtual devices so multi-chip SPMD logic is
exercised without TPU hardware — the analog of the reference's
backend-parameterized test strategy (SURVEY.md §4.2/§4.5: one suite, N
backends; in-process fakes for distribution). Must run before jax initializes.
"""

import os

# Tests run on an 8-device virtual CPU mesh unless opted onto hardware with
# DL4J_TPU_TEST_ON_TPU=1. Both settings go through the environment, before
# jax is imported, so the tests' child processes inherit them.
if not os.environ.get("DL4J_TPU_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# fp64 available for gradient checks (reference GradientCheckUtil enforces fp64).
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fixed_seed():
    """Deterministic streams per test (reference tests fix Nd4j seeds)."""
    from deeplearning4j_tpu.ndarray.rng import get_random

    get_random().set_seed(12345)
    np.random.seed(12345)
    yield
