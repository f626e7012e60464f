"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process-on-localhost emulation strategy
(reference ``tests/unittests/test_dist_base.py:642``) but device-faking via
XLA is stronger: all sharding/collective paths compile and execute in one
process (SURVEY.md §4 'Mocks/fakes').
"""

import os

# Must be set before jax initializes its backends; subprocess tests
# inherit it. The env var is only read when jax is first imported, so the
# config update below covers a plugin having imported jax already.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# NOTE: x64 is NOT enabled globally — the finite-difference gradient checks
# in op_test.py scope it with `jax.enable_x64()`. (Global x64 triggers an
# XLA CPU compiler abort in grad-of-shard_map-ring-attention graphs.)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
