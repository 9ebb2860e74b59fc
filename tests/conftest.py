"""Test env: force CPU PJRT with 8 virtual devices BEFORE jax initializes.

Mirrors the reference's fake-device strategy (fake_cpu_device.h /
test/custom_runtime/): all tests — including multi-chip sharding tests — run
on a virtual 8-device CPU mesh so CI needs no accelerator.
"""

import os

# FORCE cpu: the tests are written for the CPU backend — 8 virtual devices,
# Pallas kernels interpreted, bitwise/tolerance chains stated for XLA CPU —
# and must run the same on a host that has a chip. The env var covers child
# processes; config.update after import covers this one.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# no persistent compile cache for the test run unless the caller placed
# one: six workers and their child gangs would all write the checkout's
# default directory, and XLA:CPU reloads its own entries with a
# machine-feature warning per program. Tests of the cache set their own.
os.environ.setdefault("PADDLE2_TPU_CACHE_DIR", "")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    np.random.seed(0)
    import paddle2_tpu as paddle
    paddle.seed(0)
    yield
