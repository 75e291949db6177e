"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors the reference's approach to distributed testing without a cluster
(SURVEY.md §4: dmlc_local.py multi-process on one machine) — here a single
process with 8 XLA host devices exercises every sharding/collective path.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Unit tests run on the 8-device virtual CPU mesh; the env var alone selects
# the platform.
os.environ["JAX_PLATFORMS"] = "cpu"
# Tier-1 neither reads nor writes a persistent compile cache — not the
# checkout's .jax_cache, not one placed from outside — so compile-count
# assertions cannot depend on an earlier run (utils/compile.py resolver).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ["MXNET_TPU_COMPILE_CACHE"] = "0"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tier "
        "(reference: tests/python/train + multi-node)")


@pytest.fixture(autouse=True)
def _seed():
    """Deterministic tests: reseed numpy and the framework PRNG per test."""
    np.random.seed(0)
    import mxnet_tpu as mx

    mx.random.seed(0)
    yield
