"""Test configuration: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding paths are exercised without TPU hardware (the bench and
``chip_smoke.py`` use the real chip; tests never should).  The environment
variable alone selects the backend; it is set before jax is imported."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-run soak tests excluded from the tier-1 gate "
        "(-m 'not slow')",
    )


@pytest.fixture(autouse=True)
def _clear_config():
    from gigapaxos_tpu.utils.config import Config

    yield
    Config.clear()
