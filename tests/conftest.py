"""Test config: force a virtual 8-device CPU platform BEFORE any backend
initializes. Tests never take the chip; multi-chip sharding is tested on
the virtual CPU mesh, and chip_smoke.py is what runs on the chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

assert all(d.platform == "cpu" for d in jax.devices()), (
    "a backend initialized before conftest could force CPU"
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (big allocations / long runs) excluded from the "
        "tier-1 `-m 'not slow'` pass")
