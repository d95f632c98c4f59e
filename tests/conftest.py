"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

The environment is set before anything imports jax, which is how the tests
check multi-chip sharding without chips. Nothing here may import jax.
"""
import os

# store alias tripwire: fail loudly if any consumer mutates an object it
# received from a watch event / write return value without cloning first
os.environ.setdefault("KTPU_STORE_INTEGRITY", "1")

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running bench/e2e tests, excluded from tier-1 "
        "(-m 'not slow')")
