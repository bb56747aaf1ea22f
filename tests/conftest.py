"""Test env: force a virtual 8-device CPU mesh BEFORE jax backend init.

Mirrors the reference's fake_cpu_device.h pattern (SURVEY §4): distributed/
sharding tests run against virtual devices, no TPU pod needed. The env vars
cover worker subprocesses (DataLoader workers, launch tests) that start fresh
interpreters; `jax.config.update` covers this process.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the slow tier holds multi-process
    # fault/elastic tests whose wall clock exceeds ~10s standalone
    config.addinivalue_line(
        "markers", "slow: long multi-process tests excluded from tier-1")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables between test modules: a full-suite process
    otherwise accumulates every jitted step (the hybrid-engine ones are
    large) and the XLA CPU compiler can abort under the memory pressure."""
    yield
    jax.clear_caches()
