"""The benchmark's tests run on the CPU, with eight host devices unless
``XLA_FLAGS`` says otherwise.  A test that needs several devices says so
with ``@pytest.mark.devices(n)`` and is skipped where fewer are there."""

import os
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

HERE = Path(__file__).resolve().parent


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "devices(n): test needs at least n JAX devices")


def pytest_collection_modifyitems(config, items):
    have = len(jax.devices())
    for item in items:
        mark = item.get_closest_marker("devices")
        if (mark and mark.args[0] > have
                and HERE in Path(str(item.fspath)).resolve().parents):
            item.add_marker(pytest.mark.skip(
                reason=f"needs {mark.args[0]} devices, have {have}"))
