"""Test configuration.

- Forces JAX onto a virtual 8-device CPU mesh
  (``--xla_force_host_platform_device_count=8``), which exercises the same
  GSPMD partitioning paths XLA uses on a real TPU pod slice.
- Provides native ``async def`` test support (no pytest-asyncio in the image):
  coroutine tests run under ``asyncio.run`` with a default 60s timeout.
"""

import os

# The suite and every child process it spawns run on CPU jax: the
# environment asks for it by name, which is what the program honours
# (dynamo_tpu/utils/platform.pin_platform).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio
import inspect

import pytest

ASYNC_TEST_TIMEOUT = float(os.environ.get("DYN_TEST_TIMEOUT", "60"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "async_timeout(seconds): per-test override of the async timeout")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        timeout = ASYNC_TEST_TIMEOUT
        marker = pyfuncitem.get_closest_marker("async_timeout")
        if marker is not None and marker.args:
            timeout = max(timeout, float(marker.args[0]))

        async def _run():
            await asyncio.wait_for(fn(**kwargs), timeout=timeout)

        asyncio.run(_run())
        return True
    return None
