"""Test configuration.

- Forces JAX onto a virtual 8-device CPU mesh
  (``--xla_force_host_platform_device_count=8``), which exercises the same
  GSPMD partitioning paths XLA uses on a real TPU pod slice.
- Provides native ``async def`` test support (no pytest-asyncio in the image):
  coroutine tests run under ``asyncio.run`` with a default 60s timeout.
- Keeps a whole run inside its time limit: one persistent jax compile cache
  under the temporary directory for the run's workers and child processes,
  and the files handed to xdist's workers longest first (``LONGEST_FIRST``).
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

# One persistent compile cache for the whole run, shared by the xdist
# workers and by every child process a test spawns (jax reads the three
# variables itself). The suite builds the same toy programs hundreds of
# times over (every ``random_init`` engine is a new set of jit instances
# over the same HLO) and compiling them was most of its CPU time; the key
# is the HLO, the compile options and the compiler's version, so a hit is
# the program a compile would have built. It lives under the temporary
# directory, never in the checkout (the chip tool copies the checkout);
# a run that comes with the variable set keeps its own directory.
import tempfile

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        tempfile.gettempdir(), "dynamo_tpu_tests_jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import asyncio
import inspect

import pytest

ASYNC_TEST_TIMEOUT = float(os.environ.get("DYN_TEST_TIMEOUT", "60"))


# The files that take longest, longest first (seconds of a whole run on
# six workers, junit, PR 42: from 706 down to 40; the first seven again at
# PR 48: 1,411 / 687 / 588 / 519 / 313 / 311 / 263). ``--dist loadfile``
# hands a worker one file at a time and by default starts with the files
# that hold the most tests, which left the few long end-to-end files
# (17 tests in 706 s, 2 in 190 s) to the end of the run, each alone on
# its worker while the other five had nothing left. The order below is
# the order the files are handed out in; a file not named keeps its place
# behind them. Add a file here when it grows past the last one's time.
LONGEST_FIRST = (
    "benchmarks/test_benchmarks_e2e.py",
    "test_pallas_tpu_lowering.py",
    "test_dots3.py",
    "test_qwen3_next.py",
    "test_sdar.py",
    "test_deepseek.py",
    "test_packed_step.py",
    "test_mesh_sharded.py",
    "test_bench.py",
    "test_multistep.py",
    "test_pipeline_parallel.py",
    "test_spec_decode.py",
    "benchmarks/test_sdar_cell.py",
    "test_joyai.py",
    "test_disagg.py",
    "test_multihost_e2e.py",
    "test_model.py",
    "test_chip_smoke.py",
    "test_longcat.py",
    "test_olmo_hybrid.py",
    "test_moe_grouped.py",
    "benchmarks/test_benchmarks.py",
    "test_kv_write.py",
    "test_packed_attention_rows.py",
    "benchmarks/test_longcat_cell.py",
    "test_mixed_batch.py",
    "test_kvbm.py",
    "test_steptrace.py",
    "test_guided.py",
    "test_gemma.py",
    "test_routing.py",
    "test_moe.py",
    "test_echo_scoring.py",
    "benchmarks/test_joyai_cell.py",
    "test_sampling_topk.py",
    "test_sampling_extras.py",
    "test_drain.py",
    "test_fault_tolerance.py",
    "test_quant.py",
    "test_ring_serving.py",
    "test_engine.py",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "async_timeout(seconds): per-test override of the async timeout")
    # xdist, where it is loaded: hand the files out in the order collected
    # (``pytest_collection_modifyitems`` below), not by their test counts
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    here = os.path.dirname(os.path.abspath(__file__))
    rank = {os.path.join(here, *name.split("/")): i
            for i, name in enumerate(LONGEST_FIRST)}
    # stable: the tests of a file, and the files not named, keep their order
    items.sort(key=lambda item: rank.get(str(item.path), len(rank)))


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
