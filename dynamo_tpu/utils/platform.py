"""Where a process runs and where its compiled programs are kept.

Three facts every process that compiles (``worker.main``, ``run.py``,
``bench.py``, the kernel phase of ``chip_smoke.py``) settles once at
start-up, before its first jax computation:

- **platform** — ``pin_platform``: the ``JAX_PLATFORMS`` environment
  variable decides; unset means ``tpu``. jax then refuses to start on
  anything else, so a failed TPU initialisation is an error, never a quiet
  CPU run. The CPU backend is asked for by name (``JAX_PLATFORMS=cpu`` —
  what the test suite does).
- **chip** — ``single_chip_env``: the environment that makes libtpu show a
  child process exactly one chip of a multi-chip host. Launchers
  (``chip_smoke.py``, ``planner/connectors.py``) put it on every worker
  they spawn; a process that has initialised a jax backend holds its chips
  and must not start such a child.
- **compile cache** — ``enable_compilation_cache``: jax's persistent cache
  at ``JAX_COMPILATION_CACHE_DIR`` when that is set (jax reads it itself;
  this module never assigns it), else at one fixed git-ignored path inside
  the checkout. The path is part of the cache key, so it never derives
  from a temporary name, a pid or a time. A process started for the CPU
  compiles uncached: its toy programs build in milliseconds, XLA:CPU
  reloads stored executables with a machine-type warning apiece, and the
  test suite must leave nothing in the checkout for the chip tool to copy
  (the suite sets the variable itself, to a directory under the temporary
  one: ``tests/conftest.py``).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

# <checkout>/.jax_cache — derived from the package location only
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def pin_platform() -> str:
    """Hold this process to the platform it was started for and return its
    name. Call before the first jax computation."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not platforms:
        import jax

        platforms = "tpu"
        jax.config.update("jax_platforms", platforms)
    return platforms


def single_chip_env(chip: int) -> Dict[str, str]:
    """Environment for a child process that must see chip ``chip`` of this
    host and no other: libtpu builds a 1x1x1 topology over the one visible
    chip, so ``jax.devices()`` in the child is that chip alone, and
    ``JAX_PLATFORMS=tpu`` makes the child die instead of serving from the
    CPU if the chip cannot be had."""
    return {
        "JAX_PLATFORMS": "tpu",
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def compilation_cache_dir() -> str:
    """Where this process's persistent compile cache lives."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILATION_CACHE_DIR)


def enable_compilation_cache(platform: str) -> Optional[str]:
    """Turn on jax's persistent compilation cache so a restarted process
    loads the step programs an earlier one compiled; returns the directory
    (None for a process started for the CPU, which compiles uncached).
    ``platform`` is what ``pin_platform`` returned. The two thresholds go
    to zero so every serving program is kept, including the small ones.
    Call before the first jax computation."""
    if platform.startswith("cpu"):
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return compilation_cache_dir()


def force_cpu_platform(n_devices: int) -> int:
    """Move this process onto an ``n_devices``-wide virtual CPU mesh even if
    a backend was already initialised (the multichip dry run in
    ``__graft_entry__`` is called from a process that may have compiled on
    the chip first). Returns the resulting device count."""
    import jax
    from jax.extend.backend import clear_backends

    clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    return len(jax.devices())


__all__ = ["DEFAULT_COMPILATION_CACHE_DIR", "pin_platform",
           "single_chip_env", "compilation_cache_dir",
           "enable_compilation_cache", "force_cpu_platform"]
