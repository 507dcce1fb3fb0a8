"""chip_smoke.py's own logic on the CPU, and the start-up rules it relies
on (``dynamo_tpu/utils/platform.py``): where the compile cache lives and
which platform a process is held to.

The smoke proper runs on the chip through the chip tool; here the explicit
``--cpu-dry-run`` drives every phase of it at toy size, and the plain
command must refuse a machine with no TPU within seconds.
"""

import glob
import json
import os
import subprocess
import sys
import time

import pytest

from dynamo_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
HAS_TPU = os.path.exists("/dev/vfio") or bool(glob.glob("/dev/accel*"))


def test_cpu_dry_run_passes_and_says_where_it_ran():
    r = subprocess.run([sys.executable, SMOKE, "--cpu-dry-run"], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    # the worker's own report, echoed by the smoke
    assert "worker0: platform=cpu" in r.stdout
    assert "attn_impl=scan" in r.stdout
    for kernel in ("decode", "prefill", "ragged", "mla_decode",
                   "mla_prefill", "mla_ragged"):
        assert f"kernel {kernel} " in r.stdout, r.stdout
    # the compiled step programs were read for copies of the page pool
    for program in ("decode", "fused", "mixed"):
        assert f"program {program} " in r.stdout, r.stdout
    assert r.stdout.count("no pool-sized copy") == 3
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": last["device"]["count"]}}


@pytest.mark.skipif(HAS_TPU, reason="this machine has a TPU: the plain "
                    "command would run the whole smoke")
def test_without_the_flag_no_tpu_is_a_failure_within_seconds():
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, SMOKE], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "FAILED" in r.stdout
    # no result line, and nothing was served
    assert '"ok"' not in r.stdout
    assert "jax worker serving" not in r.stdout


class TestCompileCachePlacement:
    def test_the_variable_wins_and_is_never_assigned(self, monkeypatch,
                                                     tmp_path):
        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert platform.enable_compilation_cache("tpu") == str(tmp_path)
        # jax reads the variable itself: the program set no directory in
        # code and left the variable as it found it
        assert jax.config.jax_compilation_cache_dir == before
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)

    def test_default_is_one_fixed_path_inside_the_checkout(self,
                                                           monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = platform.enable_compilation_cache("tpu")
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        # git would not commit it
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored

    def test_a_cpu_process_compiles_uncached(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert platform.enable_compilation_cache("cpu") is None
        assert jax.config.jax_compilation_cache_dir == before


class TestPlatformPin:
    def test_the_environment_decides(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert platform.pin_platform() == "cpu"

    def test_unset_means_tpu_and_jax_then_refuses_the_cpu(self):
        """A worker started with no JAX_PLATFORMS on a machine without a
        TPU must die, not serve from the CPU."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        r = subprocess.run(
            [sys.executable, "-c",
             "from dynamo_tpu.utils.platform import pin_platform\n"
             "print(pin_platform())\n"
             "import jax\n"
             "print(jax.devices())\n"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        assert r.stdout.splitlines()[0] == "tpu"
        if not HAS_TPU:
            assert r.returncode != 0
            assert "Unable to initialize backend 'tpu'" in r.stderr

    def test_single_chip_env_shows_one_chip(self):
        env = platform.single_chip_env(2)
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["JAX_PLATFORMS"] == "tpu"
        # without the two bounds libtpu refuses a second process on the
        # host (measured on the four-chip machine, CHANGES.md PR 21)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
