"""ManagedProcess: spawn framework processes for e2e tests.

Parity: reference ``tests/utils/managed_process.py:69-258`` — spawn a real
CLI process, wait for a readiness condition (log line or open TCP port),
capture output for debugging, and guarantee teardown. Child processes run
on CPU jax, asked for by name in their environment (see conftest).

Output capture runs on ONE dedicated pump thread per process (started with
the process, exits on EOF/close): readiness waits and ``drain_until`` just
poll the captured ``lines``, so no reader is ever abandoned mid-``readline``
with the pipe contended between threads.
"""

from __future__ import annotations

import asyncio
import os
import socket
import subprocess
import sys
import threading
import time
from typing import List, Optional


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cpu_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONUNBUFFERED", "1")
    return env


class ManagedProcess:
    def __init__(self, args: List[str], name: str = "proc",
                 ready_line: Optional[str] = None,
                 ready_port: Optional[int] = None,
                 timeout: float = 60.0,
                 env_overrides: Optional[dict] = None):
        self.args = [sys.executable, "-m"] + args
        self.name = name
        self.ready_line = ready_line
        self.ready_port = ready_port
        self.timeout = timeout
        self.env_overrides = env_overrides or {}
        self.proc: Optional[subprocess.Popen] = None
        self.lines: List[str] = []
        self._pump: Optional[threading.Thread] = None

    def _pump_output(self) -> None:
        try:
            for line in self.proc.stdout:
                self.lines.append(line)  # list.append is GIL-atomic
        except ValueError:
            pass  # stdout closed during stop()

    def _has_line(self, needle: str, start: int = 0) -> bool:
        # len() first: the pump appends concurrently, and a slice is a
        # consistent snapshot under the GIL
        return any(needle in ln for ln in self.lines[start:len(self.lines)])

    async def start(self) -> "ManagedProcess":
        env = cpu_env()
        env.update(self.env_overrides)
        self.proc = subprocess.Popen(
            self.args, cwd="/root/repo", env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        self._pump = threading.Thread(target=self._pump_output, daemon=True,
                                      name=f"pump-{self.name}")
        self._pump.start()
        deadline = time.monotonic() + self.timeout
        while time.monotonic() < deadline:
            if self.ready_line is not None and self._has_line(self.ready_line):
                return self
            if self.proc.poll() is not None:
                self._pump.join(timeout=2.0)  # collect the last lines
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode}:\n"
                    + "".join(self.lines))
            if self.ready_line is None:
                if self.ready_port is not None:
                    try:
                        with socket.create_connection(
                                ("127.0.0.1", self.ready_port), timeout=0.25):
                            return self
                    except OSError:
                        pass
                else:
                    return self
            await asyncio.sleep(0.05)
        raise TimeoutError(f"{self.name} not ready in {self.timeout}s:\n"
                           + "".join(self.lines))

    async def drain_until(self, needle: str, timeout: float = 10.0) -> bool:
        """Wait until a captured output line contains ``needle`` (True) or
        the timeout passes (False)."""
        deadline = time.monotonic() + timeout
        while True:
            if self._has_line(needle):
                return True
            if time.monotonic() >= deadline or (
                    self.proc.poll() is not None
                    and not self._pump.is_alive()):
                return self._has_line(needle)
            await asyncio.sleep(0.1)

    def kill(self, sig: int = 9) -> None:
        if self.proc is not None and self.proc.poll() is None:
            if sig == 9:
                self.proc.kill()
            else:
                self.proc.send_signal(sig)

    async def stop(self) -> None:
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda: self.proc.wait(timeout=10))
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self._pump is not None:
            self._pump.join(timeout=2.0)  # EOF after child exit ends the pump
        if self.proc.stdout is not None:
            self.proc.stdout.close()

    async def __aenter__(self) -> "ManagedProcess":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()


__all__ = ["ManagedProcess", "free_port", "cpu_env"]
