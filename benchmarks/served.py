"""The served path as users start it: one coordinator, the layout's workers,
one frontend, each a process of its own. This module never imports jax, so
the process that runs it never holds a chip.

The process handling follows ``chip_smoke.py`` (children in their own
process groups, logs per child, ready line plus ``/health``); it is a copy,
not an import, so the yardstick does not move when that script does.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class Failed(Exception):
    """The run cannot produce a result; the message says why."""


def free_ports(n: int) -> list:
    """``n`` distinct free ports: all bound at once, so the kernel cannot
    hand the same one out twice."""
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def get_json(url: str, timeout: float = 10.0):
    """Parsed body, or None while the server does not answer 200."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())
    except (urllib.error.URLError, OSError, ValueError):
        return None


def log_tail(path: str, limit: int = 2500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - limit))
            return f.read().decode("utf-8", "replace")
    except OSError as e:
        return f"<no log: {e}>"


def wait_for(what: str, ok, procs: list, timeout: float,
             interval: float = 0.2):
    """Poll ``ok()`` until truthy; fail if a process it depends on exits
    first or the time runs out."""
    deadline = time.monotonic() + timeout
    while True:
        got = ok()
        if got:
            return got
        for p in procs:
            if p.poll() is not None:
                raise Failed(f"{p.name} exited rc={p.returncode} while "
                             f"waiting for {what}:\n{log_tail(p.log_path)}")
        if time.monotonic() > deadline:
            raise Failed(f"timed out after {timeout:.0f}s waiting for {what}\n"
                         + "\n".join(f"--- {p.name}\n{log_tail(p.log_path)}"
                                     for p in procs))
        time.sleep(interval)


class Stack:
    """Coordinator + workers + frontend for one run, under ``run_dir``."""

    def __init__(self, run_dir: str, layout: dict, model_dir: str,
                 model_name: str, worker_args: list, platform: str,
                 cache_dir: str, traced: bool, worker_env: dict):
        self.run_dir = run_dir
        self.layout = layout
        self.model_dir, self.model_name = model_dir, model_name
        self.worker_args = worker_args
        self.platform = platform
        self.traced = traced
        self.worker_env = worker_env
        self.procs: list = []
        self.workers: list = []
        self.base_url = ""
        self.env = dict(os.environ, JAX_PLATFORMS=platform,
                        PYTHONUNBUFFERED="1", PYTHONPATH=REPO,
                        JAX_COMPILATION_CACHE_DIR=cache_dir)
        self.env.pop("BENCH_RUN", None)

    def spawn(self, name: str, argv: list, env: dict):
        log_path = os.path.join(self.run_dir, f"{name}.log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=log,
                                    stderr=subprocess.STDOUT,
                                    start_new_session=True)
        proc.name, proc.log_path = name, log_path
        self.procs.append(proc)
        return proc

    def start(self) -> None:
        coord_port, http_port, *system_ports = free_ports(
            2 + len(self.layout["workers"]))
        coord_addr = f"127.0.0.1:{coord_port}"
        coord = self.spawn("coordinator", [
            sys.executable, "-m", "dynamo_tpu.runtime.coordinator",
            "--host", "127.0.0.1", "--port", str(coord_port)], self.env)

        def port_open():
            try:
                socket.create_connection(("127.0.0.1", coord_port),
                                         timeout=0.25).close()
                return True
            except OSError:
                return False
        wait_for("the coordinator's port", port_open, [coord], 30)

        for i, spec in enumerate(self.layout["workers"]):
            env = dict(self.env, DYN_SYSTEM_ENABLED="1",
                       DYN_SYSTEM_PORT=str(system_ports[i]),
                       # the ring holds a window of dispatches between polls
                       DYN_STEPTRACE_RING="16384", **self.worker_env)
            if self.platform == "tpu":
                env.update(spec.get("env", {}))
            ctl = os.path.join(self.run_dir, f"worker{i}.ctl")
            if self.traced:
                env["DYN_TRACE_EXPORT"] = os.path.join(
                    self.run_dir, f"worker{i}.traces.jsonl")
            w = self.spawn(f"worker{i}", [
                sys.executable, os.path.join(HERE, "worker_launch.py"),
                "--ctl", ctl, "--",
                "--coordinator", coord_addr, "--model-path", self.model_dir,
                "--model-name", self.model_name, "--random-weights"]
                + self.worker_args + spec.get("args", []), env)
            w.ctl = ctl
            w.system_url = f"http://127.0.0.1:{env['DYN_SYSTEM_PORT']}"
            self.workers.append(w)
        for w in self.workers:
            w.health = wait_for(
                f"{w.name} to serve",
                lambda w=w: (_ready_line(w.log_path)
                             and get_json(w.system_url + "/health")),
                [w, coord], 1100)
            if w.health.get("platform") != self.platform:
                raise Failed(f"{w.name} serves on {w.health.get('platform')!r}"
                             f", not {self.platform!r}")

        env = dict(self.env)
        if self.traced:
            env["DYN_TRACE_EXPORT"] = os.path.join(
                self.run_dir, "frontend.traces.jsonl")
        frontend = self.spawn("frontend", [
            sys.executable, "-m", "dynamo_tpu.frontend.main",
            "--coordinator", coord_addr, "--http-host", "127.0.0.1",
            "--http-port", str(http_port)]
            + self.layout.get("frontend_args", []), env)
        self.base_url = f"http://127.0.0.1:{http_port}"

        def listed():
            body = get_json(self.base_url + "/v1/models")
            return body and any(m["id"] == self.model_name
                                for m in body.get("data", []))
        wait_for("the frontend to list the model", listed,
                 [frontend, coord] + self.workers, 120)

    def request(self, w, what: str, req: dict) -> None:
        """Hand one request to the launcher thread in worker ``w``."""
        answer = os.path.join(w.ctl, f"{what}.json")
        if os.path.exists(answer):
            os.remove(answer)
        tmp = os.path.join(w.ctl, f"{what}.req.tmp")
        with open(tmp, "w") as f:
            json.dump(req, f)
        os.replace(tmp, os.path.join(w.ctl, f"{what}.req"))

    def answer(self, w, what: str, timeout: float) -> dict:
        answer = os.path.join(w.ctl, f"{what}.json")

        def got():
            try:
                with open(answer) as f:
                    return json.load(f)
            except (OSError, ValueError):
                return None
        return wait_for(f"{w.name} to answer {what}", got, [w], timeout,
                        interval=0.05)

    def ask_worker(self, w, what: str, req: dict, timeout: float) -> dict:
        self.request(w, what, req)
        return self.answer(w, what, timeout)

    def check_alive(self) -> None:
        for p in self.procs:
            if p.poll() is not None:
                raise Failed(f"{p.name} exited rc={p.returncode} during the "
                             f"run:\n{log_tail(p.log_path)}")

    def stop(self) -> None:
        """Newest first, so a worker's drain still finds its coordinator;
        SIGTERM, then SIGKILL for what is left of the process group; every
        child is waited for."""
        for p in reversed(self.procs):
            for sig, grace in ((signal.SIGTERM, 8.0), (signal.SIGKILL, 5.0)):
                try:
                    os.killpg(p.pid, sig)
                    p.wait(timeout=grace)
                except (ProcessLookupError, subprocess.TimeoutExpired):
                    pass
        self.procs = []


def _ready_line(log_path: str) -> str:
    try:
        with open(log_path, errors="replace") as f:
            for line in f:
                if line.startswith("jax worker serving"):
                    return line.strip()
    except OSError:
        pass
    return ""
