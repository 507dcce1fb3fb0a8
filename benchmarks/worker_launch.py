"""Start one worker for the benchmark: ``dynamo_tpu.worker.main`` unchanged,
plus the two things only the process that holds the chip can do.

    python3 benchmarks/worker_launch.py --ctl <dir> -- <worker.main arguments>

A daemon thread polls ``<dir>`` for requests from the benchmark's parent:

- ``device.req`` -> ``device.json``: the device as jax reports it and the
  peak bytes in use on the fullest local device.
- ``trace.req`` (``{"seconds": s}``) -> a ``jax.profiler`` trace of ``s``
  seconds written under ``<dir>/trace``, then ``trace.json`` with the
  wall-clock start and stop, which put the trace on the step ring's clock.

The same launcher runs with ``--trace 0`` and ``--trace 1``; the profiler is
armed only when the parent writes ``trace.req``, so the two runs differ by
tracing alone. The program has no profiler hook of its own (PERF.md lists
one for the tracing issue, after which this file can go).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _write(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _take(path: str):
    """The request at ``path``, removed, or None."""
    try:
        with open(path) as f:
            req = json.load(f)
        os.remove(path)
        return req
    except (OSError, ValueError):
        return None


def _device_report() -> dict:
    import jax

    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _trace(ctl: str, seconds: float) -> dict:
    import jax

    out = os.path.join(ctl, "trace")
    # no Python tracer: it records every call of the step loop's thread,
    # which slows the host it is there to observe
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    # the annotation is on the trace's own clock: the reduction cuts the
    # device events to it, and t0 puts it on the step ring's clock
    with jax.profiler.TraceAnnotation("bench_slice"):
        t0 = time.time()
        time.sleep(seconds)
        t1 = time.time()
    jax.profiler.stop_trace()
    return {"start_unix": t0, "stop_unix": t1, "dir": out,
            "stop_trace_s": time.time() - t1}


def _serve(ctl: str) -> None:
    while True:
        if _take(os.path.join(ctl, "device.req")) is not None:
            _write(os.path.join(ctl, "device.json"), _device_report())
        req = _take(os.path.join(ctl, "trace.req"))
        if req is not None:
            _write(os.path.join(ctl, "trace.json"),
                   _trace(ctl, float(req["seconds"])))
        time.sleep(0.05)


def main() -> None:
    argv = sys.argv[1:]
    if len(argv) < 3 or argv[0] != "--ctl" or argv[2] != "--":
        sys.exit("usage: worker_launch.py --ctl <dir> -- <worker args>")
    ctl = argv[1]
    os.makedirs(ctl, exist_ok=True)
    threading.Thread(target=_serve, args=(ctl,), daemon=True).start()
    sys.argv = ["dynamo_tpu.worker.main"] + argv[3:]
    from dynamo_tpu.worker.main import main as worker_main

    worker_main()


if __name__ == "__main__":
    main()
