#!/usr/bin/env python3
"""Reduce a profiler trace (``*.xplane.pb``) to what the benchmark reports.

    python3 benchmarks/xplane.py <trace dir>      one JSON object on stdout

``reduce(planes, slice_name)`` works on plain data - ``[{"name", "lines":
[{"name", "events": [(name, start_ns, duration_ns)]}]}]`` - so the tests
check it on a small recorded trace without the profiler; ``read_planes``
turns an ``.xplane.pb`` into that with nothing but jax's own reader. This
file runs in a child of the benchmark (``JAX_PLATFORMS=cpu``): the parent
never imports jax.

The traced window is the ``bench_slice`` annotation the worker's launcher
wrote around its sleep, which is on the same clock as the device events.
Device busy time is the union of the intervals in which an operation ran
on the device (the device plane's ``XLA Ops`` line), cut to that window and
averaged over the device planes; idle is the rest.
"""

from __future__ import annotations

import glob
import json
import os
import sys

SLICE = "bench_slice"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000      # shorter holes between kernels are not host stalls


def read_planes(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


CONTAINERS = (" while(", " conditional(", " call(")


def is_container(op: str) -> bool:
    """A loop, branch or call: its event spans its body's operations, which
    are on the same line, so it counts for busy time and not as an
    operation of its own."""
    return any(c in op for c in CONTAINERS)


def is_mosaic(op: str) -> bool:
    """A Pallas kernel: a custom call into Mosaic."""
    return "tpu_custom_call" in op


def short(op: str) -> str:
    """``%name kind shape`` of an HLO instruction's text, which is what the
    trace names an operation with."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:96]
    shape, _, tail = rest.partition(" ")
    kind = tail.split("(", 1)[0]
    return f"{head} {kind} {shape}"[:96]


def merge(intervals: list) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_slice(planes: list, slice_name: str = SLICE):
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == slice_name:
                    return start, start + dur
    return None


def device_planes(planes: list) -> list:
    devs = [p for p in planes if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if devs:
        return devs
    # the CPU backend (the harness's own tests) has no device plane: its
    # XLA executor threads stand in, as one device
    for p in planes:
        events = [ev for ln in p["lines"] if ln["name"].startswith("tf_XLA")
                  for ev in ln["events"]]
        if events:
            return [{"name": "/host:CPU as device", "lines": [
                {"name": OPS_LINE, "events": events}]}]
    return []


def reduce(planes: list, slice_name: str = SLICE) -> dict:
    devs = device_planes(planes)
    window = find_slice(planes, slice_name)
    if not devs and window is not None:
        # the profiler writes no device plane for a slice in which no
        # program started on the device: an idle slice, busy for 0 s
        return {"devices": 0, "window_s": (window[1] - window[0]) / 1e9,
                "busy_s": 0.0, "ops": [], "modules": [], "gaps": [],
                "lines": []}
    if not devs:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line: " + ", ".join(
                             p["name"] for p in planes))
    if window is None:
        # no annotation (a trace not taken by the launcher): the span of
        # the device events
        starts = [s for p in devs for ln in p["lines"]
                  for _n, s, _d in ln["events"]]
        ends = [s + d for p in devs for ln in p["lines"]
                for _n, s, d in ln["events"]]
        window = (min(starts), max(ends))
    w0, w1 = window
    busy_ns = 0
    ops: dict = {}
    modules: dict = {}
    gaps = []
    for k, plane in enumerate(devs):
        for line in plane["lines"]:
            if line["name"] not in (OPS_LINE, MODULES_LINE):
                continue
            table = ops if line["name"] == OPS_LINE else modules
            cut = []
            for name, start, dur in line["events"]:
                s, e = max(start, w0), min(start + dur, w1)
                if e <= s:
                    continue
                cut.append((s, e))
                if is_container(name):
                    continue
                key = short(name) + (" [mosaic]" if is_mosaic(name) else "")
                acc = table.setdefault(key, [0, 0])
                acc[0] += e - s
                acc[1] += 1
            if line["name"] != OPS_LINE:
                continue
            merged = merge(cut)
            busy_ns += sum(e - s for s, e in merged)
            edges = [w0] + [x for se in merged for x in se] + [w1]
            for i in range(0, len(edges), 2):
                if edges[i + 1] - edges[i] >= MIN_GAP_NS:
                    gaps.append((k, edges[i] - w0, edges[i + 1] - edges[i]))
    n = len(devs)
    gaps.sort(key=lambda g: -g[2])
    return {
        "devices": n,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        # seconds per device, most expensive first
        "ops": sorted(([name, t / 1e9 / n, c] for name, (t, c)
                       in ops.items()), key=lambda x: -x[1])[:60],
        "modules": sorted(([name, t / 1e9 / n, c] for name, (t, c)
                           in modules.items()), key=lambda x: -x[1])[:40],
        # (device, seconds from the window's start, seconds long)
        "gaps": [[k, s / 1e9, d / 1e9] for k, s, d in gaps[:3000]],
        # what the trace holds, for a reader: plane, line, events, and the
        # span of the line relative to the window's start, in seconds
        "lines": [[p["name"], ln["name"], len(ln["events"]),
                   (min(s for _n, s, _d in ln["events"]) - w0) / 1e9,
                   (max(s + d for _n, s, d in ln["events"]) - w0) / 1e9]
                  for p in planes for ln in p["lines"] if ln["events"]],
    }


if __name__ == "__main__":
    print(json.dumps(reduce(read_planes(sys.argv[1]))))
