#!/usr/bin/env python3
"""The step loop's phases in a profiler trace, laid over the device's idle
time: both are in the same ``*.xplane.pb``, on the same clock.

    python3 benchmarks/hostspans.py <trace dir>     one JSON object on stdout

The program's step loop opens a ``jax.profiler.TraceAnnotation`` named
``loop.<phase>`` (``plan``, ``dispatch``, ``fetch``, ``process``, ``idle``,
``blocked``) around every phase of every dispatch, with the dispatch's ring
number as ``seq`` (``dynamo_tpu/engine/steptrace.py``). ``reduce`` cuts the
device's idle gaps (as ``xplane.py`` finds them: holes of 20 us and more
between operations, inside the ``bench_slice`` window) by the annotation
that was open, exactly, with no wall clock in between. A program without
the annotations (an older one) gives ``phases: {}``, and readers return
nothing.

``reduce`` works on plain data like ``xplane.reduce`` - the same planes,
each host line with the ``loop.*`` events once more under ``"loop"`` as
``[name, start_ns, duration_ns, seq, kind]`` - so the tests check it on a
recorded trace; ``read_planes`` is ``xplane.read_planes`` plus those. This
file runs in a child of the benchmark (``JAX_PLATFORMS=cpu``): the parent
never imports jax.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys

import xplane

PREFIX = "loop."
NONE = "no annotation open"
EDGE = "slice edge (annotation in flight lost)"
# the phases in which the host, and not the device or a client, is what the
# device waits for
HOST_PHASES = ("loop.plan", "loop.process", "loop.dispatch")
LONG_GAP_NS = 1_000_000


def read_planes(trace_dir: str) -> list:
    """``xplane.read_planes`` with, per line, the ``loop.*`` events once
    more with their ``seq`` and ``kind``."""
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
            events, loop = [], []
            for ev in line.events:
                name = ev.name
                events.append((name, int(ev.start_ns), int(ev.duration_ns)))
                if name.startswith(PREFIX):
                    stats = dict(ev.stats)
                    loop.append((name, int(ev.start_ns), int(ev.duration_ns),
                                 int(stats.get("seq", -1)),
                                 str(stats.get("kind", ""))))
            entry = {"name": line.name, "events": events}
            if loop:
                entry["loop"] = loop
            lines.append(entry)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def idle_gaps(planes: list, window: tuple) -> list:
    """``(start_ns, end_ns)`` of every hole of ``MIN_GAP_NS`` and more
    between the operations of each device, inside the window."""
    w0, w1 = window
    gaps = []
    for plane in xplane.device_planes(planes):
        for line in plane["lines"]:
            if line["name"] != xplane.OPS_LINE:
                continue
            cut = [(max(s, w0), min(s + d, w1))
                   for _n, s, d in line["events"]]
            merged = xplane.merge([c for c in cut if c[1] > c[0]])
            edges = [w0] + [x for se in merged for x in se] + [w1]
            for i in range(0, len(edges), 2):
                if edges[i + 1] - edges[i] >= xplane.MIN_GAP_NS:
                    gaps.append((edges[i], edges[i + 1]))
    return gaps


def reduce(planes: list, slice_name: str = xplane.SLICE) -> dict:
    loop = [ev for p in planes for ln in p["lines"]
            for ev in ln.get("loop", ())]
    window = xplane.find_slice(planes, slice_name)
    if window is None:
        if not loop:
            return {"phases": {}}
        window = (min(s for _n, s, *_r in loop),
                  max(s + d for _n, s, d, *_r in loop))
    w0, w1 = window
    # a threaded phase is annotated twice under one name, on the loop's
    # thread (hand-over included) and on the worker's: one phase, so the
    # intervals of a name are merged before anything is counted
    events: dict = {}
    for name, s, d, _seq, _kind in loop:
        events.setdefault(name, []).append((max(s, w0), min(s + d, w1)))
    spans = {name: xplane.merge([c for c in cuts if c[1] > c[0]])
             for name, cuts in events.items()}
    starts = {name: [s for s, _e in iv] for name, iv in spans.items()}
    # an annotation in flight when the profile starts or stops is lost
    # whole: idle time outside the annotations the trace does hold is an
    # artefact of the slice's edges, not host time nobody owns
    first = min((iv[0][0] for iv in spans.values() if iv), default=w1)
    last = max((iv[-1][1] for iv in spans.values() if iv), default=w0)
    n_dev = max(1, len(xplane.device_planes(planes)))
    idle: dict = {}
    overlap_ns = idle_ns = 0
    long_gaps = []
    for g0, g1 in idle_gaps(planes, window):
        idle_ns += g1 - g0
        split, clips = {}, []
        for name, iv in spans.items():
            i = max(0, bisect.bisect_right(starts[name], g0) - 1)
            for s, e in iv[i:]:
                if s >= g1:
                    break
                if e > g0:
                    clips.append((max(s, g0), min(e, g1)))
                    split[name] = split.get(name, 0) + clips[-1][1] \
                        - clips[-1][0]
        merged = xplane.merge(clips)
        covered = sum(e - s for s, e in merged)
        overlap_ns += sum(split.values()) - covered
        if spans:
            edge = max(0, min(g1, first) - g0) + max(0, g1 - max(g0, last))
            split[EDGE] = min(edge, (g1 - g0) - covered)
        split[NONE] = (g1 - g0) - covered - split.get(EDGE, 0)
        for name, ns in split.items():
            if ns:
                idle[name] = idle.get(name, 0) + ns
        if g1 - g0 >= LONG_GAP_NS:
            long_gaps.append([(g0 - w0) / 1e9, (g1 - g0) / 1e9,
                              {k: v / 1e9 for k, v in split.items() if v}])
    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": n_dev,
        # seconds per device
        "idle_s": idle_ns / 1e9 / n_dev,
        "idle_by_phase": {k: v / 1e9 / n_dev for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        # idle time counted under two annotations of different names at
        # once: they are meant not to overlap, so this is meant to be 0
        "overlap_s": overlap_ns / 1e9 / n_dev,
        # name -> [annotations, seconds open] inside the window
        "phases": {name: [len(events[name]), sum(e - s for s, e in iv) / 1e9]
                   for name, iv in sorted(spans.items()) if iv},
        # (seconds from the window's start, seconds long, its seconds by
        # what was open)
        "long_gaps": sorted(long_gaps, key=lambda g: -g[1])[:200],
        # ring numbers of the dispatches whose phases reach into the window
        "seqs": sorted({seq for _n, s, d, seq, _k in loop
                        if seq >= 0 and s + d > w0 and s < w1}),
    }


def host_share(red: dict):
    """Share (%) of the window in which the device idled behind the host:
    idle time under a ``plan``, ``process`` or ``dispatch`` annotation.
    None where the trace has no ``loop.*`` annotation at all."""
    if not red.get("phases") or not red.get("window_s"):
        return None
    behind = sum(red["idle_by_phase"].get(p, 0.0) for p in HOST_PHASES)
    return 100.0 * behind / red["window_s"]


if __name__ == "__main__":
    print(json.dumps(reduce(read_planes(sys.argv[1]))))
