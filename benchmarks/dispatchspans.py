#!/usr/bin/env python3
"""The host's side of a dispatch in a profiler trace, laid over the device's
idle time: what ``hostspans.py`` counts under ``loop.dispatch`` and
``loop.fetch``, taken apart.

    python3 benchmarks/dispatchspans.py <trace dir>   one JSON object on stdout

The step loop hands a dispatch (and the fetch of its result) to a worker
thread and annotates it twice under one name and ``seq``: on the loop's
thread, hand-over and resume included, and on the worker thread around the
call itself. Inside the call the engine marks its stages with
``dispatch.<stage>`` annotations carrying the same ``seq`` and ``kind``
(``dynamo_tpu/engine/steptrace.py stage``): ``assemble`` (plan -> host
arrays), ``upload`` (host arrays -> device arrays), ``enqueue`` (the jitted
call until it returns its futures), ``wait`` (a synchronous kind: until the
result is on the host). ``reduce`` cuts the device's idle gaps (as
``hostspans.idle_gaps`` finds them: 20 us and more, inside ``bench_slice``)
by the part of a dispatch that was open: ``handover`` (the loop's
annotation open, the worker's not yet), the four stages, ``other`` (inside
the call, under no stage), ``resume`` (the worker's closed, the loop's not
yet). The parts of one phase partition the union of its two annotations, so
their idle seconds add up to ``hostspans.reduce``'s for that phase.

``reduce`` works on plain data like ``hostspans.reduce`` - the same planes,
each host line with its ``loop.*`` events under ``"loop"`` and its
``dispatch.*`` events under ``"dispatch"``, both as ``[name, start_ns,
duration_ns, seq, kind]`` - so the tests check it on a recorded trace. A
profile without ``dispatch.*`` annotations (an older program) gives
``stages: {}``, and readers return nothing. This file runs in a child of the
benchmark (``JAX_PLATFORMS=cpu``): the parent never imports jax.

``share(run, which)`` is what the per-layer readers call: it runs that
child once per worker of a traced run, leaves the whole table in the run
directory as ``dispatch_phases.worker<i>.json`` (the later readers of the
same run find it there) and returns one of three shares of the slice.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import subprocess
import sys

import hostspans
import xplane

PREFIX = "dispatch."
PHASES = ("loop.dispatch", "loop.fetch")
# the parts of a threaded phase, in the order a dispatch passes them
PARTS = ("handover", "assemble", "upload", "enqueue", "wait", "resume",
         "other")
# which parts each share adds up, of which phases
SHARES = {
    "assemble": (("loop.dispatch",), ("assemble",)),
    "enqueue": (("loop.dispatch",), ("upload", "enqueue")),
    "handover": (PHASES, ("handover", "resume")),
}


def read_planes(trace_dir: str) -> list:
    """``hostspans.read_planes`` with, per line, the ``dispatch.*`` events
    once more with their ``seq`` and ``kind``."""
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
            events, marked = [], {"loop": [], "dispatch": []}
            for ev in line.events:
                name = ev.name
                events.append((name, int(ev.start_ns), int(ev.duration_ns)))
                key = ("loop" if name.startswith(hostspans.PREFIX) else
                       "dispatch" if name.startswith(PREFIX) else None)
                if key:
                    stats = dict(ev.stats)
                    marked[key].append(
                        (name, int(ev.start_ns), int(ev.duration_ns),
                         int(stats.get("seq", -1)),
                         str(stats.get("kind", ""))))
            entry = {"name": line.name, "events": events}
            entry.update({k: v for k, v in marked.items() if v})
            lines.append(entry)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _segments(phase: str, twins: list, stages: list) -> list:
    """One threaded phase of one dispatch as disjoint ``(start, end,
    phase, part, kind)`` pieces that cover the union of its annotations.
    ``twins``: its one or two ``(start, end, on_loop_thread, kind)``
    annotations; ``stages``: the ``(start, end, part)`` marked inside."""
    twins = sorted(twins)
    kind = twins[0][3]
    if len(twins) > 1:
        # the loop's annotation opens first and closes last
        outer, inner = twins[0], twins[1]
    elif twins[0][2]:
        # the worker's half lost (it cannot be: it lies inside): all other
        outer, inner = twins[0], None
    else:
        # the loop's half was in flight at an edge of the profile and is
        # lost whole: the call's own stages are still told apart
        outer, inner = None, twins[0]
    out = []

    def piece(s, e, part):
        if e > s:
            out.append((s, e, phase, part, kind))
    if inner is None:
        piece(outer[0], outer[1], "other")
        return out
    i0, i1 = inner[0], inner[1]
    if outer is not None:
        piece(outer[0], i0, "handover")
    at = i0
    for s, e, part in sorted(stages):
        # stages do not nest or overlap (``steptrace.stage``); one that
        # did would count once, under the one that opened first
        s, e = max(s, at), min(e, i1)
        if e <= s:
            continue
        piece(at, s, "other")
        piece(s, e, part)
        at = e
    piece(at, i1, "other")
    if outer is not None:
        piece(max(i1, outer[0]), outer[1], "resume")
    return out


def reduce(planes: list, slice_name: str = xplane.SLICE) -> dict:
    host_lines = [ln for p in planes for ln in p["lines"]
                  if ln.get("loop") or ln.get("dispatch")]
    marks = [ev for ln in host_lines for ev in ln.get("dispatch", ())]
    loop = [ev for ln in host_lines for ev in ln.get("loop", ())]
    if not marks:
        return {"stages": {}}
    window = xplane.find_slice(planes, slice_name)
    if window is None:
        window = (min(s for _n, s, *_r in loop + marks),
                  max(s + d for _n, s, d, *_r in loop + marks))
    w0, w1 = window
    # the twins of every threaded phase, by (phase, seq); the loop's thread
    # is the one that also carries the phases it runs itself
    twins: dict = {}
    for ln in host_lines:
        on_loop = any(n not in PHASES for n, *_r in ln.get("loop", ()))
        for name, s, d, seq, kind in ln.get("loop", ()):
            if name in PHASES:
                twins.setdefault((name, seq), []).append(
                    (s, s + d, on_loop, kind))
    stages: dict = {}
    opened: dict = {}
    for name, s, d, seq, _kind in marks:
        part = name[len(PREFIX):]
        stages.setdefault(seq, []).append((s, s + d, part))
        cut = min(s + d, w1) - max(s, w0)
        if cut > 0:
            acc = opened.setdefault(part, [0, 0])
            acc[0] += 1
            acc[1] += cut
    segments = []
    enqueues = {"0": 0, "1": 0, "more": 0}
    for (phase, seq), tw in twins.items():
        inside = stages.get(seq, ()) if phase == "loop.dispatch" else ()
        segments += _segments(phase, tw, inside)
        # a dispatch (not an exclusive window's gather) wholly inside the
        # slice enqueues exactly one program
        if (phase == "loop.dispatch" and tw[0][3] != "gather"
                and min(t[0] for t in tw) >= w0
                and max(t[1] for t in tw) <= w1):
            n = sum(1 for _s, _e, part in inside if part == "enqueue")
            enqueues["more" if n > 1 else str(n)] += 1
    segments.sort()
    starts = [s for s, *_r in segments]
    n_dev = max(1, len(xplane.device_planes(planes)))
    # phase -> dispatch kind -> part -> idle ns
    tables: dict = {phase: {} for phase in PHASES}
    overlap_ns = idle_ns = 0
    for g0, g1 in hostspans.idle_gaps(planes, window):
        idle_ns += g1 - g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        covered_to = g0
        for s, e, phase, part, kind in segments[i:]:
            if s >= g1:
                break
            s, e = max(s, g0), min(e, g1)
            if e <= s:
                continue
            # two dispatches' annotations are meant never to overlap
            overlap_ns += max(0, min(e, covered_to) - s)
            covered_to = max(covered_to, e)
            by = tables[phase].setdefault(kind, {})
            by[part] = by.get(part, 0) + e - s

    def seconds(kinds: list) -> dict:
        ns = {part: sum(by.get(part, 0) for by in kinds) for part in PARTS}
        return {part: v / 1e9 / n_dev for part, v in ns.items() if v}
    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": n_dev,
        # seconds per device
        "idle_s": idle_ns / 1e9 / n_dev,
        # per threaded phase: the device's idle seconds under each part of
        # it, once in all and once per dispatch kind
        "phases": {phase: {
            "idle_s": sum(seconds(list(kinds.values())).values()),
            "idle_by_part": seconds(list(kinds.values())),
            "idle_by_kind": {kind: seconds([by]) for kind, by
                             in sorted(kinds.items())},
        } for phase, kinds in tables.items()},
        # idle time counted under two dispatches at once: meant to be 0
        "overlap_s": overlap_ns / 1e9 / n_dev,
        # stage -> [annotations, seconds open] inside the window
        "stages": {part: [n, ns / 1e9] for part, (n, ns)
                   in sorted(opened.items())},
        # dispatches wholly inside the window by their ``dispatch.enqueue``
        # annotations: all under "1"
        "enqueues": enqueues,
        # ring numbers of the dispatches whose stages reach into the window
        "seqs": sorted({seq for _n, s, d, seq, _k in marks
                        if seq >= 0 and s + d > w0 and s < w1}),
    }


def share_of(red: dict, which: str):
    """One of ``SHARES`` (%) of the window. None where the trace has no
    ``dispatch.*`` annotation at all."""
    if not red.get("stages") or not red.get("window_s"):
        return None
    phases, parts = SHARES[which]
    idle = sum(red["phases"][ph]["idle_by_part"].get(part, 0.0)
               for ph in phases for part in parts)
    return 100.0 * idle / red["window_s"]


def share(run, which: str):
    """``share_of`` a traced run, averaged over its workers."""
    shares = []
    for i, trace in enumerate(run.device_traces):
        path = os.path.join(run.run_dir, f"dispatch_phases.worker{i}.json")
        if os.path.exists(path):
            with open(path) as f:
                red = json.load(f)
        else:
            out = subprocess.run(
                [sys.executable, __file__, trace["mark"]["dir"]],
                capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
            if out.returncode != 0:
                continue
            red = json.loads(out.stdout.strip().splitlines()[-1])
            with open(path, "w") as f:
                json.dump(red, f)
        value = share_of(red, which)
        if value is not None:
            shares.append(value)
    return sum(shares) / len(shares) if shares else None


if __name__ == "__main__":
    print(json.dumps(reduce(read_planes(sys.argv[1]))))
