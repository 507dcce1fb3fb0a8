#!/usr/bin/env python3
"""The device's time in a profiler trace by the program's own stages: the
sibling of ``hostspans.py`` (the host's phases over the device's idle time)
for the time the device is busy.

    python3 benchmarks/scopespans.py <trace dir> <stages>   one JSON object
    python3 benchmarks/scopespans.py --run <run dir>         the seven shares

The step programs name what they do with ``dynamo_tpu/engine/stages.py
stage(name)``, a ``jax.named_scope`` whose name one table holds with its
GROUP (``mixer_in``, ``cache_write``, ``mixer``, ``mixer_out``, ``ffn``,
``around_layers``). In a chip's trace the path of scopes an operation was
traced under is not on the event: it is the ``tf_op`` stat of the
operation's EVENT METADATA on the device plane's ``XLA Ops`` line, beside
``hlo_category`` and ``source`` (file:line). ``jax.profiler.ProfileData``
(what ``xplane.py`` reads with) gives event stats only, so ``read_planes``
here parses the ``.xplane.pb`` itself, with the protobuf runtime and the
five message types declared below - no tensorflow, no new package; where
``google.protobuf`` is missing it raises ``ImportError`` and ``share``
returns None.

``reduce(planes, stages)`` works on plain data so that a recorded trace
tests it: ``xplane.reduce``'s planes, each line of a device plane with its
events' metadata ids once more under ``"meta"`` and the plane with
``"metadata": {id: {"tf_op", "category", "source"}}``. It takes the LEAF
operations of the ``XLA Ops`` line inside ``bench_slice`` exactly as
``xplane.reduce`` does (the same window, loops / branches / calls left out,
an operation half outside cut) and sums device seconds by group, by stage
path and by the enclosing ``XLA Modules`` event (one a dispatch: the packed
step, the decode block, ...), with calls, milliseconds a dispatch,
``hlo_category`` and, for what no stage covers, the operation's name and
``source``. A FUSION carries one ``tf_op``, its root's: a fusion that spans
two stages counts whole under the root's, so the table is by root.

``stages`` is the program's table as its worker's ``startup.engine`` span
carries it (``group:stage,stage;group:...``, ``stages.as_attribute``): the
grouping rule is the program's one list, read from the ``startup`` trace a
traced run exports anyway, never a second list here - a ``tf_op`` is a path
of scopes, transforms (``jit(...)``, ``while``, ``body``) and a primitive,
and only the table says which component is a stage. A stage's group is that
of the longest registered prefix of its path; an operation under no stage is
``unnamed`` - after PR 53 what was ADDED to the program, by the compiler
(parameter relayouts in the entry computation, ``copy-start`` / ``-done``,
layout copies) and by jax's lowering of a loop (a ``lax.scan``'s slices of
the stack it scans: ``while/body/dynamic_slice`` at the scan's own line),
since ``engine/program_check.unstaged`` holds every step program to tracing
nothing outside a stage. Device time inside a loop or a call under no leaf
operation (the holes between a body's operations) is ``unnamed`` too, as
``(between operations)``: the seven shares partition the busy time.

``share(run, group)`` is what the per-layer readers call: it runs this file
as a child once per worker of a traced run, leaves the whole table in the
run directory as ``stage_times.worker<i>.json`` and returns the group's
share (%) of the slice's busy time. None where the program ships no table
(an older program), where nothing on the machine reads metadata, where the
trace carries none (the CPU backend's), and where the seven shares do not
add up to 100 within 0.5.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import subprocess
import sys
import time

import xplane

GROUPS = ("mixer_in", "cache_write", "mixer", "mixer_out", "ffn",
          "around_layers")
UNNAMED = "unnamed"
BETWEEN = "(between operations)"
TOLERANCE = 0.5          # % the seven shares may miss 100 by
TOP_UNNAMED = 40


# ------------------------------------------------------------ the protobuf

def _messages():
    """``XSpace`` of ``tsl/profiler/protobuf/xplane.proto``, as far as the
    reader needs it, built with the protobuf runtime alone."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="dynamo_tpu_bench_xplane.proto", package="dynamo_tpu_bench",
        syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            m.field.add(name=fname, number=number, type=ftype, label=label,
                        type_name=type_name)
        return m

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, s, dbl, msg = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                             F.TYPE_DOUBLE, F.TYPE_MESSAGE)
    pkg = ".dynamo_tpu_bench."
    message("XStat", ("metadata_id", 1, i64, one, None),
            ("double_value", 2, dbl, one, None),
            ("uint64_value", 3, u64, one, None),
            ("int64_value", 4, i64, one, None),
            ("str_value", 5, s, one, None),
            ("bytes_value", 6, F.TYPE_BYTES, one, None),
            ("ref_value", 7, u64, one, None))
    message("XEvent", ("metadata_id", 1, i64, one, None),
            ("offset_ps", 2, i64, one, None),
            ("duration_ps", 3, i64, one, None),
            ("stats", 4, msg, many, pkg + "XStat"),
            ("num_occurrences", 5, i64, one, None))
    message("XLine", ("id", 1, i64, one, None), ("name", 2, s, one, None),
            ("timestamp_ns", 3, i64, one, None),
            ("events", 4, msg, many, pkg + "XEvent"))
    message("XEventMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None), ("display_name", 4, s, one, None),
            ("stats", 5, msg, many, pkg + "XStat"))
    message("XStatMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None))
    # a proto3 map is a repeated entry message of key and value
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        message(entry, ("key", 1, i64, one, None),
                ("value", 2, msg, one, pkg + value))
    message("XPlane", ("id", 1, i64, one, None), ("name", 2, s, one, None),
            ("lines", 3, msg, many, pkg + "XLine"),
            ("event_metadata", 4, msg, many, pkg + "EventMetadataEntry"),
            ("stat_metadata", 5, msg, many, pkg + "StatMetadataEntry"))
    message("XSpace", ("planes", 1, msg, many, pkg + "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("dynamo_tpu_bench.XSpace"))


def read_planes(trace_dir: str) -> list:
    """``xplane.read_planes`` with, per device plane, the event metadata
    the readers need and, per line, each event's metadata id."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    space = _messages()()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            t0 = line.timestamp_ns
            entry = {"name": line.name, "events": [
                (names.get(ev.metadata_id, ""), t0 + ev.offset_ps // 1000,
                 ev.duration_ps // 1000) for ev in line.events]}
            if device and line.name in (xplane.OPS_LINE,
                                        xplane.MODULES_LINE):
                entry["meta"] = [ev.metadata_id for ev in line.events]
            lines.append(entry)
        out = {"name": plane.name, "lines": lines}
        if device:
            stat = {e.key: e.value.name for e in plane.stat_metadata}
            used = {m for ln in lines for m in ln.get("meta", ())}
            metadata = {}
            for e in plane.event_metadata:
                if e.key not in used:
                    continue
                stats = {stat.get(st.metadata_id): st.str_value
                         or stat.get(st.ref_value, "")
                         for st in e.value.stats}
                metadata[e.key] = {
                    "tf_op": stats.get("tf_op", ""),
                    "category": stats.get("hlo_category", ""),
                    "source": stats.get("source", "")}
            out["metadata"] = metadata
        planes.append(out)
    return planes


# ------------------------------------------------------------- the stages

def parse_stages(attribute: str) -> dict:
    """``stage path -> group`` of a ``startup.engine`` span's ``stages``
    attribute (``group:stage,stage;group:...``)."""
    table = {}
    for part in attribute.split(";"):
        group, _, names = part.partition(":")
        for name in names.split(","):
            if name:
                table[name] = group
    return table


def stage_of(tf_op: str, stages: dict, parts: frozenset = None):
    """The stage an operation was traced under: the longest registered
    path that the components of its ``tf_op`` run through - a path of
    scopes, transforms and, last, the primitive, where components that are
    no stage (``jit(...)``, ``while``, ``body``, ``closed_call``) may stand
    between a stage and its child. None where it holds no stage."""
    if parts is None:
        parts = path_parts(stages)
    best = None
    for c in tf_op.rstrip(":").split("/"):
        if best is None:
            if c in parts:
                best = c
        elif f"{best}/{c}" in parts:
            best = f"{best}/{c}"
    while best is not None and best not in stages:
        best = best.rpartition("/")[0] or None
    return best


def path_parts(stages: dict) -> frozenset:
    """Every prefix of a registered path (``layer.attn/index`` of
    ``layer.attn/index/score`` is no stage but is on the way to one)."""
    return frozenset("/".join(p.split("/")[:n]) for p in stages
                     for n in range(1, p.count("/") + 2))


# -------------------------------------------------------------- reduction

def module_kind(name: str) -> str:
    """``jit__packed_step_impl`` of ``jit__packed_step_impl(1234...)``."""
    return name.split("(", 1)[0]


def reduce(planes: list, stages: dict, slice_name: str = xplane.SLICE):
    """The table (module docstring), or None where no operation of the
    slice carries metadata."""
    devs = [p for p in xplane.device_planes(planes) if p.get("metadata")]
    if not devs:
        return None
    window = xplane.find_slice(planes, slice_name)
    if window is None:
        starts = [s for p in devs for ln in p["lines"]
                  for _n, s, _d in ln["events"]]
        ends = [s + d for p in devs for ln in p["lines"]
                for _n, s, d in ln["events"]]
        window = (min(starts), max(ends))
    w0, w1 = window
    parts = path_parts(stages)
    n = len(devs)
    busy_ns = named = 0
    groups = dict.fromkeys(GROUPS + (UNNAMED,), 0)
    by_stage: dict = {}       # stage -> [ns, events, {category: ns}]
    by_module: dict = {}      # kind -> {"calls", "ns", "stages": {..}}
    unnamed: dict = {}        # (op, source, tf_op, category) -> [ns, events]
    for plane in devs:
        lines = {ln["name"]: ln for ln in plane["lines"]}
        ops = lines[xplane.OPS_LINE]
        mods = lines.get(xplane.MODULES_LINE, {"events": []})
        spans = sorted((s, s + d, module_kind(name))
                       for name, s, d in mods["events"]
                       if min(s + d, w1) > max(s, w0))
        mod_starts = [s for s, _e, _k in spans]
        for _s, _e, kind in spans:
            by_module.setdefault(kind, {"calls": 0, "ns": 0, "stages": {}})[
                "calls"] += 1
        # (a recorded trace is JSON: its keys are strings)
        metadata = {int(k): v for k, v in plane["metadata"].items()}
        resolved: dict = {}   # metadata id -> (stage, group, category)
        cut = []
        for (name, start, dur), mid in zip(ops["events"], ops["meta"]):
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            cut.append((s, e))
            if xplane.is_container(name):
                continue
            if mid not in resolved:
                md = metadata.get(mid, {})
                stage = stage_of(md.get("tf_op", ""), stages, parts)
                resolved[mid] = (stage, stages[stage] if stage else UNNAMED,
                                 md.get("category", ""))
                named += bool(md.get("tf_op"))
            stage, group, category = resolved[mid]
            ns = e - s
            groups[group] += ns
            acc = by_stage.setdefault(stage or UNNAMED, [0, 0, {}])
            acc[0] += ns
            acc[1] += 1
            acc[2][category] = acc[2].get(category, 0) + ns
            i = bisect.bisect_right(mod_starts, start) - 1
            kind = spans[i][2] if i >= 0 and start < spans[i][1] else "other"
            mod = by_module.setdefault(
                kind, {"calls": 0, "ns": 0, "stages": {}})
            mod["ns"] += ns
            mod["stages"][stage or UNNAMED] = mod["stages"].get(
                stage or UNNAMED, 0) + ns
            if stage is None:
                md = metadata.get(mid, {})
                key = (xplane.short(name), md.get("source", ""),
                       md.get("tf_op", ""), category)
                u = unnamed.setdefault(key, [0, 0])
                u[0] += ns
                u[1] += 1
        busy_ns += sum(e - s for s, e in xplane.merge(cut))
    if not named:
        # events with metadata entries but no ``tf_op`` anywhere: a
        # backend that names no scope (the CPU's)
        return None
    leaf_ns = sum(groups.values())
    # busy time under a loop or call but under none of its operations; a
    # negative rest would be operations that overlap on the line
    between = busy_ns - leaf_ns
    groups[UNNAMED] += max(0, between)

    def sec(ns):
        return ns / 1e9 / n

    shares = ({g: 100.0 * v / busy_ns for g, v in groups.items()}
              if busy_ns else {})
    return {
        "devices": n,
        "window_s": (w1 - w0) / 1e9,
        # seconds per device, as ``xplane.reduce`` counts them
        "busy_s": sec(busy_ns),
        "between_ops_s": sec(between),
        "groups": {g: sec(v) for g, v in groups.items()},
        # % of ``busy_s``; they add up to 100 where no operations overlap
        "shares": shares,
        "partition_error": abs(sum(shares.values()) - 100.0) if shares
        else None,
        # stage path -> seconds, operation events, its group, seconds by
        # hlo_category; most expensive first
        "stages": {st: {"group": stages.get(st, UNNAMED),
                        "seconds": sec(ns), "events": ev,
                        "categories": {c: sec(v) for c, v in sorted(
                            cats.items(), key=lambda kv: -kv[1])}}
                   for st, (ns, ev, cats) in sorted(
                       by_stage.items(), key=lambda kv: -kv[1][0])},
        # the enclosing XLA Modules event (a dispatch) by its jit name:
        # dispatches in the slice, seconds of leaf operations, and per
        # stage seconds and milliseconds a dispatch
        "modules": {kind: {
            "calls": m["calls"], "seconds": sec(m["ns"]),
            "stages": {st: {"seconds": sec(ns), "ms_per_call":
                            (ns / 1e6 / m["calls"] if m["calls"] else None)}
                       for st, ns in sorted(m["stages"].items(),
                                            key=lambda kv: -kv[1])}}
            for kind, m in sorted(by_module.items(),
                                  key=lambda kv: -kv[1]["ns"])},
        # what no stage covers: [operation, seconds, events, hlo_category,
        # source, tf_op], most expensive first
        "unnamed": ([[BETWEEN, sec(between), 0, "", "", ""]]
                    if between > 0 else []) + [
            [op, sec(ns), ev, category, source, tf_op]
            for (op, source, tf_op, category), (ns, ev) in sorted(
                unnamed.items(), key=lambda kv: -kv[1][0])[:TOP_UNNAMED]],
    }


def share_of(red, group: str):
    """The group's share (%) of the slice's busy time; None of no table,
    and where the seven shares miss 100 by more than ``TOLERANCE``."""
    if not red or not red.get("shares"):
        return None
    if red["partition_error"] > TOLERANCE:
        return None
    return red["shares"].get(group)


# ------------------------------------------------------------ a traced run

def worker_stages(run, i: int):
    """The ``stages`` attribute of worker ``i``'s ``startup.engine`` span
    (its exported ``startup`` trace), or None of an older program."""
    path = os.path.join(run.run_dir, f"worker{i}.traces.jsonl")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        for line in f:
            record = json.loads(line)
            if record.get("name") != "startup":
                continue
            for span in record.get("spans", ()):
                if span.get("name") == "startup.engine":
                    return (span.get("attrs") or {}).get("stages")
    return None


def share(run, group: str):
    """``share_of`` a traced run, averaged over its workers."""
    shares = []
    for i, trace in enumerate(run.device_traces):
        path = os.path.join(run.run_dir, f"stage_times.worker{i}.json")
        if os.path.exists(path):
            with open(path) as f:
                red = json.load(f)
        else:
            attribute = worker_stages(run, i)
            if not attribute:
                continue
            out = subprocess.run(
                [sys.executable, __file__, trace["mark"]["dir"], attribute],
                capture_output=True, text=True, timeout=300)
            if out.returncode != 0:
                print("bench: scopespans failed for worker "
                      f"{i}:\n{out.stderr[-2000:]}", file=sys.stderr)
                red = None
            else:
                red = json.loads(out.stdout.strip().splitlines()[-1])
            with open(path, "w") as f:
                json.dump(red, f)
        value = share_of(red, group)
        if value is not None:
            shares.append(value)
    return sum(shares) / len(shares) if shares else None


class KeptRun:
    """A traced run as ``run.py`` left it in its directory, as far as
    ``share`` reads one."""

    def __init__(self, run_dir: str) -> None:
        self.run_dir = run_dir
        with open(os.path.join(run_dir, "run.json")) as f:
            self.device_traces = json.load(f)["device_traces"]


if __name__ == "__main__":
    if sys.argv[1] == "--run":
        # a person's form: the seven shares of a traced run's directory
        # (``benchmarks/.runs/<cell>``), the tables left beside them
        kept = KeptRun(sys.argv[2])
        print(json.dumps({g: share(kept, g) for g in GROUPS + (UNNAMED,)}))
    else:
        t0 = time.monotonic()
        red = reduce(read_planes(sys.argv[1]), parse_stages(sys.argv[2]))
        if red is not None:
            red["reduce_s"] = time.monotonic() - t0
        print(json.dumps(red))
