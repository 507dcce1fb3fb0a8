#!/usr/bin/env python
"""The most expensive device operations of a profiler trace, each with the
scope it was traced under; or the device's time by the program's stages.

    python tools/xplane_scopes.py <trace dir or .xplane.pb> [--top 10]
    python tools/xplane_scopes.py <trace dir> --by-stage [--top 10]

The step programs name their stages with ``engine/stages.py stage`` (a
``jax.named_scope`` of a registered name: ``embed``, ``layer.attn_in``,
``layer.kv_write``, ``layer.attn``, ``layer.attn_out``, ``layer.ffn`` /
``layer.moe``, ``logits``, ``sample``, ``step.*``). In a TPU profile the
scope path is not on the event: it is the ``tf_op`` stat of the operation's
*event metadata* on the device plane's ``XLA Ops`` line, beside
``hlo_category`` and ``source`` (file:line). ``jax.profiler.ProfileData``
reads event stats only, so this reads the ``.xplane.pb`` with the xplane
protobuf that ships with tensorflow. Loops (``while``) and calls span their
bodies' operations and are left out. Take the trace with ``POST
/v1/profile`` on a worker's system server (docs/observability.md).

``--by-stage`` is the operator's use of the names the benchmark reads a
traced run by: the device's seconds by group and by stage against THIS
checkout's table of stages, by dispatch (``XLA Modules`` event) with
milliseconds a dispatch, and the ``--top`` most expensive operations that
no stage covers with their source lines - ``benchmarks/scopespans.py``'s
reduction (its docstring has what a fusion counts under and what
``unnamed`` means; it needs the protobuf runtime alone), over the window of
the trace's ``profile_slice`` annotation (a benchmark run's: ``bench_slice``).
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

CONTAINERS = ("while", "conditional", "call")


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def top_ops(path: str, top: int = 10) -> list:
    """``[(seconds, calls, operation, category, scope, source)]`` summed
    over the device planes, most expensive first."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(newest_xplane(path), "rb") as f:
        space.ParseFromString(f.read())
    rows = []
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            total: dict = {}
            for ev in line.events:
                acc = total.setdefault(ev.metadata_id, [0, 0])
                acc[0] += ev.duration_ps
                acc[1] += 1
            for mid, (ps, calls) in total.items():
                md = plane.event_metadata[mid]
                stats = {names.get(s.metadata_id): s.str_value
                         or names.get(s.ref_value, "") for s in md.stats}
                if stats.get("hlo_category") in CONTAINERS:
                    continue
                rows.append((ps / 1e12, calls, md.display_name or md.name,
                             stats.get("hlo_category", ""),
                             stats.get("tf_op", "").rstrip(":"),
                             stats.get("source", "")))
    return sorted(rows, reverse=True)[:top]


def by_stage(path: str, top: int = 10) -> list:
    """The lines ``--by-stage`` prints."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for entry in (repo, os.path.join(repo, "benchmarks")):
        if entry not in sys.path:
            sys.path.insert(0, entry)
    import scopespans

    from dynamo_tpu.engine.stages import as_attribute

    trace_dir = os.path.dirname(path) if os.path.isfile(path) else path
    planes = scopespans.read_planes(trace_dir)
    # a trace of ``POST /v1/profile``, or a benchmark run's
    window = next((name for name in ("profile_slice", scopespans.xplane.SLICE)
                   if scopespans.xplane.find_slice(planes, name)), "")
    red = scopespans.reduce(planes, scopespans.parse_stages(as_attribute()),
                            slice_name=window)
    if red is None:
        return ["the trace names no scope (no device plane with event "
                "metadata: a CPU backend's trace)"]
    busy = red["busy_s"]
    lines = [f"busy {busy:.4f} s of a window of {red['window_s']:.4f} s on "
             f"{red['devices']} device(s)", "", "by group:"]
    lines += [f"  {red['groups'][g]:9.4f} s {red['shares'][g]:6.2f} %  {g}"
              for g in red["groups"]]
    lines += ["", "by stage:"]
    lines += [f"  {d['seconds']:9.4f} s {100 * d['seconds'] / busy:6.2f} % "
              f"{d['events']:8d} x  {st}  [{d['group']}]"
              for st, d in red["stages"].items()]
    for kind, mod in red["modules"].items():
        lines += ["", f"{kind}: {mod['calls']} dispatches, "
                  f"{mod['seconds']:.4f} s"]
        lines += [f"  {d['seconds']:9.4f} s "
                  + (f"{d['ms_per_call']:9.3f} ms a dispatch"
                     if d["ms_per_call"] is not None else " " * 22)
                  + f"  {st}" for st, d in mod["stages"].items()]
    lines += ["", "under no stage:"]
    lines += [f"  {sec:9.4f} s {n:6d} x  {op}  [{cat}]  "
              f"({src or 'no source'})"
              for op, sec, n, cat, src, _tf in red["unnamed"][:top]]
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", help="trace directory or .xplane.pb file")
    p.add_argument("--top", type=int, default=10)
    p.add_argument("--by-stage", action="store_true",
                   help="the device's seconds by group and stage of the "
                        "program's table instead of the top operations")
    args = p.parse_args(argv)
    if args.by_stage:
        print("\n".join(by_stage(args.trace, args.top)))
        return 0
    for seconds, calls, op, category, scope, source in top_ops(
            args.trace, args.top):
        print(f"{seconds:9.4f} s {calls:6d} x  {op}  [{category}]\n"
              f"{'':22}{scope or '(no scope)'}  ({source or 'no source'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
