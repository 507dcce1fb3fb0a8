#!/usr/bin/env python
"""The most expensive device operations of a profiler trace, each with the
scope it was traced under.

    python tools/xplane_scopes.py <trace dir or .xplane.pb> [--top 10]

The step programs name their stages with ``jax.named_scope`` (``embed``,
``layer.attn_in``, ``layer.kv_write``, ``layer.attn``, ``layer.ffn`` /
``layer.moe``, ``logits``, ``sample``). In a TPU profile the scope path is
not on the event: it is the ``tf_op`` stat of the operation's *event
metadata* on the device plane's ``XLA Ops`` line, beside ``hlo_category``
and ``source`` (file:line). ``jax.profiler.ProfileData`` reads event stats
only, so this reads the ``.xplane.pb`` with the xplane protobuf that ships
with tensorflow. Loops (``while``) and calls span their bodies' operations
and are left out. Take the trace with ``POST /v1/profile`` on a worker's
system server (docs/observability.md).
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


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", help="trace directory or .xplane.pb file")
    p.add_argument("--top", type=int, default=10)
    args = p.parse_args(argv)
    for seconds, calls, op, category, scope, source in top_ops(
            args.trace, args.top):
        print(f"{seconds:9.4f} s {calls:6d} x  {op}  [{category}]\n"
              f"{'':22}{scope or '(no scope)'}  ({source or 'no source'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
