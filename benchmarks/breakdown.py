"""The ``breakdown`` of a traced run: the device operations that took most
time, and the device's idle time by what the step loop was doing, from the
step ring laid over the trace's clock.

Each ring record is one dispatch: it returned at ``t_unix``, took
``dispatch_ms`` on the host, and the ``gap_ms`` before it (since the last
dispatch returned) holds the host's work between dispatches - the unpack of
the previous result first (``unpack_ms`` of the record before, where the
loop did not overlap it), the planning of this one last (``plan_ms``). An
idle gap of the device is attributed by its midpoint: to ``plan``,
``unpack``, ``host between dispatches``, to the dispatch it fell into
(``in dispatch <kind>``: the host had handed the work over and the device
still idled), to ``waiting for a request`` where the loop had nothing queued
or running, else ``unattributed``.
"""

from __future__ import annotations

import bisect


def attribute(gap_mid_unix: float, records: list, ends: list) -> str:
    """``records`` in time order, ``ends`` their ``t_unix``."""
    i = bisect.bisect_left(ends, gap_mid_unix)
    if i >= len(records):
        last = records[-1] if records else None
        if last and not last["queue_depth"] and last["running"] <= last[
                "rows"] and gap_mid_unix - last["t_unix"] > 0.05:
            return "waiting for a request"
        return "unattributed"
    rec = records[i]
    t_d0 = rec["t_unix"] - rec["dispatch_ms"] / 1e3
    if gap_mid_unix >= t_d0:
        return f"in dispatch {rec['kind']}"
    gap_start = t_d0 - rec["gap_ms"] / 1e3
    if rec["gap_ms"] <= 0.0 or gap_mid_unix < gap_start:
        # the loop cleared its gap clock: it was idle, not stalled
        return "waiting for a request"
    if gap_mid_unix >= t_d0 - rec["plan_ms"] / 1e3:
        return "plan"
    prev = records[i - 1] if i else None
    if prev and gap_mid_unix < gap_start + prev["unpack_ms"] / 1e3:
        return "unpack"
    return "host between dispatches"


def build(run) -> dict:
    ops: dict = {}
    idle: dict = {}
    n = len(run.device_traces)
    for trace, records in zip(run.device_traces, run.ring):
        for name, seconds, _count in trace["ops"]:
            ops[name] = ops.get(name, 0.0) + seconds / n
        t0 = trace["mark"]["start_unix"]
        ends = [r["t_unix"] for r in records]
        for _dev, start_s, dur_s in trace["gaps"]:
            label = attribute(t0 + start_s + dur_s / 2, records, ends)
            idle[label] = idle.get(label, 0.0) + dur_s / n

    def top(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}
