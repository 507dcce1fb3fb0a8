#!/usr/bin/env python
"""Convert exported trace JSONL to Chrome trace-event JSON.

Input: the flight recorder's JSONL export (``DYN_TRACE_EXPORT=<path>``, see
``dynamo_tpu/utils/tracing.py``) — one finished trace per line, each with a
``spans`` list.  Output: a Chrome trace-event file loadable in Perfetto
(https://ui.perfetto.dev) or chrome://tracing, where a disaggregated request
renders as a flame chart: the frontend's ``http_request`` root on one
process track, each worker's hop + queue/prefill/kv_transfer/decode spans on
their own tracks, all on one shared timeline.

With ``--steptrace`` the engine's step flight recorder (a saved
``GET /v1/steptrace`` body, see ``dynamo_tpu/engine/steptrace.py``) merges
onto the same timeline as an ``engine-steps`` process track: every dispatch
(prefill/decode/chained/multistep/mixed/spec/gather) renders as a complete
event spanning its DEVICE time (``device_ms`` up to ``ready_unix``, not the
enqueue call) whose args carry the program's name, rows/tokens/queue-depth/
page-pool state and the host's phase times, with compile time and fallback
demotions flagged — so a TTFT spike in the request flame
chart lines up against the exact engine step (and compile, and pool
pressure) that caused it.

Usage:
    python tools/trace2perfetto.py traces.jsonl -o trace.json
    python tools/trace2perfetto.py traces.jsonl --trace-id <id> -o one.json
    python tools/trace2perfetto.py traces.jsonl --steptrace steps.json \
        -o merged.json    # steps.json = curl worker:PORT/v1/steptrace

Worked example (single machine, see docs/observability.md):
    DYN_TRACE_EXPORT=/tmp/traces.jsonl python -m dynamo_tpu.frontend.main ...
    curl localhost:8080/v1/chat/completions -d '{...}'
    python tools/trace2perfetto.py /tmp/traces.jsonl -o /tmp/trace.json
    # open https://ui.perfetto.dev and load /tmp/trace.json
"""

from __future__ import annotations

import argparse
import json
import sys


def _iter_traces(path: str):
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                continue  # truncated tail of a live export


def _load_steptrace(path: str) -> list:
    """StepRecords from a saved ``/v1/steptrace`` body (or a bare list)."""
    with open(path) as f:
        body = json.load(f)
    return body.get("records", body) if isinstance(body, dict) else body


def step_events(records, pid: int) -> list:
    """StepRecords -> complete events on one ``engine-steps`` process
    track, one thread per dispatch kind (dispatches of one kind never
    overlap — the engine loop serialises them — so time containment
    cannot mis-stack). An event spans the record's device time: it ends
    when the result was on the host (``ready_unix``) and lasts
    ``device_ms``. A record without them (a body saved before the ring
    had them, a dispatch whose result never came) falls back to the
    dispatch call."""
    events = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
               "args": {"name": "engine-steps"}}]
    kinds = {}
    for r in records:
        kind = r.get("kind", "?")
        if kind not in kinds:
            kinds[kind] = len(kinds) + 1
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": kinds[kind], "args": {"name": kind}})
        cat = "step"
        if r.get("compile_ms"):
            cat += ",compile"
        if r.get("fallback"):
            cat += ",fallback"
        args = {k: r[k] for k in
                ("seq", "program", "width", "rows", "batch", "tokens_real",
                 "tokens_padded", "queue_depth", "running", "pool_free",
                 "pool_pinned", "plan_ms", "dispatch_ms", "handover_ms",
                 "assemble_ms", "upload_ms", "enqueue_ms", "resume_ms",
                 "fetch_ms", "fetch_resume_ms", "process_ms", "unpack_ms",
                 "gap_ms", "compile_ms", "fallback", "chained")
                if r.get(k)}
        if r.get("ready_unix"):
            dur_ms = float(r.get("device_ms", 0.0))
            ts = float(r["ready_unix"]) - dur_ms / 1e3
        else:
            dur_ms = float(r.get("dispatch_ms", 0.0))
            ts = float(r.get("t_unix", 0.0))
        events.append({
            "name": (f"{kind}x{r['width']}" if r.get("width")
                     else kind),
            "cat": cat, "ph": "X",
            "ts": ts * 1e6,
            "dur": max(0.0, dur_ms) * 1e3,
            "pid": pid, "tid": kinds[kind],
            "args": args,
        })
    return events


def convert(traces) -> dict:
    """Spans -> complete ("X") events.  One process track per service and
    one thread track per (service, trace): Chrome trace-event viewers nest
    complete events on a track purely by time containment, which matches
    the span tree for one request's sequential stages — but overlapping
    spans of CONCURRENT requests on a shared track would mis-stack, so
    each trace gets its own tid."""
    events = []
    services = {}

    def pid_of(service: str) -> int:
        if service not in services:
            services[service] = len(services) + 1
            events.append({"name": "process_name", "ph": "M",
                           "pid": services[service], "tid": 0,
                           "args": {"name": service or "unknown"}})
        return services[service]

    tids = {}

    def tid_of(trace_id: str) -> int:
        if trace_id not in tids:
            tids[trace_id] = len(tids) + 1
        return tids[trace_id]

    for t in traces:
        for s in t.get("spans", []):
            start = s.get("start_unix")
            if start is None:
                continue
            end = s.get("end_unix") or start
            args = {"trace_id": s.get("trace_id"),
                    "span_id": s.get("span_id"),
                    "parent_span_id": s.get("parent_span_id"),
                    "kind": s.get("kind")}
            args.update(s.get("attrs") or {})
            if s.get("status") == "error":
                args["error"] = s.get("error", "")
            events.append({
                "name": s.get("name", "?"),
                "cat": "span" if s.get("status") != "error" else "span,error",
                "ph": "X",
                "ts": start * 1e6,          # microseconds
                "dur": max(0.0, (end - start)) * 1e6,
                "pid": pid_of(s.get("service") or ""),
                "tid": tid_of(s.get("trace_id") or ""),
                "args": args,
            })
            for ev in s.get("events", []):
                events.append({
                    "name": ev.get("name", "event"),
                    "cat": "event", "ph": "i", "s": "p",
                    "ts": (ev.get("time_unix") or start) * 1e6,
                    "pid": pid_of(s.get("service") or ""),
                    "tid": tid_of(s.get("trace_id") or ""),
                    "args": ev.get("attrs") or {},
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="flight-recorder JSONL -> Chrome trace-event JSON")
    p.add_argument("input", help="JSONL export (DYN_TRACE_EXPORT path, or a "
                   "file of /v1/traces/{id} bodies, one per line)")
    p.add_argument("-o", "--output", default="trace.json")
    p.add_argument("--trace-id", default=None,
                   help="convert only this trace")
    p.add_argument("--steptrace", default=None,
                   help="saved GET /v1/steptrace body to merge as an "
                        "engine-steps track on the same timeline")
    args = p.parse_args(argv)
    traces = list(_iter_traces(args.input))
    if args.trace_id:
        traces = [t for t in traces if t.get("trace_id") == args.trace_id]
        if not traces:
            print(f"trace {args.trace_id} not found in {args.input}",
                  file=sys.stderr)
            return 1
    if not traces and not args.steptrace:
        print(f"no traces in {args.input}", file=sys.stderr)
        return 1
    out = convert(traces)
    n_steps = 0
    if args.steptrace:
        records = _load_steptrace(args.steptrace)
        n_steps = len(records)
        # pid after every span-track pid: convert() numbers services 1..N
        used = {e["pid"] for e in out["traceEvents"]}
        out["traceEvents"].extend(
            step_events(records, pid=max(used, default=0) + 1))
    with open(args.output, "w") as f:
        json.dump(out, f)
    n_spans = sum(len(t.get("spans", [])) for t in traces)
    print(f"wrote {len(out['traceEvents'])} events ({len(traces)} traces, "
          f"{n_spans} spans, {n_steps} steps) to {args.output}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
