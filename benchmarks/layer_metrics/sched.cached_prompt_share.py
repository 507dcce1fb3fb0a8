"""Share of prompt tokens that the scheduler served from the prefix cache,
over the requests that reached a worker inside the window: on each worker's
exported request traces (``DYN_TRACE_EXPORT``), the ``cached_tokens`` of the
``prefill`` span over the ``prompt_tokens`` of the same record's
``worker.generate`` span. The streamed completions API carries no ``usage``,
so the spans are the source."""

import json
import os


def compute(run):
    lo, hi = run.t0_unix, run.t0_unix + run.seconds
    cached = prompt = 0.0
    for i in range(len(run.layout["workers"])):
        path = os.path.join(run.run_dir, f"worker{i}.traces.jsonl")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                record = json.loads(line)
                if not lo <= record.get("start_unix", 0.0) < hi:
                    continue
                for span in record.get("spans", []):
                    attrs = span.get("attrs") or {}
                    if span.get("name") == "worker.generate":
                        prompt += float(attrs.get("prompt_tokens") or 0.0)
                    elif span.get("name") == "prefill":
                        cached += float(attrs.get("cached_tokens") or 0.0)
    return 100.0 * cached / prompt if prompt else None
