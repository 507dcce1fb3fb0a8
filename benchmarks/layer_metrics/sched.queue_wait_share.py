"""Share of the requests' time on the workers that went to waiting for
admission: over the worker fragments that ENDED inside the window
(``DYN_TRACE_EXPORT``), the summed ``queue`` spans (enqueued to admitted)
over the summed ``queue``, ``prefill`` and ``decode`` spans. By their end
and not their start: above the knee a request waits longer than a window
lasts, so of those that start inside it none is admitted before the run
is over, and the few fragments they leave hold no stage at all."""

from layer_metrics._spans import worker_records

STAGES = ("queue", "prefill", "decode")


def compute(run):
    lo, hi = run.t0_unix, run.t0_unix + run.seconds
    seconds = dict.fromkeys(STAGES, 0.0)
    for _i, record in worker_records(run):
        end = record.get("start_unix", 0.0) + record.get("duration_s", 0.0)
        if not lo <= end < hi:
            continue
        for span in record.get("spans", []):
            if span.get("name") in seconds:
                seconds[span["name"]] += span.get("duration_s", 0.0)
    total = sum(seconds.values())
    return 100.0 * seconds["queue"] / total if total else None
