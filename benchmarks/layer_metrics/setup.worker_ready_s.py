"""A worker's time from the start of its process to its ready line: the
duration of the ``startup`` trace it finalizes when it reports ready
(``DYN_TRACE_EXPORT``), whose children divide it (``startup.imports``,
``.weights``, ``.engine``, ``.prime``, ``.register``); the mean over
workers. Nothing where no worker wrote one (an older program)."""

from layer_metrics._spans import worker_records


def compute(run):
    times = {}
    for i, record in worker_records(run):
        if record.get("name") == "startup":
            times.setdefault(i, record["duration_s"])
    return sum(times.values()) / len(times) if times else None
