"""What the readers of the workers' exported traces share."""

import json
import os


def worker_records(run):
    """Every record of every worker's ``DYN_TRACE_EXPORT`` file (one
    finished trace or fragment a line), as ``(worker index, record)``."""
    for i in range(len(run.layout["workers"])):
        path = os.path.join(run.run_dir, f"worker{i}.traces.jsonl")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                yield i, json.loads(line)
