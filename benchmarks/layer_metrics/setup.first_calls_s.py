"""Seconds of set-up that went to first calls of step programs: the summed
``compile_ms`` of the step-ring records stamped before the window (a first
call on a fresh jit bucket traces, then compiles or loads from the compile
cache, and its dispatch holds the loop meanwhile), the mean over workers."""


def compute(run):
    if not any(run.ring):
        return None
    per_worker = [sum(r["compile_ms"] for r in records
                      if r["t_unix"] < run.t0_unix) / 1e3
                  for records in run.ring]
    return sum(per_worker) / len(per_worker)
