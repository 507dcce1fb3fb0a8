"""Share of the window in which the step loop, and not the device, stood
between two dispatches, averaged over workers. ``gap_ms`` of a ring record
runs from the return of the previous dispatch to the start of this one; an
asynchronous dispatch (decode, the fused block) returns at once, so its
successor's gap holds the wait for its result too, which the previous
record's ``unpack_ms`` measures. The host's share is the gap less that
wait. The loop clears its gap clock when it has nothing to do, so this is
host overhead, not idleness for want of requests."""


def compute(run):
    lo, hi = run.t0_unix, run.t0_unix + run.seconds
    host_ms, seen = 0.0, False
    for records in run.ring:
        prev = None
        for r in records:
            if lo <= r["t_unix"] < hi:
                seen = True
                wait = prev["unpack_ms"] if prev else 0.0
                host_ms += max(0.0, r["gap_ms"] - wait)
            prev = r
    if not seen:
        return None
    return 100.0 * host_ms / 1e3 / (run.seconds * len(run.ring))
