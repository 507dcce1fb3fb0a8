"""Device time of one prefill-carrying step as the program's own step ring
has it: the median ``device_ms`` of the window's ``mixed`` and ``prefill``
records (see ``step.decode_device_ms``). Nothing where the ring has no such
field (an older program)."""

import statistics

from layer_metrics._ring import in_window


def compute(run):
    times = [r["device_ms"] for r in in_window(run, ("mixed", "prefill"))
             if r.get("device_ms")]
    return statistics.median(times) if times else None
