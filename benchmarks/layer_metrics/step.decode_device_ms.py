"""Device time of one decode step as the program's own step ring has it:
the median, over the window's ``decode``, ``chained`` and ``multistep``
records, of ``device_ms`` divided by the steps the dispatch ran (the width
of a fused block). ``device_ms`` runs from the later of the dispatch's
enqueue and the previous result's arrival to this result's arrival
(``dynamo_tpu/engine/steptrace.py``), so it covers every dispatch of the
window and not only the traced slice. Nothing where the ring has no such
field (an older program)."""

import statistics

from layer_metrics._ring import in_window


def compute(run):
    per_step = [r["device_ms"] / max(1, r["width"])
                for r in in_window(run, ("decode", "chained", "multistep"))
                if r.get("device_ms")]
    return statistics.median(per_step) if per_step else None
