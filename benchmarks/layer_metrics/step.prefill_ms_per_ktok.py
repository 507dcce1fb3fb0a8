"""Host-clock milliseconds of the prefill and mixed step programs per
thousand real prompt tokens they carried. These dispatches are synchronous
(the loop waits for their result), so the ring's ``dispatch_ms`` is their
device time plus the transfer; decode rows riding a mixed step count as one
real token each."""

from layer_metrics._ring import in_window


def compute(run):
    recs = in_window(run, ("prefill", "mixed"))
    tokens = sum(r["tokens_real"] for r in recs)
    if not tokens:
        return None
    return sum(r["dispatch_ms"] for r in recs) / tokens * 1000.0
