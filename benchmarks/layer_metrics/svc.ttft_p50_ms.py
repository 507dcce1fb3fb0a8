"""Median time from when a request was due to its first token, over the
window's completed requests, as the client timed it.
With a dozen requests in a window a percentile is an order statistic that
one stall moves by tens of percent, so it is read beside the bounded
``answer_mean_ms`` and carries no bound of its own (PERF.md, PR 23)."""

from layer_metrics import percentile


def compute(run):
    return percentile([(r.first - r.due) * 1000.0
                       for r in run.requests if r.ok], 50)
