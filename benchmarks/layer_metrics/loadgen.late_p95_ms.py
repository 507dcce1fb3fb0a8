"""How late the load generator sent: send time minus due time, 95th
percentile over the window's requests. A starved generator must not read as
a fast server."""

from layer_metrics import percentile


def compute(run):
    return percentile([(r.sent - r.due) * 1000.0 for r in run.requests
                       if r.sent >= 0], 95)
