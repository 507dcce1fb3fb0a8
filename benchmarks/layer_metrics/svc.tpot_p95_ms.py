"""95th percentile over requests of the time per output token after the
first, (last token - first token) / (tokens - 1): fused decode delivers
tokens in bursts of up to 8, so single gaps say little.
With a dozen requests in a window a percentile is an order statistic that
one stall moves by tens of percent, so it is read beside the bounded
``answer_mean_ms`` and carries no bound of its own (PERF.md, PR 23)."""

from layer_metrics import percentile


def compute(run):
    return percentile([(r.last - r.first) * 1000.0 / (r.tokens - 1)
                       for r in run.requests if r.ok and r.tokens > 1], 95)
