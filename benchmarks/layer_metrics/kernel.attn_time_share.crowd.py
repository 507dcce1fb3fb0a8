"""Share of the device's busy time in the traced slice that the attention
kernels of the four full-attention layers took in the crowd cell: the
Mosaic calls named ``paged_decode`` (one-token rows, in fused blocks and
packed steps), ``ragged_mixed`` (a packed step's prompt chunks) and
``paged_prefill`` (none is expected), over busy time. By name, because the
rule's kernels are Mosaic calls too. Nothing where the trace has no such
call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("paged_prefill", "ragged_mixed", "paged_decode"))
