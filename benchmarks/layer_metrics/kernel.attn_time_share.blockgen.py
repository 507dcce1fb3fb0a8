"""Share of the device's busy time in the traced slice that the attention
kernels took in the block-generation cells: the Mosaic calls named
``paged_prefill`` (a pass over ``[rows, block]``, under the block-wise
visibility), ``ragged_mixed`` (the token-packed block-wise prefill) and
``paged_decode`` (none is expected), over busy time. By name, because the
grouped expert matmul is a Mosaic call too. Nothing where the trace has no
such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("paged_prefill", "ragged_mixed", "paged_decode"))
