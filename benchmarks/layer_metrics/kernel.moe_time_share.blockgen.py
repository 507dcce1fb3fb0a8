"""Share of the device's busy time in the traced slice that the grouped
expert matmul took in the block-generation cells: the ``moe_grouped``
Mosaic calls over busy time (a pass sends every row's block through all
128 experts). Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("moe_grouped",))
