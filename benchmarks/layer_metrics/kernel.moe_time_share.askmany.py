"""Share of the device's busy time in the traced slice that the grouped
expert matmul took in the ask-many cell: the ``moe_grouped`` Mosaic calls
over busy time. Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("moe_grouped",))
