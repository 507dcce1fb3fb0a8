"""Share of the device's busy time in the traced slice that the grouped
expert matmul took in the conversation cells: the ``moe_grouped`` Mosaic
calls over busy time - here the held experts' part of the shortcut branch
alone (identity picks and picks held elsewhere never reach the kernel).
Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("moe_grouped",))
