"""Share of the device's busy time in the traced slice that the grouped
expert matmul took in the long-context cell: the ``moe_grouped`` Mosaic
calls over busy time - the held experts' part of the sparse block alone
(picks held elsewhere never reach the kernel; the shared expert is plain
XLA). Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("moe_grouped",))
