"""Share of the device's busy time in the traced slice that the grouped
expert matmul took: the ``moe_grouped`` Mosaic calls (``dynamo_tpu/ops/
pallas/moe_grouped.py``) among the leaf operations of the trace's ``XLA
Ops`` line, over busy time. Nothing where the trace has no such call (a
program without the kernel)."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("moe_grouped",))
