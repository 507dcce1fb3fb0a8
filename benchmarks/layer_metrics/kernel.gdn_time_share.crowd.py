"""Share of the device's busy time in the traced slice that the gated delta
rule's kernels took in the crowd cell: the Mosaic calls named ``gdn_step``
(one-token rows: nearly all of this cell's) and ``gdn_chunk`` (prompt
chunks) over busy time. Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("gdn_*",))
