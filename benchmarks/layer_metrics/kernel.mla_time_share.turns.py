"""Share of the device's busy time in the traced slice that the latent
attention kernels took in the conversation cells: every Mosaic call whose
kernel is named ``mla_*`` (``mla_decode`` in the fused block, ``mla_ragged``
in the packed step: two calls a layer, one for each attention block).
Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("mla_*",))
