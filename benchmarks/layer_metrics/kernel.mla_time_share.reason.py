"""Share of the device's busy time in the traced slice that the latent
attention kernels took: the ``mla_decode`` and ``mla_prefill`` Mosaic calls
by name (``kernel.attn_time_share`` counts every Mosaic call as attention,
which would count ``moe_grouped`` too). Nothing where the trace has no such
call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("mla_decode", "mla_prefill"))
