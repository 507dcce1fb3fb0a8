"""Share of the device's busy time in the traced slice that the latent
attention kernels took: every Mosaic call whose kernel is named ``mla_*``
(``mla_decode``, ``mla_prefill`` and whatever form of the kernel a later
step program calls, so that the share does not fall silent when one of them
leaves the path; ``kernel.attn_time_share`` counts every Mosaic call as
attention, which would count ``moe_grouped`` too). Nothing where the trace
has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("mla_*",))
