"""Share of the device's busy time in the traced slice that the full
layers' latent attention over the selection took for the rows of several
tokens in the long-context cell: the Mosaic calls named ``mla_selected``
(``ops/pallas/mla_ragged.py`` with a bias: the row's whole context streams
through and the selection is a mask) over busy time. The one-token rows'
gathered form is plain XLA and not in it. Nothing where the trace has no
such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("mla_selected",))
