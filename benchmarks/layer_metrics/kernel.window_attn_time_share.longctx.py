"""Share of the device's busy time in the traced slice that the window
layers' latent attention took for the rows of several tokens in the
long-context cell: the Mosaic calls named ``mla_window``
(``ops/pallas/mla_ragged.py`` with a bias over a row's ring pages) over
busy time. The one-token rows' form is plain XLA and not in it. Nothing
where the trace has no such call."""

from layer_metrics._kernels import time_share


def compute(run):
    return time_share(run, ("mla_window",))
