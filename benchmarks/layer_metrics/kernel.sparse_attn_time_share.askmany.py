"""Share of the device's busy time in the traced slice that the attention
over the selection took in the ask-many cell: the Mosaic calls named
``selected_rows`` (the decode kernel with a bias: the rows of one token)
and ``selected_chunks`` (the ragged kernel with a bias: the rows of
several) - a row's whole context streams through and the selection is a
mask - over busy time. Nothing where the trace has no such call."""

from layer_metrics._kernels import time_share
from layer_metrics._keye import SELECTED_KERNELS


def compute(run):
    return time_share(run, SELECTED_KERNELS)
