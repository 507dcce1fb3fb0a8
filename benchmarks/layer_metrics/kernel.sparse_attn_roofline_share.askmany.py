"""The attention over the selection against its roofline in the ask-many
cell: the least time the chip could take to attend the SELECTED tokens of
the traced slice's queries (the ring's ``selected_keys``: 2 FLOPs a
multiply-add of every query head's score and value against a selected
token, each selected token's keys and values read once a query;
``keye_cost.sparse_attn_cost``: what the mathematics needs, not the whole
context the masked form streams) in the six layers, over the device time
of the ``selected_rows`` and ``selected_chunks`` calls. Low by construction
while the kernels stream every visible key: at a context of 24.7 k they
read twelve times what is selected; a kernel that fetches the selected rows
is read on the same yardstick. Nothing where the trace has no such call or
the ring no ``selected_keys``."""

import keye_cost
from layer_metrics._kernels import mosaic_ops
from layer_metrics._keye import SELECTED_KERNELS, roofline_share


def _work(hf, dtype, r):
    return keye_cost.sparse_attn_cost(hf, dtype, r["selected_keys"])


def compute(run):
    return roofline_share(
        run, lambda _i, trace: sum(s for _n, s, _c in
                                   mosaic_ops(trace, SELECTED_KERNELS)), _work)
