"""The full layers' attention over the selection against its roofline in
the long-context cell: the least time the chip could take to attend the
SELECTED keys of the traced slice's prompt chunks (the ring's
``selected_keys``, less the one-token rows' share: 2 FLOPs a multiply-add
of every head's score and value against a selected row, each selected row
read once; ``dots3_cost.sparse_attn_cost``: what the mathematics needs, not
the whole context the masked form streams) in the three full layers, over
the device time of the ``mla_selected`` calls. Low by construction while
the kernel streams every visible key: at a context of 16 k it reads 8 x
what is selected. Nothing where the trace has no such call or the ring no
``selected_keys``."""

import dots3_cost
from layer_metrics._dots3 import chunk_share, roofline_share


def _work(hf, dtype, r):
    return dots3_cost.sparse_attn_cost(
        hf, dtype, r["selected_keys"] * chunk_share(r))


def compute(run):
    return roofline_share(run, "mla_selected",
                          dots3_cost.kinds(run.config["hf"])[0], _work)
