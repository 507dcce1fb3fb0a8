"""The indexer against its roofline in the ask-many cell: the least time
the chip could take to score the traced slice's queries against the keys
they can see (the ring's ``score_pairs``: 2 FLOPs a multiply-add of every
index head's dot product and the weighted sum; each row's visible index
keys read once, a float32 score a pair written;
``keye_cost.index_cost``) in the six layers, over the device time under
the stages ``layer.attn/index/score`` and ``layer.attn/index/topk``. The
selection itself (the threshold search) is counted as no work: what it
takes lowers the share. Nothing where the program ships no table of stages
or the ring no ``selected_keys``."""

import keye_cost
from layer_metrics._keye import (INDEX_STAGES, roofline_share,
                                 stage_seconds)


def _work(hf, dtype, r):
    return keye_cost.index_cost(hf, dtype, r["score_pairs"],
                                keye_cost.record_row_keys(r))


def compute(run):
    def seconds(i, _trace):
        got = stage_seconds(run, i, INDEX_STAGES)
        return got[0] if got else None
    return roofline_share(run, seconds, _work)
