"""Share of the device's busy time in the traced slice that the indexer
took in the ask-many cell: the operations traced under the stages
``layer.attn/index/score`` (a row's index keys gathered, the weighted ReLU
scores of its queries against them) and ``layer.attn/index/topk`` (the
exact threshold selection and the bias it becomes) - plain XLA, so read
from the slice's table of stages (``scopespans``), not from a kernel's
name. Nothing where the program ships no table or traces no such stage."""

from layer_metrics._keye import INDEX_STAGES, stage_seconds


def compute(run):
    shares = []
    for i, _trace in enumerate(run.device_traces):
        got = stage_seconds(run, i, INDEX_STAGES)
        if got and got[0] > 0.0 and got[1] > 0.0:
            shares.append(100.0 * got[0] / got[1])
    return sum(shares) / len(shares) if shares else None
