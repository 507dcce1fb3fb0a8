"""The chunk form of the gated delta rule against its roofline in the crowd
cell, at 30 heads of 96 x 192: the least time the chip could take for the
prompt tokens the traced slice's ``gdn_chunk`` calls took through the rule
- the recurrence's own FLOPs a token a head over the peak FLOP/s, or the
tokens' q, k, v, g, beta in and o out plus each row's state once in and
once out a call over the peak bytes/s, whichever is larger
(``olmo_hybrid_cost.rule_cost``: counted from the rule, not from the
kernel's chunking or its padded tiles) - over the device time those calls
took. The tokens and rows are the step ring's (``gdn_tokens``; rows of
several tokens = ``state_rows - gdn_step_rows``) of the prefill-carrying
records stamped inside the slice, times the linear layers. Nothing where
the trace has no such call or the ring no such counts."""

from layer_metrics._olmo import rule_share


def _work(r):
    if r["kind"] not in ("prefill", "mixed") or not r["gdn_tokens"]:
        return None
    return r["gdn_tokens"], r["state_rows"] - r["gdn_step_rows"]


def compute(run):
    return rule_share(run, "gdn_chunk", _work)
