"""The one-token form of the gated delta rule against its roofline in the
crowd cell, at 30 heads of 96 x 192: the least time the chip could take for
the row-steps the traced slice's ``gdn_step`` calls computed (the ring's
``gdn_step_rows``: decode rows of fused blocks and of packed steps, times
the twelve linear layers) - each row's state read and written, 2 x 2.2 MB a
layer, which bounds it by far (``olmo_hybrid_cost.rule_cost``: counted from
the rule, 18,432 elements a state, not from the 128 x 256 of a padded
tile) - over the device time those calls took. The kernel walks every row
of its program, rows without a token included; only rows with one are
counted as work. Nothing where the trace has no such call or the ring no
such counts."""

from layer_metrics._olmo import rule_share


def _work(r):
    if not r["gdn_step_rows"]:
        return None
    return r["gdn_step_rows"], r["gdn_step_rows"]


def compute(run):
    return rule_share(run, "gdn_step", _work)
