"""The one-token form of the gated delta rule against its roofline in the
long-document cell: the least time the chip could take for the row-steps
the traced slice's ``gdn_step`` calls computed (the ring's
``gdn_step_rows``: decode rows of fused blocks and of packed steps, times
the linear layers) - each row's state read and written, 2 x 2 MiB a layer,
which bounds it by far (``gdn_cost.rule_cost``) - over the device time
those calls took. The kernel walks every row of its program, rows without
a token included; only rows with one are counted as work. Nothing where
the trace has no such call or the ring no such counts."""

from layer_metrics._gdn import roofline_share


def _work(r):
    if not r["gdn_step_rows"]:
        return None
    return r["gdn_step_rows"], r["gdn_step_rows"]


def compute(run):
    return roofline_share(run, "gdn_step", _work)
