"""Tokens a forward pass yields a row, in generation by diffusion over
blocks: the positions the window's pass dispatches revealed (the step
ring's ``revealed``) over their row-passes (``row_passes``: passes summed
over the rows alive at each, committing passes included). A causal decode
step reads 1 here by construction; a block of 4 denoised in 2 revealing
passes and committed by a third reads 4/3. Nothing where the ring has no
such field (a program that does not generate by blocks)."""

from layer_metrics._ring import in_window


def compute(run):
    recs = [r for r in in_window(run, ("multistep",)) if r.get("row_passes")]
    row_passes = sum(r["row_passes"] for r in recs)
    return sum(r["revealed"] for r in recs) / row_passes if recs else None
