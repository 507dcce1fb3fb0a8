"""Share of the router's picks that went to zero-compute experts: the step
ring's ``moe_zero_assignments`` (picks at or above the computing experts:
the token itself times its weight, no row, no fetch, no FLOP) over
``moe_assignments`` (every pick of a valid token), summed over the window's
dispatches, in %. 256 of 768 outputs would draw a third under a uniform
router. Nothing where the ring has no such fields."""

from layer_metrics._picks import pick_share


def compute(run):
    return pick_share(run, "moe_zero_assignments")
