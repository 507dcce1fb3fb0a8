"""Share of the router's picks that this rank computed in the long-context
cell: the step ring's ``moe_held_assignments`` over ``moe_assignments``,
summed over the window's dispatches, in %. 8 of 256 outputs draw a
thirty-second under a uniform router; the rest was held on the other 31
chips and added nothing here. Nothing where the ring has no such fields."""

from layer_metrics._picks import pick_share


def compute(run):
    return pick_share(run, "moe_held_assignments")
