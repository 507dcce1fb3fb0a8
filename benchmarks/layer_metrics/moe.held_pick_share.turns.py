"""Share of the router's picks that this rank computed: the step ring's
``moe_held_assignments`` (picks of an expert held here: the rows of the
grouped matmul) over ``moe_assignments``, summed over the window's
dispatches, in %. 16 of 768 outputs would draw 2.1 % under a uniform
router; what is left beside this and the zero picks was held elsewhere and
added nothing here. Nothing where the ring has no such fields."""

from layer_metrics._picks import pick_share


def compute(run):
    return pick_share(run, "moe_held_assignments")
