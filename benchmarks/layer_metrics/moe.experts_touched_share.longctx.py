"""Share of the HELD experts that the window's forward passes touched in
the long-context cell: the step ring's ``experts_touched`` over the held
expert slots of those dispatches (steps x sparse layers x experts held:
``dots3_cost.expert_slots``), in %. A packed step of 544 tokens x 8 picks
of 256 sends ~17 tokens to each of the 8 held experts and touches every
one; a decode step of 32 rows about two thirds. Nothing where the ring has
no such field."""

import dots3_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = dots3_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
