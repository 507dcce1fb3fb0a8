"""Share of the HELD experts that the window's forward passes touched in
the long-document cell: the step ring's ``experts_touched`` over the held
expert slots of those dispatches (steps x layers x experts held:
``gdn_cost.expert_slots``), in %. A packed step of 2,048 prompt tokens
touches every held expert; a decode step of 64 rows x 2.5 held picks about
two thirds. Nothing where the ring has no such field."""

import gdn_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = gdn_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
