"""Share of the experts that the window's forward passes touched in the
ask-many cell: the step ring's ``experts_touched`` over the expert slots of
those dispatches (steps x layers x experts: ``keye_cost.expert_slots``), in
%. A decode step of 48 rows x 8 picks of 128 touches about 95 % of a
layer's experts. Nothing where the ring has no such field."""

import keye_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = keye_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
