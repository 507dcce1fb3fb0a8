"""Share of the experts that the window's forward passes touched: the step
ring's ``experts_touched`` (experts with at least one assignment, summed
over a dispatch's expert layers and steps; what the program's counter
``dynamo_worker_moe_experts_touched_total`` adds up) over the expert slots
of those dispatches (steps x expert layers x experts:
``dynamo_worker_moe_expert_slots_total``), in %. Nothing where the ring has
no such field (a program without the grouped expert layer)."""

import moe_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = moe_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
