"""Share of the HELD experts that the window's forward passes touched: the
step ring's ``experts_touched`` (held experts with at least one row, summed
over a dispatch's layers and steps) over the held expert slots of those
dispatches (steps x layers x experts held: ``longcat_cost.expert_slots``,
what ``dynamo_worker_moe_expert_slots_total`` adds up), in %. Nothing where
the ring has no such field."""

import longcat_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = longcat_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
