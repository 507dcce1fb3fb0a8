"""Share of the experts that the window's forward passes touched, in the
block-generation cells: the step ring's ``experts_touched`` (experts with
at least one assignment, summed over a dispatch's layers and passes) over
the expert slots of those dispatches (passes x layers x experts,
``blockgen_cost.expert_slots``), in %. Nothing where the ring has no such
field."""

import blockgen_cost
from layer_metrics._ring import in_window


def compute(run):
    touched = slots = 0
    per_pass = blockgen_cost.expert_slots(run.config["hf"])
    for r in in_window(run):
        if not r.get("experts_touched"):
            continue
        touched += r["experts_touched"]
        slots += per_pass * (max(1, r["width"])
                             if r["kind"] == "multistep" else 1)
    return 100.0 * touched / slots if slots else None
