"""A whole decode step's share of the memory roofline in the ask-many cell:
the bytes the window's decode steps had to move - every matrix outside the
experts once, the experts they touched (the ring's ``experts_touched``),
the head, and in the six layers every row's index keys of its whole
context and the keys and values of its 2,048 selected tokens
(``keye_cost.decode_step_bytes``: what the mathematics needs, not the
whole context the masked kernel streams) - over the chip's peak bytes per
second, divided by the device time of those dispatches (the ring's
``device_ms``). A row's context is what its one query sees (a decode
step's ``score_pairs`` a step). Nothing where the ring has no
``selected_keys``, nor on the CPU backend of the harness's own tests."""

import keye_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    need = device_s = 0.0
    for r in in_window(run, ("decode", "chained", "multistep")):
        if not r.get("selected_keys") or not r.get("device_ms"):
            continue
        steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
        need += (steps * keye_cost.decode_step_bytes(
            hf, dtype, r["rows"], r["score_pairs"] / steps)
                 + r.get("experts_touched", 0)
                 * keye_cost.expert_bytes(hf, dtype))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
