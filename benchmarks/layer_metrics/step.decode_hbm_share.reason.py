"""A whole decode step's share of the memory roofline in the reasoning
cells: the bytes the window's decode steps had to read - every matrix
outside the routed experts once, the routed experts they touched (the step
ring's ``experts_touched``), the output head and the live latent cache
(``moe_cost.decode_step_bytes``) - over the chip's peak bytes per second,
divided by the device time of those dispatches (the ring's ``device_ms``).
Its own byte count: ``peaks.weight_bytes`` reads every expert and knows no
compressed query. Nothing where the ring has no ``experts_touched`` (a
program without the grouped expert layer), nor on the CPU backend of the
harness's own tests, which has no memory roofline to take a share of."""

import moe_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    need = device_s = 0.0
    for r in in_window(run, ("decode", "chained", "multistep")):
        if not r.get("experts_touched") or not r.get("device_ms"):
            continue
        steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
        used_tokens = (run.num_pages - r["pool_free"]) * run.page_size
        ctx = used_tokens / max(1, r["running"]) * r["rows"]
        # the fixed part and the cache once per step, the experts as
        # counted over all of the dispatch's steps
        need += (steps * moe_cost.decode_step_bytes(hf, dtype, 0, ctx)
                 + r["experts_touched"] * moe_cost.expert_bytes(hf, dtype))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
