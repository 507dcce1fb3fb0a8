"""A whole decode step's share of the memory roofline in the conversation
cells: the bytes the window's decode steps had to read - every matrix
outside the experts once (two attention blocks, two dense FFNs and the
router a layer), the held experts they touched (the step ring's
``experts_touched``), the head's vocabulary slice and the live latent cache
over its two cache layers a layer (``longcat_cost.decode_step_bytes``) -
over the chip's peak bytes per second, divided by the device time of those
dispatches (the ring's ``device_ms``). Its own byte count because
``peaks.weight_bytes`` and ``moe_cost`` read DeepSeek's key names. Nothing
where the ring has no ``experts_touched``, nor on the CPU backend of the
harness's own tests."""

import longcat_cost
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
        need += (steps * longcat_cost.decode_step_bytes(hf, dtype, ctx)
                 + r["experts_touched"]
                 * longcat_cost.expert_bytes(hf, dtype))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
