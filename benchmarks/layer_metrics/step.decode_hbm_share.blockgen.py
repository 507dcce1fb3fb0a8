"""A whole pass's share of the memory roofline in the block-generation
cells: the bytes the window's pass dispatches had to read - every matrix
outside the routed experts and the output head once a pass, the live cache
of the rows once a pass (``blockgen_cost.pass_bytes``), and the experts the
passes touched (the step ring's ``experts_touched``) - over the chip's peak
bytes per second, divided by the device time of those dispatches. Nothing
where the ring has no pass dispatch, nor on the CPU backend of the
harness's own tests."""

import blockgen_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    need = device_s = 0.0
    for r in in_window(run, ("multistep",)):
        if not r.get("row_passes") or not r.get("device_ms"):
            continue
        used_tokens = (run.num_pages - r["pool_free"]) * run.page_size
        ctx = used_tokens / max(1, r["running"]) * r["rows"]
        need += (r["passes"] * blockgen_cost.pass_bytes(hf, dtype, ctx)
                 + r["experts_touched"]
                 * blockgen_cost.expert_bytes(hf, dtype))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
