"""A whole decode step's share of the memory roofline in the long-document
cell: the bytes the window's decode steps had to move - every matrix
outside the experts once, the held experts they touched (the ring's
``experts_touched``), the head, the live paged cache of the two
full-attention layers, and every row's recurrent state and carried
convolution inputs of the six linear layers READ AND WRITTEN
(``gdn_cost.decode_step_bytes``) - over the chip's peak bytes per second,
divided by the device time of those dispatches (the ring's
``device_ms``). The context is the pool's pages in use spread over the
running rows: pages are given for a whole prompt at admission, so rows
still in prefill count theirs early. Nothing where the ring has no
``state_rows``, nor on the CPU backend of the harness's own tests."""

import gdn_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    need = device_s = 0.0
    for r in in_window(run, ("decode", "chained", "multistep")):
        if not r.get("state_rows") or not r.get("device_ms"):
            continue
        steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
        used_tokens = (run.num_pages - r["pool_free"]) * run.page_size
        ctx = used_tokens / max(1, r["running"]) * r["rows"]
        need += (steps * gdn_cost.decode_step_bytes(hf, dtype, r["rows"],
                                                    ctx)
                 + r.get("experts_touched", 0)
                 * gdn_cost.expert_bytes(hf, dtype))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
