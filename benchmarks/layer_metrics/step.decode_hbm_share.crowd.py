"""A whole decode step's share of the memory roofline in the crowd cell:
the bytes the window's decode steps had to move - every matrix outside the
embedding once a step (the sixteen layers and the head), the recurrent
state they read and wrote (the ring's ``state_bytes``: no model arithmetic
here) and the live keys and values of the four full-attention layers
(``olmo_hybrid_cost.decode_step_bytes``) - over the chip's peak bytes per
second, divided by the device time of those dispatches (the ring's
``device_ms``). The context is the pool's pages in use spread over the
running rows: pages are given for a whole prompt at admission, so rows
still in prefill count theirs early. Nothing where the ring has no
``state_bytes`` (a program without the counter), nor on the CPU backend of
the harness's own tests."""

import olmo_hybrid_cost as cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    need = device_s = 0.0
    for r in in_window(run, ("decode", "chained", "multistep")):
        if not r.get("state_bytes") or not r.get("device_ms"):
            continue
        steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
        used_tokens = (run.num_pages - r["pool_free"]) * run.page_size
        ctx = used_tokens / max(1, r["running"]) * r["rows"]
        need += steps * cost.decode_step_bytes(hf, dtype, 0, ctx) \
            + r["state_bytes"]
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0:
        return None
    return 100.0 * need / peaks.peak(run.devices[0]["kind"])[
        "hbm_bytes_per_s"] / device_s
