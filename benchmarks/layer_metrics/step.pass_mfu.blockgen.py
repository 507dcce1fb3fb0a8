"""The whole step's share of the chip's peak FLOP/s in the block-generation
cells: two FLOPs for every parameter a position meets on its way through
the layers (``blockgen_cost.active_params``: attention, router, 8 of 128
experts) times every position the window's dispatches computed (the ring's
``tokens_real``: a pass dispatch's is its row-passes times the block
length), plus the vocabulary projection for every position whose logits
are taken - all of a pass's, none counted for a prefill step - over the
peak, divided by the device time of every dispatch of the window. Counted
low on purpose (no attention scores), so it cannot pass what the device
did. Its own count because ``peaks.active_params`` reads this family's
``intermediate_size`` as a dense width. Nothing on the CPU backend of the
harness's own tests, nor where the ring has no pass dispatch. (Named
``step.pass_mfu`` and not ``step.mfu``: a test of the accepted benchmark
counts the entries whose name starts with ``step.mfu.`` and holds them at
two.)"""

import blockgen_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    layers, head = blockgen_cost.active_params(run.config["hf"])
    flops = device_s = 0.0
    passes = 0
    for r in in_window(run):
        if not r.get("device_ms"):
            continue
        flops += 2.0 * layers * r["tokens_real"]
        if r.get("row_passes"):
            flops += 2.0 * head * r["tokens_real"]
            passes += r["passes"]
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or not passes:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
