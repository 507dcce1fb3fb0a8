"""The whole step's share of the chip's peak FLOP/s: the matrix
multiplications the window's real tokens need - two FLOPs for every
parameter a token is multiplied with on its way through the layers
(``peaks.active_params``: of an expert layer the experts it is routed to,
not all), and the vocabulary projection for every token a decode dispatch
samples - over the peak, divided by the device time of every dispatch of
the window (the ring's ``device_ms``). It stands beside the kernels' shares
of their rooflines: a step that drops a kernel leaves that kernel's share
silent, and this one still bounds what the step does with the chip.
Counted low on purpose (no attention scores, no projection for a prompt's
last token), so it cannot pass what the device did. Nothing on the CPU
backend of the harness's own tests, which has no peak to take a share of."""

import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    layers, head = peaks.active_params(run.config["hf"])
    flops = device_s = 0.0
    for r in in_window(run):
        if not r.get("device_ms"):
            continue
        flops += 2.0 * layers * r["tokens_real"]
        if r["kind"] in ("decode", "chained", "multistep"):
            flops += 2.0 * head * r["tokens_real"]
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or flops <= 0.0:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
