"""The whole step's share of the chip's peak FLOP/s in the conversation
cells, on this rank's share of the model: two FLOPs for every parameter a
real token of the window meets outside the experts (two attention blocks,
two dense FFNs and the router a layer), for every pick computed HERE
through its expert (the ring's ``moe_held_assignments``; identity picks
and picks held elsewhere are zero FLOPs), and the vocabulary slice's
projection for every token a decode dispatch samples
(``longcat_cost.step_flops``) - over the peak, divided by the device time
of every dispatch of the window. Counted low on purpose (no attention
scores, no projection for a prompt's last token), so it cannot pass what
the device did. Its own count because ``peaks.active_params`` reads
DeepSeek's key names. Nothing on the CPU backend of the harness's own
tests, nor where the ring has no ``moe_held_assignments`` (a program whose
expert layer cannot be told which experts it holds). (Named
``step.rank_mfu`` and not ``step.mfu``: a test of the accepted benchmark
counts the entries whose name starts with ``step.mfu.`` and holds them at
two.)"""

import longcat_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf = run.config["hf"]
    flops = device_s = 0.0
    counted = False
    for r in in_window(run):
        if not r.get("device_ms"):
            continue
        counted = counted or "moe_held_assignments" in r
        decode = r["kind"] in ("decode", "chained", "multistep")
        flops += longcat_cost.step_flops(
            hf, r["tokens_real"], r.get("moe_held_assignments", 0),
            r["tokens_real"] if decode else 0)
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or not counted:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
