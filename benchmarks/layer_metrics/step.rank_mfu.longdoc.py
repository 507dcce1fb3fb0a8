"""The whole step's share of the chip's peak FLOP/s in the long-document
cell, on this rank's share of the model (``gdn_cost.step_flops``): two
FLOPs for every parameter a real token of the window meets outside the
experts (the mixers, the router, the shared expert), for every pick
computed HERE through its expert (the ring's ``moe_held_assignments``),
the gated delta rule's own FLOPs in the linear layers, the causal attention
scores of the full layers from the ring's ``score_pairs`` (a new token at
position p against p + 1 keys: at 12 k tokens of context a step is not
bounded without them) and the head for every token a decode dispatch
samples - over the peak, divided by the device time of every dispatch of
the window. A prompt's last token's projection is left out (counted low).
Nothing on the CPU backend of the harness's own tests, nor where the ring
has no ``score_pairs`` (a program without the family). (Named
``step.rank_mfu`` and not ``step.mfu``: a test of the accepted benchmark
counts the entries whose name starts with ``step.mfu.``.)"""

import gdn_cost
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
        counted = counted or "score_pairs" in r
        decode = r["kind"] in ("decode", "chained", "multistep")
        flops += gdn_cost.step_flops(
            hf, r["tokens_real"], r.get("moe_held_assignments", 0),
            r["tokens_real"] if decode else 0, r.get("score_pairs", 0))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or not counted:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
