"""The whole step's share of the chip's peak FLOP/s in the crowd cell
(``olmo_hybrid_cost.step_flops``): two FLOPs for every parameter a real
token of the window meets outside the embedding and the head, the gated
delta rule's own ``7 Dk Dv`` a token a head in the twelve linear layers, the
causal attention scores of the four full layers from the ring's
``score_pairs`` and the head for every token a decode dispatch samples -
over the peak, divided by the device time of every dispatch of the window.
A prompt's last token's projection is left out (counted low). Decode at 48
rows is bound by bytes, so this reads a few percent: it is the share a
larger batch would raise. Nothing on the CPU backend of the harness's own
tests, nor where the ring has no ``score_pairs`` (a program without the
family). (Named ``step.rank_mfu`` and not ``step.mfu``: a test of the
accepted benchmark counts the entries whose name starts with
``step.mfu.``.)"""

import olmo_hybrid_cost as cost
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
        flops += cost.step_flops(hf, r["tokens_real"],
                                 r["tokens_real"] if decode else 0,
                                 r.get("score_pairs", 0))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or not counted:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
