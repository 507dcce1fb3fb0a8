"""The whole step's share of the chip's peak FLOP/s in the long-context
cell, on this rank's share of the model (``dots3_cost.step_flops``): two
FLOPs for every parameter a real token of the window meets outside the
experts (both kinds of attention with the indexer's projections, the dense
FFN, the routers, the shared experts), for every pick computed HERE
through its expert (the ring's ``moe_held_assignments``), the indexer's
scores of every visible key (``score_pairs``), the latent attention over
the SELECTED keys only (``selected_keys``: what the mathematics needs, not
the whole context), the window layers' attention over at most a window a
query, and the head for every token a decode dispatch samples - over the
peak, divided by the device time of every dispatch of the window. A
prompt's last token's projection is left out (counted low). Nothing on the
CPU backend of the harness's own tests, nor where the ring has no
``selected_keys`` (a program without the family)."""

import dots3_cost
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
        counted = counted or "selected_keys" in r
        decode = r["kind"] in ("decode", "chained", "multistep")
        flops += dots3_cost.step_flops(
            hf, r["tokens_real"], r.get("moe_held_assignments", 0),
            r["tokens_real"] if decode else 0, r.get("score_pairs", 0),
            r.get("selected_keys", 0), dots3_cost.record_window_pairs(hf, r))
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or not counted:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
