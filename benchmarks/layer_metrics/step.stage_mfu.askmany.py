"""The whole step's share of the chip's peak FLOP/s in the ask-many cell
(``stage``: this chip is one pipeline stage of the deployment; the name keeps
clear of the two ``step.mfu.<cell>`` entries, whose count an accepted test
pins):
the matrix multiplications the window's real tokens need - two FLOPs for
every parameter a token is multiplied with on its way through the layers
(``keye_cost.active_params``: attention, indexer, router and the eight
experts it is routed to, not all 128), the vocabulary projection for every
token a decode dispatch samples, and in every layer the indexer's scores
against the keys a query sees and the attention's against the tokens it
selected (``keye_cost.index_cost``, ``sparse_attn_cost``: the ring's
``score_pairs`` and ``selected_keys``) - over the peak, divided by the
device time of every dispatch of the window (the ring's ``device_ms``).
The share of the whole step that a later claim in this cell is bounded by:
a kernel taken off the path leaves its own share silent and this one
standing. Counted from the mathematics, so it cannot pass what the device
did. Nothing on the CPU backend of the harness's own tests."""

import keye_cost
import peaks
from layer_metrics._ring import in_window


def compute(run):
    if run.platform != "tpu":
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    layers, head = keye_cost.active_params(hf)
    L = hf["num_hidden_layers"]
    flops = device_s = 0.0
    for r in in_window(run):
        if not r.get("device_ms"):
            continue
        flops += 2.0 * layers * r["tokens_real"]
        if r["kind"] in ("decode", "chained", "multistep"):
            flops += 2.0 * head * r["tokens_real"]
        flops += L * (
            keye_cost.index_cost(hf, dtype, r.get("score_pairs", 0), 0)[0]
            + keye_cost.sparse_attn_cost(hf, dtype,
                                         r.get("selected_keys", 0))[0])
        device_s += r["device_ms"] / 1e3
    if device_s <= 0.0 or flops <= 0.0:
        return None
    return 100.0 * flops / peaks.peak(run.devices[0]["kind"])[
        "bf16_flops_per_s"] / device_s
