"""The grouped expert matmul's share of its roofline in the long-context
cell, over EVERY dispatch of the traced slice (nearly every step carries a
prompt chunk): the least time the chip could take for the slice's
``moe_grouped`` calls - every held expert a dispatch touched read once a
layer and step (the ring's ``experts_touched``), 2 FLOPs per multiply-add
of every pick computed here (``moe_held_assignments``;
``dots3_cost.grouped_cost``) - over the device time those calls took. At
544 tokens x 0.25 held picks a layer the weights' read bounds it. Nothing
where the trace has no such call or the ring no such counts."""

import dots3_cost
import peaks
from layer_metrics._kernels import mosaic_ops


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        recs = [r for r in records if t0 <= r["t_unix"] < t1
                and r.get("experts_touched")
                and "moe_held_assignments" in r]
        kernel_s = sum(s for _n, s, _c in mosaic_ops(trace,
                                                     ("moe_grouped",)))
        if not recs or kernel_s <= 0.0:
            continue
        flops, nbytes = dots3_cost.grouped_cost(
            hf, dtype, sum(r["experts_touched"] for r in recs),
            sum(r["moe_held_assignments"] for r in recs))
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
