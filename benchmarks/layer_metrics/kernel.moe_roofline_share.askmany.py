"""The grouped expert matmul's share of its roofline in the ask-many cell,
over every dispatch of the traced slice: the least time the chip could take
for the slice's ``moe_grouped`` calls - every expert a dispatch touched read
once a layer and step (the ring's ``experts_touched``), 2 FLOPs per
multiply-add of every pick (``moe_assignments``;
``keye_cost.grouped_cost``) - over the device time those calls took. At 48
rows x 8 picks a layer a decode step touches nearly every expert for three
tokens each: the weights' read bounds it. Nothing where the trace has no
such call or the ring no such counts."""

import keye_cost
import peaks
from layer_metrics._kernels import mosaic_ops


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        recs = [r for r in records if t0 <= r["t_unix"] < t1
                and r.get("experts_touched") and r.get("moe_assignments")]
        kernel_s = sum(s for _n, s, _c in mosaic_ops(trace,
                                                     ("moe_grouped",)))
        if not recs or kernel_s <= 0.0:
            continue
        flops, nbytes = keye_cost.grouped_cost(
            hf, dtype, sum(r["experts_touched"] for r in recs),
            sum(r["moe_assignments"] for r in recs))
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
