"""The grouped expert matmul's share of its roofline in the passes of the
block-generation cells: the least time the chip could take for the
``moe_grouped`` calls of the traced slice's pass dispatches - the larger of
their FLOPs over the peak FLOP/s and their bytes over the peak bytes/s
(``blockgen_cost.grouped_cost``: every touched expert's three matrices
once, 2 FLOPs per multiply-add of every assignment) - over the device time
those calls took.

The touched experts are the step ring's, of the pass dispatches stamped
inside the slice; their assignments are ``row_passes`` x block x experts
per token x layers. The trace's reduction sums a kernel's calls by shape,
so the passes' calls are told from the prefill steps' by their row count:
a pass has ``batch`` rows of ``block_size`` positions, so its calls have
``blockgen_cost.grouped_rows(batch x block x experts per token)`` rows
(3,072 at 32 rows), whatever the prefill steps of the slice look like; a
prefill step whose slots give the same row count could not be told apart,
and the reader then returns nothing. Nothing where the trace has no such
call or the ring no pass dispatch."""

import re

import blockgen_cost
import peaks
from layer_metrics._kernels import mosaic_ops


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    k, B = hf["num_experts_per_tok"], hf["block_size"]
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        in_slice = [r for r in records if t0 <= r["t_unix"] < t1]
        recs = [r for r in in_slice
                if r.get("row_passes") and r.get("experts_touched")]
        pass_rows = {blockgen_cost.grouped_rows(hf, r["batch"] * B * k)
                     for r in recs}
        pass_rows -= {blockgen_cost.grouped_rows(hf, r["tokens_padded"] * k)
                      for r in in_slice if r["kind"] in ("prefill", "mixed")}
        kernel_s = sum(
            s for name, s, _c in mosaic_ops(trace, ("moe_grouped",))
            if int(re.search(r"\[(\d+),", name).group(1)) in pass_rows)
        if not recs or kernel_s <= 0.0:
            continue
        touched = sum(r["experts_touched"] for r in recs)
        assignments = sum(r["row_passes"] for r in recs) \
            * B * k * hf["num_hidden_layers"]
        flops, nbytes = blockgen_cost.grouped_cost(hf, dtype, touched,
                                                   assignments)
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
