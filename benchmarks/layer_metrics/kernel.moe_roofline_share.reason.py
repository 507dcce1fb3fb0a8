"""The grouped expert matmul's share of its roofline in decode steps: the
least time the chip could take for the ``moe_grouped`` calls of the traced
slice's decode dispatches - the larger of their FLOPs over the peak FLOP/s
and their bytes over the peak bytes/s (``moe_cost.grouped_cost``: every
touched expert's three matrices once, 2 FLOPs per multiply-add of every
assignment) - over the device time those calls took.

The touched experts and the assignments are the step ring's, of the
``decode``/``chained``/``multistep`` records stamped inside the slice. The
trace's reduction sums a kernel's calls by shape over the whole slice, so
the decode steps' calls are told from the prefill-carrying steps' by their
row count: a decode record's step has ``batch`` token slots, so its calls
have ``moe_cost.grouped_rows(batch x experts per token)`` rows (2,176 at 16
rows), whatever the prefill-carrying steps of the slice look like - a
padded step of 8,192 slots (98,304 rows) or a token-packed one of 256
(6,144). A prefill-carrying step whose slots give the same row count could
not be told from a decode step: its calls are then left out with the
decode steps' own, and the reader returns nothing. At 16 rows the bytes
bound it. Nothing where the trace has no such call."""

import re

import moe_cost
import peaks
from layer_metrics._kernels import mosaic_ops

DECODE_KINDS = ("decode", "chained", "multistep")
PREFILL_KINDS = ("prefill", "mixed")


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        in_slice = [r for r in records if t0 <= r["t_unix"] < t1]
        recs = [r for r in in_slice
                if r["kind"] in DECODE_KINDS and r.get("experts_touched")]
        k = hf["num_experts_per_tok"]
        decode_rows = {moe_cost.grouped_rows(hf, r["batch"] * k)
                       for r in recs}
        decode_rows -= {moe_cost.grouped_rows(hf, r["tokens_padded"] * k)
                        for r in in_slice if r["kind"] in PREFILL_KINDS}
        kernel_s = sum(
            s for name, s, _c in mosaic_ops(trace, ("moe_grouped",))
            if int(re.search(r"\[(\d+),", name).group(1)) in decode_rows)
        if not recs or kernel_s <= 0.0:
            continue
        touched = sum(r["experts_touched"] for r in recs)
        assignments = sum(
            r["rows"] * hf["num_experts_per_tok"] * moe_cost.expert_layers(hf)
            * (max(1, r["width"]) if r["kind"] == "multistep" else 1)
            for r in recs)
        flops, nbytes = moe_cost.grouped_cost(hf, dtype, touched, assignments)
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
