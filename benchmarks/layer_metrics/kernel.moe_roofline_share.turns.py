"""The grouped expert matmul's share of its roofline in the decode steps of
the conversation cells: the least time the chip could take for the
``moe_grouped`` calls of the traced slice's decode dispatches - the larger
of their FLOPs over the peak FLOP/s and their bytes over the peak bytes/s
(``longcat_cost.grouped_cost``: every touched held expert's three matrices
once, 2 FLOPs per multiply-add of every pick computed here) - over the
device time those calls took.

The touched experts and the picks computed here are the step ring's
(``experts_touched``, ``moe_held_assignments``), of the ``decode`` /
``chained`` / ``multistep`` records stamped inside the slice. The trace's
reduction sums a kernel's calls by shape over the whole slice, so the
decode steps' calls are told from the packed steps' by their row count
(``longcat_cost.grouped_rows``: 1,792 at 128 rows, where a packed step of
512 slots has 8,192); a prefill-carrying step whose slots give the same
row count is left out with the decode steps' own, and the reader returns
nothing. At 128 rows x 12 picks of which a forty-eighth land here, the
bytes bound it. Nothing where the trace has no such call or the ring no
such counts."""

import re

import longcat_cost
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
        recs = [r for r in in_slice if r["kind"] in DECODE_KINDS
                and r.get("experts_touched")
                and "moe_held_assignments" in r]
        decode_rows = {longcat_cost.grouped_rows(hf, r["batch"])
                       for r in recs}
        decode_rows -= {longcat_cost.grouped_rows(hf, r["tokens_padded"])
                        for r in in_slice if r["kind"] in PREFILL_KINDS}
        kernel_s = sum(
            s for name, s, _c in mosaic_ops(trace, ("moe_grouped",))
            if int(re.search(r"\[(\d+),", name).group(1)) in decode_rows)
        if not recs or kernel_s <= 0.0:
            continue
        flops, nbytes = longcat_cost.grouped_cost(
            hf, dtype, sum(r["experts_touched"] for r in recs),
            sum(r["moe_held_assignments"] for r in recs))
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
