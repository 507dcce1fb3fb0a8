"""What the readers of the gated delta rule's kernels share: a kernel's
share of its roofline over the traced slice, with the work counted from
the rule (``gdn_cost.rule_cost``) and the step ring's counts."""

import gdn_cost
import peaks
from layer_metrics._kernels import mosaic_ops


def roofline_share(run, kernel: str, work):
    """``work(record) -> (tokens, rows)`` through ONE linear layer for a
    ring record of the slice (None: the record has no such work). The
    least time the chip could take for that work in every linear layer -
    the larger of FLOPs over the peak FLOP/s and bytes over the peak
    bytes/s - over the device time of the Mosaic calls named ``kernel``,
    in %, averaged over workers. Nothing where the trace has no such call
    or the ring no such counts (a program without the family)."""
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    layers = gdn_cost.linear_layers(hf)
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        tokens = rows = 0
        for r in records:
            if t0 <= r["t_unix"] < t1 and "gdn_tokens" in r:
                got = work(r)
                if got:
                    tokens += got[0] * layers
                    rows += got[1] * layers
        kernel_s = sum(s for _n, s, _c in mosaic_ops(trace, (kernel,)))
        if not tokens or kernel_s <= 0.0:
            continue
        flops, nbytes = gdn_cost.rule_cost(hf, dtype, tokens, rows)
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None
