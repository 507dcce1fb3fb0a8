"""What the readers of the dense hybrid family's kernels share: a kernel's
share of its roofline over the traced slice, with the work counted from
the rule and the attention's pairs (``olmo_hybrid_cost``) and the step
ring's counts - never from a kernel's padded tiles."""

import olmo_hybrid_cost as cost
import peaks
from layer_metrics._kernels import mosaic_ops


def in_slice(trace: dict, records: list, field: str) -> list:
    """The worker's ring records stamped inside the traced slice that
    carry ``field`` (a program without the family writes none)."""
    t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
    return [r for r in records if t0 <= r["t_unix"] < t1 and field in r]


def floor_share(run, kernel: str, work):
    """``work(trace, records) -> (FLOPs, bytes)`` the named kernel's calls
    of the slice had to do (None: none). The least time the chip could
    take for it - the larger of FLOPs over the peak FLOP/s and bytes over
    the peak bytes/s - over the device time of the Mosaic calls named
    ``kernel``, in %, averaged over workers. Nothing where the trace has no
    such call or the ring no such counts."""
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        kernel_s = sum(s for _n, s, _c in mosaic_ops(trace, (kernel,)))
        got = work(trace, records)
        if not got or kernel_s <= 0.0:
            continue
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(got[0] / peak["bf16_flops_per_s"],
                      got[1] / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None


def rule_share(run, kernel: str, work):
    """``floor_share`` of one of the rule's kernels: ``work(record) ->
    (tokens, rows)`` through ONE linear layer (None: no such work)."""
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    layers = cost.linear_layers(hf)

    def total(trace, records):
        tokens = rows = 0
        for r in in_slice(trace, records, "gdn_tokens"):
            got = work(r)
            if got:
                tokens += got[0] * layers
                rows += got[1] * layers
        return cost.rule_cost(hf, dtype, tokens, rows) if tokens else None
    return floor_share(run, kernel, total)
