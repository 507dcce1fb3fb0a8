"""What the readers of dots3-note-prev's attention kernels share: a
kernel's share of its roofline over the traced slice, with the work counted
from the mathematics (``dots3_cost``: the SELECTED rows, the window's keys)
and the step ring's counts, not from what the kernel streams."""

import dots3_cost
import peaks
from layer_metrics._kernels import mosaic_ops


def roofline_share(run, kernel: str, layers: int, work):
    """``work(hf, dtype, record) -> (FLOPs, bytes)`` of ONE layer for a
    prefill-carrying ring record of the slice. The least time the chip
    could take for that work in ``layers`` layers - the larger of FLOPs
    over the peak FLOP/s and bytes over the peak bytes/s - over the device
    time of the Mosaic calls named ``kernel``, in %, averaged over workers.
    Nothing where the trace has no such call or the ring no
    ``selected_keys`` (a program without the family)."""
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        flops = nbytes = 0.0
        for r in records:
            if (t0 <= r["t_unix"] < t1 and "selected_keys" in r
                    and r["kind"] in ("prefill", "mixed")):
                f, b = work(hf, dtype, r)
                flops += f * layers
                nbytes += b * layers
        kernel_s = sum(s for _n, s, _c in mosaic_ops(trace, (kernel,)))
        if not flops or kernel_s <= 0.0:
            continue
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / kernel_s)
    return sum(shares) / len(shares) if shares else None


def chunk_share(r: dict) -> float:
    """The share of a prefill-carrying record's tokens that sit in rows of
    several tokens, taking every row but one for a one-token row (the
    cell's steps carry one chunk): what of the record's counts the masked
    kernels ran (the one-token rows run the gathered form in XLA)."""
    tokens = float(r.get("tokens_real", 0))
    return max(0.0, tokens - max(0, r.get("rows", 1) - 1)) / tokens \
        if tokens else 0.0
