"""What the readers of keye-vl-2.0-30b-a3b's indexer and attention share: a
mechanism's share of its roofline over the traced slice, with the work
counted from the mathematics (``keye_cost``: the visible index keys once a
row, the SELECTED tokens a query) and the step ring's counts, not from what
the program streams; and the device seconds the program spent under a stage
(``scopespans``'s table of the slice)."""

import json
import os

import peaks
import scopespans

# the stages the indexer is traced under (plain XLA: no kernel's name finds
# it) and the Mosaic calls that attend the selection
INDEX_STAGES = ("layer.attn/index/score", "layer.attn/index/topk")
SELECTED_KERNELS = ("selected_rows", "selected_chunks")


def stage_seconds(run, i: int, paths: tuple):
    """Device seconds of worker ``i``'s traced slice under the stages
    ``paths`` (``stage_times.worker<i>.json``, which a ``stage.*`` reader
    leaves; made here where none has run yet), and the slice's busy
    seconds: ``(seconds, busy_s)``, or None where there is no table."""
    path = os.path.join(run.run_dir, f"stage_times.worker{i}.json")
    if not os.path.exists(path):
        scopespans.share(run, "mixer")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        red = json.load(f)
    if not red or not red.get("stages"):
        return None
    return (sum(red["stages"][p]["seconds"] for p in paths
                if p in red["stages"]), red["busy_s"])


def slice_records(trace: dict, records: list) -> list:
    """The ring records of a worker stamped inside its traced slice that
    count a selection (a program without the family counts none)."""
    t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
    return [r for r in records
            if t0 <= r["t_unix"] < t1 and r.get("selected_keys")]


def roofline_share(run, seconds_of, work):
    """``work(hf, dtype, record) -> (FLOPs, bytes)`` of ONE layer for a
    ring record of the slice. The least time the chip could take for that
    work in every layer - the larger of FLOPs over the peak FLOP/s and
    bytes over the peak bytes/s - over ``seconds_of(i, trace)``, the device
    time the mechanism took in worker ``i``'s slice, in %, averaged over
    workers. Nothing where that time is nothing or the ring counts no
    selection."""
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    layers = hf["num_hidden_layers"]
    shares = []
    for i, (trace, records) in enumerate(zip(run.device_traces, run.ring)):
        flops = nbytes = 0.0
        for r in slice_records(trace, records):
            f, b = work(hf, dtype, r)
            flops += f * layers
            nbytes += b * layers
        took = seconds_of(i, trace)
        if not flops or not took or took <= 0.0:
            continue
        peak = peaks.peak(run.devices[0]["kind"])
        floor_s = max(flops / peak["bf16_flops_per_s"],
                      nbytes / peak["hbm_bytes_per_s"])
        shares.append(100.0 * floor_s / took)
    return sum(shares) / len(shares) if shares else None
