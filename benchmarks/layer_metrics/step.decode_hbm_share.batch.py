"""Decode's share of the memory roofline: the bytes one decode step has to
read (every weight once, and the cache of every running sequence, from
shapes: ``peaks.py``) over the chip's peak bytes per second, divided by the
device time the decode programs took per step in the traced slice.

Device time is that of the decode and fused multi-step programs on the
trace's ``XLA Modules`` line; steps and context come from the ring records
of the slice (a fused block of width w is w steps; its rows' context is the
mean tokens in use per running sequence, from the page pool). Reads nothing,
and returns nothing, where the trace names no decode program."""

import peaks

# the names the trace gives the decode programs today: the one-step program
# and the fused multi-step block, which is a closure and so has no name
DECODE_PROGRAMS = ("jit__step_impl(", "jit__unknown(")


def compute(run):
    if not run.device_traces:
        return None
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    page = run.page_size
    shares = []
    for trace, records in zip(run.device_traces, run.ring):
        t0, t1 = trace["mark"]["start_unix"], trace["mark"]["stop_unix"]
        recs = [r for r in records if t0 <= r["t_unix"] < t1
                and r["kind"] in ("decode", "multistep", "chained")]
        device_s = sum(s for name, s, _c in trace["modules"]
                       if name.startswith(DECODE_PROGRAMS))
        if not recs or device_s <= 0.0:
            continue
        need = 0.0
        for r in recs:
            steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
            used_tokens = (run.num_pages - r["pool_free"]) * page
            ctx = used_tokens / max(1, r["running"]) * r["rows"]
            need += steps * (peaks.weight_bytes(hf, dtype)
                             + ctx * peaks.kv_bytes_per_token(hf, dtype))
        floor_s = need / peaks.peak(run.devices[0]["kind"])[
            "hbm_bytes_per_s"]
        shares.append(100.0 * floor_s / device_s)
    return sum(shares) / len(shares) if shares else None
