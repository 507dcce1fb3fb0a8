"""Recurrent state's share of the cache bytes in use in the crowd cell: over
the window's ring records, the running rows times what a sequence keeps of
state (a float32 ``[30, 96, 192]`` a linear layer: 26.5 MB) over that plus
the pages in use times a page's bytes (16 tokens x 61,440 B), from the
ring's ``running`` and ``pool_free`` (``olmo_hybrid_cost``). Above a half
the rows are bounded by their states, below it by the full layers' keys and
values: the quantity a snapshot of states for prefix reuse would be sized
by. Nothing where the ring has no ``state_rows`` (a program without the
family)."""

import olmo_hybrid_cost as cost
from layer_metrics._ring import in_window


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    page = run.page_size * cost.kv_bytes_per_token(hf, dtype)
    state = paged = 0.0
    for r in in_window(run):
        if "state_rows" not in r:
            continue
        state += r["running"] * cost.sequence_state_bytes(hf)
        paged += (run.num_pages - r["pool_free"]) * page
    if state + paged <= 0.0:
        return None
    return 100.0 * state / (state + paged)
