"""Device bytes of cache IN USE for each token of context the running rows
hold, in the ask-many cell, averaged over the window's decode dispatches:
the pages in use (the ring's ``pool_free``) times a page's bytes in both
pools (``keye_cost.cache_bytes_per_token``: keys, values and the index key,
six layers) over the tokens of context of the rows that decode (a decode
step's ``score_pairs`` a step: a row's one query sees its whole context).
Rows that ask about the same document hold its blocks ONCE - index keys
with them - so the bytes a live token costs fall far under the 13,056 B a
token of an unshared context. Nothing where the ring has no
``selected_keys`` (a program without the family)."""

import keye_cost
from layer_metrics._ring import in_window


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    per_token = keye_cost.cache_bytes_per_token(hf, dtype)
    values = []
    for r in in_window(run, ("decode", "chained", "multistep")):
        steps = max(1, r["width"]) if r["kind"] == "multistep" else 1
        live = r.get("score_pairs", 0) / steps
        held = (run.num_pages - r["pool_free"]) * run.page_size
        if not r.get("selected_keys") or live <= 0 or held <= 0:
            continue
        values.append(per_token * held / live)
    return sum(values) / len(values) if values else None
