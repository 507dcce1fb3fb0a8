"""Device bytes of cache IN USE for each token of context held, in the
long-context cell, averaged over the window's dispatches: the pages in use
(the ring's ``pool_free``) times a page's bytes in the latent and the
index pool of the three full layers, plus one window slot
(``dots3_cost.window_bytes_per_sequence``: six rings, whatever the
context) for every running row, over the tokens those pages hold. A
whole-context window cache would add 6 x 2,176 B a token; the rings add
their fixed size over the row's context, which FALLS as contexts grow.
Pages are given for a whole prompt at admission, so a row still in prefill
counts its tokens early. Nothing where the ring has no ``selected_keys``
(a program without the family)."""

import dots3_cost
from layer_metrics._ring import in_window


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]
    wargs = run.config["bench"]["worker_args"]
    chunk = (int(wargs[wargs.index("--max-prefill-chunk") + 1])
             if "--max-prefill-chunk" in wargs else 1024)
    per_token = dots3_cost.page_bytes_per_token(hf, dtype)
    per_row = dots3_cost.window_bytes_per_sequence(hf, dtype, chunk)
    values = []
    for r in in_window(run):
        tokens = (run.num_pages - r["pool_free"]) * run.page_size
        if "selected_keys" not in r or tokens <= 0 or not r["running"]:
            continue
        values.append(per_token + r["running"] * per_row / tokens)
    return sum(values) / len(values) if values else None
