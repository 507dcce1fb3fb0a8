"""Share of the keys a full-attention layer's queries could see that they
attended in the long-context cell: the step ring's ``selected_keys``
(min(index_topk, p + 1) for a query at position p) over ``score_pairs``
(p + 1), summed over the window's dispatches, in %. At contexts of 8-25 k
tokens against a selection of 2,048 most queries attend 8-25 % of what the
indexer scored. Nothing where the ring has no ``selected_keys`` (a program
without the family) or counted none."""

from layer_metrics._ring import in_window


def compute(run):
    picked = seen = 0
    for r in in_window(run):
        picked += r.get("selected_keys", 0)
        seen += r.get("score_pairs", 0) if "selected_keys" in r else 0
    return 100.0 * picked / seen if picked and seen else None
