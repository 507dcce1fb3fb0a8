"""The decode attention kernel against its roofline in the crowd cell, at
30 key/value heads of 128 without grouping: the least time the chip could
take to read the keys and values that the one-token rows of the traced
slice attended - one (query, key) pair is a key and a value of every
key/value head, 2 x 30 x 128 x 2 B a full layer
(``olmo_hybrid_cost.attn_pair_bytes``; the FLOPs, 4 x 30 x 128 a pair, are
a hundredth of that time) - over the device time of the Mosaic calls named
``paged_decode``. The pairs are the ring's: ``score_pairs`` of the fused
blocks and decode steps, whose rows are all of one token, and for a packed
step its ``decode_kernel_rows`` times the mean context of a running row
(pages in use over ``running``: the ring has no count of the one-token
rows' pairs alone; a packed step's rows are a tenth of the kernel's work
here). Counted from the pairs, never from the 128-token chunks the kernel
streams. Nothing where the trace has no such call or the ring no such
counts."""

import olmo_hybrid_cost as cost
from layer_metrics._olmo import floor_share, in_slice


def compute(run):
    hf, dtype = run.config["hf"], run.config["bench"]["dtype"]

    def work(trace, records):
        pairs = 0.0
        for r in in_slice(trace, records, "score_pairs"):
            if r["kind"] in ("decode", "chained", "multistep"):
                pairs += r["score_pairs"]
            elif r.get("decode_kernel_rows"):
                used = (run.num_pages - r["pool_free"]) * run.page_size
                pairs += r["decode_kernel_rows"] * used / max(1, r["running"])
        if not pairs:
            return None
        layers = cost.full_layers(hf)
        return (cost.score_flops(hf, pairs),
                pairs * layers * cost.attn_pair_bytes(hf, dtype))
    return floor_share(run, "paged_decode", work)
