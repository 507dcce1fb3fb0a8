"""The window layers' attention against its roofline in the long-context
cell: the least time the chip could take to attend a window of at most 513
keys for every token of the traced slice's prompt chunks
(``dots3_cost.window_attn_cost`` over ``record_window_pairs``, less the
one-token rows' share; the chunk's queries read the chunk and the window
before it ONCE, ``chunk_window_keys``, so the FLOPs bound it) in the six
window layers, over the device time of the ``mla_window`` calls. The kernel streams the whole ring (1,024
positions at a chunk of 512) and pads the rotary key to the latent's
width. Nothing where the trace has no such call or the ring no
``selected_keys``."""

import dots3_cost
from layer_metrics._dots3 import chunk_share, roofline_share


def _work(hf, dtype, r):
    chunk = r.get("tokens_real", 0) * chunk_share(r)
    return dots3_cost.window_attn_cost(
        hf, dtype, dots3_cost.record_window_pairs(hf, r) * chunk_share(r),
        dots3_cost.chunk_window_keys(hf, chunk))


def compute(run):
    return roofline_share(run, "mla_window",
                          dots3_cost.kinds(run.config["hf"])[1], _work)
