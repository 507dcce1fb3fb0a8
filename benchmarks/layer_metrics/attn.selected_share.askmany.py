"""``attn.selected_share`` in the ask-many cell: the keys a layer's queries
attended over the keys its indexer scored (the step ring's ``selected_keys``
over ``score_pairs``), in % - at contexts of 24.7 k against a selection of
2,048 about a twelfth. The quantity and its reader are the long-context
cell's; a per-layer entry lists its cells under one name."""

from layer_metrics import reader

compute = reader("attn.selected_share.longctx").compute
