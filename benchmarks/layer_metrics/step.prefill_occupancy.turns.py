"""``step.prefill_occupancy`` in the conversation cells, which are judged on ``out_tok_per_s`` (a per-layer
metric names one end-to-end metric and lists its cells, so the quantity is split)."""

from layer_metrics import reader

compute = reader("step.prefill_occupancy").compute
