"""``loop.idle_behind_host_share`` in the cells judged on ``out_tok_per_s`` (a per-layer metric
names one end-to-end metric, so the quantity is split by what it moves)."""

from layer_metrics import reader

compute = reader("loop.idle_behind_host_share").compute
