"""``setup.worker_ready_s`` in the block-generation cells, which are judged on ``setup_s`` (a per-layer
metric names one end-to-end metric and lists its cells, so the quantity is split)."""

from layer_metrics import reader

compute = reader("setup.worker_ready_s").compute
