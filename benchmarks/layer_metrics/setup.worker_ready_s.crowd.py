"""``setup.worker_ready_s`` (the worker's start-up until its ready line) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("setup.worker_ready_s").compute
