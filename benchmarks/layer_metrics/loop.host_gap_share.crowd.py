"""``loop.host_gap_share`` (the host's share of the window between dispatches) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("loop.host_gap_share").compute
