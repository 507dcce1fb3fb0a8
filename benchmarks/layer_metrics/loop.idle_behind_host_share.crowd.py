"""``loop.idle_behind_host_share`` (the device's idle share behind the host) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("loop.idle_behind_host_share").compute
