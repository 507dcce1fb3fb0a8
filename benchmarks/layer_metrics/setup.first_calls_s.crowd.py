"""``setup.first_calls_s`` (the first calls of step programs before the window) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("setup.first_calls_s").compute
