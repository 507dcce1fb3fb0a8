"""``step.compiles_in_window`` (first calls of step programs inside the window) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("step.compiles_in_window").compute
