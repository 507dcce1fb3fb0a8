"""``step.decode_device_ms`` (the median device time of a decode step) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("step.decode_device_ms").compute
