"""``step.prefill_occupancy`` (real over padded token slots of the prefill-carrying steps) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("step.prefill_occupancy").compute
