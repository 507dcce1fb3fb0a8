"""``sched.queue_wait_share`` (the share of a request's time spent queued) in the crowd cell (a per-layer
metric lists the cells that report it, so the quantity has the cell's
name)."""

from layer_metrics import reader

compute = reader("sched.queue_wait_share").compute
