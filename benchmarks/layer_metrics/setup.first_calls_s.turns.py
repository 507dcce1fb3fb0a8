"""``setup.first_calls_s`` under the name the conversation cells report it by, like the other readers of
these cells; it moves ``setup_s`` wherever it is read."""

from layer_metrics import reader

compute = reader("setup.first_calls_s").compute
