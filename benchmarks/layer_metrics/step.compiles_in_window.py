"""First calls of fresh step programs inside the window (the workers'
``compile_events_total`` at its end minus at its start). Warm-up is meant
to leave none; each one stalls every request in the batch."""


def compute(run):
    return run.compiles_in_window
