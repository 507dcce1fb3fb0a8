"""Share of the window's row-passes that revealed nothing: the committing
passes (the step ring's ``commits``, one a finished block) over
``row_passes``, in %. What a committing pass fused with the next block's
first pass would take away. Nothing where the ring has no such field."""

from layer_metrics._ring import in_window


def compute(run):
    recs = [r for r in in_window(run, ("multistep",)) if r.get("row_passes")]
    row_passes = sum(r["row_passes"] for r in recs)
    return (100.0 * sum(r["commits"] for r in recs) / row_passes
            if recs else None)
