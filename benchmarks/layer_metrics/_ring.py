"""What several readers share: the step-ring records of the window."""


def in_window(run, kinds=None) -> list:
    """Ring records of every worker stamped inside the measured window."""
    lo, hi = run.t0_unix, run.t0_unix + run.seconds
    return [r for recs in run.ring for r in recs
            if lo <= r["t_unix"] < hi and (kinds is None
                                           or r["kind"] in kinds)]
