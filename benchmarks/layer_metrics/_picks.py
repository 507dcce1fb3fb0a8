"""What the readers of the router's pick counts share: a ring field's sum
over the window as a share of every pick (``moe_assignments``)."""

from layer_metrics._ring import in_window


def pick_share(run, field: str):
    """Sum of ``field`` over sum of ``moe_assignments``, %, over the
    window's dispatches; None where the ring has no such counts (a program
    whose expert layer cannot be told which experts it holds)."""
    part = picks = 0
    for r in in_window(run):
        if not r.get("moe_assignments"):
            continue
        part += r.get(field, 0)
        picks += r["moe_assignments"]
    return 100.0 * part / picks if picks else None
