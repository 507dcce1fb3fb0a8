"""Real tokens over padded tokens in the prefill and mixed step programs
of the window: a step is padded to [power-of-two rows, power-of-two
length], and the device computes every padded slot."""

from layer_metrics._ring import in_window


def compute(run):
    recs = in_window(run, ("prefill", "mixed"))
    padded = sum(r["tokens_padded"] for r in recs)
    if not padded:
        return None
    return 100.0 * sum(r["tokens_real"] for r in recs) / padded
