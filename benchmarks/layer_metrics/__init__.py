"""One file per per-layer metric, found by the metric's name: each has one
``compute(run)`` that returns a number, or None where there is nothing to
read. A quantity has one name in every cell that reports it; the cell is in
the entry's ``workloads``, not in the name (the names that end in a cell's
traffic mix are that cell's own cost function or kernel)."""

import importlib.util
import os


def reader(name: str):
    """The module ``layer_metrics/<name>.py``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def listed(bench: dict, group: str, cell: str) -> dict:
    """The metrics of ``bench[group]`` that ``cell`` reports, by name: the
    entries that list it, and the entries with no list, which are every
    cell's - a cell added later reports those without an edit to them."""
    return {m["name"]: m for m in bench[group]
            if cell in m.get("workloads", [cell])}


def percentile(values: list, q: float):
    """Linear interpolation between order statistics (numpy's default);
    None of no values."""
    s = sorted(values)
    if not s:
        return None
    k = (len(s) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)
