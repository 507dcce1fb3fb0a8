"""What the kernel readers share: the Mosaic calls of the traced slice by
the kernel's name. ``xplane.py`` names an operation ``%<name>.<n> <kind>
<shape>`` and marks a Mosaic custom call; a Pallas kernel's ``name`` is its
instruction's."""

from fnmatch import fnmatchcase


def mosaic_ops(trace: dict, names: tuple) -> list:
    """``[op name, seconds, calls]`` of the slice's Mosaic calls whose
    kernel is one of ``names`` (a name, or a pattern such as ``mla_*``)."""
    def kernel(op_name: str) -> str:
        return op_name.split(" ", 1)[0].lstrip("%").split(".", 1)[0]
    return [op for op in trace["ops"] if op[0].endswith("[mosaic]")
            and any(fnmatchcase(kernel(op[0]), n) for n in names)]


def time_share(run, names: tuple):
    """Share of the device's busy time that the named kernels took, %,
    averaged over workers; None where no trace has such a call."""
    shares = []
    for trace in run.device_traces:
        ops = mosaic_ops(trace, names)
        if ops and trace["busy_s"] > 0.0:
            shares.append(100.0 * sum(s for _n, s, _c in ops)
                          / trace["busy_s"])
    return sum(shares) / len(shares) if shares else None
