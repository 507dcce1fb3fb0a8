"""Share of the device's busy time in the traced slice that the Pallas
attention kernels took: the Mosaic custom calls (``tpu_custom_call``) among
the leaf operations of the trace's ``XLA Ops`` line, which carry the
kernels' function names today (``_paged_decode``, ``_ragged_mixed``...)."""


def is_attention_kernel(op_name: str) -> bool:
    """The five Pallas kernels are the program's only Mosaic calls, and the
    reduction marks those (``xplane.is_mosaic``)."""
    return op_name.endswith("[mosaic]")


def compute(run):
    if not run.device_traces:
        return None
    shares = []
    for trace in run.device_traces:
        if trace["busy_s"] <= 0.0:
            continue
        attn = sum(s for name, s, _c in trace["ops"]
                   if is_attention_kernel(name))
        shares.append(100.0 * attn / trace["busy_s"])
    return sum(shares) / len(shares) if shares else None
