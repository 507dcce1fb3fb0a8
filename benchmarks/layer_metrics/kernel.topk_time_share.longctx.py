"""Share of the device's busy time in the traced slice that the SORT of the
selection took in the long-context cell: the operations of kind ``sort``
whose rows are as long as the page table's tokens (``--max-context``:
``s32[rows, 1, 25600]``) among the slice's costliest (``xplane.py`` keeps
the sixty largest) over busy time. That is one stage of the exact top
2,048 and not all of it: the rows of ONE token find the selection as a
mask (``ops/sparse_latent.topk_mask``: a threshold search, plain fusions
the trace gives no name) and bring its positions to the front by this
sort of one operand; the rows of several tokens stop at the mask and sort
nothing. The expert layer's sorts (``moe.grouped_experts``: a step's picks,
other lengths) are not counted. Nothing where the trace has no such
operation."""

import re


def _selection_sort(name: str, tokens: int) -> bool:
    kind = name.split(" ", 2)[1:2] == ["sort"] or name.startswith("%sort")
    shape = re.search(r"\[([\d,]+)\]", name)
    return bool(kind and shape
                and int(shape.group(1).split(",")[-1]) == tokens)


def compute(run):
    args = run.config["bench"]["worker_args"]
    if "--max-context" not in args:
        return None
    tokens = int(args[args.index("--max-context") + 1])
    shares = []
    for trace in run.device_traces:
        took = sum(s for name, s, _c in trace["ops"]
                   if _selection_sort(name, tokens))
        if took and trace["busy_s"] > 0.0:
            shares.append(100.0 * took / trace["busy_s"])
    return sum(shares) / len(shares) if shares else None
