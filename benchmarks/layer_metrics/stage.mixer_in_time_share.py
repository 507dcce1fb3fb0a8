"""Share of the device's busy time in the traced slice under a token mixer's
way in (norm, q/k/v or ``q|k|v|z`` / ``b|a`` projections, compressed query,
rotary, qk-norm, causal convolution, a layer's leaves read from their stack:
``layer.attn_in``, ``layer.gdn_in``, ``layer.attn<i>/in``,
``layer.weights``). One of the seven ``stage.*_time_share`` that partition
the busy time (``scopespans.py``: the leaf operations of the ``XLA Ops``
line by the ``tf_op`` of their event metadata against the program's table of
stages, ``dynamo_tpu/engine/stages.py``, shipped on the worker's
``startup.engine`` span); a share rises when anything else falls, so the
seven are read together. The whole table goes to
``stage_times.worker<i>.json``. Nothing where the program ships no table,
the trace names no scope or nothing on the machine reads event metadata."""

import scopespans


def compute(run):
    return scopespans.share(run, "mixer_in")
