"""The stages of a step program: the one table of names its device time is
read by.

A step program names what it is doing with ``stage(name)``, a
``jax.named_scope`` whose name this table has to hold. The name reaches a
chip's trace as the ``tf_op`` stat of every operation traced under it
(``docs/observability.md``, "Names in the device trace"), and the readers -
``benchmarks/scopespans.py`` for a benchmark run, ``tools/xplane_scopes.py
--by-stage`` for a trace taken with ``POST /v1/profile`` - sum the device's
seconds by stage and by the stage's GROUP. The groups are the same for every
family and cut a layer at the same places in each, so that one family's
``ffn`` is another's:

``mixer_in``      a token mixer's way in: norm, q/k/v (or ``q|k|v|z``,
                  ``b|a``) projections, compressed query, rotary, qk-norm,
                  causal convolution
``cache_write``   the write of new keys / values / latents / index keys /
                  window slots into the pools, and of a recurrent state and
                  its carried convolution inputs where it is not the kernel's
``mixer``         the mixing itself: the attention / delta-rule kernels and
                  what feeds them (page gathers, chunk plans, the indexer's
                  scores and selection, gates)
``mixer_out``     output gate, output projection, branch norm, the residual
                  add
``ffn``           the dense FFN or the expert layer, its norm and residual
``around_layers`` everything a step does outside the layer loop

A name is a path (``layer.moe/route``): ``stage("route")`` inside
``stage("layer.moe")`` opens it. A stage's group is that of the longest
registered prefix of its path. Names are metadata of the compiled program,
written while it is traced: a running step pays nothing for them, and
``stage`` refuses an unknown name then, not later.

``engine/program_check.unstaged`` lists what a step program traces under no
stage (nothing, held by ``tests/test_stages.py``); what a chip's trace then
still shows without one is what was ADDED to the program: by the compiler
(relayouts of parameters in the entry computation, ``copy-start`` /
``-done``, layout copies) and by jax's lowering of a loop (a ``lax.scan``'s
slices of the stack it scans, ``while/body/dynamic_slice`` at the scan's
own line - no equation of the program, and a copy of a layer's weights
where the compiler gives the slice another layout). That is the ``unnamed``
share of ``stage.unnamed_time_share``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax

GROUPS = ("mixer_in", "cache_write", "mixer", "mixer_out", "ffn",
          "around_layers")
# what the readers count operations under that no stage covers
UNNAMED = "unnamed"


def _table(group: str, *names: str) -> Dict[str, str]:
    return {name: group for name in names}


# path -> group. A child is listed only where a reader is meant to see it
# apart from its parent; its group is its own (an indexer's key written
# under ``layer.kv_write`` is a cache write wherever it was computed).
STAGES: Dict[str, str] = {
    **_table("mixer_in",
             "layer.attn_in", "layer.attn_in/q_compress",
             "layer.attn_in/qk_norm", "layer.gdn_in", "layer.weights",
             "layer.attn0/in", "layer.attn0/in/q_compress",
             "layer.attn1/in", "layer.attn1/in/q_compress"),
    **_table("cache_write",
             "layer.kv_write", "layer.gdn_in/conv_write",
             "layer.attn0/kv_write",
             "layer.attn1/kv_write"),
    **_table("mixer",
             "layer.attn", "layer.attn/index/score",
             "layer.attn/index/topk", "layer.attn/sparse",
             "layer.attn/window", "layer.gdn",
             "layer.attn0/attn", "layer.attn1/attn"),
    **_table("mixer_out",
             "layer.attn_out", "layer.attn_out/gate", "layer.gdn_out",
             "layer.attn0/out", "layer.attn1/out"),
    **_table("ffn",
             "layer.ffn", "layer.ffn0", "layer.ffn1", "layer.moe",
             "layer.moe/route", "layer.moe/sort", "layer.moe/experts",
             "layer.moe/shared", "layer.moe/combine"),
    **_table("around_layers",
             "embed", "logits", "sample", "sample/top_candidates",
             "pass/confidence", "pass/reveal", "pass/commit",
             "step.inputs", "step.chain", "step.stop", "step.counts"),
}

def _parts_of_paths() -> frozenset:
    """Every run of components of a registered path: what ``stage`` may be
    asked to open (``route`` of ``layer.moe/route``)."""
    names = set()
    for path in STAGES:
        parts = path.split("/")
        names.update("/".join(parts[a:b]) for a in range(len(parts))
                     for b in range(a + 1, len(parts) + 1))
    return frozenset(names)


_NAMES = _parts_of_paths()


def stage(name: str):
    """``jax.named_scope(name)`` for a name the table holds: a registered
    path or a part of one (``stage("route")`` inside ``stage("layer.moe")``
    is ``layer.moe/route``; a helper traced alone, by a test or a tool,
    opens its part under nothing). Anything else raises ``KeyError`` while
    the program is traced. Whether the parts add up to a registered path
    is ``engine/program_check.unstaged``'s to say: it reads the whole
    program."""
    if name not in _NAMES:
        raise KeyError(
            f"{name!r} is not a stage of a step program: register it in "
            "dynamo_tpu/engine/stages.py STAGES (and in "
            "docs/observability.md, 'Names in the device trace')")
    return jax.named_scope(name)


def stage_of(path: str) -> Optional[str]:
    """The longest registered prefix of a path of scopes (``layer.moe/sort``
    of ``layer.moe/sort/anything``), or None where none is registered."""
    parts = path.split("/")
    for n in range(len(parts), 0, -1):
        if "/".join(parts[:n]) in STAGES:
            return "/".join(parts[:n])
    return None


def group_of(path: str) -> str:
    """The group of ``stage_of(path)``; ``UNNAMED`` where there is none."""
    found = stage_of(path)
    return UNNAMED if found is None else STAGES[found]


def as_attribute() -> str:
    """The table as one string, ``group:stage,stage;group:...``: what the
    worker's ``startup.engine`` span carries, so that a reader of the
    process's device trace needs no copy of the table."""
    return ";".join(
        f"{group}:" + ",".join(s for s, g in STAGES.items() if g == group)
        for group in GROUPS)


__all__ = ["GROUPS", "UNNAMED", "STAGES", "stage", "stage_of", "group_of",
           "as_attribute"]
