"""What a compiled step program does to the page pool.

The pool is donated through every step program and the new K/V rows are
scattered into it in place (``ops/attention._write_pages``). Whether that
holds is the compiler's decision: a scatter whose window is not contiguous
in the pool's layout, or an operand a kernel wants in another layout than
the scan carries, makes XLA's layout assignment copy the WHOLE pool, per
layer, and nothing in the Python says so (PERF.md section 6, PR 25: 71 %
of the chip's time). So the check reads the compiled program:
``pool_copies`` lists the instructions of an optimised HLO text that write
an array as large as the pool, and ``check_step_programs`` compiles the
decode, fused and mixed programs of an engine from shapes alone and reports
those beside ``memory_analysis()``'s temporary bytes; an engine that
serves its prefill-carrying steps token-packed has a fourth, ``packed``.
Used by
``tests/test_kv_write.py`` (toy model, CPU),
``tests/test_pallas_tpu_lowering.py`` (a tp=2 mesh, the TPU compiler) and
the kernel child of ``chip_smoke.py`` (serving geometry, on the chip).
"""

from __future__ import annotations

import math
import re
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

# HLO's names for the cache dtypes the engine serves
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
              "int8": "s8"}
# opcodes whose pool-sized result aliases their pool-sized operand: no new
# array is written. A fusion counts as one of these when its root does.
_IN_PLACE = frozenset({
    "parameter", "get-tuple-element", "bitcast", "scatter",
    "dynamic-update-slice", "optimization-barrier"})
_INSTR = re.compile(
    r"^\s*(ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$")


def pool_copies(hlo_text: str, pool_shape, dtype) -> List[str]:
    """Instructions of an optimised HLO module that write a whole array of
    the pool's dtype and element count (in any shape or layout: a copy of
    a reshaped view moves the same bytes) — ``copy``, a fusion around one,
    anything but the in-place updates. One line each, as printed."""
    want_dtype = _HLO_DTYPE[jnp.dtype(dtype).name]
    want_elems = math.prod(pool_shape)
    roots: Dict[str, str] = {}      # computation name -> its root's opcode
    found, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(2)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        is_root, _name, dt, dims, opcode, rest = m.groups()
        if is_root and comp:
            roots[comp] = opcode
        if dt != want_dtype or not dims:
            continue
        if math.prod(int(d) for d in dims.split(",")) != want_elems:
            continue
        if opcode == "fusion":
            called = re.search(r"calls=(%[\w.\-]+)", rest)
            # fused computations are printed before their callers
            if called and roots.get(called.group(1)) in _IN_PLACE:
                continue
        elif opcode in _IN_PLACE:
            continue
        found.append(line.strip())
    return found


def _pool_shape(engine, num_pages: Optional[int]):
    shape = engine.pages.shape
    if num_pages is not None:
        shape = (shape[0], num_pages) + shape[2:]
    return tuple(shape)


def lower_step_programs(engine, batch: int, chunk: int, width: int = 8,
                        sharding=None, num_pages: Optional[int] = None,
                        tokens: Optional[int] = None
                        ) -> Dict[str, "jax.stages.Lowered"]:
    """The decode step ``[batch, 1]``, the fused block of ``width`` decode
    steps, the padded prefill-carrying step ``[batch, chunk]`` and, where
    the engine packs (``engine.padded_reason`` is None), the token-packed
    step of ``tokens`` slots (default ``chunk``) over ``batch`` rows, of a
    stacked-pool engine, lowered from shapes alone (``engine.params`` may
    be abstract, nothing is placed on a device). ``sharding`` places every
    argument, for a described device; ``num_pages`` overrides the pool's
    page count."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype),
                                    engine.params)
    pages = sds(_pool_shape(engine, num_pages), engine.pages.dtype)
    i32, f32 = jnp.int32, jnp.float32

    def step_args(B, S, lead=None):
        lead = B if lead is None else lead
        return (params, pages, sds((lead, S), i32), sds((lead, S), i32),
                sds((B, engine.table_width), i32), sds((B,), i32),
                sds((B,), i32), sds((2,), jnp.uint32), sds((), i32),
                sds((B,), f32), sds((B,), i32), sds((B,), f32))

    B = batch
    # the engine's own jitted programs: the names a device trace shows and
    # the donation are the served ones
    out = {
        "decode": engine._jit_step.lower(*step_args(B, 1)),
        "fused": engine._get_jit_multistep(width).lower(
            params, pages, sds((B, 1), i32), sds((B, 1), i32),
            sds((B, engine.table_width), i32), sds((B,), i32),
            sds((B,), jnp.bool_), sds((B,), i32), sds((B,), i32),
            sds((2,), jnp.uint32), sds((), i32), sds((B,), f32),
            sds((B,), i32), sds((B,), f32), sds((B, 1), i32), None, None),
        "mixed": engine._jit_step.lower(*step_args(B, chunk)),
    }
    if engine.padded_reason is None:
        out["packed"] = engine._jit_packed.lower(
            *step_args(B, tokens or chunk, lead=1))
    return out


def check_step_programs(engine, batch: int, chunk: int, width: int = 8,
                        sharding=None, num_pages: Optional[int] = None,
                        tokens: Optional[int] = None) -> List[dict]:
    """Compile the programs and report, for each, the pool-sized
    copies in its HLO and its temporary bytes beside the pool's bytes. A
    program is ``ok`` with no such copy and temporaries under one pool."""
    lowered = lower_step_programs(engine, batch, chunk, width, sharding,
                                  num_pages, tokens)
    shape, dtype = _pool_shape(engine, num_pages), engine.pages.dtype
    pool_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    out = []
    for name, low in lowered.items():
        compiled = low.compile()
        copies = pool_copies(compiled.as_text(), shape, dtype)
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        out.append({"program": name, "pool_shape": list(shape),
                    "pool_bytes": pool_bytes, "temp_bytes": temp,
                    "pool_copies": copies,
                    "ok": not copies and temp < pool_bytes})
    return out


__all__ = ["pool_copies", "lower_step_programs", "check_step_programs"]
