"""What a compiled step program does to the page pool, and to the logits.

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

The second line it holds is the sampler's: ``lax.top_k`` over the
vocabulary is, on the chip, a sort of every column of every row (PERF.md
section 6, PR 30: 19 % of the cell's time), which
``ops/sampling.top_candidates`` replaces where the vocabulary is large
enough to pay for it. ``vocab_sorts`` lists the instructions of an
optimised HLO text that order an axis as long as the vocabulary.

The third is the expert layer's: ``expert_temporaries`` lists the
instructions that write a ``[tokens, experts, expert width]`` array, which
the mask form of a mixture layer does and the grouped one must not
(``tests/test_moe_grouped.py``).

The fourth is the weights': a forward that scans over PERIODS of layers and
hands the inner loop its period's ``[G, ...]`` slice of a ``[P, G, ...]``
stack has that slice written first - a loop's operand has to be a buffer -
so every non-expert matrix of a period is read and written once a period a
step before any matmul reads it: 1.00 s of an 8 s slice of
``dots3-note-prev.longctx``, 6.5 ms of an 88 ms packed step and 4.9 of a
19 ms decode step, with no scope to its name (PERF.md section 5, PR 49);
1.3 GB of temporaries and a near-doubled decode step at Olmo-Hybrid's
widths, found in the compiled program before any chip call (section 6,
PR 51). The families index one flat stack inside the inner body instead
(``models/moe.flat_layers``, PR 52) and ``weight_copies`` lists what a
compiled program still writes of a weight outside a matmul, by where it
runs: inside a loop's body (to be empty), in the entry computation (the
layout a consumer wants of a whole stack, once a dispatch: listed), or
into the chip's fast memory (a fetch ahead of the matmul, no traffic of
its own).

The fifth is the trace's: a chip's profile names an operation by the stage
it was traced under (``engine/stages.py``), and an equation traced under
none is device time no reader can place (13.5 % of a cell's busy time hid so
until PR 52). ``unstaged`` lists those of a step program from its jaxpr - no
compile - and ``check_step_programs`` reports them: to be empty, so that
what a trace still shows without a stage is the compiler's own
(``tests/test_stages.py``).
Used by
``tests/test_kv_write.py``, ``tests/test_sampling_topk.py`` and
``tests/test_weight_copies.py`` (toy models, CPU),
``tests/test_pallas_tpu_lowering.py`` (a tp=2 mesh and the cells' step
programs at the published widths, the TPU compiler) and
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
# a computation's first line: ``[ENTRY ]%name (parameters) -> type {``
_HEAD = re.compile(r"^(ENTRY )?(%[\w.\-]+) \(.*\{\s*$")


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
        head = _HEAD.match(line)
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


_ORDERING = re.compile(r" (sort|custom-call)\(([^)]*)\)")


def vocab_sorts(hlo_text: str, vocab: int) -> List[str]:
    """Instructions of an optimised HLO module that order an axis of
    ``vocab`` columns: a ``sort``, or XLA's ``TopK`` custom call (what a
    lone ``lax.top_k`` becomes), on an operand that carries that axis.
    One line each, as printed."""
    want = str(vocab)
    wide = set()        # names of the arrays that carry the axis
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and want in m.group(4).split(","):
            wide.add(m.group(2))
        m = _ORDERING.search(line)
        if (m and (m.group(1) == "sort"
                   or 'custom_call_target="TopK"' in line)
                and any(a.strip() in wide for a in m.group(2).split(","))):
            found.append(line.strip())
    return found


def expert_temporaries(hlo_text: str, tokens: int, experts: int,
                       width: int) -> List[str]:
    """Instructions of an optimised HLO module that write an array with a
    token, an expert and an expert-width axis at once - ``[T, E, I]`` in
    any order: what computing every expert on every token materialises
    (13 GB at 64 experts over ``[64, 1024]`` slots; PERF.md section 4) and
    the grouped expert layer (``models/moe.grouped_experts``) never does.
    One line each, as printed."""
    want = sorted((tokens, experts, width))
    found = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and m.group(4) and sorted(
                int(d) for d in m.group(4).split(",")) == want:
            found.append(line.strip())
    return found


# opcodes that move an array's elements and compute nothing: a value that
# reaches a weight through these alone is that weight, written again
_MOVES = frozenset({"copy", "transpose", "reshape", "slice", "dynamic-slice",
                     "copy-done"})
# ... and those that write nothing: the same buffer under another name
_VIEWS = frozenset({"bitcast", "get-tuple-element", "tuple", "while",
                    "optimization-barrier", "parameter", "copy-start"})
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_CALLED = re.compile(r"(body|calls|to_apply)=(%[\w.\-]+)")


def _closing(text: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i
    return len(text) - 1


def _computations(hlo_text: str):
    """``(name -> instructions, entry's name)`` of an HLO module's text. An
    instruction is ``(is_root, name, type, opcode, operands, attributes,
    line)``; ``type`` is the printed result type, a tuple's included."""
    comps, entry, body = {}, None, None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head:
            body = comps[head.group(2)] = []
            entry = head.group(2) if head.group(1) else entry
            continue
        m = re.match(r"^\s*(ROOT )?(%[\w.\-]+) = (.*)$", line)
        if not m or body is None:
            continue
        rest = m.group(3)
        cut = (_closing(rest, 0) + 1 if rest.startswith("(")
               else rest.index(" "))
        typ, rest = rest[:cut], rest[cut:].lstrip()
        op = re.match(r"([\w\-]+)\(", rest)
        if not op:
            continue
        end = _closing(rest, op.end() - 1)
        body.append((bool(m.group(1)), m.group(2), typ, op.group(1),
                     re.findall(r"%[\w.\-]+", rest[op.end():end]),
                     rest[end + 1:], line.strip()))
    return comps, entry


def _array_bytes(typ: str) -> List[int]:
    """Bytes of each array a printed result type holds."""
    return [_ITEMSIZE.get(dt, 4)
            * math.prod(int(d) for d in dims.split(",") if d)
            for dt, dims in _ARRAY.findall(typ)]


def weight_copies(hlo_text: str, params, min_bytes: int = 4 << 20
                  ) -> Dict[str, List[dict]]:
    """Instructions of an optimised HLO module that write a weight again:
    at least ``min_bytes`` whose operand chain leads back to an entry
    parameter with the dtype and shape of a leaf of ``params`` through
    nothing but moves (slices, copies, transposes, a fusion of those) -
    never a ``dot``, a ``convolution`` or a kernel, which READ a weight,
    nor a bitcast, which writes nothing. An array that merely has a
    weight's element count is not one: ``bf16[38400,16,128]``, a step's
    gathered index keys, counts what five layers of ``wq_a`` count.

    Told apart by where they run and where they write: ``"loop"``, inside
    a ``while`` body to the device's main memory (once a period or a layer
    of every step - a loop handed a slice of a stack,
    ``models/moe.flat_layers``: to be empty but for what a test names);
    ``"entry"``, outside every loop (once a dispatch: the layout a consumer
    wants of a whole stack, hoisted; listed, not failed); ``"on_chip"``,
    a result the compiler placed in the chip's fast memory (``S(1)`` in
    its layout: a layer's matrix fetched ahead of the matmul that reads it
    there, in place of that matmul's own read). Each ``{"bytes", "leaf",
    "line"}``, the leaf by its parameter's name."""
    leaves = {(_HLO_DTYPE.get(jnp.dtype(l.dtype).name), tuple(l.shape))
              for l in jax.tree_util.tree_leaves(params)}
    comps, entry = _computations(hlo_text)
    found: Dict[str, List[dict]] = {"loop": [], "entry": [], "on_chip": []}

    def walk(comp: str, args: list, where: Optional[str]):
        """The root's weight (a leaf's name, a dict of them by index for a
        tuple, or None) of a computation called with ``args``; ``where``
        is None inside a fusion, whose caller is the one that writes."""
        held: Dict[str, object] = {}
        root = None
        for is_root, name, typ, opcode, operands, attrs, line in comps[comp]:
            ins = [held.get(o) for o in operands]
            first = ins[0] if ins else None
            out = None
            if opcode == "parameter":
                index = int(re.search(r"parameter\((\d+)\)", line).group(1))
                if comp == entry:
                    m = _ARRAY.match(typ)
                    shape = tuple(int(d) for d in m.group(2).split(",") if d)
                    out = name if (m.group(1), shape) in leaves else None
                elif index < len(args):
                    out = args[index]
            elif opcode == "get-tuple-element":
                index = int(re.search(r"index=(\d+)", attrs).group(1))
                out = first.get(index) if isinstance(first, dict) else None
            elif opcode == "tuple":
                out = dict(enumerate(ins))
            elif opcode == "while":
                # a weight is loop-invariant: what goes in at an index
                # comes out at it
                body = dict(_CALLED.findall(attrs)).get("body")
                walk(body, [first], "loop")
                out = first
            elif opcode in ("fusion", "call"):
                called = dict(_CALLED.findall(attrs))
                out = walk(called.get("calls") or called.get("to_apply"),
                           ins, None if opcode == "fusion" else where)
            elif opcode in _MOVES or opcode in _VIEWS:
                out = first
            held[name] = out
            if is_root:
                root = out
            if where is None or opcode in _VIEWS or opcode == "call":
                continue
            outs = out.values() if isinstance(out, dict) else [out]
            for leaf, size, layout in zip(outs, _array_bytes(typ),
                                          re.findall(r"\]\{([^}]*)\}", typ)):
                if isinstance(leaf, str) and size >= min_bytes:
                    found["on_chip" if "S(1)" in layout else where].append(
                        {"bytes": size, "leaf": leaf, "line": line[:200]})
        return root

    walk(entry, [], "entry")
    return found


def _equations(fn, args):
    """``(equation, scopes)`` of every leaf equation of the jitted ``fn``
    traced at ``args`` (shapes will do; nothing is lowered or compiled):
    loops, branches and calls are walked into with the scopes that were
    open around them, a Pallas kernel is one equation, and a
    ``fori_loop``'s own counter - jax's equation, traced by no line of the
    program - is left out."""
    from jax._src import source_info_util

    def loop_counter(eqn) -> bool:
        for frame in eqn.source_info.traceback.frames:
            if source_info_util.is_user_filename(frame.file_name):
                return False
            if "_fori_" in frame.function_name:
                return True
        return False

    def walk(jaxpr, outer: tuple):
        for eqn in jaxpr.eqns:
            scopes = outer + tuple(
                s.name for s in eqn.source_info.name_stack.stack
                if type(s).__name__ == "Scope")
            inner = [] if eqn.primitive.name == "pallas_call" else [
                getattr(v, "jaxpr", v) for p in eqn.params.values()
                for v in (p if isinstance(p, (tuple, list)) else (p,))
                if hasattr(getattr(v, "jaxpr", v), "eqns")]
            for sub in inner:
                yield from walk(sub, scopes)
            if not inner and not loop_counter(eqn):
                yield eqn, "/".join(scopes)

    yield from walk(fn.trace(*args).jaxpr.jaxpr, ())


def unstaged(fn, args) -> List[dict]:
    """The equations of a step program that no registered stage covers
    (``engine/stages.py``), each as ``{"primitive", "scopes", "bytes",
    "source"}``: what it writes and the line that traced it."""
    from jax._src import source_info_util

    from dynamo_tpu.engine.stages import stage_of

    return [{
        "primitive": eqn.primitive.name,
        "scopes": scopes,
        "bytes": sum(
            math.prod(v.aval.shape) * jnp.dtype(v.aval.dtype).itemsize
            for v in eqn.outvars if hasattr(v.aval, "shape")),
        "source": source_info_util.summarize(eqn.source_info)}
        for eqn, scopes in _equations(fn, args)
        if stage_of(scopes) is None]


def stages_opened(fn, args) -> Dict[str, int]:
    """``stage -> equations traced under it`` of a step program: the
    stages a chip's trace of it can show."""
    from dynamo_tpu.engine.stages import stage_of

    found: Dict[str, int] = {}
    for _eqn, scopes in _equations(fn, args):
        stage = stage_of(scopes)
        if stage is not None:
            found[stage] = found.get(stage, 0) + 1
    return found


def _pool_shape(engine, num_pages: Optional[int]):
    """The paged pool's shape (of a family with a recurrent state,
    ``engine.pages`` holds the state pools beside it)."""
    shape = engine.kv_pool.shape
    if num_pages is not None:
        shape = (shape[0], num_pages) + shape[2:]
    return tuple(shape)


def step_programs(engine, batch: int, chunk: int, width: int = 8,
                  sharding=None, num_pages: Optional[int] = None,
                  tokens: Optional[int] = None) -> Dict[str, tuple]:
    """``name -> (jitted program, its arguments as shapes)`` for the decode
    step ``[batch, 1]``, the fused block of ``width`` decode steps (of an
    engine that generates by diffusion over blocks, in their place, the
    fused dispatch of ``width`` passes), the
    padded prefill-carrying step ``[batch, chunk]``, where the engine
    packs (``engine.padded_reason`` is None) the token-packed step of
    ``tokens`` slots (default ``chunk``) over ``batch`` rows, and where
    it speculates the verify step ``[batch, spec_K + 1]``
    (``engine.params`` may be abstract, nothing is placed on a device).
    ``sharding`` places every argument, for a described device;
    ``num_pages`` overrides the pool's page count."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype),
                                    engine.params)
    pages = sds(_pool_shape(engine, num_pages), engine.kv_pool.dtype)
    if isinstance(engine.pages, dict):
        # the slot pools as the engine holds them, beside the paged pool
        # (and what other pool the page table addresses, at its pages)
        pages = {**{k: sds(a.shape[:1] + (_pool_shape(engine, num_pages)[1],)
                           + a.shape[2:] if k in engine.page_pools
                           else a.shape, a.dtype)
                    for k, a in engine.pages.items()}, "kv": pages}
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
        "decode": (engine._jit_step, step_args(B, 1)),
        "fused": (engine._get_jit_multistep(width), (
            params, pages, sds((B, 1), i32), sds((B, 1), i32),
            sds((B, engine.table_width), i32), sds((B,), i32),
            sds((B,), jnp.bool_), sds((B,), i32), sds((B,), i32),
            sds((2,), jnp.uint32), sds((), i32), sds((B,), f32),
            sds((B,), i32), sds((B,), f32), sds((B, 1), i32), None, None)),
        "mixed": (engine._jit_step, step_args(B, chunk)),
    }
    G = getattr(engine, "gen_block", 1)
    if G > 1:
        # generation by diffusion over blocks: no program feeds one token
        # a row; the fused dispatch scans ``width`` passes over
        # ``[batch, G]`` (the revealing and the committing pass are one
        # program)
        del out["decode"], out["fused"]
        state = {"tok": sds((B, G), i32), "rev": sds((B, G), jnp.bool_),
                 "alive": sds((B,), jnp.bool_),
                 **{k: sds((B,), i32)
                    for k in ("pidx", "start", "tail", "budget")}}
        samp = {"temp": sds((B,), f32), "top_k": sds((B,), i32),
                "top_p": sds((B,), f32), "seeds": sds((B,), i32),
                "min_p": sds((B,), f32), "steps": sds((B,), i32),
                "tau": sds((B,), f32)}
        out["passes"] = (engine._get_jit_passes(width), (
            params, pages, sds((B, engine.table_width), i32), state,
            sds((2,), jnp.uint32), sds((), i32), samp))
    if engine.padded_reason is None:
        out["packed"] = (engine._jit_packed,
                         step_args(B, tokens or chunk, lead=1))
    if engine.spec_K > 0:
        out["spec"] = (engine._jit_spec, step_args(B, engine.spec_K + 1))
    return out


def check_step_programs(engine, batch: int, chunk: int, width: int = 8,
                        sharding=None, num_pages: Optional[int] = None,
                        tokens: Optional[int] = None) -> List[dict]:
    """Compile the programs and report, for each, the pool-sized
    copies in its HLO, its temporary bytes beside the pool's bytes, the
    sorts over the vocabulary and the equations it traces under no stage
    (``unstaged``). A program is ``ok`` with no such copy, temporaries under
    one pool, no such equation and, where the sampler's selection is the
    grouped one (``ops/sampling.candidate_form``; a toy vocabulary takes
    ``lax.top_k`` by design), no such sort; ``selection`` is that form."""
    from dynamo_tpu.ops.sampling import candidate_form

    programs = step_programs(engine, batch, chunk, width, sharding,
                             num_pages, tokens)
    shape, dtype = _pool_shape(engine, num_pages), engine.kv_pool.dtype
    pool_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    vocab = engine.model_cfg.vocab_size
    selection = candidate_form(vocab)
    out = []
    for name, (fn, args) in programs.items():
        compiled = fn.lower(*args).compile()
        text = compiled.as_text()
        copies = pool_copies(text, shape, dtype)
        sorts = vocab_sorts(text, vocab) if selection != "direct" else []
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        no_stage = unstaged(fn, args)
        out.append({"program": name, "pool_shape": list(shape),
                    "pool_bytes": pool_bytes, "temp_bytes": temp,
                    "pool_copies": copies, "selection": selection,
                    "vocab_sorts": sorts, "unstaged": no_stage,
                    "ok": not copies and not sorts and not no_stage
                    and temp < pool_bytes})
    return out


__all__ = ["pool_copies", "vocab_sorts", "expert_temporaries",
           "weight_copies", "unstaged", "stages_opened",
           "step_programs", "check_step_programs"]
