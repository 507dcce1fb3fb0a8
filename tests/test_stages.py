"""Every step program is traced under the one table of stages
(``dynamo_tpu/engine/stages.py``, ISSUE 53): nothing a family or the engine
writes is left without a stage, every family cuts its layers at the same
five places, and a name outside the table is refused while the program is
traced. Tracing only - no lowering, no compile, toy widths (the tiny
overlays of the benchmark's configurations, with the one width the kernels
ask for so that the programs are the chip's)."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import stages
from dynamo_tpu.engine.program_check import (
    stages_opened, step_programs, unstaged)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, CHUNK = 8, 64

# the cells' configurations: one family each (the expert family twice:
# under block diffusion, and behind a learned selection)
FAMILIES = {
    "qwen3-4b": "llama", "joyai-llm-flash": "deepseek",
    "sdar-30b-a3b-chat": "moe", "longcat-flash-omni": "longcat",
    "qwen3-next-80b-a3b-instruct": "qwen3_next",
    "dots3-note-prev": "dots3", "olmo-hybrid-7b": "olmo_hybrid",
    "keye-vl-2.0-30b-a3b": "moe"}
LAYER_GROUPS = {"mixer_in", "cache_write", "mixer", "mixer_out", "ffn"}


def _programs(config: str, impl: str) -> tuple:
    by_diffusion = config.startswith("sdar")
    names = ("mixed", "passes") if by_diffusion else (
        "decode", "fused", "mixed")
    return names + (("packed",) if impl == "pallas" else ())


CASES = [(config, impl, program) for config in FAMILIES
         for impl in ("pallas", "scan")
         for program in _programs(config, impl)]


@functools.lru_cache(maxsize=None)
def _engine_programs(config: str, impl: str) -> dict:
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.config import ModelConfig

    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{config}.json")) as f:
        hf = json.load(f)
    bench = hf.pop("benchmark")
    hf.update(bench["tiny"]["config"])
    # the kernels' one demand on a geometry: a head (a latent) of 128
    for key in ("kv_lora_rank", "swa_kv_lora_rank"):
        if key in hf:
            hf[key] = 128
    if "kv_lora_rank" not in hf:
        hf["head_dim"] = 128
    args = bench["tiny"]["worker_args"]
    args = dict(zip(args[::2], args[1::2]))
    cfg = ModelConfig.from_hf(hf)
    family = get_family(cfg)
    assert family.__name__.endswith("." + FAMILIES[config])
    params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    width = int(args.get("--decode-multistep", 4))
    kw = dict(num_pages=64, page_size=8, max_num_seqs=ROWS, max_context=256,
              max_prefill_chunk=CHUNK, attn_impl=impl,
              decode_multistep=width)
    if "--state-slots" in args:
        kw["state_slots"] = ROWS
    if "--denoising-steps" in args:
        kw["denoising_steps"] = int(args["--denoising-steps"])
        kw["confidence_threshold"] = float(args["--confidence-threshold"])
    engine = JaxEngine(cfg, params, JaxEngineConfig(**kw))
    assert engine.attn_impl == impl
    return {"engine": engine,
            **step_programs(engine, ROWS, CHUNK, width=width, tokens=CHUNK)}


@pytest.mark.parametrize("config,impl,program", CASES)
def test_a_step_program_traces_nothing_outside_a_stage(config, impl,
                                                       program):
    """``program_check.unstaged`` is empty: what a chip's trace of this
    program shows under no stage is then the compiler's own."""
    fn, args = _engine_programs(config, impl)[program]
    found = unstaged(fn, args)
    assert found == [], "\n".join(
        f"{d['primitive']} ({d['bytes']} B) under {d['scopes']!r} at "
        f"{d['source']}" for d in found[:20])


@pytest.mark.parametrize("config", sorted(FAMILIES))
def test_every_family_cuts_a_layer_at_the_same_five_places(config):
    """Inside its layer loop a family opens stages of all five layer
    groups, in the token-packed step the chip serves and in the fused
    block (of a model that generates by diffusion: the passes)."""
    programs = _engine_programs(config, "pallas")
    for name in ("packed", "passes" if "passes" in programs else "fused"):
        opened = stages_opened(*programs[name])
        groups = {stages.STAGES[s] for s in opened if s.startswith("layer.")}
        assert groups == LAYER_GROUPS, (name, sorted(opened))
        around = {s for s in opened if stages.STAGES[s] == "around_layers"}
        assert {"embed", "logits", "step.inputs"} <= around, (name, around)


def _constrained(engine) -> dict:
    """The ``pen`` argument of a step with penalties, a bias, seeds and a
    guided mask, as shapes."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    W, V = engine.cfg.penalty_window, engine.model_cfg.vocab_size
    f32, i32 = jnp.float32, jnp.int32
    return {"ids": sds((ROWS, W), i32), "cnt": sds((ROWS, W), f32),
            "ctx": sds((ROWS, W), f32), "bias": sds((ROWS, W), f32),
            "fp": sds((ROWS,), f32), "pp": sds((ROWS,), f32),
            "rp": sds((ROWS,), f32), "seeds": sds((ROWS,), i32),
            "min_p": sds((ROWS,), f32),
            "mask": sds((ROWS, (V + 31) // 32), jnp.uint32)}


def test_the_engines_small_programs_and_constrained_steps_are_staged():
    """What the engine enqueues between two steps - the hand-over behind a
    mixed step, the fill of a chained step's tokens, the chained decode
    step - and a step with penalties and a guided mask trace nothing
    outside a stage either."""
    programs = _engine_programs("qwen3-4b", "pallas")
    engine = programs["engine"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)
    fn, args = programs["decode"]
    packed_out = sds((ROWS, 2 + 2 * engine.cfg.num_top_logprobs))
    small = {
        "handover": (engine._get_jit_handover(),
                     (packed_out, sds((5, ROWS)), sds((ROWS, 4)))),
        "fill": (engine._get_jit_fill(),
                 (sds((1, CHUNK)), packed_out, sds((2, ROWS)))),
        "chained": (engine._jit_chained, args[:2] + (packed_out,)
                    + args[3:]),
        "decode with penalties": (fn, args + (_constrained(engine),)),
    }
    for name, (fn, args) in small.items():
        assert unstaged(fn, args) == [], name
    assert set(stages_opened(*small["handover"])) == {"step.chain"}
    assert set(stages_opened(*small["fill"])) == {"step.chain"}
    assert "step.chain" in stages_opened(*small["chained"])


def test_a_name_outside_the_table_is_refused_while_the_program_is_traced():
    def step(x):
        with stages.stage("layer.attn_in"):
            x = x * 2
        with stages.stage("layer.speculative_glue"):
            return x + 1

    with pytest.raises(KeyError, match="layer.speculative_glue"):
        jax.jit(step).trace(jnp.ones(4))
    # a part of a registered path opens alone (a helper under test), but
    # the parts have to add up: ``unstaged`` reads the whole program

    def misplaced(x):
        with stages.stage("route"):
            return x + 1

    found = unstaged(jax.jit(misplaced), (jnp.ones(4),))
    assert [d["scopes"] for d in found] == ["route"]


def test_a_stages_group_is_that_of_its_longest_registered_prefix():
    assert stages.group_of("layer.moe/experts/anything") == "ffn"
    assert stages.group_of("layer.attn0/kv_write") == "cache_write"
    assert stages.group_of("layer.gdn_in/conv_write") == "cache_write"
    assert stages.group_of("layer.gdn_in") == "mixer_in"
    assert stages.group_of("layer.attn0") == stages.UNNAMED
    assert stages.group_of("while/body") == stages.UNNAMED
    assert set(stages.STAGES.values()) == set(stages.GROUPS)


def test_the_table_a_worker_ships_is_the_table_the_reader_parses():
    """``startup.engine``'s ``stages`` attribute, through the benchmark's
    parser, is the registry: the reader keeps no list of its own."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import scopespans

    assert scopespans.parse_stages(stages.as_attribute()) == stages.STAGES
    assert scopespans.GROUPS == stages.GROUPS
    assert scopespans.UNNAMED == stages.UNNAMED


def test_every_registered_stage_is_in_the_observability_table():
    """``docs/observability.md``, "Names in the device trace": one row a
    stage, with its group."""
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    section = text.split("Names in the device trace", 1)[1].split(
        "A FUSION carries one name", 1)[0]
    rows = dict(re.findall(r"^\| `([^`]+)` \| `(\w+)` \|", section,
                           flags=re.M))
    assert rows == stages.STAGES


def test_no_scope_is_opened_outside_the_registrys_helper():
    """No ``jax.named_scope`` / ``jax.named_call`` is left in the program
    outside ``engine/stages.py``."""
    left = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "dynamo_tpu")):
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith(".py") or path.endswith(
                    os.path.join("engine", "stages.py")):
                continue
            with open(path) as f:
                for n, line in enumerate(f, 1):
                    if re.search(r"named_(scope|call)\(", line):
                        left.append(f"{path}:{n}")
    assert left == []
