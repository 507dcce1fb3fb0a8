"""AOT TPU lowering of every Pallas kernel at REAL serving geometries.

Interpret-mode tests (the rest of the suite) validate kernel MATH but
cannot catch Mosaic lowering errors — tiling-rule violations, unsupported
ops, bad block specs — which otherwise surface only on the first real
chip compile. ``jax.export`` with ``platforms=["tpu"]`` runs the
pallas->mosaic lowering (and its verifier) on CPU, so a kernel that
breaks the Mosaic rules fails HERE, before any chip time is spent. Full
Mosaic->TPU codegen (VMEM limits included) happens in the TPU compiler:
the kernel phase of ``chip_smoke.py`` covers it on the chip.

Geometries are the real targets: Llama-3-class GQA (Hq=24/Hkv=8/Dh=128)
and DeepSeek-V3 MLA (nh=128, dkv=512).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True)
def _native_kernels(monkeypatch):
    """Pin interpret OFF during export: ``_resolve_interpret(None)`` keys
    off ``jax.default_backend()`` (cpu here), but these tests lower for
    the TPU platform — the kernels must take their native path."""
    from dynamo_tpu.ops.pallas import (decode, gdn, mla_decode,
                                       mla_decode_masked, mla_prefill,
                                       mla_ragged, moe_grouped, prefill,
                                       ragged)

    for mod in (decode, prefill, mla_decode, mla_decode_masked, mla_prefill,
                mla_ragged, ragged, moe_grouped, gdn):
        monkeypatch.setattr(mod, "_resolve_interpret",
                            lambda interpret: False)


def _export_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _assert_mosaic(exp):
    assert "tpu_custom_call" in exp.mlir_module()


L, N, PS, P, B = 2, 64, 16, 16, 4


def test_gqa_decode_kernel_lowers():
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked

    Hq, Hkv, Dh = 24, 8, 128

    def fn(q, pages, table, positions, total):
        return paged_decode_attention_stacked(
            q, pages, 1, table, positions, total, 0.088, interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((B, 1, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    _assert_mosaic(exp)


def test_gqa_decode_kernel_window_softcap_lowers():
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked

    Hq, Hkv, Dh = 16, 8, 128  # gemma-2-9b-class heads

    def fn(q, pages, table, positions, total):
        return paged_decode_attention_stacked(
            q, pages, 1, table, positions, total, 0.0625,
            window=4096, softcap=50.0, interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((B, 1, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    _assert_mosaic(exp)


@pytest.mark.parametrize("window,softcap", [(None, None), (4096, 50.0)])
def test_gqa_prefill_kernel_lowers(window, softcap):
    from dynamo_tpu.ops.pallas.prefill import paged_prefill_attention_stacked

    Hq, Hkv, Dh, S = 24, 8, 128, 512

    def fn(q, pages, table, positions, total):
        return paged_prefill_attention_stacked(
            q, pages, 1, table, positions, total, 0.088,
            window=window, softcap=softcap, interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((B, S, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P * 4), jnp.int32),
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    _assert_mosaic(exp)


@pytest.mark.parametrize("window,softcap", [(None, None), (4096, 50.0)])
def test_packed_ragged_kernel_lowers(window, softcap):
    """The packed step's attention (`ops/pallas/ragged.py`: the ragged
    kernel over the prompt chunks, the decode kernel over the one-token
    rows, both in one program) lowers at Qwen3-4B widths and the batch
    cell's step: 32/8 heads of 128, page 16, 1,152 slots, 64 rows — the
    program the engine's packed step runs on chip."""
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    Hq, Hkv, Dh, T, R = 32, 8, 128, 1152, 64

    def fn(q, pages, table, starts, q_lens, kv_lens):
        return ragged_mixed_attention_packed(
            q, pages, 1, table, starts, q_lens, kv_lens, 0.088,
            window=window, softcap=softcap, interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((T, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((R, P * 8), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32))
    _assert_mosaic(exp)
    text = exp.mlir_module()
    assert "ragged_mixed" in text and "paged_decode" in text


def test_mla_decode_kernel_lowers_v3_geometry():
    from dynamo_tpu.ops.pallas.mla_decode import mla_paged_decode_stacked

    nh, dkv, dr = 128, 512, 64  # DeepSeek-V3

    def fn(q_lat, q_pe, pages, table, total):
        return mla_paged_decode_stacked(
            q_lat, q_pe, pages, 1, table, total, 0.1, interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((B, 1, nh, dkv), jnp.float32),
        jax.ShapeDtypeStruct((B, 1, nh, dr), jnp.float32),
        jax.ShapeDtypeStruct((L, N, 2, 1, PS, dkv), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    _assert_mosaic(exp)


def test_flagship_decode_step_lowers_for_tpu():
    """The WHOLE serving decode step (llama scan forward with the Pallas
    decode kernel inside the layer scan + on-device sampling) exports for
    the TPU platform at a 3B-like geometry — the program the driver
    compile-checks and the engine actually serves on chip."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked
    from dynamo_tpu.ops.sampling import sample_tokens

    # 3B-like shapes but 2 layers: layer count only repeats the scan body
    cfg = ModelConfig.llama32_3b()
    import dataclasses
    cfg = dataclasses.replace(cfg, num_layers=2)

    def step(params, pages, tokens, positions, table, total, new, rng,
             temp, top_k, top_p):
        logits, pages = llama.forward(
            params, cfg, tokens, positions, pages, table, total, new,
            attn_impl=paged_decode_attention_stacked)
        sampled, logprobs = sample_tokens(logits, rng, temp, top_k, top_p)
        return pages, sampled, logprobs

    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    Bs, Pw = 8, 32
    exp = jax.export.export(jax.jit(step), platforms=["tpu"])(
        params,
        jax.ShapeDtypeStruct((cfg.num_layers, 128, 2, cfg.num_kv_heads,
                              16, cfg.head_dim), jnp.bfloat16),
        jax.ShapeDtypeStruct((Bs, 1), jnp.int32),
        jax.ShapeDtypeStruct((Bs, 1), jnp.int32),
        jax.ShapeDtypeStruct((Bs, Pw), jnp.int32),
        jax.ShapeDtypeStruct((Bs,), jnp.int32),
        jax.ShapeDtypeStruct((Bs,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((Bs,), jnp.float32),
        jax.ShapeDtypeStruct((Bs,), jnp.int32),
        jax.ShapeDtypeStruct((Bs,), jnp.float32))
    _assert_mosaic(exp)


def test_prompt_scoring_program_lowers_for_tpu():
    """The engine's paged prompt-scoring program (chunked-prefill scan
    with the Pallas prefill kernel inside, per-chunk LM-head gather)
    exports for the TPU platform at a 3B-like geometry — the program a
    completions echo+logprobs request runs on chip."""
    import dataclasses

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = dataclasses.replace(ModelConfig.llama32_3b(), num_layers=2)
    eng = JaxEngine(
        cfg, jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))),
        JaxEngineConfig(num_pages=16, page_size=16, max_num_seqs=2,
                        max_prefill_chunk=256, max_context=512,
                        attn_impl="pallas"))
    exp = jax.export.export(jax.jit(eng._score_impl), platforms=["tpu"])(
        eng.params,
        jax.ShapeDtypeStruct((1, 512), jnp.int32),
        jax.ShapeDtypeStruct((1, 512), jnp.bool_))
    _assert_mosaic(exp)


def test_tp_sharded_steps_compile_in_the_tpu_compiler():
    """``jax.export`` stops at lowering; GSPMD partitioning happens in the
    TPU compiler, and that refuses a Mosaic call it is left to partition
    ("Mosaic kernels cannot be automatically partitioned") — what every
    tp>1 worker would have hit on the chip before ``JaxEngine._per_shard``
    ran the kernels per shard. libtpu compiles without a chip against a
    topology description, so the refusal is catchable here: the decode, the
    padded prefill and the token-packed step of a tp=2 engine at
    Llama-3.2-3B widths must compile, with no all-gather (the cache stays
    sharded on Hkv; nor may the sampler's grouped selection, which a
    vocabulary of 32,768 engages, gather what the tail holds) and no copy
    of a chip's slab of the pool."""
    import dataclasses

    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P_

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import pool_copies, vocab_sorts
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.sampling import candidate_form
    from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh
    from dynamo_tpu.parallel.sharding import ModelSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    cfg = dataclasses.replace(ModelConfig.llama32_3b(), num_layers=2,
                              vocab_size=32768)
    assert candidate_form(cfg.vocab_size).startswith("grouped")
    mesh = make_mesh(MeshSpec(tp=2), devices=topo.devices[:2])
    specs = ModelSharding(cfg, mesh).param_specs()
    abs_params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=4, max_prefill_chunk=128,
        max_context=256, attn_impl="pallas", mesh=mesh))

    def sds(shape, dtype, spec=P_()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def param_sds(path, leaf):
        node = specs
        for k in path:
            node = node[k.key]
        return sds(leaf.shape, leaf.dtype, node)

    params = jax.tree_util.tree_map_with_path(param_sds, abs_params)
    # the serving default's page count, as a shape: a pool of 16 pages the
    # compiler would prefetch whole into faster memory
    L_, _n, two, Hkv_, ps_, Dh_ = eng.pages.shape
    N_ = 2048
    pages = sds((L_, N_, two, Hkv_, ps_, Dh_), eng.pages.dtype,
                P_(None, None, None, "tp", None, None))
    assert eng.padded_reason is None        # a tp mesh packs
    # (program, rows, width of the token arrays, their leading axis)
    for impl, B_, S_, lead in ((eng._step_impl, 4, 1, 4),
                               (eng._step_impl, 2, 128, 2),
                               (eng._packed_step_impl, 4, 256, 1)):
        compiled = jax.jit(impl, donate_argnums=(1,)).lower(
            params, pages, sds((lead, S_), jnp.int32),
            sds((lead, S_), jnp.int32),
            sds((B_, eng.table_width), jnp.int32), sds((B_,), jnp.int32),
            sds((B_,), jnp.int32), sds((2,), jnp.uint32),
            sds((), jnp.int32), sds((B_,), jnp.float32),
            sds((B_,), jnp.int32), sds((B_,), jnp.float32)).compile()
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo
        # the packed step holds both kernels, each per shard: the ragged
        # one for the chunks, the decode one for the one-token rows
        assert (("ragged_mixed" in hlo and "paged_decode" in hlo)
                == (impl == eng._packed_step_impl))
        assert "all-gather" not in hlo, "the sharded cache was gathered"
        # nor is a chip's slab of it copied or re-laid around the cache
        # write (engine/program_check.py)
        assert pool_copies(hlo, (L_, N_, two, Hkv_ // 2, ps_, Dh_),
                           eng.pages.dtype) == []
        assert vocab_sorts(hlo, cfg.vocab_size) == []


def test_the_sampling_tail_sorts_no_axis_of_the_vocabulary(monkeypatch):
    """Every step program ends in ``JaxEngine._sample_tail`` (the fused
    block in the same calls under its own scope). At Qwen3's ``[32,
    151936]`` XLA's TPU pipeline turns the tail's two ``lax.top_k`` into
    one key+index sort of all 151,936 columns (6.2 ms a step on the chip:
    PERF.md section 6, PR 30); on ``ops/sampling.top_candidates`` the
    compiled tail holds the sorts of the group maxima and of the gathered
    candidates and nothing as long as the vocabulary."""
    import dataclasses

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.engine import jax_engine
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import vocab_sorts
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import sampling

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    R, V = 32, 151936
    cfg = dataclasses.replace(ModelConfig.tiny(), vocab_size=V)
    eng = JaxEngine(cfg, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))),
        JaxEngineConfig(num_pages=8, page_size=4, max_num_seqs=4,
                        max_prefill_chunk=16, max_context=64))
    assert eng.cfg.num_top_logprobs > 0      # the alternatives ride along
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled_tail():
        def tail(logits, rng, step, temperature, top_k, top_p):
            return eng._sample_tail(logits, None, rng, step, temperature,
                                    top_k, top_p)[1]
        return jax.jit(tail).lower(
            sds((R, V), jnp.float32), sds((2,), jnp.uint32),
            sds((), jnp.int32), sds((R,), jnp.float32),
            sds((R,), jnp.int32), sds((R,), jnp.float32)).compile().as_text()

    hlo = compiled_tail()
    assert vocab_sorts(hlo, V) == []
    G = -(-V // sampling.GROUP_WIDTH)
    assert vocab_sorts(hlo, G), "the sort of the group maxima is gone too"
    # one selection serves the sampler and the alternatives: XLA merges
    # the two calls (a second one would order the maxima twice)
    assert len(vocab_sorts(hlo, G)) == 1
    # the parent's tail, for the reader's sake: it has to see that sort
    monkeypatch.setattr(sampling, "top_candidates", jax.lax.top_k)
    monkeypatch.setattr(jax_engine, "top_candidates", jax.lax.top_k)
    assert vocab_sorts(compiled_tail(), V)


def test_deepseek_mla_forward_lowers_for_tpu():
    """DeepSeek forward with BOTH MLA kernels (decode S=1 and prefill
    S>1 traces) exports for TPU at a V3-like attention geometry."""
    import dataclasses

    from dynamo_tpu.models import deepseek
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked

    cfg = ModelConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=128, num_kv_heads=1, head_dim=512,
        model_type="deepseek_v2", dtype="bfloat16",
        q_lora_rank=0, kv_lora_rank=512, qk_rope_head_dim=64,
        qk_nope_head_dim=128, v_head_dim=128,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=128,
        n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=1.0)
    del dataclasses
    params = jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0)))

    for S in (1, 64):  # decode kernel trace + prefill kernel trace
        def fwd(params, pages, tokens, positions, table, total, new):
            return deepseek.forward(
                params, cfg, tokens, positions, pages, table, total, new,
                attn_impl=paged_decode_attention_stacked)

        exp = jax.export.export(jax.jit(fwd), platforms=["tpu"])(
            params,
            jax.ShapeDtypeStruct((cfg.num_layers, 64, 2, 1, 16,
                                  cfg.kv_lora_rank), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B, 12), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
        _assert_mosaic(exp)


def test_mla_prefill_kernel_lowers_v3_geometry():
    from dynamo_tpu.ops.pallas.mla_prefill import mla_paged_prefill_stacked

    nh, dkv, dr, S = 128, 512, 64, 256  # adaptive SB shrinks at nh=128

    def fn(q_lat, q_pe, pages, table, positions, total):
        return mla_paged_prefill_stacked(
            q_lat, q_pe, pages, 1, table, positions, total, 0.1,
            interpret=False)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((B, S, nh, dkv), jnp.float32),
        jax.ShapeDtypeStruct((B, S, nh, dr), jnp.float32),
        jax.ShapeDtypeStruct((L, N, 2, 1, PS, dkv), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, P * 2), jnp.int32),
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32))
    _assert_mosaic(exp)


class TestVmemStackClamp:
    """The scoped-VMEM query-block clamp, calibrated against a REAL v5e
    compile failure (round 5): SB=128 at Llama-3B bench geometry allocated
    16.79 MiB of kernel stack against the chip's 16 MiB limit. The AOT
    lowering tests above cannot catch this (Mosaic's stack accounting runs
    in the final TPU compile, not in export lowering), so the estimator
    itself is pinned here."""

    def test_llama_bench_geometry_shrinks(self):
        from dynamo_tpu.ops.pallas.prefill import _fit_query_block

        # the exact shape that OOM'd on chip: Hq=24, Dh=128, span=128
        slab = 2 * 2 * 8 * 128 * 128 * 2
        assert _fit_query_block(512, 24, 128, 128, slab) == 64
        # small test geometries keep the full block (no needless shrink)
        assert _fit_query_block(64, 2, 128, 128, slab) == 64
        assert _fit_query_block(512, 8, 128, 128, slab) == 128

    def test_mla_v3_geometry_shrinks(self):
        from dynamo_tpu.ops.pallas.mla_prefill import _query_block

        slab = 2 * 2 * 128 * 512 * 2
        # V3: nh=128, dkv=512 — the old fixed 2048-row target estimated
        # ~39 MiB of stack; the clamp must cut rows to fit the budget
        sb = _query_block(512, 128, 512, 128, slab)
        assert 128 * sb * (22 * 128 + 32 * 512) + slab <= 14 * 2**20
        assert sb >= 1

    def test_estimates_fit_budget_across_geometries(self):
        from dynamo_tpu.ops.pallas.prefill import (VMEM_STACK_BUDGET,
                                                   _fit_query_block)

        for Hq, Dh in [(8, 128), (24, 128), (32, 128), (16, 256), (96, 128)]:
            for span in (64, 128, 256):
                slab = 2 * 2 * 8 * span * Dh * 2
                sb = _fit_query_block(1024, Hq, Dh, span, slab)
                est = Hq * sb * (14 * span + 24 * Dh) + slab
                assert sb >= 8
                assert est <= VMEM_STACK_BUDGET or sb == 8, (Hq, Dh, span)


@pytest.mark.parametrize("tokens", [16, 8192])
def test_grouped_expert_layer_compiles_in_the_tpu_compiler(tokens):
    """``models/moe.grouped_experts`` with the ``moe_grouped`` kernel at the
    widths of the benchmark's MLA + MoE cell (256 experts of 2,048 x 768,
    top-8, four layers stacked) and its two shapes: 16 decode rows (16-row
    tiles) and the 8,192 slots of a padded ``[16,512]`` step (128-row
    tiles). The TPU compiler has to take the kernel's VMEM (three weight
    blocks of 3.1 MB, double-buffered), keep the stacked experts where they
    are (a per-layer slice handed to the custom call would be a 2.4 GB
    copy) and scatter nothing (70-80 ns an index on the chip)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models.moe import grouped_experts

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    E, H, I, k, layers = 256, 2048, 768, 8, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer_of_a_stack(xt, top_w, top_i, wg, wu, wd, layer, valid):
        return grouped_experts(xt, top_w, top_i, wg, wu, wd, layer=layer,
                               valid=valid, use_pallas=True)

    text = jax.jit(layer_of_a_stack).lower(
        sds((tokens, H), jnp.bfloat16), sds((tokens, k), jnp.float32),
        sds((tokens, k), jnp.int32), sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, I, H), jnp.bfloat16), sds((), jnp.int32),
        sds((tokens,), jnp.bool_)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%moe_grouped" in calls[0]
    # the kernel's weight operands are the stacks themselves
    assert calls[0].count(f"bf16[{layers},{E},") == 3
    assert not [ln for ln in text.splitlines()
                if " scatter(" in ln or f"bf16[{E},{H},{I}]" in ln
                or f"bf16[{E},{I},{H}]" in ln]


@pytest.mark.parametrize("tokens,rows", [(128, 1792), (512, 8192)])
def test_grouped_layer_with_a_held_range_compiles_in_the_tpu_compiler(
        tokens, rows):
    """``grouped_experts`` as one rank of an expert-parallel layer, at the
    widths of the benchmark's LongCat-Flash cell (16 held of 512 experts of
    6,144 x 2,048, a 768-wide router with 256 zero-compute experts, top-12,
    four layers stacked) and its two shapes: 128 decode rows (16-row tiles)
    and the 512 slots of its packed step (128-row tiles). The kernel's rows
    are the bound a held range can receive (``T x min(k, held)`` and a tile
    a held expert: what ``benchmarks/longcat_cost.grouped_rows`` counts),
    the held stacks are its operands as they are, and nothing scatters."""
    from dynamo_tpu.models.moe import grouped_experts

    one_chip = _v5e_chip()
    E, H, I, k, layers = 16, 6144, 2048, 12, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def share_of_a_layer(xt, top_w, top_i, wg, wu, wd, layer, valid):
        return grouped_experts(xt, top_w, top_i, wg, wu, wd, layer=layer,
                               valid=valid, use_pallas=True,
                               first_expert=0, num_routed=512)

    text = jax.jit(share_of_a_layer).lower(
        sds((tokens, H), jnp.bfloat16), sds((tokens, k), jnp.float32),
        sds((tokens, k), jnp.int32), sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, I, H), jnp.bfloat16), sds((), jnp.int32),
        sds((tokens,), jnp.bool_)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%moe_grouped" in calls[0]
    assert f"f32[{rows},{H}]" in calls[0]
    assert calls[0].count(f"bf16[{layers},{E},") == 3
    assert not [ln for ln in text.splitlines() if " scatter(" in ln]


def test_the_routing_plan_looks_nothing_up_a_row_in_the_tpu_compiler():
    """``grouped_experts`` at the long-document step of the Qwen3-Next cell
    (1,152 slots, top-10 of 512 with 128 held, experts of 2,048 x 512): the
    ``sort`` scope of the compiled program holds no gather with a single
    index a row of the grouped call (27,904) or an assignment (11,520) -
    neither from the ``[E]``-sized tables nor from the sorted list. What it
    looks up is a tile's (218 indices) and the two table rows a tile's
    window of the sorted list straddles (436 indices of 128 entries), and
    no loop stands in for a gather. The row-wise plan cost 8 ns an index,
    five times a row (PERF.md section 6, PR 44)."""
    import math
    import re

    from dynamo_tpu.models.moe import grouped_experts

    one_chip = _v5e_chip()
    T, k, E, routed, H, I, layers = 1152, 10, 128, 512, 2048, 512, 2
    tm, n_tiles = 128, -(-T * k // 128) + E
    M = n_tiles * tm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def share_of_a_layer(xt, top_w, top_i, wg, wu, wd, layer, valid):
        return grouped_experts(xt, top_w, top_i, wg, wu, wd, layer=layer,
                               valid=valid, use_pallas=True,
                               first_expert=0, num_routed=routed)

    text = jax.jit(share_of_a_layer).lower(
        sds((T, H), jnp.bfloat16), sds((T, k), jnp.float32),
        sds((T, k), jnp.int32), sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, H, I), jnp.bfloat16),
        sds((layers, E, I, H), jnp.bfloat16), sds((), jnp.int32),
        sds((T,), jnp.bool_)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and f"f32[{M},{H}]" in calls[0]

    def scoped(ln):
        found = re.search(r'op_name="([^"]*)"', ln)
        return found is not None and "/sort/" in found.group(1)

    looked_up = {}                  # indices a gather -> entries an index
    for ln in text.splitlines():
        if " gather(" not in ln or not scoped(ln):
            continue
        count, entries = (
            math.prod(int(d) for d in re.search(pattern, ln).group(1).split(
                ","))
            for pattern in (r"= \w+\[([\d,]*)\]",
                            r"slice_sizes=\{([\d,]*)\}"))
        looked_up[count // entries] = entries
    assert looked_up, "the sort scope's gathers were not found by name"
    assert max(looked_up) == 2 * n_tiles and looked_up[2 * n_tiles] == tm
    assert all(entries == 1 for indices, entries in looked_up.items()
               if indices != 2 * n_tiles)
    # a gather of longer slices is expanded into a loop of dynamic slices
    assert not [ln for ln in text.splitlines()
                if " while(" in ln and scoped(ln)]
    assert not [ln for ln in text.splitlines() if " scatter(" in ln]


# -- generation by diffusion over blocks (SDAR): the block-wise visibility --

@pytest.mark.parametrize("S", [4, 512])
def test_gqa_prefill_kernel_lowers_with_a_visibility_block(S):
    """``paged_prefill`` under the block-wise visibility at SDAR-30B-A3B's
    heads (32/4 x 128): a pass over ``[32 rows, 4 positions]`` and a padded
    block-wise prefill chunk."""
    from dynamo_tpu.ops.pallas.prefill import paged_prefill_attention_stacked

    Hq, Hkv, Dh, R = 32, 4, 128, 32

    def fn(q, pages, table, positions, total):
        return paged_prefill_attention_stacked(
            q, pages, 1, table, positions, total, 0.088, interpret=False,
            block=4)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((R, S, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((R, P * 8), jnp.int32),
        jax.ShapeDtypeStruct((R, S), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32))
    _assert_mosaic(exp)


def test_packed_ragged_kernel_lowers_with_a_visibility_block():
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    Hq, Hkv, Dh, T, R = 32, 4, 128, 1024, 32

    def fn(q, pages, table, starts, q_lens, kv_lens):
        return ragged_mixed_attention_packed(
            q, pages, 1, table, starts, q_lens, kv_lens, 0.088,
            interpret=False, block=4)

    exp = _export_tpu(
        fn,
        jax.ShapeDtypeStruct((T, Hq, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((L, N, 2, Hkv, PS, Dh), jnp.bfloat16),
        jax.ShapeDtypeStruct((R, P * 8), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32),
        jax.ShapeDtypeStruct((R,), jnp.int32))
    _assert_mosaic(exp)
    # the ragged kernel alone, as before the decode kernel entered the
    # causal packed step
    assert "paged_decode" not in exp.mlir_module()


def _sdar_config(layers: int):
    import json
    import os

    from dynamo_tpu.models.config import ModelConfig
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "sdar-30b-a3b-chat.json")
    with open(path) as f:
        hf = json.load(f)
    hf.pop("benchmark")
    hf["num_hidden_layers"] = layers
    return ModelConfig.from_hf(hf)


def test_block_diffusion_programs_compile_in_the_tpu_compiler():
    """The fused pass dispatch ``passes3[32,4]`` and the token-packed
    block-wise prefill step of SDAR-30B-A3B-Chat at its published widths
    (two of its layers; the cell's rows, pool and dispatch width) compile
    for a v5e with the Mosaic kernels in them, copy no pool, sort no axis
    of the vocabulary, build no ``[positions, experts, width]`` temporary
    and keep their temporaries under 0.5 GB (engine/program_check.py)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import (
        expert_temporaries, pool_copies, step_programs, vocab_sorts)
    from dynamo_tpu.models import moe

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    cfg = _sdar_config(2)
    assert (cfg.generation, cfg.gen_block, cfg.qk_norm) == (
        "block_diffusion", 4, True)
    abs_params = jax.eval_shape(
        lambda: moe.init_params(cfg, jax.random.PRNGKey(0)))
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=32, max_context=2048,
        attn_impl="pallas", decode_multistep=3, denoising_steps=2))
    assert eng.padded_reason is None
    programs = step_programs(
        eng, 32, 512, width=3, num_pages=2048, tokens=1024,
        sharding=SingleDeviceSharding(topo.devices[0]))
    assert set(programs) == {"passes", "mixed", "packed"}
    pool = (2, 2048) + tuple(eng.pages.shape[2:])
    for name in ("passes", "packed"):
        fn, args = programs[name]
        compiled = fn.lower(*args).compile()
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo or "moe_grouped" in hlo
        assert pool_copies(hlo, pool, eng.pages.dtype) == []
        assert vocab_sorts(hlo, cfg.vocab_size) == []
        positions = 32 * 4 if name == "passes" else 1024
        assert expert_temporaries(hlo, positions, 128, 768) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_expert_stacks_are_drawn_without_a_float32_copy():
    """``moe.init_params`` draws ``w_gate [7, 128, 2048, 768]`` (5.6 GB in
    float32) a layer at a time inside one program (``llama.randn_stack``):
    the compiled initialiser's temporaries stay under two layers' worth of
    float32, so the worker and the reference child load on a 16 GB chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models.llama import randn_stack

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    shape = (128, 2048, 768)
    one_layer_f32 = 4 * 128 * 2048 * 768
    key = jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(lambda k: randn_stack(
        k, 7, shape, 0.012, jnp.bfloat16)).lower(key).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 7 * one_layer_f32 // 2
    assert mem.temp_size_in_bytes < 2 * one_layer_f32


# -- latent attention over a token-packed step (ISSUE 38) -----------------

def _v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no compile-only TPU topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _loops_that_write_a_weight(hlo: str, params, evicted: str = None):
    """``(what a loop's body writes of a weight, the bytes the entry
    computation does)`` of a compiled step program, by
    ``engine/program_check.weight_copies`` (ISSUE 52). ``evicted`` names,
    by a pattern over the leaf, the one kind of write a test allows a
    loop: a ``copy-done`` that lays ONE layer's matrix back in the
    device's main memory from the fast memory the compiler had fetched it
    into (its memory-space assignment's, in the layout the consumer wants:
    the per-layer face of a stored layout against a consumer's, PERF.md
    section 7 - no loop's operand and no slice of several layers)."""
    from dynamo_tpu.engine.program_check import weight_copies

    found = weight_copies(hlo, params)
    loop = [d for d in found["loop"] if not (
        evicted and " copy-done(" in d["line"]
        and re.search(evicted, d["leaf"]) and d["bytes"] <= 51e6)]
    return loop, sum(d["bytes"] for d in found["entry"])


@pytest.mark.parametrize("T", [256, 512, 1152])
@pytest.mark.parametrize("nh", [32, 16])
def test_mla_ragged_kernel_compiles_in_the_tpu_compiler(nh, T):
    """``mla_ragged`` at the MLA cell's geometry (JoyAI-LLM-Flash: 32
    heads, latent 512, rope 64, page 16, 16 rows over a table of 512
    pages) and at DeepSeek-V2-Lite's (16 heads), on the three token axes
    the cell's packed step meets: the TPU compiler takes it, which
    includes the scoped-VMEM limit that export lowering does not check."""
    from dynamo_tpu.ops.pallas.mla_ragged import mla_ragged_attention_packed

    one_chip = _v5e_chip()
    dkv, dr, R = 512, 64, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q_lat, q_pe, pages, table, starts, q_lens, kv_lens):
        return mla_ragged_attention_packed(
            q_lat, q_pe, pages, 1, table, starts, q_lens, kv_lens, 0.072)

    text = jax.jit(fn).lower(
        sds((T, nh, dkv), jnp.float32), sds((T, nh, dr), jnp.bfloat16),
        sds((5, 4096, 2, 1, PS, dkv), jnp.bfloat16), sds((R, 512), jnp.int32),
        sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "mla_ragged" in calls[0]


@pytest.mark.parametrize("nh,dkv,P,name", [
    (128, 512, 1600, "mla_selected_rows"), (64, 1024, 64, "mla_window_rows")],
    ids=["full_layer_pages", "window_layer_rings"])
def test_mla_masked_decode_kernel_compiles_in_the_tpu_compiler(nh, dkv, P,
                                                               name):
    """``mla_masked_decode_stacked`` at the long-context cell's two
    geometries (dots3-note-prev, 24 rows, pages of 16, rope 64): a full
    layer's 128 heads over a latent of 512 and a table of 1,600 pages, and
    a window layer's 64 heads over a latent of 1,024 with the rings as
    pages - 64 a row, two chunks of 32 (a slab of 4 MB). The TPU compiler
    takes both under the default scoped-VMEM limit, under the caller's
    name."""
    from dynamo_tpu.ops.pallas.mla_decode_masked import (
        mla_masked_decode_stacked)

    one_chip = _v5e_chip()
    R, dr = 24, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q_lat, q_pe, pages, table, lens, bias):
        return mla_masked_decode_stacked(
            q_lat, q_pe, pages, 2, table, lens, bias, 0.072, name=name)

    text = jax.jit(fn).lower(
        sds((R, nh, dkv), jnp.bfloat16), sds((R, nh, dr), jnp.bfloat16),
        sds((6, (R + 1) * P, 2, 1, PS, dkv), jnp.bfloat16),
        sds((R, P), jnp.int32), sds((R,), jnp.int32),
        sds((R, P * PS), jnp.float32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and name in calls[0]
    assert f"f32[{R},{nh},{dkv}]" in calls[0]


def test_mla_engine_packs_without_a_pool_copy():
    """An MLA engine on the kernels declares no reason to pad, and its
    token-packed step at the MLA cell's widths (JoyAI-LLM-Flash's dense
    layer and one expert layer; 16 rows, 512 slots, the cell's pool)
    compiles for a v5e with ``mla_ragged`` and ``moe_grouped`` in it, no
    pool-sized copy (whole latent pages are written in place) and no sort
    over the vocabulary (engine/program_check.py)."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import (
        pool_copies, step_programs, vocab_sorts)
    from dynamo_tpu.models import deepseek
    from dynamo_tpu.models.config import ModelConfig

    one_chip = _v5e_chip()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "joyai-llm-flash.json")
    with open(path) as f:
        hf = json.load(f)
    hf.pop("benchmark")
    hf["num_hidden_layers"] = 2
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0)))
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=16, max_context=8192,
        max_prefill_chunk=1024, attn_impl="pallas", decode_multistep=4))
    assert eng.padded_reason is None and eng._packed_cap == 1152
    programs = step_programs(eng, 16, 512, width=4, sharding=one_chip,
                             num_pages=4096)
    assert set(programs) == {"decode", "fused", "mixed", "packed"}
    fn, args = programs["packed"]
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert "mla_ragged" in hlo and "moe_grouped" in hlo
    assert "mla_prefill" not in hlo
    pool = (2, 4096) + tuple(eng.pages.shape[2:])
    assert pool[2:] == (2, 1, 16, 512)
    assert pool_copies(hlo, pool, eng.pages.dtype) == []
    assert vocab_sorts(hlo, cfg.vocab_size) == []
    # a third of the padded [16, 512] step's 1.66 GB of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# -- the gated delta rule and a 256-wide head (ISSUE 43) -------------------

@pytest.mark.parametrize("N,R", [(1152, 64), (64, 64)])
def test_the_gated_delta_rule_compiles_in_the_tpu_compiler(N, R):
    """``gdn_chunk`` and ``gdn_step`` at Qwen3-Next's widths (16 key heads,
    32 value heads of 128, six linear layers' states of 65 slots) over the
    cell's packed step (1,152 slots, 64 rows) and over a decode step (one
    slot a row: the chunk form is not in the program): the TPU compiler
    takes both, and the state pool is aliased, not copied."""
    from dynamo_tpu.ops import gdn

    one_chip = _v5e_chip()
    Hk, Hv, D = 16, 32, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rule(q, k, v, g, b, pool, layer, start, new, total, slots):
        rows = gdn.token_rows(N, start, new, total, slots)
        return gdn.gated_delta_rule(q, k, v, g, b, pool, layer, rows,
                                    use_pallas=True, several=N > R)

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    compiled = jax.jit(rule, donate_argnums=(5,)).lower(
        sds((N, Hk, D), bf16), sds((N, Hk, D), bf16), sds((N, Hv, D), bf16),
        sds((N, Hv), f32), sds((N, Hv), f32), sds((6, 65, Hv, D, D), f32),
        sds((), i32), *[sds((R,), i32)] * 4).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    names = sorted(ln.split("=")[0].strip().split(".")[0] for ln in calls)
    assert names == (["%gdn_chunk", "%gdn_step"] if N > R else ["%gdn_step"])
    pool_bytes = 6 * 65 * Hv * D * D * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 2


def test_the_paged_kernels_compile_at_a_head_of_256():
    """``paged_decode`` and ``ragged_mixed`` at Qwen3-Next's gated
    attention (16 query heads over 2 key/value heads of 256: eight queries
    a key head), the cell's pool and table, 64 rows and 1,152 slots."""
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    one_chip = _v5e_chip()
    Hq, Hkv, Dh, R, T, P = 16, 2, 256, 64, 1152, 800

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = sds((2, 51200, 2, Hkv, PS, Dh), jnp.bfloat16)
    i32 = jnp.int32

    def decode(q, pages, table, positions, total):
        return paged_decode_attention_stacked(q, pages, 1, table, positions,
                                              total, Dh ** -0.5)

    def packed(q, pages, table, starts, q_lens, kv_lens):
        return ragged_mixed_attention_packed(q, pages, 1, table, starts,
                                             q_lens, kv_lens, Dh ** -0.5)

    text = jax.jit(decode).lower(
        sds((R, 1, Hq, Dh), jnp.bfloat16), pages, sds((R, P), i32),
        sds((R, 1), i32), sds((R,), i32)).compile().as_text()
    assert "paged_decode" in text
    text = jax.jit(packed).lower(
        sds((T, Hq, Dh), jnp.bfloat16), pages, sds((R, P), i32),
        sds((R,), i32), sds((R,), i32), sds((R,), i32)).compile().as_text()
    assert "ragged_mixed" in text and "paged_decode" in text


def test_qwen3_next_step_programs_compile_for_a_v5e():
    """The long-document cell's two step programs - the token-packed step
    of 1,152 slots over 64 rows and the fused block of two decode steps -
    at the published widths (abstract weights: 4,133,998,720 parameters)
    and the cell's pools compile for a v5e with the rule's two kernels, the
    paged kernels and ``moe_grouped`` in them, with neither the paged pool
    nor the state pool copied, inside the chip's memory."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import pool_copies, step_programs
    from dynamo_tpu.models import qwen3_next
    from dynamo_tpu.models.config import ModelConfig

    one_chip = _v5e_chip()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "qwen3-next-80b-a3b-instruct.json")
    with open(path) as f:
        hf = json.load(f)
    args = hf.pop("benchmark")["worker_args"]
    args = {args[i]: int(args[i + 1]) for i in range(0, len(args), 2)
            if args[i + 1].isdigit()}
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: qwen3_next.init_params(cfg, jax.random.PRNGKey(0)))
    rows, chunk = args["--max-num-seqs"], args["--max-prefill-chunk"]
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=rows,
        max_context=args["--max-context"], max_prefill_chunk=chunk,
        attn_impl="pallas", decode_multistep=args["--decode-multistep"],
        state_slots=args["--state-slots"]))
    assert eng.padded_reason is None
    assert eng._packed_cap == args["--min-prefill-bucket"]
    assert eng.packed_attention == (
        "chunks:ragged_mixed,one_token:paged_decode")
    programs = step_programs(
        eng, rows, chunk, width=args["--decode-multistep"],
        sharding=one_chip, num_pages=args["--num-pages"],
        tokens=eng._packed_cap)
    pool = (2, args["--num-pages"]) + tuple(eng.kv_pool.shape[2:])
    state = f"f32[6,{rows + 1},32,128,128]"
    want = {"packed": {"gdn_chunk", "gdn_step", "moe_grouped",
                       "paged_decode", "ragged_mixed"},
            "fused": {"gdn_step", "moe_grouped", "paged_decode"}}
    temp = {"packed": 0.45e9, "fused": 0.13e9}
    for name, kernels in want.items():
        fn, fn_args = programs[name]
        compiled = fn.lower(*fn_args).compile()
        hlo = compiled.as_text()
        calls = {ln.split("=")[0].strip().lstrip("%").split(".")[0]
                 for ln in hlo.splitlines() if "tpu_custom_call" in ln}
        assert calls == kernels, name
        assert pool_copies(hlo, pool, eng.kv_pool.dtype) == []
        assert not [ln for ln in hlo.splitlines()
                    if " copy(" in ln and state in ln.split(" copy(")[0]]
        # no loop writes a layer's weights again (a period's three linear
        # layers' were, 176 / 227 MB a period, as the outer scan's slices:
        # 0.688 / 0.400 GB of temporaries); the one whole-stack copy left
        # is the fused block's 67 MB of the full layers' ``wq``, once a
        # dispatch
        loop, entry_bytes = _loops_that_write_a_weight(hlo, abs_params)
        assert loop == [], (name, loop)
        assert entry_bytes < 70e6, (name, entry_bytes)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < temp[name], name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


def test_dots3_step_programs_compile_for_a_v5e():
    """The long-context cell's two step programs - the token-packed step
    of 640 slots over 24 rows and the fused block of two decode steps - at
    the published widths (abstract weights: 3,093,416,192 parameters) and
    the cell's three pools compile for a v5e. Each row kind of each
    attention kind goes to a kernel: in the packed step the masked form of
    the ragged latent kernel for the chunk (``mla_selected``,
    ``mla_window``) and the latent decode kernel with a bias for the rows
    of one token (``mla_selected_rows`` over their pages, ``mla_window_rows``
    over their rings as pages), in the fused block the two ``_rows``
    kernels alone, ``moe_grouped`` in both. Around them XLA makes NO pass
    over a step's ``[T, heads, latent]`` elements: the queries enter
    heads-major as ``W_UK``'s matmul writes them, the output leaves
    heads-major as ``W_UV``'s reads it, the one-token rows' results are
    laid in place - no copy and no zero broadcast of that size, no add of
    two of them, and nowhere a temporary of the table's rings
    (``[24,64,2,16,1024]``: the gathered form's read a row at a time).
    Neither sorts an axis as long as the page table's tokens (the
    selection stays a mask), neither copies the latent pages, the index
    pages or the rings, and both fit the chip's memory."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import (_INSTR, pool_copies,
                                                 step_programs)
    from dynamo_tpu.models import dots3
    from dynamo_tpu.models.config import ModelConfig

    one_chip = _v5e_chip()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "dots3-note-prev.json")
    with open(path) as f:
        hf = json.load(f)
    args = hf.pop("benchmark")["worker_args"]
    args = {args[i]: int(args[i + 1]) for i in range(0, len(args), 2)
            if args[i + 1].isdigit()}
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.PRNGKey(0)))
    rows, chunk = args["--max-num-seqs"], args["--max-prefill-chunk"]
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=rows,
        max_context=args["--max-context"], max_prefill_chunk=chunk,
        attn_impl="pallas", decode_multistep=args["--decode-multistep"],
        state_slots=args["--state-slots"]))
    assert eng.padded_reason is None
    assert eng._packed_cap == args["--min-prefill-bucket"]
    assert eng.cache_kinds == (
        "paged[L=3,Hkv=1,Dh=512]+index[L=3,D=128]"
        f"+window[L=6,S={rows},R=1024,D=1024]")
    programs = step_programs(
        eng, rows, chunk, width=args["--decode-multistep"],
        sharding=one_chip, num_pages=args["--num-pages"],
        tokens=eng._packed_cap)
    pool = (3, args["--num-pages"]) + tuple(eng.kv_pool.shape[2:])
    other = [f"bf16[3,{args['--num-pages']},16,128]",
             f"bf16[6,{rows + 1},64,2,1,16,1024]"]
    want = {"packed": {"mla_selected", "mla_selected_rows", "mla_window",
                       "mla_window_rows", "moe_grouped"},
            "fused": {"mla_selected_rows", "mla_window_rows", "moe_grouped"}}
    temp = {"packed": 1.15e9, "fused": 1.06e9}
    table_tokens = f"{args['--max-context']}]"
    # a step's latent queries or outputs, either attention kind, whatever
    # the order of the axes: T x heads x latent elements
    T, wcfg = eng._packed_cap, cfg.window_cfg()
    assert (T, wcfg.num_heads, wcfg.kv_lora_rank) == (640, 64, 1024)
    assert (cfg.num_heads, cfg.kv_lora_rank) == (128, 512)
    whole = T * wcfg.num_heads * wcfg.kv_lora_rank
    assert whole == T * cfg.num_heads * cfg.kv_lora_rank
    rings = f"[{rows},64,2,16,1024]"
    for name, kernels in want.items():
        fn, fn_args = programs[name]
        compiled = fn.lower(*fn_args).compile()
        hlo = compiled.as_text()
        calls = {ln.split("=")[0].strip().lstrip("%").split(".")[0]
                 for ln in hlo.splitlines() if "tpu_custom_call" in ln}
        assert calls == kernels, name
        assert not [ln for ln in hlo.splitlines() if " sort(" in ln
                    and table_tokens in ln.split(" sort(")[0]], name
        assert pool_copies(hlo, pool, eng.kv_pool.dtype) == []
        assert not [ln for ln in hlo.splitlines() if " copy(" in ln
                    and any(s in ln.split(" copy(")[0] for s in other)]
        assert rings not in hlo, name
        if name == "packed":
            # (a fusion's own instructions are read too: a transposing or
            # stacking fusion holds its copy or concatenate inside)
            zeros = set(re.findall(
                r"(%constant[\w.]*) = [a-z0-9]+\[\]\S* constant\(0\)", hlo))
            glue = []
            for m in filter(None, map(_INSTR.match, hlo.splitlines())):
                _root, _name, _dt, dims, opcode, rest = m.groups()
                if dims and math.prod(map(int, dims.split(","))) in (
                        whole, 2 * whole) and (
                        opcode in ("copy", "concatenate")
                        or opcode == "broadcast"
                        and rest.split(")")[0] in zeros):
                    glue.append(m.group(0)[:160])
            assert glue == [], glue
        # no loop is handed a slice of a weight stack (ISSUE 52: a period's
        # three window layers' were, 886 / 651 MB written a period, with
        # 1.279 / 1.715 GB of temporaries); what a loop still writes is an
        # eviction of one layer's ``wkv_b`` / ``wq_b``, and the entry
        # computation the whole-stack layouts of those, once a dispatch
        assert not re.search(r"bf16\[3,(8192,5120|1024,20480|1024,16384)\]",
                             hlo), name
        loop, entry_bytes = _loops_that_write_a_weight(
            hlo, abs_params, evicted=r"w(kv|q)_b__")
        assert loop == [], (name, loop)
        assert entry_bytes < 0.8e9, (name, entry_bytes)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < temp[name], name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9



# -- Olmo-Hybrid: a rule of 30 heads of 96 x 192, attention at a group of
# -- one (ISSUE 51) ---------------------------------------------------------

@pytest.mark.parametrize("N,R", [(640, 48), (48, 48)])
def test_the_gated_delta_rule_compiles_where_nothing_tiles(N, R):
    """``gdn_chunk`` and ``gdn_step`` at Olmo-Hybrid's widths - 30 key heads
    serving 30 value heads (blocks of 3 and of 6 heads), a state of 96 x 192
    float32 (neither a multiple of 128), twelve linear layers' states of 49
    slots - over the crowd cell's packed step (640 slots, 48 rows) and over
    a decode step: the TPU compiler takes both as they are, the tiles pad
    in VMEM, and the state pool is aliased and never copied or padded in
    HBM."""
    from dynamo_tpu.ops import gdn
    from dynamo_tpu.ops.pallas.gdn import supports, why_not

    one_chip = _v5e_chip()
    H, Dk, Dv = 30, 96, 192
    assert supports(H, H, Dk, Dv) and why_not(H, H, Dk, Dv) is None
    assert "multiple of 8" in why_not(3, 3, 12, 24)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rule(q, k, v, g, b, pool, layer, start, new, total, slots):
        rows = gdn.token_rows(N, start, new, total, slots)
        return gdn.gated_delta_rule(q, k, v, g, b, pool, layer, rows,
                                    use_pallas=True, several=N > R)

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    compiled = jax.jit(rule, donate_argnums=(5,)).lower(
        sds((N, H, Dk), bf16), sds((N, H, Dk), bf16), sds((N, H, Dv), bf16),
        sds((N, H), f32), sds((N, H), f32), sds((12, 49, H, Dk, Dv), f32),
        sds((), i32), *[sds((R,), i32)] * 4).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    names = sorted(ln.split("=")[0].strip().split(".")[0] for ln in calls)
    assert names == (["%gdn_chunk", "%gdn_step"] if N > R else ["%gdn_step"])
    pool_bytes = 12 * 49 * H * Dk * Dv * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 2
    assert not [ln for ln in hlo.splitlines()
                if " copy(" in ln and "f32[12,49,30,96,192]"
                in ln.split(" copy(")[0]]


def test_the_paged_kernels_compile_at_a_group_of_one():
    """``paged_decode`` and ``ragged_mixed`` at Olmo-Hybrid's full
    attention: 30 query heads over 30 key/value heads of 128, ONE query a
    key head, the crowd cell's pool and table, 48 rows and 640 slots. The
    double-buffered slab of 30 heads is 3.75 MiB and the chunk read out of
    it as much again twice over: the ragged kernel's query block is 32
    there (at 64 the TPU compiler counted 17.63 MiB of its 16), and the
    blocks of the GQA cells stay what they were."""
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked
    from dynamo_tpu.ops.pallas.prefill import _fit_query_block
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

    def block(Hq, Hkv, Dh, T=1152, span=128):
        return _fit_query_block(T, Hq, Dh, span, 2 * 2 * Hkv * span * Dh * 2)
    assert block(30, 30, 128, 640) == 32
    # qwen3-4b, llama-3.2-3b, qwen3-next, sdar: as before this PR
    assert [block(32, 8, 128), block(24, 8, 128), block(16, 2, 256),
            block(32, 4, 128)] == [64, 64, 64, 64]

    one_chip = _v5e_chip()
    Hq, Hkv, Dh, R, T, P = 30, 30, 128, 48, 640, 80

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = sds((4, 4096, 2, Hkv, PS, Dh), jnp.bfloat16)
    i32 = jnp.int32

    def decode(q, pages, table, positions, total):
        return paged_decode_attention_stacked(q, pages, 1, table, positions,
                                              total, Dh ** -0.5)

    def packed(q, pages, table, starts, q_lens, kv_lens):
        return ragged_mixed_attention_packed(q, pages, 1, table, starts,
                                             q_lens, kv_lens, Dh ** -0.5)

    text = jax.jit(decode).lower(
        sds((R, 1, Hq, Dh), jnp.bfloat16), pages, sds((R, P), i32),
        sds((R, 1), i32), sds((R,), i32)).compile().as_text()
    assert "paged_decode" in text
    text = jax.jit(packed).lower(
        sds((T, Hq, Dh), jnp.bfloat16), pages, sds((R, P), i32),
        sds((R,), i32), sds((R,), i32), sds((R,), i32)).compile().as_text()
    assert "ragged_mixed" in text and "paged_decode" in text


def test_olmo_hybrid_step_programs_compile_for_a_v5e():
    """The crowd cell's two step programs - the token-packed step of 640
    slots over its rows and the fused block of decode steps - at the
    published widths (abstract weights: 4,100,788,944 parameters) and the
    cell's pools compile for a v5e with the rule's two kernels and the
    paged kernels in them, with neither the paged pool nor the state pool
    copied, inside the chip's memory."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import pool_copies, step_programs
    from dynamo_tpu.models import olmo_hybrid
    from dynamo_tpu.models.config import ModelConfig

    one_chip = _v5e_chip()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "olmo-hybrid-7b.json")
    with open(path) as f:
        hf = json.load(f)
    args = hf.pop("benchmark")["worker_args"]
    args = {args[i]: int(args[i + 1]) for i in range(0, len(args), 2)
            if args[i + 1].isdigit()}
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    rows, chunk = args["--max-num-seqs"], args["--max-prefill-chunk"]
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=rows,
        max_context=args["--max-context"], max_prefill_chunk=chunk,
        attn_impl="pallas", decode_multistep=args["--decode-multistep"],
        state_slots=args["--state-slots"]))
    assert eng.padded_reason is None
    assert eng._packed_cap == args["--min-prefill-bucket"]
    assert eng.packed_attention == (
        "chunks:ragged_mixed,one_token:paged_decode")
    assert eng.cache_kinds == (
        f"paged[L=4,Hkv=30,Dh=128]+state[L=12,S={rows},f32]")
    programs = step_programs(
        eng, rows, chunk, width=args["--decode-multistep"],
        sharding=one_chip, num_pages=args["--num-pages"],
        tokens=eng._packed_cap)
    pool = (4, args["--num-pages"]) + tuple(eng.kv_pool.shape[2:])
    state = f"f32[12,{rows + 1},30,96,192]"
    want = {"packed": {"gdn_chunk", "gdn_step", "paged_decode",
                       "ragged_mixed"},
            "fused": {"gdn_step", "paged_decode"}}
    temp = {"packed": 0.32e9, "fused": 0.19e9}
    for name, kernels in want.items():
        fn, fn_args = programs[name]
        compiled = fn.lower(*fn_args).compile()
        hlo = compiled.as_text()
        calls = {ln.split("=")[0].strip().lstrip("%").split(".")[0]
                 for ln in hlo.splitlines() if "tpu_custom_call" in ln}
        assert calls == kernels, name
        assert pool_copies(hlo, pool, eng.kv_pool.dtype) == []
        assert not [ln for ln in hlo.splitlines()
                    if " copy(" in ln and state in ln.split(" copy(")[0]]
        # PR 51's cure, held: the linear layers' weights are indexed in the
        # loop and no period's slice is written (1.40 - 1.59 GB of
        # temporaries in the first form); the fused block relays the full
        # layers' ``wv`` whole, 118 MB once a dispatch
        loop, entry_bytes = _loops_that_write_a_weight(hlo, abs_params)
        assert loop == [], (name, loop)
        assert entry_bytes < 120e6, (name, entry_bytes)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < temp[name], name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- a layer's weights are read where they lie (ISSUE 52) -------------------

@pytest.mark.parametrize("form", ["slices", "indices"])
def test_a_nested_scan_over_indices_writes_no_weight_on_a_v5e(form):
    """The toy of ``tests/test_weight_copies.py`` in the TPU compiler, two
    periods of three layers of 2 MB: handed a period's slice the inner
    loop has its ``[3, 1024, 1024]`` written first, by the outer loop's
    body; over indices alone each matmul reads its layer out of
    the stack and NOTHING of a weight is written, in a loop or outside."""
    from dynamo_tpu.engine.program_check import weight_copies
    from dynamo_tpu.models.moe import flat_layers, layer_at

    one_chip = _v5e_chip()
    P, G, W = 2, 3, 1024

    def slices(params, x):
        def period(h, wp):
            def layer(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(layer, h, wp)[0], None
        return jax.lax.scan(period, x, params["w"])[0]

    def indices(params, x):
        flat = flat_layers(params["w"])

        def period(h, p):
            def layer(h, j):
                return jnp.tanh(h @ layer_at(flat, p * G + j)), None
            return jax.lax.scan(layer, h, jnp.arange(G))[0], None
        return jax.lax.scan(period, x, jnp.arange(P))[0]

    params = {"w": jax.ShapeDtypeStruct((P, G, W, W), jnp.bfloat16,
                                        sharding=one_chip)}
    x = jax.ShapeDtypeStruct((64, W), jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit({"slices": slices, "indices": indices}[form]).lower(
        params, x).compile().as_text()
    found = weight_copies(hlo, params, min_bytes=1 << 20)
    if form == "indices":
        assert found == {"loop": [], "entry": [], "on_chip": []}
    else:
        # (at 6 MB the compiler has room for the slice in its fast memory;
        # the cells' 650 - 890 MB a period went to the main one)
        written = found["loop"] + found["on_chip"]
        assert [d["bytes"] for d in written] == [G * W * W * 2]
        assert "bf16[3,1024,1024]" in written[0]["line"]
        assert found["entry"] == []


# -- q, k and v from one stored matrix (ISSUE 54) ---------------------------

@pytest.mark.parametrize("form", ["three", "one"])
def test_products_that_share_their_operand_write_their_weights_on_a_v5e(form):
    """The toy of ISSUE 54, one ``lax.scan`` over 36 layers of Qwen3-4B's
    projection widths around a plain grouped attention: three products of
    one left operand have each layer's ``wq`` / ``wk`` / ``wv`` written
    ahead of them - fetched into the chip's fast memory in the layout
    their consumer wants, not the stack's - and indexing the stack inside
    the body changes nothing of it (tried for the issue); ONE stored
    ``[36, 2560, 6144]`` and one product, split after, and nothing of a
    weight is written, in the loop or outside."""
    from dynamo_tpu.engine.program_check import weight_copies

    one_chip = _v5e_chip()
    L, D, T, H, Hkv, Dh = 36, 2560, 64, 32, 8, 128
    Q, KV = H * Dh, Hkv * Dh

    def layers(params, x):
        def layer(h, lp):
            if form == "one":
                q, k, v = jnp.split(h @ lp["wqkv"], (Q, Q + KV), axis=-1)
            else:
                q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
            q = q.reshape(T, Hkv, H // Hkv, Dh)
            k, v = k.reshape(T, Hkv, Dh), v.reshape(T, Hkv, Dh)
            s = jax.nn.softmax(jnp.einsum("tkgd,ukd->kgtu", q, k), -1)
            o = jnp.einsum("kgtu,ukd->tkgd", s, v).reshape(T, Q)
            return h + o @ lp["wo"], None
        return jax.lax.scan(layer, x, params)[0]

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    qkv = ({"wqkv": sds(L, D, Q + 2 * KV)} if form == "one" else
           {"wq": sds(L, D, Q), "wk": sds(L, D, KV), "wv": sds(L, D, KV)})
    params = {**qkv, "wo": sds(L, Q, D)}
    hlo = jax.jit(layers).lower(params, sds(T, D)).compile().as_text()
    found = weight_copies(hlo, params, min_bytes=1 << 20)
    if form == "one":
        assert found == {"loop": [], "entry": [], "on_chip": []}
    else:
        # each of the three a layer at a time (21 / 5 / 5 MB), never ``wo``
        written = [d for where in found.values() for d in where]
        assert {re.search(r"__(\w+?)__", d["leaf"]).group(1)
                for d in written} == {"wq", "wk", "wv"}
        assert {d["bytes"] for d in written} == {D * Q * 2, D * KV * 2}


def _cell_engine(name: str, family):
    """An engine over abstract weights of a shipped configuration at the
    cell's worker arguments, and those arguments."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models.config import ModelConfig

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs", name + ".json")
    with open(path) as f:
        hf = json.load(f)
    args = hf.pop("benchmark")["worker_args"]
    args = dict(zip(args[::2], args[1::2]))
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    more = {field: kind(args[flag]) for flag, field, kind in (
        ("--denoising-steps", "denoising_steps", int),
        ("--confidence-threshold", "confidence_threshold", float),
        ("--min-prefill-bucket", "min_prefill_bucket", int),
        ("--min-decode-bucket", "min_decode_bucket", int),
        ("--min-prefill-seqs-bucket", "min_prefill_seqs_bucket", int),
    ) if flag in args}
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16,
        max_num_seqs=int(args.get("--max-num-seqs", 64)),
        max_context=4096, max_prefill_chunk=1024, attn_impl="pallas",
        decode_multistep=int(args.get("--decode-multistep", 8)), **more))
    return eng, args


# (configuration, program, rows, token slots, the most temporaries, GB):
# the programs the two cells' rings name - ``multistep8[64]`` and
# ``packed[1152,64]`` of ``qwen3-4b.batch``, ``passes3[32,4]`` and
# ``packed[1024,8]`` of ``sdar-30b-a3b-chat.blockgen``
_GQA_PROGRAMS = [
    ("qwen3-4b", "fused", 64, 1152, 0.05),
    ("qwen3-4b", "packed", 64, 1152, 0.05),
    ("sdar-30b-a3b-chat", "passes", 32, 1024, 0.30),
    ("sdar-30b-a3b-chat", "packed", 8, 1024, 0.40),
]


@pytest.mark.parametrize("name,program,rows,tokens,temp_gb", _GQA_PROGRAMS,
                         ids=[f"{c[0]}-{c[1]}" for c in _GQA_PROGRAMS])
def test_gqa_step_programs_write_no_projection_weight_on_a_v5e(
        name, program, rows, tokens, temp_gb):
    """The dense and the MoE GQA cells' real step programs (abstract
    weights at the published widths, the cells' pools) compiled for a v5e:
    the engine serves ``wqkv`` and the compiled program writes nothing of
    it - no loop writes any weight, and neither the entry computation nor
    the chip's fast memory holds a re-laid ``wq`` / ``wk`` / ``wv`` /
    ``wqkv``. On the three the fused block of ``qwen3-4b`` transposed the
    whole stacks once a dispatch (``copy.103`` ``bf16[36,2560,4096]`` and
    two of a quarter its size: 1.13 GB of temporaries, 1.136 GB in all)
    and every program fetched-and-re-laid each layer's three ahead of
    three small products (PERF.md section 6, PR 54)."""
    from dynamo_tpu.engine.program_check import (pool_copies, step_programs,
                                                 weight_copies)
    from dynamo_tpu.models import llama, moe

    one_chip = _v5e_chip()
    family = {"qwen3-4b": llama, "sdar-30b-a3b-chat": moe}[name]
    eng, args = _cell_engine(name, family)
    assert eng.qkv == "fused" and eng.padded_reason is None
    assert "wqkv" in eng.params["layers"]
    num_pages = int(args["--num-pages"])
    fn, fn_args = step_programs(
        eng, rows, 1024, width=eng.multistep, sharding=one_chip,
        num_pages=num_pages, tokens=tokens)[program]
    compiled = fn.lower(*fn_args).compile()
    hlo = compiled.as_text()
    # the one product, under its stage, over every row or slot
    width = eng.model_cfg.q_size + 2 * eng.model_cfg.kv_size
    assert re.search(rf"convolution[.\d]* = bf16\[(\d+,)+{width}\]", hlo)
    pool = (eng.kv_pool.shape[0], num_pages) + tuple(eng.kv_pool.shape[2:])
    assert pool_copies(hlo, pool, eng.kv_pool.dtype) == []
    found = weight_copies(hlo, eng.params, min_bytes=1 << 20)
    assert found["loop"] == [], found["loop"]
    projection = [d for where in ("entry", "on_chip") for d in found[where]
                  if re.search(r"__(wq|wk|wv|wqkv)__", d["leaf"])]
    assert projection == [], projection
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_gb * 1e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- Keye-VL-2.0: a learned selection in front of grouped-query pages
# -- (ISSUE 56) --------------------------------------------------------------

@pytest.mark.parametrize("form,N", [("rows", 48), ("chunks", 640)])
def test_the_masked_gqa_kernels_compile_in_the_tpu_compiler(form, N):
    """``selected_attention_rows`` at the ask-many cell's geometry (32
    query heads over 4 key/value heads of 128, 48 rows, pages of 16, a
    table of 1,600 pages): the decode kernel with a bias a row
    (``selected_rows``, a chunk of 32 pages: a slab of 2 MB under the
    default scoped-VMEM limit) and the ragged kernel with a bias a slot
    (``selected_chunks``: a block of 32 slots' bias lines beside the
    slabs, under the limit the call asks for). The TPU compiler takes
    both, each under its own name."""
    from dynamo_tpu.ops.pallas.ragged import selected_attention_rows

    one_chip = _v5e_chip()
    R, Hq, Hkv, Dh, P = 48, 32, 4, 128, 1600
    S = P * PS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pages, table, starts, new, total, rows_bias, to, bias):
        one = (rows_bias, to) if form == "rows" else None
        return selected_attention_rows(
            q, pages, 3, table, starts, new, total, one,
            bias if form == "chunks" else None, 0.088)

    text = jax.jit(fn).lower(
        sds((N, Hq, Dh), jnp.bfloat16),
        sds((6, 16384, 2, Hkv, PS, Dh), jnp.bfloat16),
        sds((R, P), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.int32), sds((R, S), jnp.float32),
        sds((R,), jnp.int32), sds((N, S), jnp.float32)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and f"selected_{form}" in calls[0]


def test_keye_step_programs_compile_for_a_v5e():
    """The ask-many cell's two step programs - the token-packed step of
    640 slots over 48 rows and the fused block of two decode steps - at
    the published widths (abstract weights: 4.375 B parameters) and the
    cell's two pools compile for a v5e. Each row kind goes to a masked
    kernel: in the packed step ``selected_chunks`` for the rows of several
    tokens and ``selected_rows`` for the rows of one, in the fused block
    ``selected_rows`` alone, ``moe_grouped`` in both. Neither sorts an
    axis as long as the page table's tokens (the selection stays a mask),
    neither copies the key/value pages or the index pages, no loop is
    handed a slice of a weight stack, and both fit the chip's memory
    beside the weights and the pools."""
    import json
    import os

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.program_check import pool_copies, step_programs
    from dynamo_tpu.models import moe
    from dynamo_tpu.models.config import ModelConfig

    one_chip = _v5e_chip()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "keye-vl-2.0-30b-a3b.json")
    with open(path) as f:
        hf = json.load(f)
    args = hf.pop("benchmark")["worker_args"]
    args = {args[i]: int(args[i + 1]) for i in range(0, len(args), 2)
            if args[i + 1].isdigit()}
    cfg = ModelConfig.from_hf(hf)
    abs_params = jax.eval_shape(
        lambda: moe.init_params(cfg, jax.random.PRNGKey(0)))
    rows, chunk = args["--max-num-seqs"], args["--max-prefill-chunk"]
    eng = JaxEngine(cfg, abs_params, JaxEngineConfig(
        num_pages=16, page_size=16, max_num_seqs=rows,
        max_context=args["--max-context"], max_prefill_chunk=chunk,
        attn_impl="pallas", decode_multistep=args["--decode-multistep"]))
    assert eng.padded_reason is None and eng.one_token_form == "masked"
    assert eng._packed_cap == args["--min-prefill-bucket"]
    assert eng.cache_kinds == "paged[L=6,Hkv=4,Dh=128]+index[L=6,D=64]"
    programs = step_programs(
        eng, rows, chunk, width=args["--decode-multistep"],
        sharding=one_chip, num_pages=args["--num-pages"],
        tokens=eng._packed_cap)
    pool = (6, args["--num-pages"]) + tuple(eng.kv_pool.shape[2:])
    index = f"bf16[6,{args['--num-pages']},1024]"
    want = {"packed": {"selected_chunks", "selected_rows", "moe_grouped"},
            "fused": {"selected_rows", "moe_grouped"}}
    table_tokens = f"{args['--max-context']}]"
    for name, kernels in want.items():
        fn, fn_args = programs[name]
        compiled = fn.lower(*fn_args).compile()
        hlo = compiled.as_text()
        calls = {ln.split("=")[0].strip().lstrip("%").split(".")[0]
                 for ln in hlo.splitlines() if "tpu_custom_call" in ln}
        assert calls == kernels, name
        assert not [ln for ln in hlo.splitlines() if " sort(" in ln
                    and table_tokens in ln.split(" sort(")[0]], name
        assert pool_copies(hlo, pool, eng.kv_pool.dtype) == []
        assert not [ln for ln in hlo.splitlines() if " copy(" in ln
                    and index in ln.split(" copy(")[0]], name
        loop, _entry = _loops_that_write_a_weight(hlo, abs_params)
        assert loop == [], (name, loop)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 0.8e9, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
