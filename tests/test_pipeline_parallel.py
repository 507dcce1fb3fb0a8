"""Pipeline parallelism: staged layers + microbatch ring on the pp axis.

Equivalence contract: pipeline_forward must reproduce llama.forward's
last-token logits AND paged-KV writes exactly (same math, different
schedule), on the virtual CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh
from dynamo_tpu.parallel.pipeline import pipeline_forward


def _setup(B=4, S=8, P_=4, L=4, ps=4):
    cfg = ModelConfig.tiny(num_layers=L)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pages = llama.make_pages(cfg, num_pages=1 + B * P_, page_size=ps,
                             dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        1, cfg.vocab_size, size=(B, S)), jnp.int32)
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
    table = jnp.arange(1, 1 + B * P_, dtype=jnp.int32).reshape(B, P_)
    # mixed real lengths incl. a padded row
    new = jnp.asarray([S, S - 2, S, 3], jnp.int32)
    total = new
    return cfg, params, pages, tokens, positions, table, total, new


@pytest.mark.parametrize("pp,micro", [(2, 2), (4, 4), (2, 4)])
def test_pipeline_matches_plain_forward(pp, micro):
    cfg, params, pages, tokens, positions, table, total, new = _setup()
    ref_logits, ref_pages = llama.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=pp), devices=jax.devices()[:pp])
    pages2 = llama.make_pages(cfg, num_pages=pages.shape[1], page_size=4,
                              dtype=jnp.float32)
    pp_logits, pp_pages = pipeline_forward(
        params, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=micro)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    # identical paged-KV writes (skip garbage page 0)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_composes_with_tp():
    """pp=2 x tp=2: weights staged over pp AND head/ffn-sharded over tp
    (manual pp + automatic GSPMD tp inside the stage body) must reproduce
    the plain forward bit-for-bit up to f32 reduction order."""
    from dynamo_tpu.parallel.pipeline import pp_sharding_fns

    cfg, params, pages, tokens, positions, table, total, new = _setup()
    ref_logits, ref_pages = llama.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=2, tp=2), devices=jax.devices()[:4])
    shard_params, shard_pages = pp_sharding_fns(mesh, cfg)
    p2 = shard_params(params)
    wq = p2["layers"]["wq"]
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[0] == cfg.num_layers // 2      # staged over pp
    assert shard_shape[2] == cfg.q_size // 2          # heads over tp
    pages2 = shard_pages(llama.make_pages(
        cfg, num_pages=pages.shape[1], page_size=4, dtype=jnp.float32))
    pp_logits, pp_pages = pipeline_forward(
        p2, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_composes_with_dp():
    """pp=2 x dp=2: batch rows split across dp replicas OUTSIDE the
    pipeline ring; K/V writes all_gather over dp so the replicated page
    pool stays consistent. Logits AND cache writes must match the plain
    forward (VERDICT r4 item 6: the pp x dp restriction)."""
    cfg, params, pages, tokens, positions, table, total, new = _setup()
    ref_logits, ref_pages = llama.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=2, dp=2), devices=jax.devices()[:4])
    pages2 = llama.make_pages(cfg, num_pages=pages.shape[1], page_size=4,
                              dtype=jnp.float32)
    pp_logits, pp_pages = pipeline_forward(
        params, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_pp_tp_dp_all_compose():
    """pp=2 x tp=2 x dp=2 on all 8 virtual devices: stages + head shards
    + batch replicas in one mesh (the reference engines' free pp x tp x dp
    composition, launch/dynamo-run/src/main.rs:28)."""
    from dynamo_tpu.parallel.pipeline import pp_sharding_fns

    cfg, params, pages, tokens, positions, table, total, new = _setup()
    ref_logits, ref_pages = llama.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=2, tp=2, dp=2), devices=jax.devices()[:8])
    shard_params, shard_pages = pp_sharding_fns(mesh, cfg)
    p2 = shard_params(params)
    pages2 = shard_pages(llama.make_pages(
        cfg, num_pages=pages.shape[1], page_size=4, dtype=jnp.float32))
    pp_logits, pp_pages = pipeline_forward(
        p2, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_stage_runs_pallas_decode_kernel():
    """The stacked Pallas decode kernel runs INSIDE a pp stage (shard_map
    local cache slab; interpret mode on CPU): a decode step through the
    pipeline with attn_impl must match the plain forward."""
    from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked

    cfg = ModelConfig.tiny(num_layers=4, head_dim=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    B, P_, ps = 4, 4, 8
    prompt_len = 7
    table = jnp.arange(1, 1 + B * P_, dtype=jnp.int32).reshape(B, P_)
    toks = jnp.asarray(np.random.RandomState(1).randint(
        1, cfg.vocab_size, size=(B, prompt_len)), jnp.int32)
    pos = jnp.tile(jnp.arange(prompt_len, dtype=jnp.int32)[None], (B, 1))
    lens = jnp.full((B,), prompt_len, jnp.int32)
    pages = llama.make_pages(cfg, 1 + B * P_, ps, dtype=jnp.float32)
    _, pages = llama.forward(params, cfg, toks, pos, pages, table, lens,
                             lens)

    # one decode token through both paths
    dt = jnp.asarray([[9], [8], [7], [6]], jnp.int32)
    dpos = jnp.full((B, 1), prompt_len, jnp.int32)
    dtotal = jnp.full((B,), prompt_len + 1, jnp.int32)
    done = jnp.ones((B,), jnp.int32)
    ref_logits, _ = llama.forward(params, cfg, dt, dpos, pages, table,
                                  dtotal, done)
    mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
    pp_logits, _ = pipeline_forward(
        params, cfg, dt, dpos, pages, table, dtotal, done, mesh=mesh,
        n_microbatches=2, attn_impl=paged_decode_attention_stacked)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-3, atol=2e-3)


def test_pp1_falls_through_to_plain():
    cfg, params, pages, tokens, positions, table, total, new = _setup()
    mesh = make_mesh(MeshSpec(pp=1), devices=jax.devices()[:1])
    a, _ = pipeline_forward(params, cfg, tokens, positions, pages, table,
                            total, new, mesh=mesh)
    pages2 = llama.make_pages(cfg, num_pages=pages.shape[1], page_size=4,
                              dtype=jnp.float32)
    b, _ = llama.forward(params, cfg, tokens, positions, pages2, table,
                         total, new)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_rejects_families_without_stage_adapter():
    """MLA layers differ from every staged body — running them through
    one would serve silently wrong outputs, so the forward (and the
    worker flag) refuse loudly."""
    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=4, num_kv_heads=1, head_dim=32,
        model_type="deepseek_v2", dtype="float32",
        q_lora_rank=0, kv_lora_rank=32, qk_rope_head_dim=16,
        qk_nope_head_dim=32, v_head_dim=32,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=1.0)
    from dynamo_tpu.models import deepseek as _ds
    params = _ds.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
    pages = llama.make_pages(cfg, 9, 4, dtype=jnp.float32)
    tok = jnp.ones((2, 4), jnp.int32)
    pos = jnp.tile(jnp.arange(4, dtype=jnp.int32)[None], (2, 1))
    tbl = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    lens = jnp.full((2,), 4, jnp.int32)
    with pytest.raises(ValueError, match="no stage adapter"):
        pipeline_forward(params, cfg, tok, pos, pages, tbl, lens, lens,
                         mesh=mesh)


@pytest.mark.parametrize("pp,tp,backend", [(2, 1, "grouped"),
                                           (2, 2, "grouped"),
                                           (2, 2, "dispatch")])
def test_pipeline_moe_matches_plain_forward(pp, tp, backend):
    """Mixtral/Qwen3-MoE through the MoE stage adapter: routed experts
    inside the stage with the expert FFN width tp-sharded (the combine is
    linear, so one psum completes the partial down-products) — logits AND
    cache writes must match moe.forward on both expert backends."""
    from dynamo_tpu.models import moe as _moe
    from dynamo_tpu.parallel.pipeline import pp_sharding_fns

    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2,
                           moe_intermediate_size=32, num_kv_heads=2,
                           model_type="qwen3_moe", num_layers=4,
                           moe_backend=backend, moe_capacity_factor=4.0)
    params = _moe.init_params(cfg, jax.random.PRNGKey(4))
    B, S, P_ = 4, 8, 4
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        1, cfg.vocab_size, size=(B, S)), jnp.int32)
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
    table = jnp.arange(1, 1 + B * P_, dtype=jnp.int32).reshape(B, P_)
    new = jnp.asarray([S, S - 2, S, 3], jnp.int32)
    total = new
    pages = llama.make_pages(cfg, 1 + B * P_, 4, dtype=jnp.float32)
    ref_logits, ref_pages, _aux = _moe.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=pp, tp=tp), devices=jax.devices()[:pp * tp])
    shard_params, shard_pages = pp_sharding_fns(mesh, cfg)
    p2 = shard_params(params)
    if tp > 1:  # expert FFN width really shards
        wg = p2["layers"]["w_gate"]
        assert wg.sharding.shard_shape(wg.shape)[-1] == 32 // tp
    pages2 = shard_pages(llama.make_pages(cfg, 1 + B * P_, 4,
                                          dtype=jnp.float32))
    pp_logits, pp_pages = pipeline_forward(
        p2, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pp,tp", [(2, 1), (2, 2)])
def test_pipeline_gemma_matches_plain_forward(pp, tp):
    """gemma-2 through the pipeline stage adapter (4-norm sandwich,
    GeGLU, alternating per-layer windows, both softcaps, embed scaling)
    must reproduce gemma.forward's logits AND cache writes — pp and
    pp x tp (manual psums around the sandwich norms)."""
    from dynamo_tpu.models import gemma as _gemma
    from dynamo_tpu.parallel.pipeline import pp_sharding_fns

    cfg = ModelConfig.tiny(model_type="gemma2", num_layers=4,
                           num_kv_heads=2, sliding_window=6,
                           attn_logit_softcap=40.0,
                           final_logit_softcap=25.0)
    params = _gemma.init_params(cfg, jax.random.PRNGKey(3))
    B, S, P_ = 4, 8, 4
    tokens = jnp.asarray(np.random.RandomState(2).randint(
        1, cfg.vocab_size, size=(B, S)), jnp.int32)
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
    table = jnp.arange(1, 1 + B * P_, dtype=jnp.int32).reshape(B, P_)
    new = jnp.asarray([S, S - 2, S, 3], jnp.int32)
    total = new
    pages = _gemma.make_pages(cfg, 1 + B * P_, 4, dtype=jnp.float32)
    ref_logits, ref_pages = _gemma.forward(
        params, cfg, tokens, positions, pages, table, total, new)

    mesh = make_mesh(MeshSpec(pp=pp, tp=tp), devices=jax.devices()[:pp * tp])
    shard_params, shard_pages = pp_sharding_fns(mesh, cfg)
    p2 = shard_params(params)
    pages2 = shard_pages(_gemma.make_pages(cfg, 1 + B * P_, 4,
                                           dtype=jnp.float32))
    pp_logits, pp_pages = pipeline_forward(
        p2, cfg, tokens, positions, pages2, table, total, new,
        mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(pp_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pp_pages[:, 1:]),
                               np.asarray(ref_pages[:, 1:]),
                               rtol=2e-4, atol=2e-4)


def test_rejects_indivisible_shapes():
    cfg, params, pages, tokens, positions, table, total, new = _setup(L=4)
    mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_forward(params, cfg, tokens, positions, pages, table,
                         total, new, mesh=mesh, n_microbatches=3)


class TestPpWorkerServeE2E:
    """Process-level e2e: the real worker CLI serves HTTP with
    --pipeline-parallel-size (x --tensor-parallel-size) — VERDICT r3 §6
    asked for pp to be reachable from the worker flag surface (reference:
    ``launch/dynamo-run/src/main.rs:28``)."""

    @pytest.mark.async_timeout(240)
    async def test_pp2_tp2_worker_serves_chat(self, tmp_path):
        import aiohttp

        from dynamo_tpu.utils.testing import make_test_model_dir
        from tests.procutils import ManagedProcess, free_port
        from tests.test_serve_e2e import frontend, wait_model

        # 4 layers stage over pp=2; 4 kv heads split over tp=2
        model_dir = make_test_model_dir(
            str(tmp_path / "pp-model"), num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=4)
        coord_port, http_port = free_port(), free_port()
        base = f"http://127.0.0.1:{http_port}"
        body = {"model": "pp-model", "max_tokens": 4, "temperature": 0.0,
                "messages": [{"role": "user", "content": "staged hello"}]}
        worker = ManagedProcess(
            ["dynamo_tpu.worker.main", "--coordinator",
             f"127.0.0.1:{coord_port}",
             "--model-path", model_dir, "--model-name", "pp-model",
             "--random-weights", "--pipeline-parallel-size", "2",
             "--tensor-parallel-size", "2",
             "--page-size", "4", "--num-pages", "64", "--max-num-seqs", "4",
             "--max-prefill-chunk", "32", "--max-context", "256"],
            name="pp-worker", ready_line="jax worker serving", timeout=120.0)
        async with frontend(coord_port, http_port):
            async with worker as w:
                await wait_model(base, "pp-model")
                async with aiohttp.ClientSession() as s:
                    r1 = await (await s.post(
                        f"{base}/v1/chat/completions", json=body)).json()
                    assert r1["choices"][0]["finish_reason"] == "length"
                    assert r1["usage"]["completion_tokens"] == 4
                    text1 = r1["choices"][0]["message"]["content"]
                    r2 = await (await s.post(
                        f"{base}/v1/chat/completions", json=body)).json()
                    # greedy determinism through the staged engine
                    assert r2["choices"][0]["message"]["content"] == text1
                assert w.proc.poll() is None


class TestPipelineServing:
    async def test_engine_serves_with_pp(self):
        """Full serving equivalence: a JaxEngine whose forward is the pp=2
        pipeline must stream greedy tokens identical to a plain engine
        (prefill chunks AND pipelined decode both run through it)."""
        import asyncio
        import functools

        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.parallel.pipeline import pp_sharding_fns
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        def req(rid):
            return PreprocessedRequest(
                token_ids=[1, 2, 3, 4, 5, 6], request_id=rid,
                stop_conditions=StopConditions(max_tokens=6),
                sampling_options=SamplingOptions(temperature=0.0),
                eos_token_ids=[])

        async def run(engine):
            try:
                frames = [f async for f in engine.generate(req("r"))]
                return [t for f in frames for t in f.token_ids]
            finally:
                await engine.stop()

        cfg = ModelConfig.tiny(num_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        ecfg = JaxEngineConfig(num_pages=32, page_size=4, max_num_seqs=2,
                               max_prefill_chunk=4, max_context=32,
                               min_prefill_bucket=4, attn_impl="scan")
        want = await run(JaxEngine(cfg, params, ecfg))

        mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
        shard_params, shard_pages = pp_sharding_fns(mesh)
        ecfg2 = JaxEngineConfig(num_pages=32, page_size=4, max_num_seqs=2,
                                max_prefill_chunk=4, max_context=32,
                                min_prefill_bucket=4, attn_impl="scan",
                                shard_params_fn=shard_params,
                                shard_pages_fn=shard_pages)
        from dynamo_tpu.parallel.pipeline import pipeline_forward
        eng = JaxEngine(cfg, params, ecfg2,
                        forward_fn=functools.partial(pipeline_forward,
                                                     mesh=mesh))
        got = await run(eng)
        assert got == want

    async def test_engine_serves_with_pp_dp(self):
        """pp=2 x dp=2 serving through the engine: cfg.mesh aligns the
        batch buckets to dp and the pipeline splits rows across replicas —
        greedy tokens must match a plain engine (restriction lifted,
        VERDICT r4 item 6)."""
        import functools

        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.parallel.pipeline import (
            pipeline_forward, pp_sharding_fns)
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        def req(rid):
            return PreprocessedRequest(
                token_ids=[1, 2, 3, 4, 5, 6], request_id=rid,
                stop_conditions=StopConditions(max_tokens=6),
                sampling_options=SamplingOptions(temperature=0.0),
                eos_token_ids=[])

        async def run(engine):
            try:
                frames = [f async for f in engine.generate(req("r"))]
                return [t for f in frames for t in f.token_ids]
            finally:
                await engine.stop()

        cfg = ModelConfig.tiny(num_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        ecfg = JaxEngineConfig(num_pages=32, page_size=4, max_num_seqs=4,
                               max_prefill_chunk=4, max_context=32,
                               min_prefill_bucket=4, attn_impl="scan")
        want = await run(JaxEngine(cfg, params, ecfg))

        mesh = make_mesh(MeshSpec(pp=2, dp=2), devices=jax.devices()[:4])
        shard_params, shard_pages = pp_sharding_fns(mesh)
        ecfg2 = JaxEngineConfig(num_pages=32, page_size=4, max_num_seqs=4,
                                max_prefill_chunk=4, max_context=32,
                                min_prefill_bucket=4, attn_impl="scan",
                                mesh=mesh,
                                shard_params_fn=shard_params,
                                shard_pages_fn=shard_pages)
        eng = JaxEngine(cfg, params, ecfg2,
                        forward_fn=functools.partial(pipeline_forward,
                                                     mesh=mesh))
        got = await run(eng)
        assert got == want
