"""Int8 quantized serving (W8A8 dynamic, ops/quant.py).

Decode throughput is bandwidth-bound on the parameter stream
(BASELINE.md roofline); int8 weights halve it. The reference reaches the
same trade through FP8 engine checkpoints on H100
(docs/architecture/architecture.md R1-Distill-Llama-70B FP8 baselines);
TPU MXUs have no FP8, so symmetric int8 with dynamic activation scales
is the native equivalent. These tests pin the numerics (quantization is
worthless if it breaks the model) and the serving integration.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.quant import (
    qdot,
    quantize_params,
    quantize_weight,
)


def _tiny_cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                model_type="llama", dtype="float32",
                max_position_embeddings=256, tie_word_embeddings=False)
    base.update(kw)
    return ModelConfig(**base)


class TestNumerics:
    def test_quantize_weight_roundtrip(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.05
        w8, scale = quantize_weight(w, axis=0)
        assert w8.dtype == jnp.int8 and scale.shape == (32,)
        back = w8.astype(jnp.float32) * scale[None, :]
        # symmetric absmax int8: max relative error per channel ~1/254
        err = np.abs(np.asarray(back - w)).max()
        assert err <= np.asarray(scale).max() / 2 + 1e-8

    def test_qdot_matches_exact_matmul(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k1, (4, 7, 64))
        w = jax.random.normal(k2, (64, 96)) * 0.05
        w8, scale = quantize_weight(w, axis=0)
        y = qdot(x, w8, scale)
        ref = x @ w
        rel = (np.linalg.norm(np.asarray(y - ref))
               / np.linalg.norm(np.asarray(ref)))
        assert rel < 0.02, rel  # W8A8 dynamic: ~1% relative error

    def test_qdot_zero_rows_safe(self):
        # an all-zero activation row must not divide by zero
        x = jnp.zeros((2, 8))
        w8, scale = quantize_weight(jnp.ones((8, 4)), axis=0)
        assert np.all(np.isfinite(np.asarray(qdot(x, w8, scale))))


class TestParamTransform:
    def test_tree_structure_and_size(self):
        cfg = _tiny_cfg(dtype="bfloat16")
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        from bench import tree_bytes
        before = tree_bytes(params)
        qp = quantize_params(params)
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            assert name not in qp["layers"]
            assert qp["layers"][name + "_q"].dtype == jnp.int8
            assert qp["layers"][name + "_scale"].dtype == jnp.float32
        assert "lm_head_q" in qp and "lm_head" not in qp
        # norms stay put, embed stays bf16 (gather path)
        assert qp["layers"]["attn_norm"].dtype == jnp.bfloat16
        assert qp["embed"].dtype == jnp.bfloat16
        # the parameter stream shrinks close to 2x (embed stays bf16)
        assert tree_bytes(qp) < 0.65 * before

    def test_tied_embeddings_left_alone(self):
        cfg = _tiny_cfg(tie_word_embeddings=True)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params)
        assert "lm_head_q" not in qp and "lm_head" not in qp

    def test_forward_parity(self):
        """Quantized scan forward tracks the f32 forward: the decode-step
        logits must rank the same tokens (serving correctness), not just
        be numerically close."""
        cfg = _tiny_cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(2))
        pages = llama.make_pages(cfg, num_pages=8, page_size=16)
        B, S = 2, 12
        tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, 256)
        positions = jnp.tile(jnp.arange(S)[None], (B, 1)).astype(jnp.int32)
        table = jnp.tile(jnp.arange(1, 5)[None], (B, 1)).astype(jnp.int32)
        lens = jnp.full((B,), S, jnp.int32)
        ref, _, = llama.forward(params, cfg, tokens, positions, pages,
                                table, lens, lens)[:2]
        qlog, _, = llama.forward(quantize_params(params), cfg, tokens,
                                 positions, pages, table, lens, lens)[:2]
        ref = np.asarray(ref)
        q = np.asarray(qlog)
        cos = (ref * q).sum() / (np.linalg.norm(ref) * np.linalg.norm(q))
        assert cos > 0.999, cos
        # greedy decisions agree
        assert np.array_equal(ref.argmax(-1), q.argmax(-1))


class TestEngine:
    def test_engine_serves_int8(self):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        cfg = _tiny_cfg()
        ecfg = JaxEngineConfig(num_pages=32, page_size=16, max_num_seqs=2,
                               max_prefill_chunk=32, max_context=128,
                               attn_impl="scan", quantize="int8")
        eng = JaxEngine.random_init(cfg, ecfg)
        # the engine lays wq | wk | wv side by side before it quantises
        assert "wqkv_q" in eng.params["layers"]

        req = PreprocessedRequest(
            token_ids=list(range(1, 20)),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=4))

        async def go():
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids or [])
            await eng.stop()
            return toks

        assert len(asyncio.run(go())) == 4

    def test_engine_serves_int8_tp2(self):
        """int8 composes with tensor parallelism: the sharding specs know
        the *_q/*_scale pairs (int8 shards like the bf16 original, scales
        drop the contraction axis), and a tp=2 engine serves greedily the
        same tokens as the single-device int8 engine."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.parallel.sharding import tp_sharding
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        cfg = _tiny_cfg(vocab_size=64)  # 64 % 2 == 0: lm_head shards
        req = PreprocessedRequest(
            token_ids=list(range(1, 20)),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=4))

        async def serve(ecfg):
            eng = JaxEngine.random_init(cfg, ecfg)
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids or [])
            await eng.stop()
            return toks

        base = dict(num_pages=32, page_size=16, max_num_seqs=2,
                    max_prefill_chunk=32, max_context=128,
                    attn_impl="scan", quantize="int8", seed=7)
        ref = asyncio.run(serve(JaxEngineConfig(**base)))
        ms = tp_sharding(cfg, 2)
        sharded = asyncio.run(serve(JaxEngineConfig(
            **base, shard_params_fn=ms.shard_params,
            shard_pages_fn=ms.shard_pages)))
        assert len(sharded) == 4
        assert sharded == ref

    def test_engine_serves_int8_gemma2(self):
        """gemma-2's GeGLU/sandwich-norm sites dispatch through quant.mm
        too — the family serves int8 end-to-end."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        cfg = _tiny_cfg(model_type="gemma2", sliding_window=32,
                        attn_logit_softcap=50.0, final_logit_softcap=30.0)
        eng = JaxEngine.random_init(cfg, JaxEngineConfig(
            num_pages=32, page_size=16, max_num_seqs=2,
            max_prefill_chunk=32, max_context=128,
            attn_impl="scan", quantize="int8"))
        # the engine lays wq | wk | wv side by side before it quantises
        assert "wqkv_q" in eng.params["layers"]
        req = PreprocessedRequest(
            token_ids=list(range(1, 20)),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=4))

        async def go():
            toks = []
            async for out in eng.generate(req):
                toks.extend(out.token_ids or [])
            await eng.stop()
            return toks

        assert len(asyncio.run(go())) == 4

    def test_unsupported_family_rejected(self):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig

        cfg = _tiny_cfg(model_type="mixtral", num_experts=4,
                        num_experts_per_tok=2)
        with pytest.raises(ValueError, match="llama family"):
            JaxEngine.random_init(cfg, JaxEngineConfig(
                num_pages=16, page_size=16, max_num_seqs=2,
                max_context=64, attn_impl="scan", quantize="int8"))

    def test_custom_forward_rejected(self):
        """Pipeline-parallel stage bodies are not quant-aware (the stage
        tail would silently fall back to embed.T once lm_head is popped);
        the engine must reject quantize + forward_fn instead of serving
        wrong logits."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig

        def fake_forward(*a, **k):  # never called
            raise AssertionError

        cfg = _tiny_cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="forward_fn"):
            JaxEngine(cfg, params, JaxEngineConfig(
                num_pages=16, page_size=16, max_num_seqs=2,
                max_context=64, attn_impl="scan", quantize="int8"),
                forward_fn=fake_forward)

    def test_bad_mode_rejected(self):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig

        with pytest.raises(ValueError, match="int8"):
            JaxEngine.random_init(_tiny_cfg(), JaxEngineConfig(
                num_pages=16, page_size=16, max_num_seqs=2,
                max_context=64, attn_impl="scan", quantize="int4"))
