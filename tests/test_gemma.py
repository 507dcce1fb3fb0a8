"""Gemma-2 family: HF checkpoint parity + sliding-window correctness.

Parity contract mirrors the llama HF test: our jax forward must reproduce
transformers' Gemma2ForCausalLM logits from the same tiny checkpoint —
which exercises GeGLU, the 4-norm sandwich, (1+w) RMSNorm, embedding
scaling, BOTH softcaps, query_pre_attn_scalar, and the alternating
sliding-window mask (prompt longer than the window)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import gemma, get_family
from dynamo_tpu.models.config import ModelConfig


def _alloc(batch, max_pages):
    table = np.arange(1, batch * max_pages + 1, dtype=np.int32)
    return jnp.asarray(table.reshape(batch, max_pages))


def _prefill(params, cfg, prompt, pages, table):
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.asarray([list(range(len(prompt)))], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    return gemma.forward(params, cfg, toks, pos, pages, table, lens, lens)


def test_family_routing():
    cfg = ModelConfig.tiny(model_type="gemma2")
    assert get_family(cfg) is gemma


def test_hf_gemma2_checkpoint_parity(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config, Gemma2ForCausalLM

    hf_cfg = Gemma2Config(
        vocab_size=160, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rms_norm_eps=1e-6,
        rope_theta=10000.0, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, query_pre_attn_scalar=24,
        sliding_window=8, attn_implementation="eager")
    torch.manual_seed(0)
    model = Gemma2ForCausalLM(hf_cfg).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    from dynamo_tpu.models.hf_loader import load_hf_params
    cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
    assert cfg.model_type == "gemma2"
    assert cfg.sliding_window == 8
    params = load_hf_params(cfg, str(tmp_path))

    # prompt LONGER than the sliding window so the alternating mask matters
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, 159, size=20).tolist()
    with torch.no_grad():
        ref = model(torch.tensor([prompt])).logits[0, -1].numpy()

    pages = gemma.make_pages(cfg, num_pages=8, page_size=4,
                             dtype=jnp.float32)
    table = _alloc(1, 5)
    logits, _ = _prefill(params, cfg, prompt, pages, table)
    np.testing.assert_allclose(np.asarray(logits[0]), ref,
                               rtol=3e-3, atol=3e-3)


def test_decode_matches_full_prefill():
    """Chunk-by-chunk decode through the paged cache must equal a one-shot
    prefill — proving the sliding-window mask is position-based (works
    identically from cached pages)."""
    cfg = ModelConfig.tiny(model_type="gemma2", num_layers=4,
                           sliding_window=6, attn_logit_softcap=40.0,
                           final_logit_softcap=25.0)
    params = gemma.init_params(cfg, jax.random.PRNGKey(2))
    prompt = list(np.random.RandomState(1).randint(1, 255, size=13))

    pages_a = gemma.make_pages(cfg, 8, 8, dtype=jnp.float32)
    ref_logits, _ = _prefill(params, cfg, prompt, pages_a, _alloc(1, 4))

    pages_b = gemma.make_pages(cfg, 8, 8, dtype=jnp.float32)
    table = _alloc(1, 4)
    for i, tok in enumerate(prompt):
        toks = jnp.asarray([[tok]], jnp.int32)
        pos = jnp.asarray([[i]], jnp.int32)
        logits, pages_b = gemma.forward(
            params, cfg, toks, pos, pages_b, table,
            jnp.asarray([i + 1], jnp.int32), jnp.asarray([1], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_forward_pallas_prefill_matches_xla():
    """S>1 prefill through the Pallas prefill kernel (which now carries
    gemma's per-layer window + softcap; interpret mode on CPU) must match
    the XLA path — the engine's attn_impl="pallas" gemma serving path."""
    from dynamo_tpu.ops.pallas.prefill import paged_prefill_attention_stacked

    cfg = ModelConfig.tiny(model_type="gemma2", num_layers=4, head_dim=128,
                           sliding_window=6, attn_logit_softcap=40.0,
                           final_logit_softcap=25.0)
    params = gemma.init_params(cfg, jax.random.PRNGKey(5))
    prompt = list(np.random.RandomState(2).randint(1, 255, size=13))
    ref, _ = _prefill(params, cfg, prompt,
                      gemma.make_pages(cfg, 8, 8, dtype=jnp.float32),
                      _alloc(1, 4))
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.asarray([list(range(len(prompt)))], jnp.int32)
    lens = jnp.asarray([len(prompt)], jnp.int32)
    got, _ = gemma.forward(params, cfg, toks, pos,
                           gemma.make_pages(cfg, 8, 8, dtype=jnp.float32),
                           _alloc(1, 4), lens, lens,
                           attn_impl=paged_prefill_attention_stacked)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


async def test_engine_pallas_matches_scan():
    """Serving gemma-2 with attn_impl="pallas" (decode AND prefill
    kernels now carry the per-layer window + softcap; interpret mode on
    CPU) streams the same greedy tokens as the XLA scan path."""
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)

    cfg = ModelConfig.tiny(model_type="gemma2", num_layers=4, head_dim=128,
                           sliding_window=6, attn_logit_softcap=40.0,
                           final_logit_softcap=25.0)
    params = gemma.init_params(cfg, jax.random.PRNGKey(7))

    def req(rid):
        return PreprocessedRequest(
            token_ids=list(range(1, 11)), request_id=rid,
            stop_conditions=StopConditions(max_tokens=5),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[])

    outs = {}
    for impl in ("scan", "pallas"):
        eng = JaxEngine(cfg, params, JaxEngineConfig(
            num_pages=32, page_size=8, max_num_seqs=2, max_prefill_chunk=8,
            max_context=64, min_prefill_bucket=4, attn_impl=impl))
        try:
            assert eng.attn_impl == impl
            toks = []
            async for f in eng.generate(req(impl)):
                toks.extend(f.token_ids)
            outs[impl] = toks
        finally:
            await eng.stop()
    assert outs["pallas"] == outs["scan"]
    assert len(outs["pallas"]) == 5


