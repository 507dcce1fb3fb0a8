"""Model-layer tests: paged forward correctness, chunked prefill/decode
equivalence, HF checkpoint parity against transformers (torch CPU), sampling.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import forward, init_params, make_pages
from dynamo_tpu.ops.sampling import sample_tokens


def _alloc(batch, max_pages):
    """Sequential page tables (page 0 is reserved)."""
    table = np.arange(1, batch * max_pages + 1, dtype=np.int32)
    return jnp.asarray(table.reshape(batch, max_pages))


def _prefill_all(params, cfg, token_rows, pages, page_table):
    """Prefill each row fully in one call; rows padded to max len."""
    B = len(token_rows)
    S = max(len(r) for r in token_rows)
    toks = np.zeros((B, S), np.int32)
    new_lens = np.asarray([len(r) for r in token_rows], np.int32)
    for i, r in enumerate(token_rows):
        toks[i, :len(r)] = r
    positions = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    logits, pages = forward(params, cfg, jnp.asarray(toks), jnp.asarray(positions),
                            pages, page_table, jnp.asarray(new_lens),
                            jnp.asarray(new_lens))
    return logits, pages


def test_forward_shapes_and_cache_write():
    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    pages = make_pages(cfg, num_pages=9, page_size=8, dtype=jnp.float32)
    table = _alloc(2, 4)
    rows = [[1, 2, 3, 4, 5], [7, 8, 9]]
    logits, pages = _prefill_all(params, cfg, rows, pages, table)
    assert logits.shape == (2, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # K of row 0 token 0 landed in page_table[0,0]=1, slot 0; garbage page 0
    # took the padded writes of row 1.  (layout [L, N, 2, Hkv, ps, Dh])
    assert np.abs(np.asarray(pages[0, 1, 0, :, 0])).sum() > 0
    # row 1 only wrote 3 slots of its first page (page 5)
    assert np.abs(np.asarray(pages[0, 5, 0, :, 3])).sum() == 0


def test_decode_matches_full_prefill():
    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = list(np.random.RandomState(0).randint(1, 255, size=11))

    # Reference: one-shot prefill of the full prompt.
    pages_a = make_pages(cfg, 6, 8, dtype=jnp.float32)
    table = _alloc(1, 4)
    ref_logits, _ = _prefill_all(params, cfg, [prompt], pages_a, table)

    # Incremental: prefill all but last, then decode the last token.
    pages_b = make_pages(cfg, 6, 8, dtype=jnp.float32)
    _, pages_b = _prefill_all(params, cfg, [prompt[:-1]], pages_b, table)
    n = len(prompt) - 1
    logits, _ = forward(
        params, cfg, jnp.asarray([[prompt[-1]]], dtype=jnp.int32),
        jnp.asarray([[n]], dtype=jnp.int32), pages_b, table,
        jnp.asarray([n + 1], dtype=jnp.int32), jnp.asarray([1], dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits),
                               rtol=2e-2, atol=2e-3)


def test_chunked_prefill_matches_one_shot():
    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(2))
    prompt = list(np.random.RandomState(1).randint(1, 255, size=13))
    table = _alloc(1, 4)

    pages_a = make_pages(cfg, 6, 8, dtype=jnp.float32)
    ref_logits, _ = _prefill_all(params, cfg, [prompt], pages_a, table)

    pages_b = make_pages(cfg, 6, 8, dtype=jnp.float32)
    split = 7
    _, pages_b = _prefill_all(params, cfg, [prompt[:split]], pages_b, table)
    rest = prompt[split:]
    S = len(rest)
    logits, _ = forward(
        params, cfg, jnp.asarray([rest], dtype=jnp.int32),
        jnp.asarray([list(range(split, split + S))], dtype=jnp.int32),
        pages_b, table, jnp.asarray([len(prompt)], dtype=jnp.int32),
        jnp.asarray([S], dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(logits),
                               rtol=2e-2, atol=2e-3)


def test_hf_checkpoint_parity(tmp_path):
    """Our jax forward must reproduce transformers' logits from the same
    checkpoint (tiny random llama, torch CPU reference)."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    from dynamo_tpu.models.hf_loader import load_hf_params
    cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
    params = load_hf_params(cfg, str(tmp_path))

    prompt = [3, 17, 42, 99, 5, 64, 23]
    with torch.no_grad():
        ref = model(torch.tensor([prompt])).logits[0, -1].numpy()

    pages = make_pages(cfg, 6, 8, dtype=jnp.float32)
    table = _alloc(1, 4)
    logits, _ = _prefill_all(params, cfg, [prompt], pages, table)
    np.testing.assert_allclose(np.asarray(logits[0]), ref, rtol=2e-3, atol=2e-3)


def test_sampling_greedy_and_topk():
    rng = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.RandomState(3).randn(4, 50).astype(np.float32))
    # greedy (temperature 0) == argmax
    toks, lp = sample_tokens(logits, rng,
                             jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(toks), np.argmax(np.asarray(logits), -1))
    assert np.all(np.asarray(lp) <= 0)
    # top_k=1 == argmax even at high temperature
    toks2, _ = sample_tokens(logits, rng, jnp.full((4,), 5.0),
                             jnp.ones(4, jnp.int32), jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(toks2), np.argmax(np.asarray(logits), -1))
    # sampling with temperature draws valid ids and is seed-deterministic
    t3a, _ = sample_tokens(logits, rng, jnp.ones(4), jnp.zeros(4, jnp.int32),
                           jnp.full((4,), 0.9))
    t3b, _ = sample_tokens(logits, rng, jnp.ones(4), jnp.zeros(4, jnp.int32),
                           jnp.full((4,), 0.9))
    np.testing.assert_array_equal(np.asarray(t3a), np.asarray(t3b))
    assert np.all((np.asarray(t3a) >= 0) & (np.asarray(t3a) < 50))


class TestPallasDecodeStacked:
    """The decode kernel against the XLA path, in interpreter mode on the
    CPU (same jaxpr, no Mosaic): the whole [L, N, ...] cache enters the
    kernel and an SMEM scalar picks the layer — including with a TRACED
    index inside a ``lax.scan`` (the engine's scan+pallas decode path)."""

    def _mk(self, seed=0):
        L, N, Hkv, ps, Dh = 3, 16, 2, 8, 128
        pages = jnp.asarray(
            jax.random.normal(jax.random.PRNGKey(seed), (L, N, 2, Hkv, ps, Dh)),
            dtype=jnp.bfloat16)
        B, P = 4, 6
        table = (jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
                 % 15 + 1)
        q = jnp.asarray(
            jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 1, 4, Dh)),
            dtype=jnp.bfloat16)
        total = jnp.array([9, 17, 1, 48], jnp.int32)
        return pages, q, table, total

    def test_static_layer_matches_xla(self):
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas import paged_decode_attention_stacked
        pages, q, table, total = self._mk()
        positions = (total - 1)[:, None]
        for layer in range(pages.shape[0]):
            ref = paged_attention(q, pages, layer, table, positions,
                                  total, 0.088)
            out = paged_decode_attention_stacked(
                q, pages, layer, table, positions, total, 0.088,
                interpret=True)
            np.testing.assert_allclose(np.asarray(ref, np.float32),
                                       np.asarray(out, np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_traced_layer_inside_scan(self):
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas import paged_decode_attention_stacked
        pages, q, table, total = self._mk(seed=4)
        positions = (total - 1)[:, None]
        L = pages.shape[0]

        def body(carry, lidx):
            out = paged_decode_attention_stacked(
                q, pages, lidx, table, positions, total, 0.088,
                interpret=True)
            return carry, out

        _, outs = jax.lax.scan(body, 0, jnp.arange(L))
        for layer in range(L):
            ref = paged_attention(q, pages, layer, table, positions,
                                  total, 0.088)
            np.testing.assert_allclose(np.asarray(ref, np.float32),
                                       np.asarray(outs[layer], np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_window_softcap_matches_xla(self):
        """gemma-2 semantics in the kernel: sliding window (with the
        before-window chunks skipped) + logit soft-capping must match the
        XLA path."""
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas import paged_decode_attention_stacked
        pages, q, table, total = self._mk(seed=7)
        positions = (total - 1)[:, None]
        for win, cap in ((16, None), (0, 30.0), (16, 30.0), (40, 8.0)):
            ref = paged_attention(
                q, pages, 1, table, positions, total, 0.088,
                window=jnp.int32(win), softcap=cap)
            out = paged_decode_attention_stacked(
                q, pages, 1, table, positions, total, 0.088,
                window=win, softcap=cap, interpret=True)
            np.testing.assert_allclose(
                np.asarray(ref, np.float32), np.asarray(out, np.float32),
                rtol=2e-2, atol=2e-2, err_msg=f"win={win} cap={cap}")

    async def test_engine_pallas_scan_matches_scan_tokens(self):
        """attn_impl='pallas' (scan forward + stacked kernel, interpret on
        CPU) must generate the same greedy tokens as the plain scan path —
        this is the engine's real TPU decode program."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        cfg = ModelConfig.tiny(num_heads=2, num_kv_heads=1, head_dim=128)

        def req(rid):
            return PreprocessedRequest(
                token_ids=list(range(1, 11)), request_id=rid,
                stop_conditions=StopConditions(max_tokens=6),
                sampling_options=SamplingOptions(temperature=0.0))

        outs = {}
        for impl in ("scan", "pallas"):
            eng = JaxEngine.random_init(cfg, JaxEngineConfig(
                num_pages=32, page_size=8, max_num_seqs=2,
                max_prefill_chunk=16, max_context=64, min_prefill_bucket=4,
                attn_impl=impl))
            assert eng.attn_impl == impl
            try:
                toks = []
                async for f in eng.generate(req(impl)):
                    toks.extend(f.token_ids)
                outs[impl] = toks
            finally:
                await eng.stop()
        assert outs["scan"] == outs["pallas"]
        assert len(outs["scan"]) == 6


class TestPallasPrefill:
    """Chunked-prefill flash kernel vs the XLA paged-attention path.

    Comparison is restricted to REAL query slots: the kernel masks pad
    slots by the row's contiguous positions (q_start + s) while the XLA
    path uses the (zeroed) positions array — pad-slot outputs differ by
    design and never reach logits (pads' K/V go to the garbage page, so no
    real query attends to them)."""

    def _mk(self, seed=0):
        L, N, Hkv, ps, Dh = 2, 33, 2, 8, 128
        Hq, B, S, P = 4, 3, 16, 8
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        pages = jax.random.normal(k1, (L, N, 2, Hkv, ps, Dh)) \
            .astype(jnp.bfloat16)
        q = jax.random.normal(k2, (B, S, Hq, Dh)).astype(jnp.bfloat16)
        table = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
        return pages, q, table

    def test_matches_xla_path(self):
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas.prefill import (
            paged_prefill_attention_stacked)
        pages, q, table = self._mk()
        B, S = q.shape[:2]
        # mixed rows: fresh prompt, prefix-cache continuation, short row
        # with pad slots
        start = jnp.array([0, 24, 3], jnp.int32)
        new = jnp.array([S, S, 9], jnp.int32)
        positions = start[:, None] + jnp.arange(S)[None, :]
        positions = jnp.where(jnp.arange(S)[None, :] < new[:, None],
                              positions, 0)
        total = start + new
        for layer in range(pages.shape[0]):
            ref = paged_attention(q, pages, layer, table, positions, total,
                                  0.088)
            out = paged_prefill_attention_stacked(
                q, pages, layer, table, positions, total, 0.088,
                interpret=True)
            for b in range(B):
                nb = int(new[b])
                np.testing.assert_allclose(
                    np.asarray(ref[b, :nb], np.float32),
                    np.asarray(out[b, :nb], np.float32),
                    rtol=3e-2, atol=3e-2)

    def test_ragged_query_block(self):
        """S not divisible by the 256-row query block (e.g. a 320-token
        chunk bucket): the ragged last block must still be correct."""
        from dynamo_tpu.ops import pallas as _p
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas import prefill as pf
        L, N, Hkv, ps, Dh = 2, 33, 2, 8, 128
        Hq, B, S, P = 4, 2, 20, 8  # S=20 vs forced q_block=16
        k1, k2 = jax.random.split(jax.random.PRNGKey(9))
        pages = jax.random.normal(k1, (L, N, 2, Hkv, ps, Dh)) \
            .astype(jnp.bfloat16)
        q = jax.random.normal(k2, (B, S, Hq, Dh)).astype(jnp.bfloat16)
        table = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
        positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
        total = jnp.full((B,), S, jnp.int32)
        orig = pf.QUERY_BLOCK
        pf.QUERY_BLOCK = 16
        try:
            out = pf.paged_prefill_attention_stacked(
                q, pages, 0, table, positions, total, 0.1, interpret=True)
        finally:
            pf.QUERY_BLOCK = orig
        ref = paged_attention(q, pages, 0, table, positions, total, 0.1)
        np.testing.assert_allclose(np.asarray(ref, np.float32),
                                   np.asarray(out, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_window_softcap_matches_xla(self):
        """gemma-2 semantics in the PREFILL kernel: per-row sliding window
        (with before-window chunks skipped) + logit soft-capping must
        match the XLA path — closes the r4 gap that kept Gemma-2 prefill
        off the kernel (models/gemma.py)."""
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas.prefill import (
            paged_prefill_attention_stacked)
        pages, q, table = self._mk(seed=11)
        B, S = q.shape[:2]
        # continuation rows deep enough that a 16-token window starts
        # past chunk 0 (exercises the c0 chunk skip)
        start = jnp.array([0, 40, 3], jnp.int32)
        new = jnp.array([S, S, 9], jnp.int32)
        positions = start[:, None] + jnp.arange(S)[None, :]
        positions = jnp.where(jnp.arange(S)[None, :] < new[:, None],
                              positions, 0)
        total = start + new
        for win, cap in ((16, None), (0, 30.0), (16, 30.0), (40, 8.0)):
            ref = paged_attention(q, pages, 1, table, positions, total,
                                  0.088, window=jnp.asarray(win, jnp.int32),
                                  softcap=cap)
            out = paged_prefill_attention_stacked(
                q, pages, 1, table, positions, total, 0.088,
                window=win, softcap=cap, interpret=True)
            for b in range(B):
                nb = int(new[b])
                np.testing.assert_allclose(
                    np.asarray(ref[b, :nb], np.float32),
                    np.asarray(out[b, :nb], np.float32),
                    rtol=3e-2, atol=3e-2, err_msg=f"win={win} cap={cap}")

    def test_inside_scan_traced_layer(self):
        from dynamo_tpu.ops.attention import paged_attention
        from dynamo_tpu.ops.pallas.prefill import (
            paged_prefill_attention_stacked)
        pages, q, table = self._mk(seed=5)
        B, S = q.shape[:2]
        positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
        total = jnp.full((B,), S, jnp.int32)

        def body(carry, lidx):
            out = paged_prefill_attention_stacked(
                q, pages, lidx, table, positions, total, 0.1,
                interpret=True)
            return carry, out

        _, outs = jax.lax.scan(body, 0, jnp.arange(pages.shape[0]))
        for layer in range(pages.shape[0]):
            ref = paged_attention(q, pages, layer, table, positions, total,
                                  0.1)
            np.testing.assert_allclose(np.asarray(ref, np.float32),
                                       np.asarray(outs[layer], np.float32),
                                       rtol=3e-2, atol=3e-2)


class TestBlockwisePrefillAttention:
    """The chunked online-softmax prefill path must match the direct
    full-gather path bit-for-bit up to f32 reduction order."""

    def _mk(self, B, S, P, Hq, Hkv, ps, Dh, dtype, seed=0):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        kv = jax.random.normal(k1, (1 + B * P, 2, Hkv, ps, Dh)).astype(dtype)
        q = jax.random.normal(k2, (B, S, Hq, Dh)).astype(dtype)
        table = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
        return q, kv, table

    def test_matches_direct_path(self):
        from dynamo_tpu.ops import attention as A
        # P=24 > PAGES_PER_CHUNK so the blockwise path triggers; the direct
        # reference is computed by calling the internals explicitly
        B, S, P, Hq, Hkv, ps, Dh = 3, 16, 24, 4, 2, 8, 32
        q, kv, table = self._mk(B, S, P, Hq, Hkv, ps, Dh, jnp.float32)
        # mixed contexts: a fresh prompt, a prefix-hit continuation, a
        # mid-table context; plus padded rows of tokens beyond new_lens
        start = jnp.array([0, 64, 5], jnp.int32)
        new = jnp.array([16, 16, 9], jnp.int32)
        positions = start[:, None] + jnp.arange(S)[None, :]
        total = start + new
        out = A.paged_attention(q, kv[None], 0, table, positions, total,
                                0.17)
        # direct reference
        g = kv[table]
        k = A._gathered_to_bhtd(g[:, :, 0])
        v = A._gathered_to_bhtd(g[:, :, 1])
        qg = q.reshape(B, S, Hkv, Hq // Hkv, Dh)
        ref = A._attend(qg, k, v, positions, total, 0.17)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_stacked_path_matches(self):
        from dynamo_tpu.ops import attention as A
        B, S, P, Hq, Hkv, ps, Dh = 2, 8, 16, 4, 2, 4, 16
        L = 3
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        pages = jax.random.normal(
            k1, (L, 1 + B * P, 2, Hkv, ps, Dh)).astype(jnp.float32)
        q = jax.random.normal(k2, (B, S, Hq, Dh)).astype(jnp.float32)
        table = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
        positions = jnp.tile(jnp.arange(S)[None], (B, 1)) + 20
        total = jnp.array([28, 23], jnp.int32)
        out = A.paged_attention(q, pages, 1, table, positions, total, 0.2)
        # the same layer as a pool of its own
        ref = A.paged_attention(q, pages[1][None], 0, table, positions,
                                total, 0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_table_not_multiple_of_chunk(self):
        from dynamo_tpu.ops import attention as A
        B, S, P, Hq, Hkv, ps, Dh = 2, 4, 11, 2, 1, 4, 16
        q, kv, table = self._mk(B, S, P, Hq, Hkv, ps, Dh, jnp.float32, seed=7)
        positions = jnp.tile(jnp.arange(S)[None], (B, 1))
        total = jnp.array([4, 3], jnp.int32)
        out = A.paged_attention(q, kv[None], 0, table, positions, total, 0.3)
        g = kv[table]
        k = A._gathered_to_bhtd(g[:, :, 0])
        v = A._gathered_to_bhtd(g[:, :, 1])
        qg = q.reshape(B, S, Hkv, Hq // Hkv, Dh)
        ref = A._attend(qg, k, v, positions, total, 0.3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
