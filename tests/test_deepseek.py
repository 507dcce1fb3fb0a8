"""DeepSeek (MLA) family tests: HF logits parity from a real checkpoint,
decode/chunked-prefill equivalence over the latent paged cache, the gate's
group-limited routing, and serving-engine e2e."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import deepseek, get_family
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import make_pages


def ds_cfg(**kw):
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=1, head_dim=32,
        model_type="deepseek_v2", dtype="float32",
        q_lora_rank=0, kv_lora_rank=32, qk_rope_head_dim=16,
        qk_nope_head_dim=32, v_head_dim=32,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        n_shared_experts=2, first_k_dense_replace=1,
        routed_scaling_factor=1.0)
    d.update(kw)
    return ModelConfig(**d)


def _alloc(batch, max_pages):
    table = np.arange(1, batch * max_pages + 1, dtype=np.int32)
    return jnp.asarray(table.reshape(batch, max_pages))


def _prefill(params, cfg, rows, pages, table):
    B = len(rows)
    S = max(len(r) for r in rows)
    toks = np.zeros((B, S), np.int32)
    lens = np.asarray([len(r) for r in rows], np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    pos = np.tile(np.arange(S, dtype=np.int32), (B, 1))
    logits, out_pages, _aux = deepseek.forward(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos), pages, table,
        jnp.asarray(lens), jnp.asarray(lens))
    return logits, out_pages


def test_family_registry():
    assert get_family(ds_cfg()) is deepseek


def test_rope_interleaved_matches_complex_rotation():
    """Our interleaved rope vs an explicit complex-number reference (the
    HF apply_rotary_emb convention)."""
    B, S, D, theta = 2, 5, 8, 10000.0
    x = np.random.RandomState(0).randn(B, S, D).astype(np.float32)
    pos = np.tile(np.arange(S), (B, 1))
    out = np.asarray(deepseek.rope_interleaved(
        jnp.asarray(x), jnp.asarray(pos), theta))
    inv = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ref = np.empty_like(x)
    for b in range(B):
        for s in range(S):
            z = x[b, s].reshape(-1, 2) @ np.array([[1], [1j]])
            rot = z[:, 0] * np.exp(1j * s * inv)
            ref[b, s] = np.stack([rot.real, rot.imag], -1).reshape(-1)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestForward:
    def test_decode_matches_full_prefill(self):
        cfg = ds_cfg()
        params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
        prompt = list(np.random.RandomState(0).randint(1, 255, size=11))
        table = _alloc(1, 4)

        pages_a = make_pages(cfg, 6, 8, dtype=jnp.float32)
        ref_logits, _ = _prefill(params, cfg, [prompt], pages_a, table)

        pages_b = make_pages(cfg, 6, 8, dtype=jnp.float32)
        _, pages_b = _prefill(params, cfg, [prompt[:-1]], pages_b, table)
        n = len(prompt) - 1
        logits, _, _ = deepseek.forward(
            params, cfg, jnp.asarray([[prompt[-1]]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), pages_b, table,
            jnp.asarray([n + 1], jnp.int32), jnp.asarray([1], jnp.int32))
        np.testing.assert_allclose(np.asarray(ref_logits),
                                   np.asarray(logits), rtol=2e-2, atol=2e-3)

    def test_chunked_prefill_matches_one_shot(self):
        cfg = ds_cfg()
        params = deepseek.init_params(cfg, jax.random.PRNGKey(2))
        prompt = list(np.random.RandomState(1).randint(1, 255, size=13))
        table = _alloc(1, 4)
        pages_a = make_pages(cfg, 6, 8, dtype=jnp.float32)
        ref_logits, _ = _prefill(params, cfg, [prompt], pages_a, table)
        pages_b = make_pages(cfg, 6, 8, dtype=jnp.float32)
        split = 7
        _, pages_b = _prefill(params, cfg, [prompt[:split]], pages_b, table)
        rest = prompt[split:]
        S = len(rest)
        logits, _, _ = deepseek.forward(
            params, cfg, jnp.asarray([rest], jnp.int32),
            jnp.asarray([list(range(split, split + S))], jnp.int32),
            pages_b, table, jnp.asarray([len(prompt)], jnp.int32),
            jnp.asarray([S], jnp.int32))
        np.testing.assert_allclose(np.asarray(ref_logits),
                                   np.asarray(logits), rtol=2e-2, atol=2e-3)

    def test_blockwise_prefill_matches_direct(self):
        """Wide page table (P > PAGES_PER_CHUNK) takes the chunked
        online-softmax latent path; logits must match a run whose table
        is narrow enough for the direct full-gather path."""
        cfg = ds_cfg(max_position_embeddings=512)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(4))
        prompt = list(np.random.RandomState(3).randint(1, 255, size=29))

        # direct path: table width 4 (<= 8)
        narrow = _alloc(1, 4)
        l_direct, _ = _prefill(params, cfg, [prompt],
                               make_pages(cfg, 6, 8, jnp.float32), narrow)
        # blockwise path: same pages, table padded out to width 12
        wide = jnp.concatenate(
            [narrow, jnp.zeros((1, 8), jnp.int32)], axis=1)
        l_block, _ = _prefill(params, cfg, [prompt],
                              make_pages(cfg, 6, 8, jnp.float32), wide)
        np.testing.assert_allclose(np.asarray(l_direct),
                                   np.asarray(l_block),
                                   rtol=2e-4, atol=2e-4)

    def test_dispatch_backend_matches_dense(self):
        cfg_d = ds_cfg()
        cfg_s = ds_cfg(moe_backend="dispatch", moe_capacity_factor=4.0)
        params = deepseek.init_params(cfg_d, jax.random.PRNGKey(5))
        prompt = list(range(1, 12))
        table = _alloc(1, 4)
        l1, _ = _prefill(params, cfg_d, [prompt],
                         make_pages(cfg_d, 6, 8, jnp.float32), table)
        l2, _ = _prefill(params, cfg_s, [prompt],
                         make_pages(cfg_s, 6, 8, jnp.float32), table)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=2e-4, atol=2e-4)


class TestGate:
    def test_group_limited_restricts_to_top_groups(self):
        cfg = ds_cfg(num_experts=8, topk_method="group_limited_greedy",
                     n_group=4, topk_group=2, num_experts_per_tok=2)
        lp = {"w_router": jnp.asarray(
            np.random.RandomState(5).randn(64, 8), jnp.float32)}
        x = jnp.asarray(np.random.RandomState(6).randn(2, 3, 64), jnp.float32)
        top_w, top_i = deepseek._gate(cfg, lp, x)
        scores = np.asarray(jax.nn.softmax(
            x.astype(jnp.float32) @ lp["w_router"], axis=-1))
        gs = scores.reshape(2, 3, 4, 2).max(-1)
        for b in range(2):
            for s in range(3):
                allowed_groups = set(np.argsort(-gs[b, s])[:2])
                for e in np.asarray(top_i)[b, s]:
                    assert e // 2 in allowed_groups

    def test_noaux_tc_matches_numpy_reference(self):
        """V3 gate: sigmoid scores, bias-corrected top-2-sum group
        selection, weights from UNCORRECTED scores, renormalized."""
        cfg = ds_cfg(num_experts=8, topk_method="noaux_tc", n_group=4,
                     topk_group=2, num_experts_per_tok=2,
                     norm_topk_prob=True, routed_scaling_factor=2.0)
        rng = np.random.RandomState(8)
        w = rng.randn(64, 8).astype(np.float32)
        b = rng.uniform(-0.5, 0.5, 8).astype(np.float32)
        lp = {"w_router": jnp.asarray(w), "router_bias": jnp.asarray(b)}
        x = rng.randn(2, 3, 64).astype(np.float32)
        top_w, top_i = deepseek._gate(cfg, lp, jnp.asarray(x))
        scores = 1 / (1 + np.exp(-(x @ w)))
        sfc = scores + b
        for bi in range(2):
            for s in range(3):
                gs = np.sort(sfc[bi, s].reshape(4, 2), -1)[:, ::-1]
                group_sum = gs[:, :2].sum(-1)
                keep_groups = set(np.argsort(-group_sum)[:2])
                masked = np.where(
                    [e // 2 in keep_groups for e in range(8)],
                    sfc[bi, s], 0.0)
                want_i = set(np.argsort(-masked)[:2])
                got_i = set(np.asarray(top_i)[bi, s])
                assert got_i == want_i
                wsum = scores[bi, s][list(got_i)].sum() + 1e-20
                for j, e in enumerate(np.asarray(top_i)[bi, s]):
                    np.testing.assert_allclose(
                        np.asarray(top_w)[bi, s, j],
                        scores[bi, s, e] / wsum * 2.0, rtol=1e-5)


class TestHfParity:
    def test_matches_transformers_deepseek_v2(self, tmp_path):
        """Our MLA forward must reproduce transformers' DeepseekV2 logits
        from the same checkpoint (tiny random model, torch CPU)."""
        torch = pytest.importorskip("torch")
        from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

        hf_cfg = DeepseekV2Config(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            n_routed_experts=4, n_shared_experts=2, num_experts_per_tok=2,
            first_k_dense_replace=1, norm_topk_prob=False,
            routed_scaling_factor=1.0, topk_method="greedy",
            q_lora_rank=None, kv_lora_rank=32, qk_rope_head_dim=16,
            qk_nope_head_dim=32, v_head_dim=32, head_dim=48,
            max_position_embeddings=128, rms_norm_eps=1e-6,
            rope_theta=10000.0, tie_word_embeddings=False,
            attention_bias=False, attn_implementation="eager")
        torch.manual_seed(0)
        model = DeepseekV2ForCausalLM(hf_cfg).eval()
        model.save_pretrained(tmp_path, safe_serialization=True)

        cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
        assert cfg.kv_lora_rank == 32 and cfg.num_kv_heads == 1
        from dynamo_tpu.models.hf_loader import load_hf_params
        params = load_hf_params(cfg, str(tmp_path))

        prompt = [3, 17, 42, 99, 5, 64, 23]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0, -1].numpy()

        pages = make_pages(cfg, 6, 8, dtype=jnp.float32)
        table = _alloc(1, 4)
        logits, _ = _prefill(params, cfg, [prompt], pages, table)
        np.testing.assert_allclose(np.asarray(logits[0]), ref,
                                   rtol=3e-3, atol=3e-3)


class TestSharding:
    async def test_tp_ep_sharded_matches_unsharded(self):
        """tp=2 x ep=2 GSPMD over the MLA pytree (query heads over tp,
        routed experts over ep, latent cache replicated) must produce
        identical greedy tokens."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.parallel import MeshSpec, ModelSharding, make_mesh
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        cfg = ds_cfg()
        prompt = list(range(1, 10))

        def req(rid):
            return PreprocessedRequest(
                token_ids=prompt, request_id=rid,
                stop_conditions=StopConditions(max_tokens=5),
                sampling_options=SamplingOptions(temperature=0.0))

        async def run(engine, rid):
            try:
                return [t for f in [x async for x in engine.generate(
                    req(rid))] for t in f.token_ids]
            finally:
                await engine.stop()

        ecfg = dict(num_pages=32, page_size=4, max_num_seqs=2,
                    max_prefill_chunk=8, max_context=64,
                    min_prefill_bucket=4, attn_impl="scan")
        want = await run(JaxEngine.random_init(
            cfg, JaxEngineConfig(**ecfg)), "base")

        mesh = make_mesh(MeshSpec(tp=2, ep=2), devices=jax.devices()[:4])
        shard = ModelSharding(cfg, mesh)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
        got = await run(JaxEngine(cfg, shard.shard_params(params),
                                  JaxEngineConfig(
            shard_pages_fn=shard.shard_pages, **ecfg)), "sharded")
        assert got == want
        assert len(got) == 5


class TestYarnParity:
    def test_matches_transformers_with_yarn_scaling(self, tmp_path):
        """Real DeepSeek checkpoints ship yarn rope_scaling; the scaled
        frequencies + attention_factor must reproduce HF logits."""
        torch = pytest.importorskip("torch")
        from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

        hf_cfg = DeepseekV2Config(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
            first_k_dense_replace=1, routed_scaling_factor=1.0,
            topk_method="greedy", q_lora_rank=None, kv_lora_rank=32,
            qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
            max_position_embeddings=256, rms_norm_eps=1e-6,
            rope_theta=10000.0, tie_word_embeddings=False,
            rope_scaling={"type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 64,
                          "mscale": 0.707, "mscale_all_dim": 0.707,
                          "beta_fast": 32, "beta_slow": 1},
            attn_implementation="eager")
        torch.manual_seed(1)
        model = DeepseekV2ForCausalLM(hf_cfg).eval()
        model.save_pretrained(tmp_path, safe_serialization=True)

        cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
        assert cfg.rope_scaling_factor == 4.0
        from dynamo_tpu.models.hf_loader import load_hf_params
        params = load_hf_params(cfg, str(tmp_path))
        prompt = [5, 90, 11, 77, 40, 2, 66, 23, 8]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0, -1].numpy()
        pages = make_pages(cfg, 6, 8, dtype=jnp.float32)
        logits, _ = _prefill(params, cfg, [prompt], pages, _alloc(1, 4))
        np.testing.assert_allclose(np.asarray(logits[0]), ref,
                                   rtol=3e-3, atol=3e-3)


class TestMlaPallasDecode:
    """The latent (MLA) Pallas decode kernel (``ops/pallas/mla_decode``)
    vs the XLA latent-attention math, interpret mode on CPU — the engine's
    deepseek ``attn_impl="pallas"`` decode path."""

    def _mk(self, seed=0):
        L, N, ps, dkv, dr, nh = 3, 16, 8, 128, 16, 4
        pages = jax.random.normal(jax.random.PRNGKey(seed),
                                  (L, N, 2, 1, ps, dkv), jnp.float32)
        # slot 1 holds k_pe zero-padded to the latent width — the kernel
        # relies on the pad region being zero (as written by _cache_rows)
        pages = pages.at[:, :, 1, :, :, dr:].set(0.0)
        B, P = 4, 6
        table = (jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
                 % 15 + 1)
        q_lat = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (B, 1, nh, dkv), jnp.float32)
        q_pe = jax.random.normal(jax.random.PRNGKey(seed + 2),
                                 (B, 1, nh, dr), jnp.float32)
        total = jnp.array([9, 17, 1, 48], jnp.int32)
        return pages, q_lat, q_pe, table, total

    @staticmethod
    def _ref(q_lat, q_pe, pages, layer, table, total, scale):
        """The _mla_attend math (scores in latent space, value = latent)
        without the W_UV projection — what the kernel must reproduce."""
        g = pages[layer][table]                     # [B, P, 2, 1, ps, dkv]
        B, P, _2, _1, ps, dkv = g.shape
        ckv = g[:, :, 0, 0].reshape(B, P * ps, dkv)
        kpe = g[:, :, 1, 0].reshape(B, P * ps, dkv)[..., :q_pe.shape[-1]]
        s = (jnp.einsum("bsnk,btk->bnst", q_lat, ckv)
             + jnp.einsum("bsnd,btd->bnst", q_pe, kpe)) * scale
        t_pos = jnp.arange(P * ps)[None, None, None, :]
        s = jnp.where(t_pos < total[:, None, None, None], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnst,btk->bsnk", probs, ckv)

    def test_kernel_matches_latent_attention(self):
        from dynamo_tpu.ops.pallas.mla_decode import (
            mla_paged_decode_stacked, supports)
        pages, q_lat, q_pe, table, total = self._mk()
        assert supports(pages.shape[-1], pages.shape[-2])
        scale = 0.11
        for layer in range(pages.shape[0]):
            ref = self._ref(q_lat, q_pe, pages, layer, table, total, scale)
            out = mla_paged_decode_stacked(q_lat, q_pe, pages, layer,
                                           table, total, scale,
                                           interpret=True)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=2e-4, atol=2e-4)

    def test_traced_layer_inside_scan(self):
        from dynamo_tpu.ops.pallas.mla_decode import mla_paged_decode_stacked
        pages, q_lat, q_pe, table, total = self._mk(seed=5)
        scale = 0.09
        L = pages.shape[0]

        def body(carry, lidx):
            out = mla_paged_decode_stacked(q_lat, q_pe, pages, lidx, table,
                                           total, scale, interpret=True)
            return carry, out

        _, outs = jax.lax.scan(body, 0, jnp.arange(L))
        for layer in range(L):
            ref = self._ref(q_lat, q_pe, pages, layer, table, total, scale)
            np.testing.assert_allclose(np.asarray(ref),
                                       np.asarray(outs[layer]),
                                       rtol=2e-4, atol=2e-4)

    def test_forward_pallas_matches_xla_decode(self):
        """deepseek.forward no longer ignores attn_impl: with a supported
        geometry (dkv % 128 == 0) an impl carrying the
        ``pallas_paged_kernel`` marker routes S==1 through the MLA
        kernel; logits must match the XLA path."""
        from dynamo_tpu.ops.pallas import paged_decode_attention_stacked

        cfg = ds_cfg(kv_lora_rank=128, head_dim=128)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(1))
        prompt = list(np.random.RandomState(7).randint(1, 255, size=11))
        table = _alloc(1, 4)
        pages = make_pages(cfg, 6, 8, dtype=jnp.float32)
        _, pages = _prefill(params, cfg, [prompt[:-1]], pages, table)
        n = len(prompt) - 1
        step = lambda impl: deepseek.forward(  # noqa: E731
            params, cfg, jnp.asarray([[prompt[-1]]], jnp.int32),
            jnp.asarray([[n]], jnp.int32), pages, table,
            jnp.asarray([n + 1], jnp.int32), jnp.asarray([1], jnp.int32),
            attn_impl=impl)[0]
        ref = step(None)
        # the engine passes the stacked GQA kernel; its marker (not the
        # callable itself) opts deepseek into the MLA kernel
        out = step(paged_decode_attention_stacked)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-3, atol=2e-3)
        # an unmarked impl is ignored (XLA path), not silently swapped
        unmarked = step(object())
        np.testing.assert_allclose(np.asarray(ref), np.asarray(unmarked),
                                   rtol=1e-6, atol=1e-6)


class TestMlaPallasPrefill:
    """The latent (MLA) Pallas PREFILL kernel vs the XLA latent math —
    the engine's deepseek attn_impl="pallas" S>1 path."""

    def _mk(self, seed=0, B=3, S=16):
        L, N, ps, dkv, dr, nh = 2, 33, 8, 128, 16, 4
        pages = jax.random.normal(jax.random.PRNGKey(seed),
                                  (L, N, 2, 1, ps, dkv), jnp.float32)
        pages = pages.at[:, :, 1, :, :, dr:].set(0.0)
        P = 8
        table = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
        q_lat = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (B, S, nh, dkv), jnp.float32)
        q_pe = jax.random.normal(jax.random.PRNGKey(seed + 2),
                                 (B, S, nh, dr), jnp.float32)
        return pages, q_lat, q_pe, table

    @staticmethod
    def _ref(q_lat, q_pe, pages, layer, table, positions, total):
        g = pages[layer][table]
        B, P, _2, _1, ps, dkv = g.shape
        ckv = g[:, :, 0, 0].reshape(B, P * ps, dkv)
        kpe = g[:, :, 1, 0].reshape(B, P * ps, dkv)[..., :q_pe.shape[-1]]
        scale = 0.1
        s = (jnp.einsum("bsnk,btk->bnst", q_lat, ckv)
             + jnp.einsum("bsnd,btd->bnst", q_pe, kpe)) * scale
        t_pos = jnp.arange(P * ps)[None, None, None, :]
        mask = ((t_pos <= positions[:, None, :, None])
                & (t_pos < total[:, None, None, None]))
        s = jnp.where(mask, s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnst,btk->bsnk", probs, ckv)  # [B, S, nh, dkv]

    def test_kernel_matches_latent_attention(self):
        """Mixed rows — fresh prompt, deep prefix continuation, ragged
        short row — against the full-gather latent reference; comparison
        restricted to REAL slots (pads mask out downstream)."""
        from dynamo_tpu.ops.pallas.mla_prefill import (
            mla_paged_prefill_stacked)
        pages, q_lat, q_pe, table = self._mk()
        B, S = q_lat.shape[:2]
        start = jnp.array([0, 24, 3], jnp.int32)
        new = jnp.array([S, S, 9], jnp.int32)
        positions = start[:, None] + jnp.arange(S)[None, :]
        total = start + new
        for layer in range(pages.shape[0]):
            ref = self._ref(q_lat, q_pe, pages, layer, table, positions,
                            total)
            out = mla_paged_prefill_stacked(
                q_lat, q_pe, pages, layer, table, positions, total, 0.1,
                interpret=True)
            for b in range(B):
                nb = int(new[b])
                np.testing.assert_allclose(
                    np.asarray(ref[b, :nb]), np.asarray(out[b, :nb]),
                    rtol=2e-4, atol=2e-4)

    def test_ragged_query_block(self):
        """S not divisible by the adaptive query block: force SB below S
        and check the ragged last block."""
        from dynamo_tpu.ops.pallas import mla_prefill as mp
        pages, q_lat, q_pe, table = self._mk(seed=4, S=20)
        B, S = q_lat.shape[:2]
        positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
        total = jnp.full((B,), S, jnp.int32)
        orig = mp._TARGET_M_ROWS
        mp._TARGET_M_ROWS = 4 * 8  # nh=4 -> SB=8, 3 blocks over S=20
        try:
            out = mp.mla_paged_prefill_stacked(
                q_lat, q_pe, pages, 1, table, positions, total, 0.1,
                interpret=True)
        finally:
            mp._TARGET_M_ROWS = orig
        ref = self._ref(q_lat, q_pe, pages, 1, table, positions, total)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_forward_pallas_prefill_matches_xla(self):
        """deepseek.forward S>1 with the Pallas marker rides the MLA
        prefill kernel; logits must match the XLA path (which itself is
        HF-parity tested)."""
        from dynamo_tpu.ops.pallas.prefill import (
            paged_prefill_attention_stacked)

        cfg = ds_cfg(kv_lora_rank=128, head_dim=128)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(3))
        prompt = list(np.random.RandomState(9).randint(1, 255, size=13))
        table = _alloc(1, 4)
        ref, _ = _prefill(params, cfg, [prompt],
                          make_pages(cfg, 8, 8, jnp.float32), table)
        toks = jnp.asarray([prompt], jnp.int32)
        pos = jnp.asarray([list(range(len(prompt)))], jnp.int32)
        lens = jnp.asarray([len(prompt)], jnp.int32)
        got, _, _ = deepseek.forward(
            params, cfg, toks, pos, make_pages(cfg, 8, 8, jnp.float32),
            table, lens, lens, attn_impl=paged_prefill_attention_stacked)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


class TestMlaPallasRagged:
    """The latent (MLA) Pallas kernel of a TOKEN-PACKED step
    (``ops/pallas/mla_ragged``), interpreted, against the full-gather
    latent math told per row — independent of the blockwise reference the
    packed forward runs off the chip (``deepseek.mla_ragged_attention``),
    which is held to the same oracle here."""

    # (start position, new tokens) per row: a chunk deep in a cached
    # prefix that spans several 64-token page chunks, a fresh chunk, two
    # decode rows, a pad row
    PLAN = [(150, 21), (0, 30), (77, 1), (8, 1), (0, 0)]

    def _mk(self, seed=0):
        L, ps, dkv, dr, nh, P = 3, 8, 128, 16, 4, 24
        R = len(self.PLAN)
        pages = jax.random.normal(jax.random.PRNGKey(seed),
                                  (L, 1 + R * P, 2, 1, ps, dkv))
        pages = pages.at[:, :, 1, :, :, dr:].set(0.0)
        table = jnp.arange(1, 1 + R * P, dtype=jnp.int32).reshape(R, P)
        new = jnp.asarray([n for _s, n in self.PLAN], jnp.int32)
        total = jnp.asarray([s + n if n else 1 for s, n in self.PLAN],
                            jnp.int32)
        T = 64                      # 54 real slots, the rest pad
        q_lat = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (T, nh, dkv))
        q_pe = jax.random.normal(jax.random.PRNGKey(seed + 2),
                                 (T, nh, dr))
        return pages, q_lat, q_pe, (table, jnp.cumsum(new) - new, new,
                                    total)

    def _ref(self, q_lat, q_pe, pages, layer, rows):
        """Row by row through the padded oracle of the prefill kernel
        (whose softmax scale is 0.1)."""
        table, starts, new, total = rows
        out = np.zeros(q_lat.shape, np.float32)
        for r, (start, n) in enumerate(self.PLAN):
            if not n:
                continue
            sl = slice(int(starts[r]), int(starts[r]) + n)
            pos = (start + jnp.arange(n))[None]
            out[sl] = np.asarray(TestMlaPallasPrefill._ref(
                q_lat[sl][None], q_pe[sl][None], pages, layer,
                table[r:r + 1], pos, total[r:r + 1]))[0]
        return out

    @pytest.mark.parametrize("query_block", [None, 8])
    def test_kernel_and_reference_match_latent_attention(self, query_block,
                                                         monkeypatch):
        from dynamo_tpu.ops.pallas import mla_ragged as mr
        if query_block:
            monkeypatch.setattr(mr, "_query_block", lambda *a: query_block)
        mr._mla_ragged.clear_cache()
        pages, q_lat, q_pe, rows = self._mk()
        cfg = ds_cfg(kv_lora_rank=128, head_dim=128, num_heads=4,
                     qk_rope_head_dim=16, qk_nope_head_dim=84)
        assert abs(deepseek._mla_scale(cfg) - 0.1) < 1e-9
        for layer in (0, 2):
            ref = self._ref(q_lat, q_pe, pages, layer, rows)
            out = mr.mla_ragged_attention_packed(
                q_lat, q_pe, pages, layer, *rows, 0.1, interpret=True)
            np.testing.assert_allclose(ref, np.asarray(out),
                                       rtol=2e-4, atol=2e-4)
            xla = deepseek.mla_ragged_attention(cfg, q_lat, q_pe, pages,
                                                layer, *rows)
            np.testing.assert_allclose(ref, np.asarray(xla),
                                       rtol=2e-4, atol=2e-4)
        mr._mla_ragged.clear_cache()

    def test_traced_layer_inside_scan(self):
        from dynamo_tpu.ops.pallas.mla_ragged import (
            mla_ragged_attention_packed)
        pages, q_lat, q_pe, rows = self._mk(seed=5)

        def body(carry, lidx):
            return carry, mla_ragged_attention_packed(
                q_lat, q_pe, pages, lidx, *rows, 0.1, interpret=True)

        _, outs = jax.lax.scan(body, 0, jnp.arange(pages.shape[0]))
        for layer in range(pages.shape[0]):
            np.testing.assert_allclose(
                self._ref(q_lat, q_pe, pages, layer, rows),
                np.asarray(outs[layer]), rtol=2e-4, atol=2e-4)


class TestEngine:
    async def test_engine_generates_deepseek(self):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        eng = JaxEngine.random_init(ds_cfg(), JaxEngineConfig(
            num_pages=32, page_size=4, max_num_seqs=2, max_prefill_chunk=8,
            max_context=64, min_prefill_bucket=4, attn_impl="scan"))
        try:
            req = PreprocessedRequest(
                token_ids=list(range(1, 10)), request_id="ds",
                stop_conditions=StopConditions(max_tokens=5),
                sampling_options=SamplingOptions(temperature=0.0))
            frames = [f async for f in eng.generate(req)]
            toks = [t for f in frames for t in f.token_ids]
            assert len(toks) == 5
            # the latent cache really is tiny: Hkv=1 x kv_lora_rank wide
            assert eng.pages.shape[2:] == (2, 1, 4, 32)
        finally:
            await eng.stop()

    async def test_engine_pallas_matches_scan(self):
        """Serving deepseek with attn_impl="pallas" (the MLA decode
        kernel under the layer scan, interpret mode on CPU) produces the
        same greedy tokens as the XLA scan path."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        cfg = ds_cfg(kv_lora_rank=128, head_dim=128)
        outs = {}
        for impl in ("scan", "pallas"):
            eng = JaxEngine.random_init(cfg, JaxEngineConfig(
                num_pages=32, page_size=8, max_num_seqs=2,
                max_prefill_chunk=8, max_context=64, min_prefill_bucket=4,
                attn_impl=impl))
            try:
                assert eng.attn_impl == impl
                req = PreprocessedRequest(
                    token_ids=list(range(1, 10)), request_id=f"ds-{impl}",
                    stop_conditions=StopConditions(max_tokens=5),
                    sampling_options=SamplingOptions(temperature=0.0))
                frames = [f async for f in eng.generate(req)]
                outs[impl] = [t for f in frames for t in f.token_ids]
            finally:
                await eng.stop()
        assert outs["pallas"] == outs["scan"]
        assert len(outs["pallas"]) == 5


class TestV3Parity:
    def test_matches_transformers_deepseek_v3(self, tmp_path):
        """V3: noaux_tc sigmoid gate with e_score_correction_bias, q_lora,
        rope_interleave, yarn mscale in the softmax scale — logits parity
        against transformers' DeepseekV3."""
        torch = pytest.importorskip("torch")
        from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

        hf_cfg = DeepseekV3Config(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
            n_group=4, topk_group=2, norm_topk_prob=True,
            first_k_dense_replace=1, routed_scaling_factor=2.5,
            q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=16,
            qk_nope_head_dim=32, v_head_dim=32,
            max_position_embeddings=256, rms_norm_eps=1e-6,
            rope_theta=10000.0, tie_word_embeddings=False,
            rope_scaling={"type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 64,
                          "mscale": 1.0, "mscale_all_dim": 1.0,
                          "beta_fast": 32, "beta_slow": 1},
            attn_implementation="eager")
        torch.manual_seed(3)
        model = DeepseekV3ForCausalLM(hf_cfg).eval()
        # give the correction bias real (nonzero) values so the test
        # actually exercises the biased group selection
        with torch.no_grad():
            for layer in model.model.layers[1:]:
                layer.mlp.gate.e_score_correction_bias.uniform_(-0.5, 0.5)
        model.save_pretrained(tmp_path, safe_serialization=True)

        cfg = ModelConfig.from_pretrained(str(tmp_path), dtype="float32")
        assert cfg.topk_method == "noaux_tc"
        assert cfg.q_lora_rank == 24
        from dynamo_tpu.models.hf_loader import load_hf_params
        params = load_hf_params(cfg, str(tmp_path))
        assert "router_bias" in params["moe_layers"]

        prompt = [3, 17, 42, 99, 5, 64, 23, 81]
        with torch.no_grad():
            ref = model(torch.tensor([prompt])).logits[0, -1].numpy()
        pages = make_pages(cfg, 6, 8, dtype=jnp.float32)
        logits, _ = _prefill(params, cfg, [prompt], pages, _alloc(1, 4))
        np.testing.assert_allclose(np.asarray(logits[0]), ref,
                                   rtol=3e-3, atol=3e-3)


def test_sharding_covers_noaux_router_bias():
    """V3 pytrees carry router_bias; shard_params must have a spec for it
    (KeyError here would crash sharded serving at startup)."""
    from dynamo_tpu.parallel import MeshSpec, ModelSharding, make_mesh
    cfg = ds_cfg(num_experts=8, topk_method="noaux_tc", n_group=4,
                 topk_group=2)
    mesh = make_mesh(MeshSpec(tp=2, ep=2), devices=jax.devices()[:4])
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
    assert params["moe_layers"]["router_bias"].dtype == jnp.float32
    placed = ModelSharding(cfg, mesh).shard_params(params)
    rb = placed["moe_layers"]["router_bias"]
    assert rb.sharding.shard_shape(rb.shape) == rb.shape  # replicated
