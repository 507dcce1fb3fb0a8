"""Keye-VL-2.0's language model (``models/moe.py`` with ``cfg.index_topk``):
a learned selection of ``topk`` tokens in front of a GROUPED-QUERY paged
cache, the index keys on the block chain of the keys and values.

Seeded random weights in float32 on the CPU, at the tiny widths of
``benchmarks/configs/keye-vl-2.0-30b-a3b.json`` (a selection of 24 tokens):
the program against the plain reference (``benchmarks/reference/keye.py``)
in logits, the selection index for index against ``lax.top_k``, the prefix
cache with both pools on one chain (a hit, an eviction, a last block that is
not shared, a preempted row), the loader's refusals, and the masked Pallas
forms in interpret mode against the XLA oracle."""

import asyncio
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import get_family, llama, moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import indexer
from dynamo_tpu.ops.attention import selected_attention
from dynamo_tpu.ops.gdn import token_rows
from dynamo_tpu.protocols.common import (PreprocessedRequest,
                                         SamplingOptions, StopConditions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "keye-vl-2.0-30b-a3b.json")
TOPK = 24


def _config(tiny: bool, **over):
    with open(CONFIG) as f:
        hf = json.load(f)
    bench = hf.pop("benchmark")
    if tiny:
        hf.update(bench["tiny"]["config"])
    hf.update(over)
    return hf


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmarks", *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_keye", "reference", "keye.py")
COST = _load("keye_cost", "keye_cost.py")


def _reference_logits(hf, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.forward(hf, params, list(tokens)))


def _family(**over):
    hf = _config(tiny=True, **over)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    return hf, cfg, moe.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny():
    return _family()


# ------------------------------------------------------------ the config

def test_from_hf_reads_the_published_config_and_the_cut_counts():
    hf = _config(tiny=False)
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is moe
    # the family is read off ``sa_config``: "KeyeVL2" is in no list
    assert cfg.model_type == "KeyeVL2" and cfg.qk_norm
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048)
    assert cfg.slot_kind == "" and cfg.num_cache_layers == cfg.num_layers == 6
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.vocab_size) == (
        2048, 32, 4, 128, 128, 8, 768, 151936)
    assert cfg.rope_theta == 1e7 and cfg.rms_norm_eps == 1e-6
    assert cfg.norm_topk_prob and not cfg.tie_word_embeddings
    assert cfg.generation == "causal" and not cfg.kv_lora_rank
    # the cut, by count: 4.375 B parameters, 8.75 GB in bf16
    shapes = jax.eval_shape(
        lambda: moe.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    vectors = sum(x.size for k, x in jax.tree_util.tree_leaves_with_path(
        shapes) if "norm" in jax.tree_util.keystr(k))
    assert n - vectors == COST.total_params(hf) == 4_374_593_536
    assert round((n - vectors) * 2 / 1e9, 2) == 8.75
    # the cache: 13,056 B a token, the two pools under one page id
    pages = jax.eval_shape(lambda: llama.make_pages(cfg, 16384, 16))
    assert set(pages) == {"kv", "index"}
    assert pages["kv"].shape == (6, 16384, 2, 4, 16, 128)
    assert pages["index"].shape == (6, 16384, 16 * 64)  # a page a row
    per_token = sum(p.size * 2 for p in pages.values()) // (16384 * 16)
    assert per_token == COST.cache_bytes_per_token(hf, "bfloat16") == 13056


@pytest.mark.parametrize("over,names", [
    ({"sa_config": {"indexer_num_kv_heads": 2}}, "indexer_num_kv_heads"),
    ({"sa_config": {"topk": None}}, "topk"),
    ({"sa_config": {"indexer_head_dim": None}}, "indexer_head_dim"),
    ({"sa_config": {"block_size": 64}}, "block_size"),
    ({"sa_config": {"indexer_head_dim": 15}}, "indexer_head_dim"),
    ({"sa_config": "dsa"}, "sa_config"),
    ({"num_experts": 0, "num_local_experts": 0}, "dense FFN"),
    ({"use_sliding_window": True}, "sliding window"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "yarn"),
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, "linear"),
    ({"rope_scaling": {"type": "default", "factor": 2.0}}, "factor"),
], ids=["other_index_kv_heads", "no_topk", "no_index_head_dim",
        "an_unknown_key", "an_odd_index_head", "not_a_mapping",
        "a_dense_ffn", "a_sliding_window", "yarn", "linear",
        "a_parameter_of_plain_rotary"])
def test_the_loader_refuses_what_it_cannot_honour(over, names):
    """A ``sa_config`` or a ``rope_scaling`` the family does not implement
    is an error that names the key - never a dense Qwen3-MoE under this
    model's name."""
    hf = _config(tiny=True)
    for key, value in over.items():
        hf[key] = ({**hf[key], **value} if isinstance(value, dict)
                   and key == "sa_config" else value)
    with pytest.raises(NotImplementedError, match=names):
        ModelConfig.from_hf(hf)


def test_the_llama_tree_refuses_a_rope_scaling_it_would_ignore():
    """The small repair: a plain llama-tree file with a ``rope_scaling``
    type the loader does not implement raises; ``null`` and ``default``
    load as before."""
    base = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 1, "num_attention_heads": 2,
            "model_type": "llama"}
    assert ModelConfig.from_hf({**base, "rope_scaling": None}).rope_theta
    assert ModelConfig.from_hf(
        {**base, "rope_scaling": {"rope_type": "default"}}).rope_theta
    with pytest.raises(NotImplementedError, match="llama3"):
        ModelConfig.from_hf({**base, "rope_scaling": {
            "rope_type": "llama3", "factor": 8.0}})
    # q/k norm still goes by the model's name where no sa_config is
    assert ModelConfig.from_hf({**base, "model_type": "qwen3"}).qk_norm
    assert not ModelConfig.from_hf(base).qk_norm


def test_equal_position_streams_make_mrope_plain_rotary():
    """With text alone the three streams of ``mrope_section`` carry the
    same position: the multimodal rotation (the reference's ``mrope``, the
    general form) IS the plain rotary the program and the reference apply;
    with unequal streams it is not."""
    from dynamo_tpu.ops.rope import apply_rope

    hf = _config(tiny=False)
    section = hf["rope_scaling"]["mrope_section"]
    assert section == [16, 24, 24] and sum(section) * 2 == hf["head_dim"]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 3, 128)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 200_000, 40), jnp.int32)
    theta = float(hf["rope_theta"])
    want = REF.rope(x, pos, theta)
    got = REF.mrope(x, jnp.stack([pos, pos, pos]), theta, section)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # (the program's plain rotary, at positions whose angles float32 holds
    # to the digit whichever way theta's powers are taken)
    near = pos % 4096
    served = apply_rope(x[None], near[None], theta)[0]
    np.testing.assert_allclose(
        np.asarray(served),
        np.asarray(REF.mrope(x, jnp.stack([near] * 3), theta, section)),
        atol=1e-3)
    other = REF.mrope(x, jnp.stack([pos, pos + 3, pos]), theta, section)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 0.1


# --------------------------------------------------------- the selection

def _step(starts, new, total):
    new, total = (jnp.asarray(a, jnp.int32) for a in (new, total))
    if starts is None:
        starts = jnp.cumsum(new) - new
    N = int(new.sum())
    return token_rows(N, jnp.asarray(starts, jnp.int32), new, total,
                      jnp.zeros_like(new)), N


def test_the_selection_is_lax_top_k_index_for_index():
    """Both forms of the selection (the sorted list and the bias) over
    grouped-query index pages against ``lax.top_k`` of the reference's own
    score matrix: a chunk row that starts under ``topk`` and ends over it,
    and one-token rows on both sides."""
    rng = np.random.default_rng(3)
    J, D, ps, P = 4, 16, 8, 16
    S = P * ps
    new, total = [40, 1, 1], [60, 20, 100]
    R = len(new)
    rows, N = _step(None, new, total)
    keys = rng.standard_normal((R, S, D)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((N, J, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((N, J)), jnp.float32)
    index = np.zeros((2, R * P + 1, ps, D), np.float32)
    index[1, 1:] = keys.reshape(R * P, ps, D)
    table = jnp.asarray(1 + np.arange(R * P).reshape(R, P), jnp.int32)
    totals = jnp.asarray(total, jnp.int32)
    kw = dict(width=N, packed=True)
    with jax.default_matmul_precision("highest"):
        sel, live = indexer.select(q, w, jnp.asarray(index), 1, table, rows,
                                   totals, TOPK, **kw)
        one, bias = indexer.select_split(q, w, jnp.asarray(index), 1, table,
                                         rows, totals, TOPK, **kw)
    pos = np.asarray(indexer.token_positions(rows, totals))
    for t in range(N):
        r = int(rows.row[t])
        s = np.einsum("jd,sd->js", np.asarray(q[t]), keys[r])
        score = (np.asarray(w[t])[:, None] * np.maximum(s, 0)).sum(0)
        score[pos[t] + 1:] = -np.inf
        k = min(TOPK, pos[t] + 1)
        want = sorted(np.asarray(jax.lax.top_k(jnp.asarray(score), k)[1]))
        got = sorted(np.asarray(sel[t])[np.asarray(live[t])])
        assert got == want, t
        mask = (np.asarray(one[0][r]) if new[r] == 1
                else np.asarray(bias[t]))
        assert np.flatnonzero(mask == 0).tolist() == want, t


# ------------------------------------------------ against the reference

@pytest.mark.parametrize("packed", [False, True], ids=["padded", "packed"])
def test_prefill_then_decode_gives_the_references_logits(tiny, packed):
    """One sequence through the paged cache - prompt chunks of 16 (padded
    ``[1, 16]`` steps, or token-packed beside a second row that decodes),
    then one token a step - against the reference's full forward, logits
    for logits, at contexts on both sides of the selection of 24 (and
    where a chunk's queries straddle it)."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(0)
    T = 70
    toks = rng.integers(0, hf["vocab_size"], T)
    other = rng.integers(0, hf["vocab_size"], 60)
    want = _reference_logits(hf, params, toks)
    want_other = _reference_logits(hf, params, other)
    ps, P = 8, 16
    pages = llama.make_pages(cfg, 1 + 2 * P, ps)
    table = jnp.asarray(1 + np.arange(2 * P).reshape(2, P), jnp.int32)
    fwd = jax.jit(moe.forward, static_argnames=("cfg", "packed"))
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        # the second row's prompt, whole (30 tokens), then it decodes
        _l, pages, _a = fwd(params, cfg, i32(other[None, :30]),
                            i32(np.arange(30)[None]), pages, table[1:],
                            i32([30]), i32([30]))
        done, n_other = 0, 30
        while done < T:
            n = 16 if done < 48 else 1
            if packed:
                t = np.concatenate([toks[done:done + n],
                                    other[n_other:n_other + 1]])
                pos = np.concatenate([np.arange(done, done + n),
                                      [n_other]])
                logits, pages, _a = fwd(
                    params, cfg, i32(t[None]), i32(pos[None]), pages, table,
                    i32([done + n, n_other + 1]), i32([n, 1]), packed=True)
                worst = max(worst, np.abs(np.asarray(logits[1])
                                          - want_other[n_other]).max())
                n_other += 1
            else:
                logits, pages, _a = fwd(
                    params, cfg, i32(toks[None, done:done + n]),
                    i32(np.arange(done, done + n)[None]), pages, table[:1],
                    i32([done + n]), i32([n]))
            done += n
            worst = max(worst, np.abs(np.asarray(logits[0])
                                      - want[done - 1]).max())
    assert worst < 2e-4
    # what a token's block holds beside its keys and values
    assert np.asarray(pages["index"][:, 1]).any()


def test_a_short_table_selects_every_visible_token_and_still_writes_keys(
        tiny):
    """Where the table holds no more than ``topk`` tokens nothing is scored
    (every visible token is selected: dense attention's result) - and the
    index keys are written all the same, so a longer step later finds
    them."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(2)
    toks = rng.integers(0, hf["vocab_size"], 20)
    want = _reference_logits(hf, params, toks)
    pages = llama.make_pages(cfg, 4, 8)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)          # 24 tokens
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        jaxpr = jax.make_jaxpr(lambda *a: moe.forward(
            params, cfg, *a))(i32(toks[None]), i32(np.arange(20)[None]),
                              pages, table, i32([20]), i32([20]))
        logits, pages, _a = moe.forward(
            params, cfg, i32(toks[None]), i32(np.arange(20)[None]), pages,
            table, i32([20]), i32([20]))
    assert "relu" not in str(jaxpr) and "max" in str(jaxpr)
    np.testing.assert_allclose(np.asarray(logits[0]), want[-1], atol=2e-4)
    held = np.asarray(pages["index"][:, 1:4]).reshape(2, 24, -1)
    assert np.abs(held[:, :20]).min(axis=-1).max() > 0
    assert not held[:, 20:].any()


# ---------------------------------------------------- the served path

def _engine(cfg, params, **kw):
    base = dict(num_pages=64, page_size=8, max_num_seqs=4,
                max_prefill_chunk=32, max_context=256, decode_multistep=2)
    base.update(kw)
    return JaxEngine(cfg, params, JaxEngineConfig(**base))


def _req(tokens, rid, n, logprobs=None):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0,
                                         logprobs=logprobs),
        eos_token_ids=[])


async def _collect(eng, req):
    frames = [f async for f in eng.generate(req)]
    return [t for f in frames for t in f.token_ids], frames


def _is_the_references_greedy(hf, params, prompt, served) -> bool:
    logits = _reference_logits(hf, params, list(prompt) + list(served))
    want = np.argmax(logits[len(prompt) - 1:-1], axis=-1)
    return want.tolist() == list(served)


KERNEL = {"head_dim": 128}


@pytest.mark.async_timeout(240)
@pytest.mark.parametrize("attn_impl", ["scan", "pallas"])
async def test_the_served_path_gives_the_references_greedy_tokens(attn_impl):
    """Five requests on four rows - prompts of 5 to 150 tokens in chunks of
    at most 32 beside the rows that decode, fused blocks - stream the
    reference's greedy continuation token for token: padded steps on the
    XLA path (the gathered form), the token-packed step with the masked
    forms of the grouped-query kernels in interpret mode (heads of 128 for
    their tiles). The family keeps no slot, counts its selection, its
    one-token rows and its index pool."""
    from dynamo_tpu.worker.metrics import engine_dispatch_stats

    hf, cfg, params = _family(**(KERNEL if attn_impl == "pallas" else {}))
    eng = _engine(cfg, params, attn_impl=attn_impl)
    assert (eng.padded_reason is None) == (attn_impl == "pallas")
    assert eng.state_slots == 0 and eng.page_pools == ("kv", "index")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist()
               for n in (150, 33, 5, 90, 12)]
    try:
        with jax.default_matmul_precision("highest"):
            got = await asyncio.gather(*[
                _collect(eng, _req(p, f"r{i}", 8))
                for i, p in enumerate(prompts)])
            for p, (toks, _frames) in zip(prompts, got):
                assert len(toks) == 8
                assert _is_the_references_greedy(hf, params, p, toks)
        assert eng.multistep_blocks > 0
        form = "packed" if attn_impl == "pallas" else "padded:attn_impl"
        assert set(eng.prefill_steps) == {form}
        # the prefix cache is ON for the family: blocks were committed
        assert eng.allocator._by_hash
        assert eng.scheduler.prefix_reuse_refused == {"recurrent_state": 0}
        # (the ring is the process's: an earlier engine's records of a
        # family WITH a slot may lie in it; this family's read no slot)
        ring = [r for r in eng.steptrace.snapshot(limit=4096)["records"]
                if r["selected_keys"] and not r["state_rows"]]
        assert ring and all(r["gdn_tokens"] == 0
                            and 0 < r["selected_keys"] <= r["score_pairs"]
                            for r in ring)
        assert any(r["selected_keys"] < r["score_pairs"] for r in ring)
        stats = engine_dispatch_stats(eng)
        assert 0 < stats["attn_selected_keys"] < stats["attn_visible_keys"]
        assert set(stats["cache_bytes"]) == {"paged", "index"}
        form = {"pallas": "masked", "scan": "gathered"}[attn_impl]
        assert eng.one_token_form == form
        assert set(stats["attn_one_token_rows"]) == {form}
        assert eng.packed_attention == (
            "chunks:selected_chunks,one_token:selected_rows"
            if attn_impl == "pallas" else None)
        assert "+index[L=2,D=16]" in eng.cache_kinds
    finally:
        await eng.stop()


def _index_of(eng, page_ids):
    return np.asarray(eng.pages["index"][:, np.asarray(page_ids)])


def _kv_of(eng, page_ids):
    return np.asarray(eng.pages["kv"][:, np.asarray(page_ids)])


async def _lps(eng, prompt, rid, n=6):
    """(tokens, their log-probabilities, cached_tokens of the request)."""
    req = _req(prompt, rid, n, logprobs=1)
    toks, frames = await _collect(eng, req)
    lps = [lp for f in frames for lp in (f.log_probs or [])]
    return toks, np.asarray(lps, np.float32), frames[-1].cached_tokens or 0


@pytest.mark.async_timeout(240)
async def test_a_prefix_hit_brings_the_index_keys_the_cold_run_wrote(tiny):
    """A prompt of 100 tokens (twelve whole pages of 8 and a tail), served
    cold and served again: the second request claims the twelve committed
    blocks - the SAME page ids, whose index pages hold what the cold run
    wrote (nothing rewrites them) - computes the tail alone, and streams
    the same tokens with the same log-probabilities, the reference's
    greedy ones. With the committed blocks' index keys zeroed behind the
    allocator's back the hit selects by zeros and the log-probabilities
    move: the check can see a hit that brought keys and values alone."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 512, 100).tolist()
    eng = _engine(cfg, params)
    try:
        with jax.default_matmul_precision("highest"):
            cold, cold_lps, hit0 = await _lps(eng, prompt, "cold")
            assert hit0 == 0
            by_hash = dict(eng.allocator._by_hash)
            assert len(by_hash) >= 12
            pages = list(by_hash.values())[:12]
            index_cold, kv_cold = _index_of(eng, pages), _kv_of(eng, pages)
            assert np.abs(index_cold).min(axis=-1).max() > 0
            again, again_lps, hit1 = await _lps(eng, prompt, "again")
            assert hit1 == 96 and eng.allocator.hits >= 12
            assert again == cold
            np.testing.assert_allclose(again_lps, cold_lps, atol=1e-4)
            assert _is_the_references_greedy(hf, params, prompt, cold)
            np.testing.assert_array_equal(_index_of(eng, pages), index_cold)
            np.testing.assert_array_equal(_kv_of(eng, pages), kv_cold)
            # the planted fault: stale (zero) index keys under a hit
            eng.pages = {**eng.pages, "index": eng.pages["index"].at[
                :, np.asarray(pages)].set(0.0)}
            bad, bad_lps, hit2 = await _lps(eng, prompt, "bad")
            assert hit2 == 96
            assert (bad != cold
                    or np.abs(bad_lps - cold_lps).max() > 1e-3)
    finally:
        await eng.stop()


@pytest.mark.async_timeout(240)
async def test_an_evicted_block_is_recomputed_with_its_index_keys(tiny):
    """A pool of 31 usable pages: prompt A (100 tokens) is served and its
    blocks park in the LRU; prompt B (236 tokens, every page of the pool)
    evicts them all and OVERWRITES their pages in both pools; A again finds
    no block, recomputes every token - keys, values and index keys - and
    streams what it streamed."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(8)
    a = rng.integers(0, 512, 100).tolist()
    b = rng.integers(0, 512, 236).tolist()
    eng = _engine(cfg, params, num_pages=32, max_num_seqs=1)
    try:
        with jax.default_matmul_precision("highest"):
            first, first_lps, _ = await _lps(eng, a, "a")
            held = set(eng.allocator._by_hash)
            await _lps(eng, b, "b")
            assert not held & set(eng.allocator._by_hash)
            again, again_lps, hit = await _lps(eng, a, "a2")
        assert hit == 0 and again == first
        np.testing.assert_allclose(again_lps, first_lps, atol=1e-4)
        assert _is_the_references_greedy(hf, params, a, first)
    finally:
        await eng.stop()


@pytest.mark.async_timeout(240)
async def test_a_shared_last_block_is_never_written_by_its_second_reader(
        tiny):
    """Copy on write, as this allocator does it: a prompt of exactly twelve
    pages, served twice. The second request may not append into the twelfth
    block (the first generation's tokens follow there in the first row's
    own page): it claims eleven, takes a FRESH page for the twelfth and
    recomputes it - both pools - so the committed block is read-only in
    both, and two rows that share a prefix at once stream the same
    tokens."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 512, 96).tolist()
    eng = _engine(cfg, params)
    try:
        with jax.default_matmul_precision("highest"):
            cold, cold_lps, _ = await _lps(eng, prompt, "cold")
            pages = list(eng.allocator._by_hash.values())[:12]
            index_cold, kv_cold = _index_of(eng, pages), _kv_of(eng, pages)
            (t1, l1, h1), (t2, l2, h2) = await asyncio.gather(
                _lps(eng, prompt, "x"), _lps(eng, prompt, "y"))
        assert h1 == h2 == 88          # eleven blocks, never the twelfth
        assert t1 == t2 == cold
        np.testing.assert_allclose(l1, cold_lps, atol=1e-4)
        np.testing.assert_allclose(l2, cold_lps, atol=1e-4)
        np.testing.assert_array_equal(_index_of(eng, pages), index_cold)
        np.testing.assert_array_equal(_kv_of(eng, pages), kv_cold)
    finally:
        await eng.stop()


@pytest.mark.async_timeout(240)
async def test_a_preempted_row_resumes_from_its_committed_blocks(tiny):
    """A row preempted after some tokens gives its pages back; its whole
    pages were committed first, so the revive claims them - index keys
    with them - computes what is left, and streams what an uninterrupted
    row streams."""
    _hf, cfg, params = tiny
    rng = np.random.default_rng(5)
    b = rng.integers(0, 512, 75).tolist()
    fresh = _engine(cfg, params, max_num_seqs=1)
    try:
        with jax.default_matmul_precision("highest"):
            want, _ = await _collect(fresh, _req(b, "b", 24))
    finally:
        await fresh.stop()
    eng = _engine(cfg, params, max_num_seqs=1)
    try:
        with jax.default_matmul_precision("highest"):
            task = asyncio.ensure_future(_collect(eng, _req(b, "b2", 24)))
            sched = eng.scheduler
            while not any(len(s.generated) >= 6
                          for s in sched.active.values()):
                assert not task.done()
                await asyncio.sleep(0.01)
            assert await eng.run_exclusive(sched._preempt_one)
            again, _frames = await task
        assert again == want and sched.num_preemptions == 1
        assert eng.allocator.hits >= 9      # the revive's claim
    finally:
        await eng.stop()


def test_what_moves_one_pool_refuses_the_family_by_what_it_keeps(tiny):
    """Page export / import, the host and disk tiers, a mesh, pipeline
    stages, int8 weights and speculation either move both pools or none:
    each refuses this family by name, at start-up."""
    _hf, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="index pages"):
        cfg.paged_only("KV page export")
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="index pages"):
        eng.gather_pages_host([1, 2])
    from dynamo_tpu.kvbm.manager import TieredEngine
    with pytest.raises(NotImplementedError, match="index pages"):
        TieredEngine(eng)
    for kw, names in ((dict(quantize="int8"), "index pages"),
                      (dict(shard_pages_fn=lambda p: p), "index pages"),
                      (dict(spec_tokens=2), "verify window")):
        with pytest.raises(NotImplementedError, match=names):
            _engine(cfg, params, **kw)
    with pytest.raises(NotImplementedError, match="index pages"):
        JaxEngine(cfg, params, JaxEngineConfig(
            num_pages=16, page_size=8, max_num_seqs=2, max_context=64),
            forward_fn=moe.forward)


def test_the_worker_names_the_pools_and_refuses_by_them(tmp_path):
    """``startup.engine`` names both pools, and ``--disagg`` and the tiers
    end the worker at its arguments, by what the family keeps."""
    import sys

    from dynamo_tpu.utils.tracing import StartupTrace
    from dynamo_tpu.worker import main as worker_main

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import modeldir
    hf = _config(tiny=True)
    model = modeldir.write_model_dir(str(tmp_path / "m"), hf)
    base = ["--model-path", model, "--random-weights", "--dtype", "float32",
            "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
            "--max-context", "128", "--max-prefill-chunk", "32"]
    parser = worker_main.build_parser()
    for extra, names in ((["--disagg", "prefill"], "--disagg"),
                         (["--host-cache-bytes", "1024"], "host and disk")):
        with pytest.raises(NotImplementedError, match="index pages") as e:
            worker_main.build_engine(parser.parse_args(base + extra))
        assert names in str(e.value)
    startup = StartupTrace()
    eng = worker_main.build_engine(parser.parse_args(base), startup)
    assert eng.state_slots == 0
    assert sorted(eng.cache_bytes) == ["index", "paged"]
    attrs = [st[3] for st in startup.stages
             if st[0] == "startup.engine"][0]
    assert attrs["cache.kinds"] == "paged[L=2,Hkv=2,Dh=16]+index[L=2,D=16]"
    assert attrs["moe.experts"] == "grouped[E=8,k=2]"


# ------------------------------------------------ the masked Pallas forms

def _pools(rng, R, P, ps, Hkv, Dh):
    kv = np.zeros((2, R * P + 1, 2, Hkv, ps, Dh), np.float32)
    kv[1, 1:] = rng.standard_normal((R * P, 2, Hkv, ps, Dh))
    table = 1 + np.arange(R * P).reshape(R, P)
    return jnp.asarray(kv), jnp.asarray(table, jnp.int32)


def _random_selection(rng, rows, N, S, total, K):
    """A random selection a token: ``(sel, live, bias)``."""
    pos = np.asarray(indexer.token_positions(rows, jnp.asarray(total)))
    sel = np.zeros((N, K), np.int32)
    live = np.zeros((N, K), bool)
    bias = np.full((N, S), indexer.NEG_INF, np.float32)
    for t in range(N):
        if not bool(rows.valid[t]):
            continue
        k = min(K, pos[t] + 1)
        pick = np.sort(rng.choice(pos[t] + 1, k, replace=False))
        sel[t, :k], live[t, :k] = pick, True
        bias[t, pick] = 0.0
    return jnp.asarray(sel), jnp.asarray(live), bias


@pytest.mark.parametrize("form", ["packed", "decode", "padded"])
def test_the_masked_kernels_are_the_gather_over_the_selection(form):
    """``selected_attention_rows`` in interpret mode - the decode kernel
    with a bias for the rows of one token, the ragged kernel with a bias
    for the rows of several - against the XLA oracle
    (``selected_attention``: the selected rows fetched) over the same
    pools and the same selections: a token-packed step (two chunk rows, a
    row of no token, two one-token rows, a padded tail over three query
    blocks), a decode step ``[4, 1]`` with a dead row, and a padded ``[3,
    24]`` step whose rows lie apart on the flat axis."""
    from dynamo_tpu.ops.pallas.ragged import selected_attention_rows

    rng = np.random.default_rng(21)
    Hq, Hkv, Dh, ps, P, K = 8, 2, 128, 8, 40, 24
    S = P * ps
    if form == "packed":
        new, total, starts, N = [40, 0, 17, 1, 1], [300, 0, 17, 150, 9], \
            None, 72
    elif form == "decode":
        new, total, starts, N = [1, 1, 0, 1], [320, 5, 0, 77], \
            [0, 1, 2, 3], 4
    else:
        new, total, starts, N = [24, 7, 0], [200, 7, 0], [0, 24, 48], 72
    R = len(new)
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    if starts is None:
        starts = np.cumsum(new) - new
    rows = token_rows(N, i32(starts), i32(new), i32(total),
                      jnp.zeros(R, jnp.int32))
    kv, table = _pools(rng, R, P, ps, Hkv, Dh)
    q = jnp.asarray(rng.standard_normal((N, Hq, Dh)), jnp.float32)
    sel, live, bias = _random_selection(rng, rows, N, S, i32(total), K)
    one = tok_bias = None
    if form in ("packed", "decode"):
        first = np.clip(np.asarray(starts), 0, N - 1)
        is_one = np.asarray(new) == 1
        one = (jnp.asarray(np.where(is_one[:, None], bias[first],
                                    indexer.NEG_INF)),
               i32(np.where(is_one, np.asarray(starts), N)))
    if form in ("packed", "padded"):
        several = np.asarray(new)[np.asarray(rows.row)] > (
            1 if form == "packed" else 0)
        tok_bias = jnp.asarray(np.where(
            (several & np.asarray(rows.valid))[:, None], bias,
            indexer.NEG_INF))
    with jax.default_matmul_precision("highest"):
        want = selected_attention(q, kv, 1, table[rows.row], sel,
                                  live & rows.valid[:, None], 0.11)
        got = selected_attention_rows(q, kv, 1, table, i32(starts),
                                      i32(new), i32(total), one, tok_bias,
                                      0.11, interpret=True)
    valid = np.asarray(rows.valid)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], atol=2e-5,
                               rtol=2e-5)
    assert not np.asarray(got)[~valid].any()
