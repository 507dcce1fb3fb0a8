"""Qwen3-Next through its own family (``models/qwen3_next.py``): the
published config loads by its own keys and the cut counts to the issue's
parameter count; what the family does not implement is refused by name;
prefill into the two kinds of cache then decode out of them - a prompt cut
into chunks that are no multiple of the rule's chunk, padded and
token-packed, fused and chained blocks, on the XLA path and through the
Pallas kernels (interpret mode here) - agrees with the plain reference's
token-by-token scan (``benchmarks/reference/qwen3_next.py``); the two
kernels equal the rule iterated; the four ranks' shares of a sparse block
add up to the uncut layer; and a request's slot of the state pool is given,
freed and reused without a leak."""

import asyncio
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import get_family, qwen3_next
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import gdn
from dynamo_tpu.protocols.common import (PreprocessedRequest,
                                         SamplingOptions, StopConditions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "qwen3-next-80b-a3b-instruct.json")


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


REF = _load("ref_qwen3_next", "reference", "qwen3_next.py")


def _reference_logits(hf, params, tokens):
    """[T, V] float32: the reference's whole forward pass."""
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for kind, stack, n in REF.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(jnp.float32), stack)
                h = REF.LAYER_FNS[kind](hf, w, h)
        return REF.head(hf, params, h)


@pytest.fixture(scope="module")
def tiny():
    """(hf, cfg, params) at toy widths, float32, matrices drawn large
    enough that every mechanism moves the logits."""
    hf = _config(tiny=True)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    return hf, cfg, params


# ------------------------------------------------------------ the config

def test_from_hf_reads_the_published_config_and_the_cut_counts():
    hf = _config(tiny=False)
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is qwen3_next
    assert (cfg.num_layers, cfg.full_attention_interval, cfg.num_periods,
            cfg.state_layers, cfg.num_cache_layers) == (8, 4, 2, 6, 2)
    assert cfg.layer_kinds == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_conv_dim) == (
                16, 32, 128, 128, 4, 8192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.rotary_dim) == (16, 2, 256, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.ep_size,
            cfg.ep_rank, cfg.experts_held, cfg.expert_offset) == (
                512, 10, 4, 0, 128, 0)
    assert (cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (512, 512)
    assert cfg.norm_topk_prob and cfg.qk_norm and cfg.rope_theta == 1e7
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_word_embeddings
    assert cfg.vocab_size == 151936 and cfg.num_expert_layers == 8
    # ISSUE 43's arithmetic, by the shapes the initialiser would draw
    shapes = jax.eval_shape(
        lambda: qwen3_next.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert n == 4_133_998_720
    gdn_leaves = shapes["layers"]["gdn"]
    assert gdn_leaves["w_gate"].shape == (2, 3, 128, 2048, 512)
    assert gdn_leaves["w_router"].shape == (2, 3, 2048, 512)
    assert shapes["layers"]["full"]["wq"].shape == (2, 2048, 8192)
    pages = jax.eval_shape(lambda: qwen3_next.make_pages(
        cfg, 51200, 16, state_slots=64))
    assert pages["kv"].shape == (2, 51200, 2, 2, 16, 256)
    assert pages["state"].shape == (6, 65, 32, 128, 128)
    assert pages["state"].dtype == jnp.float32
    assert pages["conv"].shape == (6, 65, 3, 8192)


def test_the_seeded_decay_spans_the_heads():
    cfg = ModelConfig.from_hf(_config(tiny=False))
    a_log, dt_bias = qwen3_next.decay_init(cfg, ())
    decay = np.exp(-np.exp(np.asarray(a_log)) * np.log(2.0))
    assert float(jnp.max(jnp.abs(dt_bias))) == 0.0
    assert decay.min() == pytest.approx(0.9, abs=1e-4)
    assert decay.max() == pytest.approx(0.9999, abs=1e-6)
    assert np.all(np.diff(decay) > 0)


@pytest.mark.parametrize("over,names", [
    ({"model_type": "qwen3", "full_attention_interval": None,
      "linear_conv_kernel_dim": None, "linear_key_head_dim": None,
      "linear_num_key_heads": None, "linear_num_value_heads": None,
      "linear_value_head_dim": None,
      "layer_types": ["full_attention", "sliding_attention"] * 4},
     "layer_types"),
    ({"linear_conv_kernel_dim": None}, "linear_conv_kernel_dim"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["linear_attention"] * 8}, "layer_types"),
])
def test_a_file_the_loader_cannot_serve_is_refused_by_the_keys_name(over,
                                                                    names):
    """The parent built a plain 48-layer softmax-MoE attention model out of
    the keys it knew. A file with layer-kind keys that no family implements
    ends at the key's name."""
    hf = {k: v for k, v in _config(tiny=False, **over).items()
          if v is not None}
    with pytest.raises(NotImplementedError, match=names):
        ModelConfig.from_hf(hf)


def test_the_parents_silent_case_and_a_plain_layer_types_list():
    """The published file as the parent read it - ``num_experts``,
    ``head_dim`` and ``intermediate_size`` alone - now builds the family;
    a list of full-attention layers (newer Qwen3 files carry one) still
    loads as the model it describes."""
    hf = _config(tiny=False)
    cfg = ModelConfig.from_hf(hf)
    assert cfg.full_attention_interval == 4 and cfg.state_layers == 6
    plain = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "model_type": "qwen3", "layer_types": ["full_attention"] * 2}
    assert ModelConfig.from_hf(plain).state_layers == 0
    with pytest.raises(ValueError, match="full_attention_interval"):
        ModelConfig.from_hf(_config(tiny=True, num_hidden_layers=6))


# --------------------------------------------------------------- refusals

def _engine(cfg, params, **kw):
    defaults = dict(num_pages=256, page_size=8, max_num_seqs=4,
                    max_prefill_chunk=70, max_context=512,
                    min_prefill_bucket=8, decode_multistep=4,
                    num_top_logprobs=0)
    defaults.update(kw)
    return JaxEngine(cfg, params, JaxEngineConfig(**defaults))


@pytest.mark.parametrize("kw,names", [
    (dict(spec_tokens=2), "speculative"),
    (dict(quantize="int8"), "--quantize"),
    (dict(shard_pages_fn=lambda p: p), "mesh"),
])
def test_the_engine_refuses_by_name_what_moves_block_chains_only(tiny, kw,
                                                                  names):
    _hf, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        _engine(cfg, params, **kw)
    assert names in str(e.value) and "qwen3_next" in str(e.value)


def test_page_export_tiers_and_scoring_are_refused_by_name(tiny):
    from dynamo_tpu.kvbm.manager import TieredEngine

    _hf, cfg, params = tiny
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.gather_pages_host([1, 2])
    with pytest.raises(NotImplementedError, match="host and disk tiers"):
        TieredEngine(eng)
    with pytest.raises(NotImplementedError, match="prompt-scoring"):
        eng._score_batch([[1, 2, 3]])
    with pytest.raises(NotImplementedError, match="--disagg"):
        cfg.paged_only("--disagg")
    assert eng.cache_kinds == "paged[L=2,Hkv=2,Dh=16]+state[L=6,S=4,f32]"
    assert eng.table_width == 512 // 8 + 1
    # another family's engine has neither a pool nor the column
    plain = JaxEngine.random_init(ModelConfig.tiny(), JaxEngineConfig(
        num_pages=16, page_size=4, max_num_seqs=2, max_context=64))
    assert plain.state_slots == 0 and plain.table_width == 16
    assert plain.cache_kinds == "paged[L=2,Hkv=2,Dh=16]"
    assert plain.scheduler._free_slots == []
    ModelConfig.tiny().paged_only("--disagg")


# ------------------------------------------------- the rule and its kernels

def _rule_inputs(rng, N, Hk, Hv, Dk, Dv):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    f32 = jnp.float32
    return (jnp.asarray(unit(rng.normal(size=(N, Hk, Dk))) * Dk ** -0.5, f32),
            jnp.asarray(unit(rng.normal(size=(N, Hk, Dk))), f32),
            jnp.asarray(rng.normal(size=(N, Hv, Dv)), f32),
            jnp.asarray(-np.abs(rng.normal(size=(N, Hv))) * 0.05, f32),
            jnp.asarray(rng.uniform(0.1, 0.9, size=(N, Hv)), f32))


def _scan(q, k, v, g, b, S):
    """The five lines, token by token, in numpy float64."""
    rep = v.shape[1] // q.shape[1]
    out = []
    for t in range(q.shape[0]):
        kt, qt = np.repeat(k[t], rep, 0), np.repeat(q[t], rep, 0)
        S = np.exp(g[t])[:, None, None] * S
        u = b[t][:, None] * (v[t] - np.einsum("hdv,hd->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        out.append(np.einsum("hdv,hd->hv", S, qt))
    return np.stack(out), S


@pytest.mark.parametrize("use_pallas", [False, True])
def test_gdn_chunk_and_gdn_step_equal_the_rule_iterated(use_pallas):
    """A packed step of a fresh row of 150 tokens (three kernel chunks, the
    last ragged), a row of 70 that continues from its slot, a one-token
    row, and a dead row: the chunk form and the step form - in jax.numpy
    and as the two Mosaic kernels in interpret mode - give the token scan's
    outputs and final states, leave other layers' and other slots' states
    alone, and keep slot 0 finite."""
    rng = np.random.default_rng(0)
    Hk, Hv, Dk, Dv, N = 2, 4, 16, 16, 256
    new = jnp.array([150, 70, 1, 0])
    rows = gdn.token_rows(N, jnp.cumsum(new) - new, new,
                          jnp.array([150, 200, 33, 1]),
                          jnp.array([1, 2, 3, 4]))
    q, k, v, g, beta = _rule_inputs(rng, N, Hk, Hv, Dk, Dv)
    pool = jnp.asarray(rng.normal(size=(2, 5, Hv, Dk, Dv)), jnp.float32)
    o, new_pool = gdn.gated_delta_rule(q, k, v, g, beta, pool, 1, rows,
                                       use_pallas=use_pallas)
    a = [np.asarray(x, np.float64) for x in (q, k, v, g, beta)]
    for lo, hi, slot, start in ((0, 150, 1, None), (150, 220, 2, pool[1, 2]),
                                (220, 221, 3, pool[1, 3])):
        S0 = (np.zeros((Hv, Dk, Dv)) if start is None
              else np.asarray(start, np.float64))
        want, S = _scan(*[x[lo:hi] for x in a], S0)
        np.testing.assert_allclose(np.asarray(o[lo:hi]), want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(new_pool[1, slot]), S,
                                   atol=2e-5)
    assert float(jnp.max(jnp.abs(o[221:]))) == 0.0
    np.testing.assert_array_equal(np.asarray(new_pool[0]),
                                  np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(new_pool[1, 4]),
                                  np.asarray(pool[1, 4]))
    assert bool(jnp.all(jnp.isfinite(new_pool[1, 0])))


def test_the_convolution_carries_its_last_three_inputs():
    """A row's convolution over two steps equals one over the whole; a row
    of one token, a row of two and a dead row keep the right inputs."""
    rng = np.random.default_rng(1)
    Ch, K = 8, 4
    x = jnp.asarray(rng.normal(size=(40, Ch)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, Ch)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(1, 4, K - 1, Ch)), jnp.float32)
    whole = sum(np.pad(np.asarray(x), ((K - 1, 0), (0, 0)))[i:i + 40]
                * np.asarray(w)[i] for i in range(K))

    def step(pool, lo, hi, total):
        n = hi - lo
        rows = gdn.token_rows(
            32, jnp.array([0, n]), jnp.array([n, 0]), jnp.array([total, 1]),
            jnp.array([2, 3]))
        xs = jnp.zeros((32, Ch)).at[:n].set(x[lo:hi])
        y, pool = gdn.causal_conv(xs, w, pool, 0, rows)
        return np.asarray(y[:n]), pool

    got = []
    for lo, hi in ((0, 17), (17, 18), (18, 20), (20, 40)):
        y, pool = step(pool, lo, hi, hi)
        got.append(y)
    np.testing.assert_allclose(np.concatenate(got), whole, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool[0, 2]), np.asarray(x[37:40]),
                               atol=0)


# --------------------------------------------- the forward and the reference

def _table(slot, first_page, n, width=64):
    t = np.zeros((1, width + 1), np.int32)
    t[0, :n] = np.arange(first_page, first_page + n)
    t[0, -1] = slot
    return jnp.asarray(t)


def test_chunked_prefill_then_decode_agrees_with_the_reference(tiny):
    """A prompt of 149 tokens in chunks of 70, 70 and 9 (no multiple of the
    rule's 64), then a decode step: the logits after every step are the
    reference's at that position - the state and the convolution's inputs
    were carried from step to step."""
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, hf["vocab_size"], 150)
    want = _reference_logits(hf, params, toks)
    pages = qwen3_next.make_pages(cfg, 64, 4, state_slots=3)
    table = _table(2, 1, 40)
    with jax.default_matmul_precision("highest"):
        for lo, hi in ((0, 70), (70, 140), (140, 149), (149, 150)):
            logits, pages, aux = qwen3_next.forward(
                params, cfg, jnp.asarray(toks[None, lo:hi], jnp.int32),
                jnp.arange(lo, hi)[None], pages, table, jnp.array([hi]),
                jnp.array([hi - lo]))
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       np.asarray(want[hi - 1]), atol=5e-4)
    assert int(aux["moe_assignments"]) == 8 * hf["num_experts_per_tok"]
    assert float(jnp.max(jnp.abs(want))) > 1.0


def test_a_token_packed_step_with_rows_of_several_lengths(tiny):
    """One packed step: a fresh row of 101 tokens, a fresh row of 33, a
    one-token row that decodes out of caches an earlier step filled, and a
    pad row - each row's logits are the reference's."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(2)
    toks = rng.integers(0, hf["vocab_size"], 150)
    other = rng.integers(0, hf["vocab_size"], 33)
    want, want_other = (_reference_logits(hf, params, t)
                        for t in (toks, other))
    pages = qwen3_next.make_pages(cfg, 128, 4, state_slots=4)
    rows = [_table(1, 1, 40), _table(3, 60, 20), _table(2, 90, 38),
            jnp.zeros((1, 65), jnp.int32)]
    with jax.default_matmul_precision("highest"):
        _, pages, _ = qwen3_next.forward(
            params, cfg, jnp.asarray(toks[None, :149], jnp.int32),
            jnp.arange(149)[None], pages, rows[2], jnp.array([149]),
            jnp.array([149]))
        packed = np.zeros((1, 256), np.int32)
        pos = np.zeros((1, 256), np.int32)
        packed[0, :101], pos[0, :101] = toks[:101], np.arange(101)
        packed[0, 101:134], pos[0, 101:134] = other, np.arange(33)
        packed[0, 134], pos[0, 134] = toks[149], 149
        logits, pages, _ = qwen3_next.forward(
            params, cfg, jnp.asarray(packed), jnp.asarray(pos), pages,
            jnp.concatenate(rows, 0), jnp.array([101, 33, 150, 1]),
            jnp.array([101, 33, 1, 0]), packed=True)
    for row, ref in ((0, want[100]), (1, want_other[32]), (2, want[149])):
        np.testing.assert_allclose(np.asarray(logits[row]), np.asarray(ref),
                                   atol=5e-4)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The four ranks' outputs of one sparse block, the shared expert
    counted once, equal the reference's layer with every expert held."""
    hf, _cfg, _params = tiny
    whole = dict(hf, num_experts=hf["num_experts"] * hf["ep_size"])
    del whole["ep_rank"], whole["ep_size"]
    full = qwen3_next.init_params(
        ModelConfig.from_hf(whole, dtype="float32"), jax.random.PRNGKey(1),
        scale=0.3)
    lp = jax.tree_util.tree_map(lambda a: a[0], full["layers"]["full"])
    xt = jnp.asarray(np.random.default_rng(3).normal(size=(24, 64)),
                     jnp.float32)
    held = hf["num_experts"]
    with jax.default_matmul_precision("highest"):
        want = REF.sparse_block(whole, lp, xt) - xt
        xn = REF.norm(xt, lp["mlp_norm"], hf["rms_norm_eps"])
        shares = []
        for rank in range(hf["ep_size"]):
            cfg = ModelConfig.from_hf(dict(hf, ep_rank=rank),
                                      dtype="float32")
            assert cfg.expert_offset == rank * held
            cut = dict(lp, **{k: lp[k][rank * held:(rank + 1) * held]
                              for k in ("w_gate", "w_up", "w_down")})
            shares.append(qwen3_next.sparse_block(cfg, cut, xn[None])[0][0])
        shared = jax.nn.sigmoid(xn @ lp["w_sg"])[:, None] * REF.swiglu(
            xn, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total = sum(shares) - (hf["ep_size"] - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(shares[0] - shares[1]))) > 1e-3


# ------------------------------------------------------ the served path

def _req(tokens, rid, n):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0), eos_token_ids=[])


async def _collect(eng, req):
    frames = [f async for f in eng.generate(req)]
    return [t for f in frames for t in f.token_ids], frames


def _is_the_references_greedy(hf, params, prompt, served) -> bool:
    """One teacher-forced pass of the reference over prompt + served: every
    served token is the reference's first choice at its position."""
    logits = _reference_logits(hf, params, list(prompt) + list(served))
    want = jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)
    return np.asarray(want).tolist() == list(served)


@pytest.mark.parametrize("attn_impl", ["scan", "pallas"])
async def test_the_served_path_gives_the_references_greedy_tokens(attn_impl):
    """Five requests on four rows - prompts of 5 to 150 tokens computed in
    chunks of at most 70 beside the rows that decode, fused blocks and
    blocks chained behind a mixed step - stream the reference's greedy
    continuation, token for token: padded steps on the XLA path, and the
    token-packed step with ``gdn_chunk`` / ``gdn_step`` / the paged kernels
    in interpret mode (head_dim 128 for the attention kernels' tiles)."""
    over = ({"head_dim": 128, "num_attention_heads": 2,
             "num_key_value_heads": 1} if attn_impl == "pallas" else {})
    hf = _config(tiny=True, **over)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params, attn_impl=attn_impl)
    assert (eng.padded_reason is None) == (attn_impl == "pallas")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist()
               for n in (150, 33, 5, 90, 12)]
    try:
        with jax.default_matmul_precision("highest"):
            got = await asyncio.gather(*[
                _collect(eng, _req(p, f"r{i}", 8))
                for i, p in enumerate(prompts)])
            for p, (toks, frames) in zip(prompts, got):
                assert len(toks) == 8
                assert _is_the_references_greedy(hf, params, p, toks)
                assert not frames[-1].cached_tokens
        sched = eng.scheduler
        assert sorted(sched._free_slots) == [1, 2, 3, 4]
        assert sched.prefix_reuse_refused == {"recurrent_state": 5}
        assert eng.multistep_blocks > 0
        assert sum(sched.chained_blocks.values()) > 0
        form = "packed" if attn_impl == "pallas" else "padded:attn_impl"
        assert set(eng.prefill_steps) == {form}
        assert eng.allocator.hits == 0 and not eng.allocator._by_hash
        ring = [r for r in eng.steptrace.snapshot(limit=4096)["records"]
                if r["state_rows"]]
        mixed = [r for r in ring if r["kind"] in ("prefill", "mixed")]
        assert mixed and all(
            r["gdn_tokens"] + r["gdn_step_rows"] == r["tokens_real"]
            for r in mixed)
        assert any(r["gdn_tokens"] == 70 for r in mixed)
        blocks = [r for r in ring if r["kind"] == "multistep"]
        assert blocks and all(
            r["gdn_step_rows"] == r["rows"] * r["width"]
            and r["gdn_tokens"] == 0 and r["score_pairs"] > 0
            for r in blocks)
    finally:
        await eng.stop()


@pytest.mark.parametrize("attn_impl", ["scan", "pallas"])
async def test_chained_steps_carry_the_state_and_a_freed_slot_starts_from_zero(
        attn_impl):
    """Two rows and two slots, one prompt an admission pass: r1's prompt
    of 150 tokens is computed in chunks of 32 beside r0, every step of the
    run but the first enqueued while the one before it runs - the
    recurrent state and the convolution's carried inputs go from chunk to
    chunk on the device alone, and r0's token for each step comes from
    the step before. r0 ends on a stop id at the run's second step: it
    rides the third with that token and writes into its slot once more;
    r2, which waited, then takes that slot and starts from zeros. r1 and
    r2 stream the reference's greedy continuation, r0 what it streams
    alone up to its stop."""
    over = ({"head_dim": 128, "num_attention_heads": 2,
             "num_key_value_heads": 1} if attn_impl == "pallas" else {})
    hf = _config(tiny=True, **over)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    p0, p1, p2 = (rng.integers(0, 512, n).tolist() for n in (5, 150, 20))
    kw = dict(attn_impl=attn_impl, max_num_seqs=2, max_prefill_seqs=1,
              max_prefill_chunk=32, min_prefill_bucket=32,
              min_prefill_seqs_bucket=2, min_decode_bucket=2)
    with jax.default_matmul_precision("highest"):
        solo = _engine(cfg, params, **kw)
        try:
            alone, _ = await _collect(solo, _req(p0, "r0", 12))
        finally:
            await solo.stop()
        # r0's tokens: one from its prefill, four from the block behind
        # it, the 6th from the run's first step, the 7th from its second
        last = alone[6]
        assert last not in alone[:6]
        r0 = _req(p0, "r0", 24)
        r0.stop_conditions.stop_token_ids = [last]
        from dynamo_tpu.engine.steptrace import (StepRecorder,
                                                 set_step_recorder)
        set_step_recorder(StepRecorder(256))    # this engine's steps alone
        eng = _engine(cfg, params, **kw)
        try:
            got = await asyncio.gather(
                _collect(eng, r0), _collect(eng, _req(p1, "r1", 6)),
                _collect(eng, _req(p2, "r2", 6)))
            assert got[0][0] == alone[:7]
            for p, (toks, _frames) in zip((p1, p2), got[1:]):
                assert len(toks) == 6
                assert _is_the_references_greedy(hf, params, p, toks)
            sched = eng.scheduler
            assert sorted(sched._free_slots) == [1, 2]
            assert eng.allocator.num_free == eng.allocator.num_pages - 1
            ring = eng.steptrace.snapshot(limit=4096)["records"][::-1]
            mixed = [r for r in ring if r["kind"] == "mixed"]
            # the run: r1's chunk and r0, dead in the third step; with no
            # decode row left the host plans r1's last two chunks itself
            # (``budget``), and r2's prompt then rides beside r1
            assert [(r["chained_behind"], r["gdn_tokens"],
                     r["gdn_step_rows"]) for r in mixed] == [
                ("", 32, 1), ("mixed", 32, 1), ("mixed", 32, 1),
                ("", 20, 1)]
            assert sched.chained_steps == {"mixed": 2}
            assert sched.chain_refusals == {
                r: int(r == "budget") for r in sched.chain_refusals}
            form = "packed" if attn_impl == "pallas" else "padded:attn_impl"
            assert set(eng.prefill_steps) == {form}
        finally:
            await eng.stop()


async def test_a_slot_is_reused_without_a_leak_and_no_prefix_is_reused(tiny):
    """One slot: request B behind request A reads what A left in the slot
    only if the first chunk does not start from zeros. B equals itself on a
    fresh engine; the same prompt twice reads ``cached_tokens`` 0 and the
    same tokens; the gauges follow the slot."""
    from dynamo_tpu.worker.metrics import engine_dispatch_stats

    _hf, cfg, params = tiny
    rng = np.random.default_rng(5)
    a, b = (rng.integers(0, 512, n).tolist() for n in (90, 75))
    fresh = _engine(cfg, params, max_num_seqs=1)
    try:
        want, _ = await _collect(fresh, _req(b, "b", 8))
    finally:
        await fresh.stop()
    eng = _engine(cfg, params, max_num_seqs=1)
    try:
        assert engine_dispatch_stats(eng)["state_slots_total"] == 1.0
        await _collect(eng, _req(a, "a", 8))
        got, frames = await _collect(eng, _req(b, "b", 8))
        again, frames2 = await _collect(eng, _req(b, "b2", 8))
        assert got == want == again
        assert not frames[-1].cached_tokens and not frames2[-1].cached_tokens
        stats = engine_dispatch_stats(eng)
        assert stats["state_slots_in_use"] == 0.0
        assert stats["prefix_reuse_refused"] == {"recurrent_state": 3}
    finally:
        await eng.stop()


async def test_preempt_and_resume_serves_the_uninterrupted_tokens(tiny):
    """A running row is preempted after some tokens: it gives its slot and
    pages back, is admitted again, recomputed from token 0 (no page of it
    was published) - and streams what it streams uninterrupted."""
    _hf, cfg, params = tiny
    prompt = np.random.default_rng(6).integers(0, 512, 75).tolist()
    solo = _engine(cfg, params)
    try:
        want, _ = await _collect(solo, _req(prompt, "solo", 24))
    finally:
        await solo.stop()
    eng = _engine(cfg, params)
    try:
        task = asyncio.ensure_future(_collect(eng, _req(prompt, "b", 24)))
        sched = eng.scheduler
        while not any(len(s.generated) >= 6 for s in sched.active.values()):
            assert not task.done()
            await asyncio.sleep(0.01)
        assert await eng.run_exclusive(sched._preempt_one)
        assert not eng.allocator._by_hash
        got, frames = await task
        assert got == want and sched.num_preemptions == 1
        assert not frames[-1].cached_tokens
        assert sorted(sched._free_slots) == [1, 2, 3, 4]
    finally:
        await eng.stop()


def test_the_worker_refuses_at_its_arguments_and_names_the_caches(tmp_path):
    """``--state-slots`` sizes the pool; ``--disagg`` and the tiers end the
    worker at its arguments, by the family's name; ``startup.engine``
    carries the kinds of cache."""
    from dynamo_tpu.utils.tracing import StartupTrace
    from dynamo_tpu.worker import main as worker_main

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import modeldir
    model = modeldir.write_model_dir(str(tmp_path / "m"), _config(tiny=True))
    base = ["--model-path", model, "--random-weights", "--dtype", "float32",
            "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
            "--max-context", "128", "--state-slots", "3"]
    parser = worker_main.build_parser()
    for extra, names in ((["--disagg", "prefill"], "--disagg"),
                         (["--host-cache-bytes", "1024"], "host and disk")):
        with pytest.raises(NotImplementedError, match="recurrent state") as e:
            worker_main.build_engine(parser.parse_args(base + extra))
        assert names in str(e.value)
    startup = StartupTrace()
    eng = worker_main.build_engine(parser.parse_args(base), startup)
    assert eng.state_slots == 3 and eng.scheduler._free_slots == [3, 2, 1]
    attrs = [st[3] for st in startup.stages
             if st[0] == "startup.engine"][0]
    assert attrs["cache.kinds"] == "paged[L=2,Hkv=2,Dh=16]+state[L=6,S=3,f32]"
    assert attrs["linear_attention"] == (
        "gdn[chunk=64,Hk=2,Hv=4,Dk=16,Dv=16,beta<1]")
    assert attrs["moe.experts"] == "grouped[E=16,k=3][held=0+4]"
    assert attrs["prefill.form"] == "padded:attn_impl"
