"""Generation by diffusion over blocks (SDAR, ``model_type`` ``sdar_moe``)
through the engine's normal loop, at toy size in float32 on seeded random
weights, against the plain reference ``benchmarks/reference/sdar.py`` (which
shares no code with ``dynamo_tpu/models``).

Tolerances, each with its reason: served against the reference within 2e-3
nats (both float32; they differ by summation order only - the cache is
paged, the expert layer sorted, the reference dense - and read about 1e-6);
fused against pass-by-pass dispatch and cold against cached exactly equal
tokens and passes, log-probabilities within 1e-5 (the same programs at
other batch shapes)."""

import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import get_family, moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (PreprocessedRequest,
                                         SamplingOptions, StopConditions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = 4
HF = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
          moe_intermediate_size=32, num_hidden_layers=2,
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          rope_theta=10000.0, rms_norm_eps=1e-6,
          max_position_embeddings=512, model_type="sdar_moe",
          num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
          block_size=B, mask_token_id=255, tie_word_embeddings=False)
CFG = ModelConfig.from_hf(HF, dtype="float32")
# a confidence threshold inside the range the toy model's confidences take
# (0.006 to 0.012 over 256 tokens): some passes reveal by it, some by quota
TAU_FIRES = 0.0085


@pytest.fixture(scope="module")
def sdar():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar",
        os.path.join(REPO, "benchmarks", "reference", "sdar.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return moe.init_params(CFG, jax.random.PRNGKey(0))


def engine(params, steps=2, tau=2.0, width=3, **kw):
    cfg = dict(num_pages=96, page_size=B, max_num_seqs=4, max_context=256,
               max_prefill_chunk=32, decode_multistep=width,
               denoising_steps=steps, confidence_threshold=tau)
    cfg.update(kw)
    return JaxEngine(CFG, params, JaxEngineConfig(**cfg))


def prompt_of(n, salt=0):
    return [int(t) for t in np.random.default_rng([n, salt]).integers(
        0, 255, n)]


async def serve(eng, prompt, max_tokens, rid, ctx=None, **so):
    """One request through ``generate``: tokens, reveal passes,
    log-probabilities, top alternatives, finish reason, cached tokens."""
    stop = so.pop("stop_token_ids", None)
    req = PreprocessedRequest(
        token_ids=list(prompt), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=so.pop("ignore_eos", True),
                                       stop_token_ids=stop),
        sampling_options=SamplingOptions(logprobs=3, **so))
    out = {"toks": [], "rp": [], "lps": [], "tops": [], "finish": None,
           "cached": 0}
    async for f in eng.generate(req, ctx):
        out["toks"] += f.token_ids
        out["rp"] += f.reveal_pass or []
        out["lps"] += f.log_probs or []
        out["tops"] += f.top_logprobs or []
        if ctx is not None and out["toks"]:
            ctx.cancelled = True
        if f.finish_reason is not None:
            out["finish"] = f.finish_reason.value
            out["cached"] = f.cached_tokens or 0
    return out


def run(eng, *jobs):
    async def main():
        try:
            return await asyncio.gather(*jobs)
        finally:
            await eng.stop()
    return asyncio.run(main())


# -- (1) the engine's normal loop against the reference ---------------------

@pytest.mark.parametrize("steps,tau", [(1, 2.0), (2, 2.0), (4, 2.0),
                                       (4, TAU_FIRES)])
def test_served_logprobs_are_those_of_the_pass_that_revealed_each_token(
        sdar, params, steps, tau):
    """Prompt lengths of every alignment mod 4 (and one shorter than a
    block), ``max_tokens`` that end inside a block, served concurrently:
    chosen tokens and top alternatives within 2e-3 of ``score``; the
    reference's own free-running ``generate`` serves the same stream."""
    eng = engine(params, steps=steps, tau=tau)
    asks = [(13, 10), (16, 7), (31, 12), (3, 9), (22, 6)]
    got = run(eng, *(serve(eng, prompt_of(n), mt, f"r{n}")
                     for n, mt in asks))
    fired = False
    for (n, mt), g in zip(asks, got):
        prompt = prompt_of(n)
        assert len(g["toks"]) == mt and g["finish"] == "length"
        ref = sdar.score(HF, params, None, prompt, g["toks"],
                         {"reveal_pass": g["rp"]})
        for i, (tok, lp, top) in enumerate(zip(g["toks"], g["lps"],
                                               g["tops"])):
            assert abs(float(ref[i, tok]) - lp) < 2e-3
            assert len(top) == 8    # the engine's K: the backend trims
            for t, v in top.items():
                assert abs(float(ref[i, t]) - v) < 2e-3
        toks, passes, lps = sdar.generate(HF, params, prompt, mt, steps, tau)
        assert toks == g["toks"] and passes == g["rp"]
        assert max(abs(a - b) for a, b in zip(lps, g["lps"])) < 2e-3
        # the static schedule: a block's first pass reveals its quota
        first = {}
        for i, p in enumerate(g["rp"]):
            first.setdefault((n + i) // B, []).append(p)
        fired |= any(ps.count(0) > B // steps + (B % steps > 0)
                     for ps in first.values())
        assert max(g["rp"]) <= steps - 1
    assert fired == (tau < 1.0)


def test_reusing_earlier_keys_and_values_equals_full_recomputation(
        sdar, params):
    """``score`` reads, per layer, the clean pass's keys and values of the
    positions before a replayed block: by the visibility rule they depend
    on nothing at or past the block."""
    prompt = prompt_of(14)
    toks, passes, _ = sdar.generate(HF, params, prompt, 11, 2, 2.0)
    carried = {"reveal_pass": passes}
    fast = sdar.score(HF, params, None, prompt, toks, carried)
    full = sdar.score(HF, params, None, prompt, toks, carried, reuse=False)
    assert float(jnp.max(jnp.abs(fast - full))) < 1e-5
    # ... and one position off is another number (the rule is not vacuous)
    off = sdar.score(HF, params, None, prompt, toks,
                     {"reveal_pass": [1 - p for p in passes]})
    assert float(jnp.max(jnp.abs(fast - off))) > 1e-2


def test_the_whole_layer_and_the_streamed_pieces_agree(sdar, params):
    """``LAYER_FNS['block']`` (what the harness's generic walks call) and
    ``forward`` (a layer at a time, experts in blocks) are one model."""
    tokens = prompt_of(12)
    h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for kind, stack, n in sdar.layers(params):
        for i in range(n):
            w = jax.tree_util.tree_map(lambda a, i=i: a[i].astype(
                jnp.float32), stack)
            h = sdar.LAYER_FNS[kind](HF, w, h)
    assert float(jnp.max(jnp.abs(
        sdar.head(HF, params, h) - sdar.forward(HF, params, tokens)))) < 1e-4


# -- (2) what a committed block leaves in the pool --------------------------

def test_committed_blocks_hold_what_a_blockwise_prefill_writes(params):
    """After generation the prefix cache holds, for the prompt's and the
    generated blocks, the keys and values a fresh block-wise prefill of
    prompt + final tokens writes; a re-sent prompt is served from it with
    the same log-probabilities."""
    prompt = prompt_of(13)
    eng = engine(params)

    async def twice():
        cold = await serve(eng, prompt, 11, "cold")
        return cold, await serve(eng, prompt, 11, "cached")
    (cold, cached), = run(eng, twice())
    assert cached["cached"] == 12 and cold["cached"] == 0
    assert cached["toks"] == cold["toks"] and cached["rp"] == cold["rp"]
    assert max(abs(a - b) for a, b in zip(cold["lps"],
                                          cached["lps"])) < 1e-5
    final = prompt + cold["toks"]          # 24 tokens: six whole blocks
    assert len(final) % B == 0

    def pages_of(e):
        # (the budget ended with the last block: its stream was over
        # before the block was committed, so it was never published)
        from dynamo_tpu.tokens import TokenBlockSequence
        hashes = TokenBlockSequence(final, block_size=B).block_hashes()
        match = e.allocator.match_prefix(hashes[:-1])
        assert match.num_pages == len(final) // B - 1
        return np.asarray(e.pages[:, np.asarray(match.page_ids)])

    served = pages_of(eng)
    fresh = engine(params)
    # its prompt is the final sequence: every block of it is prefilled
    run(fresh, serve(fresh, final, 1, "fresh"))
    assert np.max(np.abs(served - pages_of(fresh))) < 1e-5


# -- (3) the masks ----------------------------------------------------------

def _dense_oracle(q, k, v, positions, total, block, scale):
    """q [S, Hq, D] at ``positions``; k/v [T, Hkv, D]."""
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("shd,thd->hst", q, k) * scale
    t = np.arange(k.shape[0])
    sees = (t[None, :] // block <= positions[:, None] // block) & (
        t[None, :] < total)
    s = np.where(sees[None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hst,thd->shd", p, v)


def _pool(rng, L, N, Hkv, ps, D):
    return jnp.asarray(rng.normal(size=(L, N, 2, Hkv, ps, D)), jnp.float32)


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_padded_attention_sees_whole_blocks(block, impl):
    from dynamo_tpu.ops.attention import paged_attention
    from dynamo_tpu.ops.pallas.prefill import paged_prefill_attention_stacked
    rng = np.random.default_rng(0)
    Hq, Hkv, D, ps, P, S = 4, 2, 128, 8, 20, 8
    pool = _pool(rng, 2, 48, Hkv, ps, D)
    table = jnp.asarray(rng.permutation(np.arange(1, 41)).reshape(2, P),
                        jnp.int32)
    starts = np.array([40, 96])
    positions = jnp.asarray(starts[:, None] + np.arange(S), jnp.int32)
    total = jnp.asarray(starts + S, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, S, Hq, D)), jnp.float32)
    fn = (paged_attention if impl == "xla" else
          lambda *a, **kw: paged_prefill_attention_stacked(
              *a, interpret=True, **kw))
    got = np.asarray(fn(q, pool, 1, table, positions, total, 0.1,
                        block=block))
    if block == 1:
        # the causal form, bit for bit
        assert np.array_equal(got, np.asarray(
            fn(q, pool, 1, table, positions, total, 0.1)))
    for b in range(2):
        kv = np.asarray(pool[1, np.asarray(table[b])])   # [P,2,Hkv,ps,D]
        k = kv[:, 0].transpose(0, 2, 1, 3).reshape(P * ps, Hkv, D)
        v = kv[:, 1].transpose(0, 2, 1, 3).reshape(P * ps, Hkv, D)
        want = _dense_oracle(np.asarray(q[b]), k, v,
                             np.asarray(positions[b]), int(total[b]),
                             block, 0.1)
        assert np.max(np.abs(got[b] - want)) < 2e-5


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_packed_attention_sees_whole_blocks(block, impl):
    from dynamo_tpu.ops.attention import ragged_paged_attention
    from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed
    rng = np.random.default_rng(1)
    Hq, Hkv, D, ps, P = 4, 2, 128, 8, 12
    pool = _pool(rng, 2, 40, Hkv, ps, D)
    table = jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(3, P),
                        jnp.int32)
    q_lens = np.array([12, 8, 0])
    kv_lens = np.array([52, 8, 1])
    q_starts = np.cumsum(q_lens) - q_lens
    T = 24
    q = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    fn = (ragged_paged_attention if impl == "xla" else
          lambda *a, **kw: ragged_mixed_attention_packed(
              *a, interpret=True, **kw))
    args = (q, pool, 0, table, jnp.asarray(q_starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
            0.1)
    got = np.asarray(fn(*args, block=block))
    if block == 1:
        assert np.array_equal(got, np.asarray(fn(*args)))
    for r in range(2):
        kv = np.asarray(pool[0, np.asarray(table[r])])
        k = kv[:, 0].transpose(0, 2, 1, 3).reshape(P * ps, Hkv, D)
        v = kv[:, 1].transpose(0, 2, 1, 3).reshape(P * ps, Hkv, D)
        lo = q_starts[r]
        pos = kv_lens[r] - q_lens[r] + np.arange(q_lens[r])
        want = _dense_oracle(np.asarray(q[lo:lo + q_lens[r]]), k, v, pos,
                             int(kv_lens[r]), block, 0.1)
        assert np.max(np.abs(got[lo:lo + q_lens[r]] - want)) < 2e-5
    assert np.all(got[20:] == 0)


def test_a_causal_model_hands_its_attention_no_block():
    """``qwen3-4b`` and ``joyai-llm-flash`` run the programs they ran: the
    forwards pass the visibility block only where a model has one."""
    from dynamo_tpu.models.llama import visibility
    assert visibility(ModelConfig.tiny()) == {}
    assert visibility(CFG) == {"block": B}
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-4b.json")) as f:
        hf = json.load(f)
    hf.pop("benchmark")
    cfg = ModelConfig.from_hf(hf)
    assert cfg.generation == "causal" and cfg.gen_block == 1 and cfg.qk_norm


def test_the_reveal_rule():
    from dynamo_tpu.ops.sampling import reveal
    conf = jnp.asarray([[0.5, 0.9, 0.2, 0.95], [0.3, 0.3, 0.1, 0.2],
                        [0.9, 0.9, 0.9, 0.9], [0.1, 0.2, 0.3, 0.4]])
    masked = jnp.asarray([[1, 1, 1, 1], [1, 1, 0, 1], [1, 1, 1, 1],
                          [0, 0, 0, 1]], bool)
    got = reveal(conf, masked, jnp.asarray([0, 1, 0, 2]),
                 jnp.asarray([2, 4, 4, 3]), jnp.asarray([0.8, 0.8, 0.8, 2.]))
    assert got.tolist() == [
        [False, True, False, True],     # two above the threshold = quota
        [True, False, False, False],    # none above: the most confident,
                                        # ties to the lower position
        [True, True, True, True],       # all above: more than the quota
        [False, False, False, True]]    # never more than are masked


# -- (4) fused against pass-by-pass, and the budgets ------------------------

@pytest.mark.parametrize("so", [{}, {"temperature": 1.0, "seed": 7}])
def test_fused_dispatch_serves_what_pass_by_pass_dispatch_serves(params, so):
    """Rows in different phases of their blocks (alignments 1, 0, 3, 2),
    three passes a dispatch against one: the same tokens and passes,
    greedy and under a fixed seed (a seeded row's key is a function of
    position and pass)."""
    asks = [(13, 10), (16, 9), (31, 12), (6, 7)]
    out = []
    for width in (1, 3):
        eng = engine(params, width=width)
        out.append(run(eng, *(serve(eng, prompt_of(n), mt, f"r{n}", **so)
                              for n, mt in asks)))
        assert eng.scheduler.num_preemptions == 0
    for a, b in zip(*out):
        assert a["toks"] == b["toks"] and a["rp"] == b["rp"]
        assert max(abs(x - y) for x, y in zip(a["lps"], b["lps"])) < 1e-5
    if so:
        assert out[0][0]["toks"] != run(
            (e := engine(params)), serve(e, prompt_of(13), 10, "g"))[0]["toks"]


def test_a_stop_token_inside_a_block_ends_the_stream_there(params):
    eng = engine(params)
    free = eng.allocator.num_free
    (full,) = run(eng, serve(eng, prompt_of(16), 12, "full"))
    assert eng.allocator.num_free == free       # pages reclaimed
    stop = full["toks"][5]                      # the block's second token
    at = full["toks"].index(stop)
    eng = engine(params)
    (cut,) = run(eng, serve(eng, prompt_of(16), 12, "cut",
                            stop_token_ids=[stop]))
    assert cut["finish"] == "stop" and cut["toks"] == full["toks"][:at + 1]
    assert eng.allocator.num_free == free


def test_a_cancel_in_mid_block_frees_the_rows_pages(params):
    class Ctx:
        cancelled = False
    eng = engine(params)
    free = eng.allocator.num_free
    ctx = Ctx()
    (got,) = run(eng, serve(eng, prompt_of(16), 40, "c", ctx=ctx))
    assert got["finish"] == "cancelled" and 0 < len(got["toks"]) < 40
    assert eng.allocator.num_free == free and not eng.scheduler.active


def test_a_preempted_row_resumes_at_a_block_boundary(params):
    """A pool too small for both rows (admission holds back only what a
    row is SURE to ask for, and a row that an end-of-sequence token may
    end is sure of nothing): the newer is preempted in mid-generation, its
    prompt and committed blocks are prefilled again, and it serves the
    tokens an undisturbed run serves."""
    asks = [(20, 40), (24, 40)]
    roomy = engine(params)
    want = run(roomy, *(serve(roomy, prompt_of(n), mt, f"r{n}",
                              ignore_eos=False) for n, mt in asks))
    tight = engine(params, num_pages=26)
    got = run(tight, *(serve(tight, prompt_of(n), mt, f"r{n}",
                             ignore_eos=False) for n, mt in asks))
    assert tight.scheduler.num_preemptions > 0
    for a, b in zip(want, got):
        assert len(b["toks"]) == 40 and a["toks"] == b["toks"]
        assert a["rp"] == b["rp"]


def test_counters_ring_and_spans_say_what_the_passes_did(params):
    from dynamo_tpu.worker.metrics import engine_dispatch_stats
    eng = engine(params)
    n0 = eng.steptrace.total
    (got,) = run(eng, serve(eng, prompt_of(16), 16, "m"))
    stats = engine_dispatch_stats(eng)
    # four blocks of four masks at two steps: two revealing passes and one
    # committing pass each
    assert stats["gen_passes"] == {"reveal": 8.0, "commit": 4.0}
    assert stats["gen_tokens_revealed"] == 16.0
    assert stats["gen_blocks_committed"] == 4.0
    # (the last, chained dispatch was enqueued before the host saw the
    # budget end: the device ran it over a dead row, and it counts nothing)
    recs = [r for r in eng.steptrace.snapshot(limit=64)["records"]
            if r["seq"] >= n0 and r["kind"] == "multistep"
            and r["row_passes"]]
    assert recs and all(r["program"] == "passes3[1,4]" for r in recs)
    assert sum(r["row_passes"] for r in recs) == 12
    assert sum(r["revealed"] for r in recs) == 16
    assert sum(r["commits"] for r in recs) == 4
    assert all(r["passes"] == 3 and r["tokens_real"] == r["row_passes"] * B
               for r in recs)
    assert eng.generation == "block_diffusion[B=4,steps=2,tau=2]"


def test_prompt_chunks_end_on_block_boundaries(params):
    """A chunk budget the block does not divide: a prompt of 31 tokens is
    prefilled as 8 + 8 + 8 + 4 (its seven whole blocks; the tail of three
    rides the first generated block), and a prompt shorter than a block is
    not prefilled at all."""
    eng = engine(params, max_prefill_chunk=10)
    n0 = eng.steptrace.total
    run(eng, serve(eng, prompt_of(31), 5, "long"))
    chunks = [r["tokens_real"] for r in reversed(
        eng.steptrace.snapshot(limit=64)["records"])
        if r["seq"] >= n0 and r["kind"] == "prefill"]
    assert chunks == [8, 8, 8, 4]
    eng = engine(params)
    n0 = eng.steptrace.total
    (short,) = run(eng, serve(eng, prompt_of(3), 6, "short"))
    assert len(short["toks"]) == 6
    assert not [r for r in eng.steptrace.snapshot(limit=64)["records"]
                if r["seq"] >= n0 and r["kind"] == "prefill"]


def test_a_wave_of_prompts_is_prefilled_before_its_rows_run(params):
    """Rows in mid-block wait through an admission step; with the
    decode-progress guarantee raised a wave is prefilled in consecutive
    steps (``gen_rows_waited`` counts the rows that waited)."""
    eng = engine(params, max_prefill_chunk=16, decode_progress_every=8)
    n0 = eng.steptrace.total
    run(eng, *(serve(eng, prompt_of(24, i), 8, f"w{i}") for i in range(4)))
    kinds = [r["kind"] for r in reversed(
        eng.steptrace.snapshot(limit=128)["records"]) if r["seq"] >= n0]
    first_pass = kinds.index("multistep")
    assert kinds[:first_pass].count("prefill") >= 4
    assert eng.scheduler.gen_rows_waited >= 0


# -- (5) refusals -------------------------------------------------------------

@pytest.mark.parametrize("reason,so,extra", [
    ("guided", {"guided": {"mode": "json"}}, {}),
    ("penalties", {"frequency_penalty": 0.5}, {}),
    ("penalties", {"repetition_penalty": 1.2}, {}),
    ("logit_bias", {"logit_bias": {5: 1.0}}, {}),
    ("disagg_prefill", {}, {"prefill_only": True}),
])
def test_what_does_not_compose_is_refused_by_name_and_counted(
        params, reason, so, extra):
    eng = engine(params)
    req = PreprocessedRequest(token_ids=prompt_of(9), request_id="x",
                              sampling_options=SamplingOptions(**so),
                              **extra)

    async def ask():
        frames = [f async for f in eng.generate(req)]
        await eng.stop()
        return frames
    (frame,) = asyncio.run(ask())
    assert frame.finish_reason.value == "error"
    assert "diffusion over blocks" in frame.error
    assert eng.requests_refused == {reason: 1}


def test_the_frontend_answers_them_with_a_400(params):
    """The worker advertises its generation rule on the model card and the
    preprocessor refuses before a stream opens (``ValueError`` is the
    HTTP service's 400); the rule's own parameters pass through, and are
    refused for a model that has no such rule."""
    from dynamo_tpu.preprocessor.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.protocols.openai import CompletionRequest
    from dynamo_tpu.utils.testing import make_test_card
    card = make_test_card(name="sdar")
    card.extra["generation"] = "block_diffusion"
    pre = OpenAIPreprocessor(card)

    def ask(**kw):
        return pre.preprocess_completion(CompletionRequest(
            model="sdar", prompt=[1, 2, 3], max_tokens=4, **kw))
    for kw in ({"logit_bias": {"5": 1.0}}, {"presence_penalty": 0.3},
               {"repetition_penalty": 1.3}):
        with pytest.raises(ValueError, match="diffusion over blocks"):
            ask(**kw)
    from dynamo_tpu.protocols.openai import ChatCompletionRequest
    with pytest.raises(ValueError, match="guided decoding"):
        pre.preprocess_chat(ChatCompletionRequest(
            model="sdar", messages=[{"role": "user", "content": "hi"}],
            response_format={"type": "json_object"}))
    so = ask(nvext={"denoising_steps": 3,
                    "confidence_threshold": 0.5}).sampling_options
    assert (so.denoising_steps, so.confidence_threshold) == (3, 0.5)
    with pytest.raises(ValueError, match="at least 1"):
        ask(nvext={"denoising_steps": 0})
    causal = OpenAIPreprocessor(make_test_card(name="llama"))
    with pytest.raises(ValueError, match="one next token a step"):
        causal.preprocess_completion(CompletionRequest(
            model="llama", prompt=[1, 2], max_tokens=2,
            nvext={"denoising_steps": 2}))


def test_an_engine_refuses_what_it_cannot_combine_with_the_rule(params):
    with pytest.raises(ValueError, match="speculative-num-tokens"):
        engine(params, spec_tokens=2)
    with pytest.raises(ValueError, match="multiple of the block"):
        engine(params, page_size=6, max_context=252)
    with pytest.raises(ValueError, match="mask_token_id"):
        ModelConfig.from_hf(dict(HF, mask_token_id=999))


def test_per_request_parameters_override_the_workers(params):
    eng = engine(params, steps=4)
    one, two = run(eng, serve(eng, prompt_of(16), 8, "a"),
                   serve(eng, prompt_of(16), 8, "b", denoising_steps=1))
    assert max(one["rp"]) == 3 and set(two["rp"]) == {0}


# -- (6) the initialiser ------------------------------------------------------

def test_the_worker_and_the_reference_child_draw_the_same_weights():
    """Both call the family's ``init_params`` on the same key. (That the
    compiled draw holds no float32 copy of a whole expert stack is read
    off the TPU compiler's program at the real shape:
    ``tests/test_pallas_tpu_lowering.py``.)"""
    a = get_family(CFG).init_params(CFG, jax.random.PRNGKey(0))
    b = moe.init_params(CFG, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool(jnp.array_equal(x, y)), a, b))
    assert a["layers"]["w_gate"].shape == (2, 8, 64, 32)
    assert "lm_head" in a and a["layers"]["q_norm"].shape == (2, 16)
    # the sparse families' measured scale, per fan-in
    std = float(jnp.std(a["layers"]["w_up"]))
    assert abs(std - 0.012 * (2048 / 64) ** 0.5) < 0.004


# -- (7) the programs -----------------------------------------------------------

def test_the_pass_program_copies_no_pool_and_builds_no_expert_temporary(
        params):
    from dynamo_tpu.engine.program_check import (
        expert_temporaries, pool_copies, step_programs)
    eng = engine(params, num_pages=300)
    programs = step_programs(eng, 4, 32, width=3)
    assert set(programs) == {"passes", "mixed"}
    fn, args = programs["passes"]
    text = fn.lower(*args).compile().as_text()
    assert pool_copies(text, eng.pages.shape, eng.pages.dtype) == []
    assert expert_temporaries(text, 4 * B, 8, 32) == []
