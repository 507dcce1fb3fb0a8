"""dots3-note-prev through its own family (``models/dots3.py``): the
published config loads by its own keys and the cut counts to the issue's
parameter count; what the family does not implement is refused by the
key's name; prefill into the three kinds of cache then decode out of them -
a prompt cut into chunks across a ring's wrap, padded and token-packed, on
the gathered (XLA) path and through the masked form of the ragged latent
kernel (interpret mode here) - agrees with the plain reference's whole
forward pass (``benchmarks/reference/dots3.py``) at contexts past the tiny
``index_topk`` and the tiny window; the selection equals the reference's
``lax.top_k`` index for index, with a sort and without; the ranks' shares
of a sparse block add up to the uncut layer; a sequence's window bytes do
not grow with its context; and a request's window slot is given, freed and
reused without a leak."""

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
from dynamo_tpu.models import dots3, get_family
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import indexer
from dynamo_tpu.ops import sparse_latent as sl
from dynamo_tpu.ops.gdn import token_rows
from dynamo_tpu.protocols.common import (PreprocessedRequest,
                                         SamplingOptions, StopConditions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "dots3-note-prev.json")
# the tiny widths at which the latent kernels tile (latents of 128 and,
# for the kernel's split of the rotary slot, 256)
KERNEL = {"kv_lora_rank": 128, "swa_kv_lora_rank": 256}


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


REF = _load("ref_dots3", "reference", "dots3.py")
COST = _load("dots3_cost", "dots3_cost.py")


def _reference_logits(hf, params, tokens):
    """[T, V] float32: the reference's whole forward pass."""
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for kind, stack, n in REF.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(jnp.float32), stack)
                h = REF.LAYER_FNS[kind](hf, w, h)
        return np.asarray(REF.head(hf, params, h))


def _family(**over):
    hf = _config(tiny=True, **over)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    return hf, cfg, dots3.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny():
    return _family()


class _Kernels:
    """What the engine hands a family to opt it into its Pallas kernels."""
    pallas_paged_kernel = True


# ------------------------------------------------------------ the config

def test_from_hf_reads_the_published_config_and_the_cut_counts():
    hf = _config(tiny=False)
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is dots3
    assert cfg.layer_pattern() == (3, 2, 0) and cfg.first_k_dense_replace == 1
    assert (cfg.num_cache_layers, cfg.window_layers) == (3, 6)
    assert cfg.slot_kind == "window_cache"
    # every width is the published one
    assert (cfg.hidden_size, cfg.num_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (5120, 128, 128, 64, 128, 1024, 512)
    w = cfg.window_cfg()
    assert (w.num_heads, w.qk_nope_head_dim, w.qk_rope_head_dim,
            w.v_head_dim, w.q_lora_rank, w.kv_lora_rank, w.rope_theta,
            cfg.swa_window) == (64, 192, 64, 128, 1024, 1024, 5e4, 513)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert cfg.rope_theta == 8e7 and cfg.attn_gate and w.attn_gate
    assert cfg.mla_q_scale == pytest.approx(5 ** 0.5)
    assert cfg.mla_kv_scale == pytest.approx(10 ** 0.5)
    assert w.mla_q_scale == w.mla_kv_scale == pytest.approx(5 ** 0.5)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.intermediate_size, cfg.vocab_size, cfg.topk_method) == (
        256, 8, 0, 8, 1536, 13824, 19008, "noaux_tc")
    # the published 46 layers: eleven periods and one more full layer
    row = dict(hf, num_hidden_layers=46, layer_types=(
        ["full_attention"] + ["full_attention"] + (
            ["sliding_attention"] * 3 + ["full_attention"]) * 11))
    assert ModelConfig.from_hf(row).layer_pattern() == (3, 11, 1)
    # the cut, by count: 3.09 B parameters
    shapes = jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == 3_093_416_192 and round(n / 1e9, 2) == 3.09
    # ... of which the matrices the cost file counts (all but the norms'
    # vectors, the router's bias and the index key's LayerNorm)
    vectors = sum(x.size for k, x in jax.tree_util.tree_leaves_with_path(
        shapes) if "norm" in jax.tree_util.keystr(k)
        or "router_bias" in jax.tree_util.keystr(k))
    assert n - vectors == COST.total_params(hf)


@pytest.mark.parametrize("over,names", [
    ({"swa_kv_lora_rank": None}, "swa_kv_lora_rank"),
    ({"index_topk": 0}, "index_topk"),
    ({"sliding_window_size": None}, "sliding_window_size"),
    ({"swa_rope_theta": None}, "swa_rope_theta"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"layer_types": ["sliding_attention"] * 9}, "layer_types"),
    ({"layer_types": ["full_attention"] * 9}, "layer_types"),
])
def test_a_file_the_loader_cannot_serve_is_refused_by_the_keys_name(over,
                                                                     names):
    with pytest.raises(NotImplementedError, match=names):
        ModelConfig.from_hf(_config(tiny=False, **over))


def test_an_unknown_layer_kind_names_the_kinds_that_are_implemented():
    hf = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
          "num_hidden_layers": 2, "num_attention_heads": 2,
          "layer_types": ["full_attention", "chunked_attention"]}
    with pytest.raises(NotImplementedError) as e:
        ModelConfig.from_hf(hf)
    for kind in ("chunked_attention", "linear_attention",
                 "sliding_attention", "full_attention", "dots3.py"):
        assert kind in str(e.value)
    # sliding_attention without the latent keys is not this family
    hf["layer_types"] = ["full_attention", "sliding_attention"]
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        ModelConfig.from_hf(hf)


# ----------------------------------------------------------- the engine

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
    with pytest.raises(NotImplementedError, match="window cache") as e:
        _engine(cfg, params, **kw)
    assert names in str(e.value) and "dots3_note" in str(e.value)


def test_page_export_and_tiers_are_refused_and_the_pools_are_named(tiny):
    from dynamo_tpu.kvbm.manager import TieredEngine

    _hf, cfg, params = tiny
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="window cache"):
        eng.gather_pages_host([1, 2])
    with pytest.raises(NotImplementedError, match="host and disk tiers"):
        TieredEngine(eng)
    with pytest.raises(NotImplementedError, match="--disagg"):
        cfg.paged_only("--disagg")
    assert eng.cache_kinds == ("paged[L=2,Hkv=1,Dh=32]+index[L=2,D=16]"
                               "+window[L=3,S=4,R=128,D=48]")
    assert eng.page_pools == ("kv", "index")
    assert eng.table_width == 512 // 8 + 1
    assert set(eng.cache_bytes) == {"paged", "index", "window"}
    assert eng.cache_bytes["window"] == 5 * dots3.window_bytes_per_sequence(
        cfg, 70, 8)


def test_a_sequences_window_bytes_do_not_grow_with_its_context(tiny):
    """The rings are sized by the window and the chunk alone: the pool at a
    context of ten windows is the pool at two, a sequence's share of it is
    ``window_bytes_per_sequence``, and that is far under what pages of its
    whole context would take."""
    _hf, cfg, _params = tiny
    W = cfg.swa_window
    short, long_ = (dots3.make_pages(cfg, ctx // 4 + 1, 4, state_slots=2,
                                     max_chunk=16)
                    for ctx in (2 * W * 4, 10 * W * 4))
    assert short["win"].shape == long_["win"].shape
    assert long_["kv"].shape[1] > short["kv"].shape[1]
    per_seq = short["win"].nbytes // 3
    assert per_seq == dots3.window_bytes_per_sequence(cfg, 16, 4)
    R = short["win"].shape[2] * 4
    assert R == sl.ring_size(W, 16, 4) and R >= W - 1 + 16
    # what this rank's cost file counts is the same number
    assert per_seq == cfg.window_layers * R * 2 * cfg.swa_kv_lora_rank * 4


# --------------------------------------------------------- the selection

def test_topk_mask_is_lax_top_k_without_the_sort():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((6, 300)).astype(np.float32)
    x[1, :] = np.round(x[1, :], 1)              # many ties, some at the cut
    x[2, 40:] = indexer.NEG_INF                      # fewer visible than k
    x[3, :] = 0.0                               # every key tied
    x[4, ::2] = -0.5
    x[5, :] = np.where(rng.random(300) < 0.5, 1.0, indexer.NEG_INF)
    for k in (1, 17, 64, 300):
        vals, idx = jax.lax.top_k(jnp.asarray(x), k)
        want = np.zeros(x.shape, bool)
        np.put_along_axis(want, np.asarray(idx), np.asarray(vals)
                          > indexer.NEG_INF / 2, axis=1)
        got = np.asarray(jax.jit(indexer.topk_mask, static_argnums=1)(
            jnp.asarray(x), k))
        assert (got == want).all(), k


def _step(cfg, new, total, S=None, slots=None):
    """A step's rows on the flat axis: packed (``S`` None) or ``[B, S]``."""
    new, total = jnp.asarray(new, jnp.int32), jnp.asarray(total, jnp.int32)
    R = new.shape[0]
    slots = jnp.arange(1, R + 1, dtype=jnp.int32) if slots is None else slots
    if S is None:
        starts = (jnp.cumsum(new) - new).astype(jnp.int32)
        N = int(-(-int(jnp.sum(new)) // 8) * 8)
    else:
        starts, N = jnp.arange(R, dtype=jnp.int32) * S, R * S
    return token_rows(N, starts, new, total, slots), N


def test_the_selection_is_the_references_top_k_index_for_index(tiny):
    """One full layer's indexer on a packed step of a chunk row and
    one-token rows against index pages filled by earlier steps: every
    token's list (sorted) is the reference's ``lax.top_k`` of its dense
    score row, and the masked form's bias - of the chunk row's slots and
    of the one-token row alike - is that list as a mask."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(3)
    T = 90
    x = jnp.asarray(rng.standard_normal((1, T, cfg.hidden_size)),
                    jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
    pos = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        q, k, w = dots3.index_inputs(cfg, lp, x, pos)
        wr = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
        c_q = REF.norm(x[0] @ wr["wq_a"], wr["q_a_norm"],
                       hf["rms_norm_eps"])
        scores = REF.index_scores(hf, wr, x[0], c_q,
                                  hf["qk_rope_head_dim"],
                                  float(hf["rope_theta"]))
        K = cfg.index_topk
        vals, idx = jax.lax.top_k(scores, K)
        # the keys of ONE sequence in pages 1.. of a pool, table [2, P]:
        # row 0 a chunk of the last 40 tokens, row 1 a one-token row at
        # position 59 of the same pages
        ps, P = 4, 32
        pool = jnp.zeros((2, P + 1, ps, cfg.index_head_dim), jnp.float32)
        pool = pool.at[1, 1:1 + -(-T // ps)].set(
            jnp.pad(k, ((0, -T % ps), (0, 0))).reshape(-1, ps, k.shape[-1]))
        table = jnp.tile(jnp.arange(1, P + 1, dtype=jnp.int32), (2, 1))
        rows, N = _step(cfg, [40, 1], [T, 60])
        take = jnp.concatenate([jnp.arange(50, 90), jnp.asarray([59]),
                                jnp.zeros(N - 41, jnp.int32)])
        kw = dict(width=N, packed=True)
        total = jnp.asarray([T, 60])
        sel, live = indexer.select(q[take], w[take], pool, 1, table, rows, total,
                              K, **kw)
        one, bias = indexer.select_split(q[take], w[take], pool, 1, table, rows,
                                    total, K, **kw)
    for slot, t in enumerate(np.asarray(take[:41])):
        want = np.asarray(idx[t])[np.asarray(vals[t]) > -np.inf]
        got = np.asarray(sel[slot])[np.asarray(live[slot])]
        assert sorted(got) == sorted(want), (slot, t)
        assert len(got) == min(K, t + 1)
        if slot < 40:
            assert sorted(np.flatnonzero(np.asarray(bias[slot]) == 0)) \
                == sorted(want)
    rows_bias, to = one
    assert np.asarray(to).tolist() == [N, 40]
    # the one-token row's bias opens the list it was sorted from on the
    # parent, key for key; the chunk row's is shut
    assert np.flatnonzero(np.asarray(rows_bias[1]) == 0).tolist() == sorted(
        np.asarray(sel[40])[np.asarray(live[40])]) == sorted(
        np.asarray(idx[59])[np.asarray(vals[59]) > -np.inf])
    assert set(np.unique(np.asarray(rows_bias))) == {
        np.float32(0.0), np.float32(indexer.NEG_INF)}
    assert (np.asarray(rows_bias[0]) < indexer.NEG_INF / 2).all()
    assert (np.asarray(bias[40:]) < indexer.NEG_INF / 2).all()


# ------------------------------------------------ the model, by its pools

def _table(slot, first_page, n, width):
    t = np.zeros((1, width + 1), np.int32)
    t[0, :n] = np.arange(first_page, first_page + n)
    t[0, -1] = slot
    return t


def _serve(cfg, params, tokens, chunks, *, ps=4, impl=None, chunk_cap=None):
    """Prefill ``tokens`` in the given chunk lengths then decode the rest
    a token at a time, padded ``[1, S]`` steps and token-packed ones in
    turn; returns ``{position: logits}``."""
    fam = dots3
    width = -(-len(tokens) // ps) + 2
    cap = chunk_cap or max(chunks)
    pages = fam.make_pages(cfg, width + 8, ps, state_slots=3, max_chunk=cap)
    table = _table(2, 3, width, width)
    fwd = jax.jit(
        lambda p, t, pos, pg, tb, tot, new, packed: fam.forward(
            p, cfg, t, pos, pg, tb, tot, new, attn_impl=impl,
            packed=packed), static_argnums=(7,))
    out, s = {}, 0
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate(list(chunks) + [1] * (len(tokens)
                                                    - sum(chunks))):
            packed = i % 2 == 1
            S = -(-n // 8) * 8 if packed else n
            t = np.zeros((1, S), np.int32)
            t[0, :n] = tokens[s:s + n]
            pos = np.zeros((1, S), np.int32)
            pos[0, :n] = np.arange(s, s + n)
            tb = np.concatenate([table, np.zeros_like(table)]) \
                if packed else table
            tot = [s + n, 0] if packed else [s + n]
            new = [n, 0] if packed else [n]
            lg, pages, _aux = fwd(params, jnp.asarray(t), jnp.asarray(pos),
                                  pages, jnp.asarray(tb), jnp.asarray(tot),
                                  jnp.asarray(new), packed)
            out[s + n - 1] = np.asarray(lg)[0].reshape(-1, cfg.vocab_size)[0]
            s += n
    return out


def test_chunked_prefill_then_decode_agrees_with_the_reference(tiny):
    """170 tokens - past the tiny selection (24), the tiny window (17) and
    the ring (128 positions: the prefill wraps it) - in chunks of 33 and
    less, then a token at a time: the logits out of the pools are the
    reference's whole forward pass."""
    hf, cfg, params = tiny
    tokens = np.random.default_rng(0).integers(0, 512, 170).tolist()
    want = _reference_logits(hf, params, tokens)
    got = _serve(cfg, params, tokens, [33, 20, 33, 33, 30, 5])
    assert len(got) == 6 + 16
    for p, lg in got.items():
        np.testing.assert_allclose(lg, want[p], atol=2e-5, err_msg=str(p))


F, W_ = "full_attention", "sliding_attention"


@pytest.mark.parametrize("kinds,pattern", [
    # two periods of two window layers and one more full layer: every
    # indexed read of the forward at an index past 0 - the full stack's
    # ``p`` and its tail's ``P + t``, the flat window stack's ``p * G + j``
    ([F, F, W_, W_, F, W_, W_, F], (2, 2, 1)),
    # one period (the outer loop runs once) of one window layer, and a tail
    ([F, F, W_, F], (1, 1, 1)),
    # the cell's own pattern at the tiny widths: two periods of three
    ([F, F, W_, W_, W_, F, W_, W_, W_], (3, 2, 0)),
], ids=["two_periods_and_a_tail", "one_period_and_a_tail", "the_cells"])
def test_every_layer_is_read_at_its_own_index_of_its_stack(kinds, pattern):
    """The forward's loops carry indices alone and take a layer's leaves
    out of the stacks (``moe.flat_layers`` / ``layer_at``, ISSUE 52): at
    other counts of periods, places and tail layers than the tiny
    configuration's one period of three, a prompt in two chunks and four
    tokens after it still read the reference's logits, which walks the
    layers one at a time in the published order."""
    hf, cfg, params = _family(num_hidden_layers=len(kinds), layer_types=kinds)
    assert cfg.layer_pattern() == pattern
    G, P, tail = pattern
    assert params["layers"]["win"]["wo"].shape[:2] == (P, G)
    assert params["layers"]["full"]["wo"].shape[0] == P + tail
    tokens = np.random.default_rng(2).integers(0, 512, 40).tolist()
    want = _reference_logits(hf, params, tokens)
    got = _serve(cfg, params, tokens, [23, 13])
    assert sorted(got) == [22, 35, 36, 37, 38, 39]
    for p, lg in got.items():
        np.testing.assert_allclose(lg, want[p], atol=2e-5, err_msg=str(p))


@pytest.mark.parametrize("form", ["gathered", "masked"])
def test_chunked_prefill_equals_whole_prefill_across_a_ring_wrap(tiny, form):
    """The same 160 tokens in one step of 160 (a ring of 256) and in
    chunks of 48 (a ring of 128, wrapped twice: the second chunk is laid
    across the ring's end), then four tokens one at a time, padded and
    packed in turn: the logits agree. ``masked``: the chunks on the
    kernels (interpret mode, at the widths they tile) - ``mla_window``
    over a chunk that wraps, ``mla_window_rows`` for the tokens after it -
    against the whole prompt in the gathered form."""
    _hf, cfg, params = tiny if form == "gathered" else _family(**KERNEL)
    impl, ps = (None, 4) if form == "gathered" else (_Kernels(), 8)
    tokens = np.random.default_rng(1).integers(0, 512, 164).tolist()
    whole = _serve(cfg, params, tokens, [160], ps=ps)
    parts = _serve(cfg, params, tokens, [48, 48, 48, 16], ps=ps, impl=impl)
    assert sorted(parts)[-5:] == [159, 160, 161, 162, 163]
    for p in range(159, 164):
        np.testing.assert_allclose(parts[p], whole[p], atol=3e-5,
                                   err_msg=str(p))


@pytest.fixture(scope="module")
def filled():
    """Four rows' pools at the kernels' tiny widths, filled by whole
    prompts a row a step (the gathered form): ``(cfg, pages, table, have,
    fwd, rng)``, ``have`` the tokens each row holds, ``fwd(t, pos, pages,
    table, total, new, impl, packed)`` the jitted forward."""
    _hf, cfg, params = _family(**KERNEL)
    rng = np.random.default_rng(2)
    ps, P, chunk = 8, 24, 24
    pages = dots3.make_pages(cfg, 4 * P + 1, ps, state_slots=4,
                             max_chunk=chunk)
    R = 4
    table = np.zeros((R, P + 1), np.int32)
    for r in range(R):
        table[r, :P] = 1 + r * P + np.arange(P)
        table[r, -1] = r + 1
    # tokens each row holds already, against rings of 128: one young, one
    # a chunk short of the ring's end, one past its first wrap, one whose
    # next token is the first to wrap
    have = [60, 112, 131, 128]
    fwd = jax.jit(
        lambda t, pos, pg, tb, tot, new, impl, packed: dots3.forward(
            params, cfg, t, pos, pg, tb, tot, new, attn_impl=impl,
            packed=packed), static_argnums=(6, 7))
    with jax.default_matmul_precision("highest"):
        for r, n in enumerate(have):
            s = 0
            while s < n:
                m = min(chunk, n - s)
                t = np.zeros((1, chunk), np.int32)
                t[0, :m] = rng.integers(0, 512, m)
                pos = np.zeros((1, chunk), np.int32)
                pos[0, :m] = np.arange(s, s + m)
                _lg, pages, _ = fwd(jnp.asarray(t), jnp.asarray(pos), pages,
                                    jnp.asarray(table[r:r + 1]),
                                    jnp.asarray([s + m]), jnp.asarray([m]),
                                    None, False)
                s += m
    return cfg, pages, table, have, fwd, rng, params


# a step's rows by the tokens each brings: two chunk rows beside two
# one-token rows back to back (a packed step: the chunk rows' slots come
# back zero from the one-token kernels and are filled by ``mla_selected``
# and ``mla_window``; the first chunk is laid across its ring's end), the
# same as ``[B, S]`` rows, a decode step ``[B, 1]`` (the fused block's:
# every row of one token - one in a young ring, one after its ring's wrap,
# one AT it - and one of them dead), a packed step of one-token rows alone,
# and one whose one-token rows sit on both sides of a chunk that wraps,
# beside a row of no token
STEPS = {"padded": ([24, 17, 9, 1], 24), "packed": ([24, 17, 1, 1], None),
         "decode": ([1, 0, 1, 1], 1),
         "packed_rows_of_one_token": ([1, 1, 1, 1], None),
         "packed_rows_around_a_wrapping_chunk": ([1, 24, 0, 1], None)}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_masked_kernel_form_equals_the_gathered_form(filled, step):
    """A step against pools that earlier steps filled: the masked forms
    (interpret mode: ``mla_ragged`` with a bias for the rows of several
    tokens, ``mla_selected`` and ``mla_window``; the latent decode kernel
    with a bias for the rows of one, ``mla_selected_rows`` over a full
    layer's pages and ``mla_window_rows`` over a window layer's ring)
    give the logits and the pools of the gathered form for every row."""
    cfg, pages, table, have, fwd, rng, _params = filled
    new, S = STEPS[step]
    R, packed = len(new), S is None
    with jax.default_matmul_precision("highest"):
        if packed:
            T = 48
            t, pos = np.zeros((1, T), np.int32), np.zeros((1, T), np.int32)
            s = 0
            for r, n in enumerate(new):
                t[0, s:s + n] = rng.integers(0, 512, n)
                pos[0, s:s + n] = have[r] + np.arange(n)
                s += n
        else:
            t = rng.integers(0, 512, (R, S)).astype(np.int32)
            pos = np.asarray(have)[:, None] + np.arange(S)[None]
        args = (jnp.asarray(t), jnp.asarray(pos), None, jnp.asarray(table),
                jnp.asarray(have) + jnp.asarray(new), jnp.asarray(new))
        want, pg_want, _ = fwd(*args[:2], pages, *args[3:], None, packed)
        got, pg_got, _ = fwd(*args[:2], pages, *args[3:], _Kernels(), packed)
    live = np.asarray(new) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=3e-5)
    for name in pg_want:
        # (a later layer's keys are computed from an earlier layer's
        # attention: equal to rounding, not to the bit)
        np.testing.assert_allclose(np.asarray(pg_got[name]),
                                   np.asarray(pg_want[name]), atol=3e-5,
                                   err_msg=name)


@pytest.mark.parametrize("step", ["decode", "packed_rows_of_one_token",
                                  "packed_rows_around_a_wrapping_chunk"])
def test_a_window_layer_on_the_kernels_is_the_gathered_window_slot_for_slot(
        filled, step):
    """ONE window layer (``window_block``) over rings that earlier steps
    filled, on the kernels (interpret mode: ``mla_window`` for the chunk,
    ``mla_window_rows`` for the rows of one token, laid in place) and in
    the gathered form: the same stream in EVERY slot and the same ring.
    The slots of no token - a pad slot, the slot of a row of length 0 -
    get no attention output from either form: their stream comes back as
    it went in, bit for bit."""
    from dynamo_tpu.models.llama import packed_rows

    cfg, pages, table, have, _fwd, _rng, params = filled
    wcfg = cfg.window_cfg()
    new, S = STEPS[step]
    packed = S is None
    B, S = (1, 48) if packed else (len(new), S)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((B, S, cfg.hidden_size)) * 0.5,
                    jnp.float32)
    pos = np.zeros((B, S), np.int32)
    token = np.zeros(B * S, bool)
    s = 0
    for r, n in enumerate(new):
        at = slice(s, s + n) if packed else slice(r * S, r * S + n)
        pos.reshape(-1)[at] = have[r] + np.arange(n)
        token[at] = True
        s += n
    new = jnp.asarray(new, jnp.int32)
    total = jnp.asarray(have, jnp.int32) + new
    lp = jax.tree_util.tree_map(lambda v: v[0, 1], params["layers"]["win"])

    def block(kernel):
        st = dots3.Step(jnp.zeros((B, S), jnp.int32), jnp.asarray(pos),
                        jnp.asarray(table[:, :-1]), total, new,
                        jnp.asarray(table[:, -1]), packed_rows(packed, new),
                        kernel)
        with jax.default_matmul_precision("highest"):
            out, cache = dots3.window_block(wcfg, lp, h, pages, 1, st)
        return np.asarray(out).reshape(B * S, -1), np.asarray(cache["win"])

    (want, ring_want), (got, ring_got) = block(False), block(True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(ring_got, ring_want)
    assert np.abs(want[token] - np.asarray(h).reshape(B * S, -1)[token]
                  ).max() > 1e-3
    assert (~token).any()
    for out in (got, want):
        np.testing.assert_array_equal(
            out[~token], np.asarray(h).reshape(B * S, -1)[~token])


@pytest.mark.parametrize("nh,T", [(4, 48), (64, 44)],
                         ids=["one_block", "three_blocks_and_a_pad"])
def test_the_masked_kernel_under_a_causal_bias_is_the_ragged_oracle(nh, T):
    """``mla_ragged`` with a bias takes its queries as TWO operands,
    heads-major (``[nh, T, dkv]`` and ``[nh, T, dr]``: no stack, the
    rotary half padded to lanes inside), and gives its output heads-major:
    with the causal mask written out as the bias it is
    ``models.deepseek.mla_ragged_attention`` transposed - chunk rows, a
    one-token row and a row of no token, over one query block and over
    three with a padded tail."""
    from dynamo_tpu.models.deepseek import _mla_scale, mla_ragged_attention
    from dynamo_tpu.ops.pallas.mla_ragged import mla_masked_attention_packed

    _hf, cfg, _params = _family(**KERNEL)
    wcfg = cfg.window_cfg()
    dkv, dr, ps, P = wcfg.kv_lora_rank, wcfg.qk_rope_head_dim, 8, 40
    assert dkv == 256 and dr < 128
    rng = np.random.default_rng(13)
    new = np.asarray([20, 0, 1, 17])
    total = np.asarray([300, 0, 150, 17])
    starts = np.cumsum(new) - new
    pool = jnp.asarray(rng.standard_normal((2, 4 * P + 1, 2, 1, ps, dkv)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(4 * P).reshape(4, P), jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((T, nh, dkv)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((T, nh, dr)), jnp.float32)
    bias = np.full((T, P * ps), indexer.NEG_INF, np.float32)
    for r in range(4):
        for i in range(new[r]):
            bias[starts[r] + i, :total[r] - new[r] + i + 1] = 0.0
    rows = (table, jnp.asarray(starts), jnp.asarray(new), jnp.asarray(total))
    with jax.default_matmul_precision("highest"):
        want = mla_ragged_attention(wcfg, q_lat, q_pe, pool, 1, *rows)
        got = mla_masked_attention_packed(
            q_lat.swapaxes(0, 1), q_pe.swapaxes(0, 1), pool, 1, *rows,
            jnp.asarray(bias), _mla_scale(wcfg), interpret=True,
            name="mla_window")
    assert got.shape == (nh, T, dkv) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got).swapaxes(0, 1),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[:, int(new.sum()):].any()


# (topk 24 over a table of 16 pages of 8: a context and whether every index
# key but ten is the same key)
@pytest.mark.parametrize("ctx,tied", [
    (90, True), (24, False), (25, False), (17, False), (128, False)], ids=[
    "a_tie_at_the_cut", "exactly_topk", "topk_plus_one",
    "shorter_than_topk", "the_tables_last_position"])
def test_the_masked_one_token_form_is_the_gather_over_the_sorted_list(ctx,
                                                                      tied):
    """A packed step of a chunk row and two one-token rows over the same
    pools: the one-token rows' bias (``select_split``) opens exactly the
    positions of the parent's sorted list (``select``) where ``live``, row
    for row, and the latent decode kernel over that bias (interpret mode)
    gives what ``sparse_attend`` gives over the list; the chunk row enters
    the kernel with length 0 and comes back zero. The case's row: a tie of
    scores that straddles the ``topk``-th place (the lowest positions of
    the tied win), a context of exactly ``topk``, of one more, of fewer
    (the selection is the whole context), and a row whose query sits at the
    table's last position."""
    from dynamo_tpu.ops.pallas.mla_decode_masked import (
        mla_masked_decode_stacked)

    rng = np.random.default_rng(11)
    K, J, D, nh, dkv, dr, ps, P = 24, 4, 32, 4, 128, 64, 8, 16
    S = P * ps
    new, total = [17, 1, 1], [60, ctx, 77]
    R = len(new)
    keys = rng.standard_normal((R, S, D)).astype(np.float32)
    rows, N = _step(None, new, total)
    q = rng.standard_normal((N, J, D)).astype(np.float32)
    w = np.abs(rng.standard_normal((N, J))).astype(np.float32)
    if tied:
        # small whole numbers, so that equal keys score EQUAL whatever the
        # order of the sums: ten keys that score higher, each by its own
        # factor, every other key the same - K - 10 of those are taken,
        # the lowest positions
        q[17] = rng.integers(-3, 4, (J, D))
        w[17] = rng.integers(1, 4, J)
        keys[1, :] = q[17, 0]
        high = rng.choice(ctx, 10, replace=False)
        keys[1, high] *= (2 + np.arange(10))[:, None]
    index = np.zeros((2, R * P + 1, ps, D), np.float32)
    index[1, 1:] = keys.reshape(R * P, ps, D)
    latent = jnp.asarray(rng.standard_normal((2, R * P + 1, 2, 1, ps, dkv)),
                         jnp.float32)
    table = jnp.asarray(1 + np.arange(R * P).reshape(R, P), jnp.int32)
    q, w = jnp.asarray(q), jnp.asarray(w)
    q_lat = jnp.asarray(rng.standard_normal((N, nh, dkv)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((N, nh, dr)), jnp.float32)
    total = jnp.asarray(total, jnp.int32)
    kw = dict(width=N, packed=True)
    with jax.default_matmul_precision("highest"):
        sel, live = indexer.select(q, w, jnp.asarray(index), 1, table, rows,
                              total, K, **kw)
        one, _bias = indexer.select_split(q, w, jnp.asarray(index), 1, table,
                                     rows, total, K, **kw)
        rows_bias, to = one
        first = jnp.clip(rows.start, 0, N - 1)
        got = mla_masked_decode_stacked(
            q_lat[first], q_pe[first], latent, 1, table,
            jnp.where(rows.new == 1, total, 0), rows_bias, 0.13,
            interpret=True, name="mla_selected_rows")
        want = sl.sparse_attend(q_lat[first], q_pe[first], latent, 1, table,
                                sel[first], live[first], 0.13)
    assert np.asarray(to).tolist() == [N, 17, 18]
    assert not np.asarray(got[0]).any()
    for r in (1, 2):
        slot = int(rows.start[r])
        listed = sorted(np.asarray(sel[slot])[np.asarray(live[slot])])
        assert np.flatnonzero(np.asarray(rows_bias[r]) == 0).tolist() \
            == listed
        assert len(listed) == min(K, int(total[r]))
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want[r]),
                                   atol=2e-5, rtol=2e-5)
    if tied:
        listed = set(np.flatnonzero(np.asarray(rows_bias[1]) == 0).tolist())
        rest = sorted(set(range(ctx)) - set(high.tolist()))
        assert listed == set(high.tolist()) | set(rest[:K - 10])


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("attn_impl", ["pallas", "scan"])
def test_no_step_program_on_the_kernels_sorts_the_tables_width(attn_impl):
    """The tiny configuration's step programs as the engine jits them -
    the prefill-carrying step (token-packed on the kernels) and the fused
    block: on the kernel path no ``sort`` has an operand as wide as the
    page table's tokens (the selection stays a mask; the expert layer's
    sorts of a step's picks are shorter), on the XLA path the one-token
    rows' list is sorted out of exactly that width."""
    from dynamo_tpu.engine.program_check import step_programs

    _hf, cfg, params = _family(**(KERNEL if attn_impl == "pallas" else {}))
    # (a table of 384 tokens: no other axis of the tiny model is as long)
    eng = _engine(cfg, params, attn_impl=attn_impl, max_context=384)
    assert eng.one_token_form == {"pallas": "masked",
                                  "scan": "gathered"}[attn_impl]
    S = eng.cfg.max_context
    programs = step_programs(eng, 4, 64, width=4)
    mixed = "packed" if attn_impl == "pallas" else "mixed"
    for name in (mixed, "fused"):
        fn, args = programs[name]
        wide = [e for e in _eqns(fn.trace(*args).jaxpr)
                if e.primitive.name == "sort"
                and S in e.invars[0].aval.shape]
        assert bool(wide) == (attn_impl == "scan"), (name, wide)


@pytest.mark.parametrize("nh,dkv,ctx", [(4, 128, (1100, 700)),
                                         (2, 256, (520, 1280))])
def test_the_masked_kernel_walks_chunks_of_32_pages(nh, dkv, ctx):
    """``mla_ragged`` with a bias over tables of 80 pages of 16: chunks of
    32 pages (512 keys), the last one partial, two chunk rows of different
    contexts and a one-token row the kernel is told nothing of - against
    the softmax over the bias's open keys written out."""
    from dynamo_tpu.ops.pallas.mla_ragged import (
        BIASED_PAGES_PER_CHUNK, mla_masked_attention_packed)

    rng = np.random.default_rng(7)
    ps, P, dr, T = 16, 80, 64, 48
    assert P > 2 * BIASED_PAGES_PER_CHUNK and P % BIASED_PAGES_PER_CHUNK
    S = P * ps
    new = np.asarray([24, 17, 1])
    total = np.asarray(list(ctx) + [300])
    starts = np.cumsum(new) - new
    pool = jnp.asarray(rng.standard_normal((2, 3 * P + 1, 2, 1, ps, dkv)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(3 * P).reshape(3, P), jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((T, nh, dkv)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((T, nh, dr)), jnp.float32)
    # each slot of a chunk row sees a random third of what lies before it
    bias = np.full((T, S), indexer.NEG_INF, np.float32)
    row_of = np.full(T, -1)
    for r in range(2):
        for i in range(new[r]):
            t, pos = starts[r] + i, total[r] - new[r] + i
            seen = rng.random(pos + 1) < 0.3
            seen[pos] = True
            bias[t, :pos + 1][seen] = 0.0
            row_of[t] = r
    with jax.default_matmul_precision("highest"):
        got = mla_masked_attention_packed(
            q_lat.swapaxes(0, 1), q_pe.swapaxes(0, 1), pool, 1, table,
            jnp.asarray(starts), jnp.asarray(np.where(new > 1, new, 0)),
            jnp.asarray(total), jnp.asarray(bias), 0.11, interpret=True,
            name="mla_selected")
    assert got.shape == (nh, T, dkv)
    got = np.asarray(got).swapaxes(0, 1)
    flat = np.asarray(pool)[1]
    for t in range(T):
        if row_of[t] < 0:
            assert not got[t].any()
            continue
        rows = flat[np.asarray(table)[row_of[t]]]        # [P, 2, 1, ps, dkv]
        c = rows[:, 0, 0].reshape(S, dkv)
        kr = rows[:, 1, 0].reshape(S, dkv)[:, :dr]
        s = (np.asarray(q_lat)[t] @ c.T + np.asarray(q_pe)[t] @ kr.T) * 0.11
        s = np.where(bias[t] == 0.0, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ c
        np.testing.assert_allclose(got[t], want, atol=2e-4, rtol=2e-4)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """At ``ep_size`` 4 the four ranks' routed parts, and the shared expert
    counted once, sum to the reference's sparse block over all eight
    experts (the guide's section 4)."""
    hf, cfg, _ = tiny
    rng = np.random.default_rng(4)
    H, E, Im = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    assert (E, cfg.ep_size, cfg.experts_held) == (8, 4, 2)
    full = {"mlp_norm": jnp.ones((H,)),
            "w_router": jnp.asarray(rng.standard_normal((H, E)) * 0.3,
                                    jnp.float32),
            "router_bias": jnp.asarray(rng.standard_normal(E) * 0.1,
                                       jnp.float32)}
    for leaf, shape in (("w_gate", (E, H, Im)), ("w_up", (E, H, Im)),
                        ("w_down", (E, Im, H)), ("ws_gate", (H, Im)),
                        ("ws_up", (H, Im)), ("ws_down", (Im, H))):
        full[leaf] = jnp.asarray(rng.standard_normal(shape) * 0.2,
                                 jnp.float32)
    h = jnp.asarray(rng.standard_normal((1, 12, H)), jnp.float32)
    whole = dict(hf, n_routed_experts=E, ep_size=1)
    whole.pop("ep_rank")
    with jax.default_matmul_precision("highest"):
        want = REF.sparse_block(whole, full, h[0]) - h[0]
        x = REF.norm(h, full["mlp_norm"], hf["rms_norm_eps"])
        shared = REF.swiglu(x[0], full["ws_gate"], full["ws_up"],
                            full["ws_down"])
        parts = 0
        for rank in range(4):
            rcfg = ModelConfig.from_hf(dict(hf, ep_rank=rank),
                                       dtype="float32")
            lp = dict(full, **{k: full[k][2 * rank:2 * rank + 2]
                               for k in ("w_gate", "w_up", "w_down")})
            out, aux = dots3.sparse_block(rcfg, lp, x)
            parts = parts + (out[0] - shared)
            ref = REF.sparse_block(dict(hf, ep_rank=rank), lp, h[0]) - h[0]
            np.testing.assert_allclose(out[0], ref, atol=1e-5)
            assert int(aux["moe_assignments"]) == 12 * 3
    np.testing.assert_allclose(parts + shared, want, atol=1e-5)


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
    logits = _reference_logits(hf, params, list(prompt) + list(served))
    want = jnp.argmax(logits[len(prompt) - 1:-1], axis=-1)
    return np.asarray(want).tolist() == list(served)


@pytest.mark.async_timeout(240)
@pytest.mark.parametrize("attn_impl", ["scan", "pallas"])
async def test_the_served_path_gives_the_references_greedy_tokens(attn_impl):
    """Five requests on four rows and four window slots - prompts of 5 to
    150 tokens computed in chunks of at most 70 beside the rows that
    decode, fused blocks and blocks chained behind a mixed step - stream
    the reference's greedy continuation, token for token: padded steps on
    the XLA path, and the token-packed step with the masked forms of the
    latent kernels in interpret mode (latents of 128 for their tiles);
    the one-token rows are counted under the form they ran in."""
    from dynamo_tpu.worker.metrics import engine_dispatch_stats

    hf, cfg, params = _family(**(KERNEL if attn_impl == "pallas" else {}))
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
        assert sched.prefix_reuse_refused == {"window_cache": 5}
        assert eng.multistep_blocks > 0
        form = "packed" if attn_impl == "pallas" else "padded:attn_impl"
        assert set(eng.prefill_steps) == {form}
        assert eng.allocator.hits == 0 and not eng.allocator._by_hash
        ring = [r for r in eng.steptrace.snapshot(limit=4096)["records"]
                if r["state_rows"]]
        assert ring and all(r["gdn_tokens"] == r["gdn_step_rows"] == 0
                            and 0 < r["selected_keys"] <= r["score_pairs"]
                            for r in ring)
        # a chunk of 70 from position 70 on sees 70 x (70 + 140 + 1) / 2
        # keys and attends the selection's 24 of each
        assert any(r["selected_keys"] < r["score_pairs"] for r in ring)
        stats = engine_dispatch_stats(eng)
        # (the ring may hold an earlier engine's records of this process:
        # the counters are this engine's)
        assert 0 < stats["attn_selected_keys"] < stats["attn_visible_keys"]
        assert stats["attn_visible_keys"] <= sum(
            r["score_pairs"] for r in ring)
        assert stats["state_slots_in_use"] == 0.0
        assert set(stats["cache_bytes"]) == {"paged", "index", "window"}
        # every one-token row (a decode row a step, a block's rows times
        # its width) attended its selection in the path's one form: masked
        # on the kernels, gathered - and none masked - on the reference path
        form = {"pallas": "masked", "scan": "gathered"}[attn_impl]
        assert eng.one_token_form == form
        assert set(stats["attn_one_token_rows"]) == {form}
        assert stats["attn_one_token_rows"][form] > eng.multistep_blocks > 0
        assert eng.packed_attention == (
            "chunks:mla_selected,one_token:mla_selected_rows"
            if attn_impl == "pallas" else None)
    finally:
        await eng.stop()


async def test_a_slot_is_reused_and_a_preempted_row_recomputes(tiny):
    """One slot: request B behind request A must not read what A left in
    the ring. B equals itself on a fresh engine; a row preempted after
    some tokens gives slot and pages back, is recomputed from token 0 and
    streams what it streams uninterrupted."""
    _hf, cfg, params = tiny
    rng = np.random.default_rng(5)
    a, b = (rng.integers(0, 512, n).tolist() for n in (150, 75))
    fresh = _engine(cfg, params, max_num_seqs=1)
    try:
        want, _ = await _collect(fresh, _req(b, "b", 24))
    finally:
        await fresh.stop()
    eng = _engine(cfg, params, max_num_seqs=1)
    try:
        await _collect(eng, _req(a, "a", 8))
        got, frames = await _collect(eng, _req(b, "b", 24))
        assert got == want and not frames[-1].cached_tokens
        task = asyncio.ensure_future(_collect(eng, _req(b, "b2", 24)))
        sched = eng.scheduler
        while not any(len(s.generated) >= 6 for s in sched.active.values()):
            assert not task.done()
            await asyncio.sleep(0.01)
        assert await eng.run_exclusive(sched._preempt_one)
        again, frames = await task
        assert again == want and sched.num_preemptions == 1
        assert not frames[-1].cached_tokens
        assert sched._free_slots == [1]
        assert sched.prefix_reuse_refused == {"window_cache": 4}
    finally:
        await eng.stop()


def test_the_one_token_rows_are_counted_by_the_engines_form(tiny):
    """``dynamo_worker_attn_one_token_rows_total{form}`` renders both
    forms pre-seeded; an engine counts a step's one-token rows and a
    block's rows times its width under its own form - ``gathered`` off the
    kernels - and an engine of a model without a selection counts
    nothing."""
    from prometheus_client import CollectorRegistry, generate_latest

    from dynamo_tpu.worker.metrics import (EngineDispatchCollector,
                                           engine_dispatch_stats)
    _hf, cfg, params = tiny
    eng = _engine(cfg, params)
    assert eng.one_token_form == "gathered"
    eng._count_one_token_rows(3)
    eng._count_one_token_rows(4 * 2)
    reg = CollectorRegistry()
    EngineDispatchCollector(reg).attach(lambda: engine_dispatch_stats(eng))
    text = generate_latest(reg).decode()
    name = "dynamo_worker_attn_one_token_rows_total"
    assert f'{name}{{form="gathered"}} 11.0' in text
    assert f'{name}{{form="masked"}} 0.0' in text
    eng.one_token_form = None             # what every other family's reads
    eng._count_one_token_rows(5)
    assert eng.attn_one_token_rows == {"gathered": 11}


def test_the_worker_refuses_at_its_arguments_and_names_the_caches(tmp_path):
    """``--state-slots`` sizes the rings; ``--disagg`` and the tiers end
    the worker at its arguments, by the family's name; ``startup.engine``
    names the three pools."""
    from dynamo_tpu.utils.tracing import StartupTrace
    from dynamo_tpu.worker import main as worker_main

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import modeldir
    model = modeldir.write_model_dir(str(tmp_path / "m"), _config(tiny=True))
    base = ["--model-path", model, "--random-weights", "--dtype", "float32",
            "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
            "--max-context", "128", "--state-slots", "3",
            "--max-prefill-chunk", "32"]
    parser = worker_main.build_parser()
    for extra, names in ((["--disagg", "prefill"], "--disagg"),
                         (["--host-cache-bytes", "1024"], "host and disk")):
        with pytest.raises(NotImplementedError, match="window cache") as e:
            worker_main.build_engine(parser.parse_args(base + extra))
        assert names in str(e.value)
    startup = StartupTrace()
    eng = worker_main.build_engine(parser.parse_args(base), startup)
    assert eng.state_slots == 3 and eng.scheduler._free_slots == [3, 2, 1]
    attrs = [st[3] for st in startup.stages
             if st[0] == "startup.engine"][0]
    assert attrs["cache.kinds"] == (
        "paged[L=2,Hkv=1,Dh=32]+index[L=2,D=16]+window[L=3,S=3,R=128,D=48]")
    assert "linear_attention" not in attrs
    assert attrs["moe.experts"] == "grouped[E=8,k=3][held=0+2]"
