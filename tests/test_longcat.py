"""LongCat-Flash through its own family (``models/longcat.py``): the
published config loads by its own key names; prefill then decode through the
paged latent cache - padded and token-packed, on the XLA path and through the
Pallas kernels (interpret mode here) - agrees with the plain reference's full
forward pass (``benchmarks/reference/longcat.py``) on logits at toy widths;
the grouped expert layer told which experts it holds equals the mask form;
and the shares of an expert-parallel layer add up to the uncut layer."""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import get_family, longcat
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import make_pages
from dynamo_tpu.models.moe import grouped_experts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "longcat-flash-omni.json")


def _config(tiny: bool, **over):
    with open(CONFIG) as f:
        hf = json.load(f)
    bench = hf.pop("benchmark")
    if tiny:
        hf.update(bench["tiny"]["config"])
    hf.update(over)
    return hf


def _reference():
    spec = importlib.util.spec_from_file_location(
        "ref_longcat", os.path.join(REPO, "benchmarks", "reference",
                                    "longcat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_from_hf_reads_the_published_config_by_its_own_keys():
    """No key is renamed in the file: ``num_layers``, ``ffn_hidden_size``,
    ``expert_ffn_hidden_size``, ``moe_topk``. The router's 512 + 256 come
    back from 16 held x 32 ranks + the zero-compute experts."""
    hf = _config(tiny=False)
    assert "num_hidden_layers" not in hf and "intermediate_size" not in hf
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is longcat
    assert (cfg.num_layers, cfg.attn_blocks_per_layer,
            cfg.num_cache_layers) == (4, 2, 8)
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (6144, 12288, 2048)
    assert (cfg.num_experts, cfg.zero_expert_num, cfg.zero_expert_type,
            cfg.num_experts_per_tok) == (512, 256, "identity", 12)
    assert (cfg.ep_size, cfg.ep_rank, cfg.experts_held,
            cfg.expert_offset) == (32, 0, 16, 0)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64,
                                                      128)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 1, 512)
    assert cfg.mla_q_scale == 2.0
    assert cfg.mla_kv_scale == pytest.approx(12 ** 0.5)
    assert cfg.routed_scaling_factor == 6.0 and not cfg.norm_topk_prob
    assert cfg.rope_theta == 1e7 and cfg.rope_interleave
    assert cfg.rope_scaling_factor == 0.0 and cfg.rms_norm_eps == 1e-5
    assert cfg.vocab_size == 16384 and not cfg.tie_word_embeddings
    assert cfg.num_expert_layers == 4 and cfg.first_k_dense_replace == 0
    # by count: ISSUE 41's arithmetic, 5.17 B parameters in the cut served
    shapes = jax.eval_shape(
        lambda: longcat.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(shapes))
    assert 5.17e9 < n < 5.18e9
    assert shapes["layers"]["w_router"].shape == (4, 6144, 768)
    assert shapes["layers"]["w_gate"].shape == (4, 16, 6144, 2048)
    # without ep_rank the file is a published one: every expert is held
    whole = ModelConfig.from_hf({k: v for k, v in hf.items()
                                 if k != "ep_rank"})
    assert (whole.num_experts, whole.experts_held, whole.ep_size) == (
        16, 16, 1)
    # the other families' cache layers are their layers
    assert ModelConfig.tiny().num_cache_layers == 2
    assert make_pages(ModelConfig.tiny(), 3, 4).shape[0] == 2
    assert make_pages(ModelConfig.from_hf(_config(tiny=True)), 3,
                      4).shape == (4, 3, 2, 1, 4, 32)


@pytest.mark.parametrize("keys,error,names", [
    ({"zero_expert_type": "copy"}, NotImplementedError, "zero_expert_type"),
    ({"attention_method": "MHA"}, NotImplementedError, "attention_method"),
    ({"ep_rank": 4}, ValueError, "ep_rank"),
])
def test_a_file_the_family_cannot_serve_is_refused_by_name(keys, error,
                                                           names):
    with pytest.raises(error, match=names):
        ModelConfig.from_hf(_config(tiny=True, **keys))


def test_an_ep_size_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="ep_size 3 does not divide"):
        ModelConfig.tiny(num_experts=8, ep_size=3)
    assert ModelConfig.tiny(num_experts=8, ep_size=4,
                            ep_rank=3).expert_offset == 6


# ------------------------------------------------- served against reference


def _serve(cfg, params, tokens, split, attn_impl=None, packed=False, ps=8):
    """Logits for every position from ``split - 1`` on, the way the engine
    gets them: the prompt prefilled in two chunks into the paged latent
    cache (an empty row beside it), then one decode step per token;
    ``packed`` runs every step in the token-packed form ``[1, T]``."""
    T = len(tokens)
    P = -(-T // ps) + 1
    pages = make_pages(cfg, 2 * P + 1, ps, dtype=jnp.dtype(cfg.dtype))
    table = jnp.arange(1, 2 * P + 1, dtype=jnp.int32).reshape(2, P)
    per_token = cfg.num_experts_per_tok * cfg.num_layers
    # one program a step shape, as the engine runs them
    forward = jax.jit(lambda tok, pos, pages, total, new: longcat.forward(
        params, cfg, tok, pos, pages, table, total, new,
        attn_impl=attn_impl, packed=packed))

    def step(toks, at, pages):
        n = len(toks)
        if packed:      # row 0's tokens first, padded to 8 slots
            width = -(-n // 8) * 8
            tok = np.zeros((1, width), np.int32)
            tok[0, :n] = toks
            pos = np.zeros((1, width), np.int32)
            pos[0, :n] = np.arange(at, at + n)
        else:
            tok = np.zeros((2, n), np.int32)
            tok[0] = toks
            pos = np.tile(np.arange(at, at + n, dtype=np.int32), (2, 1))
        logits, pages, aux = forward(
            jnp.asarray(tok), jnp.asarray(pos), pages,
            jnp.asarray([at + n, 0], jnp.int32),
            jnp.asarray([n, 0], jnp.int32))
        # padding and the empty row route nowhere, identity picks included
        assert int(aux["moe_assignments"]) == n * per_token
        assert (int(aux["moe_held_assignments"])
                + int(aux["moe_zero_assignments"])) <= n * per_token
        return logits[0], pages, aux

    out, at, zero = [], 0, 0
    for n in (split // 2, split - split // 2):
        logits, pages, aux = step(tokens[at:at + n], at, pages)
        zero += int(aux["moe_zero_assignments"])
        at += n
    out.append(logits)
    for t in range(split, T):
        logits, pages, _aux = step(tokens[t:t + 1], t, pages)
        out.append(logits)
    assert zero > 0                 # the identity experts were picked
    return jnp.stack(out).astype(jnp.float32)


_REFERENCE_FNS = {}


def _reference_fns(hf):
    """The reference's pieces, jitted a piece at a time as
    ``reference/score.py`` runs them; kept per configuration, so the tests
    that share one compile them once."""
    key = json.dumps(hf, sort_keys=True)
    if key not in _REFERENCE_FNS:
        ref = _reference()
        _REFERENCE_FNS[key] = (ref, {
            kind: jax.jit(lambda w, h, fn=fn: fn(hf, w, h))
            for kind, fn in ref.LAYER_FNS.items()})
    return _REFERENCE_FNS[key]


def _reference_logits(hf, params, tokens, pieces=False):
    ref, fns = _reference_fns(hf)
    f32 = jnp.float32
    walk = ref.layers(params)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for kind, stack, n in (walk if pieces else list.__iter__(walk)):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(f32), stack)
                h = fns[kind](w, h)
        return ref.head(hf, params, h)


# float32 against float32: absorbed attention over a paged cache and the
# grouped experts against the plain forward differ by summation order
# alone. Logits here are of order 0.1; 2e-4 absolute is what the harness's
# own check of the references allows on log-probabilities (the same path in
# bfloat16 is off by more than ten times that: ``tests/test_joyai.py``
# asserts it for the shared attention and expert code).
TOL = 2e-4


@pytest.mark.parametrize("path,packed", [
    ("xla", False), ("xla", True), ("pallas", False), ("pallas", True)])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(path,
                                                                 packed):
    over = {}
    if path == "pallas":
        # the kernels tile 128 lanes: the latent and the widths at their
        # smallest aligned sizes, everything else as in the tiny block
        over = dict(kv_lora_rank=128, hidden_size=256,
                    expert_ffn_hidden_size=128)
    hf = _config(tiny=True, **over)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    assert cfg.mla_q_scale != 1.0 and cfg.mla_kv_scale != 1.0
    assert (cfg.num_experts, cfg.experts_held, cfg.zero_expert_num) == (
        16, 4, 8)
    params = longcat.init_params(cfg, jax.random.PRNGKey(0))
    # a correction bias that matters: it moves the choice, not the weights
    params["layers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["layers"]["router_bias"].shape)
    tokens = np.random.default_rng(7).integers(
        0, hf["vocab_size"], size=25).tolist()
    split = 20              # two prefill chunks of one shape, five decodes
    attn = None
    if path == "pallas":
        from dynamo_tpu.ops.pallas.decode import (
            paged_decode_attention_stacked)
        attn = paged_decode_attention_stacked       # the marker, as served
    with jax.default_matmul_precision("highest"):
        served = _serve(cfg, params, tokens, split, attn_impl=attn,
                        packed=packed)
    want = _reference_logits(hf, params, tokens)[split - 1:]
    np.testing.assert_allclose(np.asarray(served), np.asarray(want),
                               atol=TOL, rtol=0)


def test_the_comparison_sees_a_latent_scale_left_out():
    """One of the faults ISSUE 41 plants on the chip, at toy size:
    ``mla_scale_kv_lora`` left out moves the logits far past ``TOL`` (the
    held range off by one is planted in ``tests/benchmarks/
    test_longcat_cell.py``'s tiny run, the identity term's absence shows in
    the mask-form test below)."""
    hf = _config(tiny=True)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = longcat.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(7).integers(
        0, hf["vocab_size"], size=16).tolist()
    want = _reference_logits(hf, params, tokens)[-1]
    faulty = dataclasses.replace(cfg, mla_kv_scale=1.0)
    with jax.default_matmul_precision("highest"):
        got = _serve(faulty, params, tokens, 16)[-1]
    assert float(jnp.max(jnp.abs(got - want))) > 50 * TOL


def test_the_reference_streams_a_layer_in_pieces():
    """Iterated, ``layers()`` yields a layer as open / experts / mid /
    close (so that the chip never holds one whole in float32); indexed, the
    layer whole. Both are the same function."""
    hf = _config(tiny=True, n_routed_experts=6)   # two blocks, one short
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = longcat.init_params(cfg, jax.random.PRNGKey(1))
    ref = _reference()
    assert [k for k, _s, _n in ref.layers(params)] == [
        "open", "experts", "mid", "close"] * 2
    assert [(k, n) for k, _s, n in list.__iter__(ref.layers(params))] == [
        ("layer", 2)]
    tokens = list(range(40, 49))
    whole = _reference_logits(hf, params, tokens)
    pieces = _reference_logits(hf, params, tokens, pieces=True)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(pieces),
                               atol=1e-6, rtol=0)


# --------------------------------------------------- the grouped layer's share


def _mask_form(xt, top_w, top_i, w_gate, w_up, w_down, first, routed,
               valid):
    """Every held expert on every token, weighted by the gate's column,
    plus the identity picks' term: the plain form of one rank's share."""
    T, H = xt.shape
    E = w_gate.shape[0]
    x = np.asarray(xt, np.float64)
    out = np.zeros((T, H))
    for t in range(T):
        if not valid[t]:
            continue
        for w, e in zip(np.asarray(top_w[t], np.float64),
                        np.asarray(top_i[t])):
            if e >= routed:
                out[t] += w * x[t]
            elif first <= e < first + E:
                g = np.asarray(w_gate[e - first], np.float64)
                u = np.asarray(w_up[e - first], np.float64)
                d = np.asarray(w_down[e - first], np.float64)
                a = x[t] @ g
                out[t] += w * (((a / (1 + np.exp(-a))) * (x[t] @ u)) @ d)
    return out


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("first,held,routed,zero", [
    (0, 4, 16, 8),        # rank 0 of 4, zero-compute experts behind
    (8, 4, 16, 8),        # rank 2 of 4
    (0, 16, 16, 8),       # every expert held, zero-compute experts
    (4, 4, 16, 0),        # a held range and no zero-compute expert
])
def test_grouped_layer_with_a_held_range_equals_the_mask_form(
        kernel, first, held, routed, zero):
    T, H, I, k = 24, 128, 128, 3
    keys = iter(jax.random.split(jax.random.PRNGKey(first + held), 8))
    xt = jax.random.normal(next(keys), (T, H))
    w_gate = 0.1 * jax.random.normal(next(keys), (held, H, I))
    w_up = 0.1 * jax.random.normal(next(keys), (held, H, I))
    w_down = 0.1 * jax.random.normal(next(keys), (held, I, H))
    p = jax.nn.softmax(jax.random.normal(next(keys), (T, routed + zero)))
    top_w, top_i = jax.lax.top_k(p, k)
    valid = jnp.arange(T) % 7 != 3
    with jax.default_matmul_precision("highest"):
        out, aux = grouped_experts(
            xt, top_w * 6.0, top_i, w_gate, w_up, w_down, valid=valid,
            use_pallas=kernel, first_expert=first, num_routed=routed)
    want = _mask_form(xt, top_w * 6.0, top_i, w_gate, w_up, w_down, first,
                      routed, np.asarray(valid))
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-4, rtol=0)
    assert not np.asarray(out)[~np.asarray(valid)].any()
    live = np.asarray(top_i)[np.asarray(valid)]
    here = (live >= first) & (live < first + held)
    assert int(aux["moe_assignments"]) == live.size
    assert int(aux["moe_held_assignments"]) == int(here.sum())
    assert int(aux["moe_zero_assignments"]) == int((live >= routed).sum())
    assert int(aux["moe_experts_touched"]) == len(set(live[here]))


def test_the_whole_layer_counts_as_before():
    """A family that holds every expert and has no zero-compute ones (the
    other two cells' configurations) passes through unchanged arithmetic:
    the same output as with the range spelled out, and ``moe_assignments``
    is what it computed."""
    T, H, I, E, k = 16, 64, 32, 8, 2
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    xt = jax.random.normal(next(keys), (T, H))
    ws = [0.1 * jax.random.normal(next(keys), s)
          for s in ((E, H, I), (E, H, I), (E, I, H))]
    top_w, top_i = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(next(keys), (T, E))), k)
    valid = jnp.arange(T) < 13
    out, aux = grouped_experts(xt, top_w, top_i, *ws, valid=valid)
    spelled, aux2 = grouped_experts(xt, top_w, top_i, *ws, valid=valid,
                                    first_expert=0, num_routed=E)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(spelled))
    assert int(aux["moe_assignments"]) == 13 * k == int(
        aux["moe_held_assignments"]) == int(aux2["moe_assignments"])
    assert int(aux["moe_zero_assignments"]) == 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Over ``ep_size`` 4 at toy size: the four ranks' expert-branch
    outputs, the identity term counted once, sum to what the uncut layer's
    branch gives (the program's with every expert held, and the plain
    reference's)."""
    hf = _config(tiny=True, n_routed_experts=16)
    del hf["ep_rank"], hf["ep_size"]
    whole = ModelConfig.from_hf(hf, dtype="float32")
    assert (whole.num_experts, whole.experts_held, whole.ep_size) == (
        16, 16, 1)
    params = longcat.init_params(whole, jax.random.PRNGKey(2))
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.hidden_size))
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    def every_share(lp, x):
        """The uncut branch, then each rank's: one program."""
        out = [longcat.expert_branch(whole, lp, x)]
        for rank in range(4):
            cfg = dataclasses.replace(whole, ep_size=4, ep_rank=rank)
            out.append(longcat.expert_branch(cfg, {
                k: (v[4 * rank:4 * rank + 4]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}, x))
        return out

    with jax.default_matmul_precision("highest"):
        (uncut, aux), *shares = jax.jit(every_share)(lp, x)
        # what every rank computes alike: the identity picks' term
        top_w, top_i = longcat._gate(whole, lp, x.reshape(18, -1))
        identity = (jnp.where(top_i >= 16, top_w, 0.0).sum(-1)[:, None]
                    * x.reshape(18, -1)).reshape(x.shape)
        total = identity
        held = 0
        for share, a in shares:
            total = total + (share - identity)
            held += int(a["moe_held_assignments"])
            assert int(a["moe_zero_assignments"]) == int(
                aux["moe_zero_assignments"])
            assert int(a["moe_assignments"]) == 18 * 3
        ref = _reference()
        f32 = jnp.float32
        w = jax.tree_util.tree_map(lambda a: a.astype(f32), lp)
        xt = x.reshape(18, -1)
        weight = ref.gate(hf, w, xt)
        plain = jnp.sum(weight[:, 16:], -1, keepdims=True) * xt
        for e in range(16):
            plain = plain + weight[:, e:e + 1] * ref.swiglu(
                xt, {k: w[k][e] for k in ("w_gate", "w_up", "w_down")})
    assert held == int(aux["moe_held_assignments"]) > 0
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(uncut).reshape(18, -1),
                               np.asarray(plain), atol=2e-5, rtol=0)


# ----------------------------------------------- the engine and its counters


def test_the_engine_sizes_cache_and_expert_slots_by_what_it_holds():
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig

    cfg = ModelConfig.from_hf(_config(tiny=True), dtype="float32")
    params = jax.eval_shape(
        lambda: longcat.init_params(cfg, jax.random.PRNGKey(0)))
    eng = JaxEngine(cfg, params, JaxEngineConfig(
        num_pages=8, page_size=4, max_num_seqs=2, max_context=64,
        max_prefill_chunk=16))
    assert eng.pages.shape == (4, 8, 2, 1, 4, 32)    # 2 cache layers a layer
    assert eng._moe_slots_per_step == 2 * 4          # layers x experts HELD
    assert set(eng.moe_totals) >= {"moe_assignments",
                                   "moe_held_assignments",
                                   "moe_zero_assignments",
                                   "moe_experts_touched"}


def test_the_ring_and_the_collector_carry_the_three_kinds_of_pick():
    from dynamo_tpu.engine.steptrace import MOE_COUNTS, StepRecorder
    from dynamo_tpu.worker.metrics import engine_dispatch_stats

    assert MOE_COUNTS == ("moe_experts_touched", "moe_assignments",
                          "moe_held_assignments", "moe_zero_assignments")
    st = StepRecorder(capacity=4)
    rec = st.record("multistep", width=3, rows=2, experts=tuple(
        jnp.asarray(v, jnp.int32) for v in (7, 72, 5, 24)))
    assert rec.moe_assignments == 0          # device scalars until fetched
    st.note_ready(rec, 1.0, 1.0)
    got = rec.to_dict()
    assert (got["experts_touched"], got["moe_assignments"],
            got["moe_held_assignments"], got["moe_zero_assignments"]) == (
        7, 72, 5, 24)
    plain = st.record("decode", rows=1)
    st.note_ready(plain, 2.0, 2.0)
    assert plain.to_dict()["moe_zero_assignments"] == 0

    class Engine:
        def moe_counts(self):
            return {"moe_assignments": 72, "moe_held_assignments": 5,
                    "moe_zero_assignments": 24, "moe_experts_touched": 7,
                    "moe_expert_slots": 24}
    stats = engine_dispatch_stats(Engine())
    assert (stats["moe_held_assignments"], stats["moe_zero_assignments"],
            stats["moe_expert_slots"]) == (5.0, 24.0, 24.0)
