"""Olmo-Hybrid through its own family (``models/olmo_hybrid.py``): the
published config loads by its own keys and the cut counts to the issue's
parameter count; a file the loader cannot place is refused by the key's
name; the rule's two kernels (interpret mode here) hold a float64 token
scan at sizes that do not tile - 3 heads of 12 x 24, 30 heads of 96 x 192 -
at random and in the worst case for the chunk's triangular inverse (``beta``
2 on every token, no decay, keys nearly parallel); the one Gated DeltaNet
mixer gives Qwen3-Next's outputs bit for bit where ``beta`` is not doubled;
prefill in chunks then decode through pages and slots - padded and
token-packed, fused blocks, XLA path and kernels - agrees with the plain
reference (``benchmarks/reference/olmo_hybrid.py``); and the block order,
the whole-width q/k norm and the absence of rotary each move the logits
when changed."""

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
from dynamo_tpu.models import get_family, olmo_hybrid, qwen3_next
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import gdn
from dynamo_tpu.ops.pallas import gdn as gdn_kernels
from dynamo_tpu.protocols.common import (PreprocessedRequest,
                                         SamplingOptions, StopConditions)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def _config(tiny: bool, name="olmo-hybrid-7b", **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        hf = json.load(f)
    bench = hf.pop("benchmark")
    if tiny:
        hf.update(bench["tiny"]["config"])
    hf.update(over)
    return {k: v for k, v in hf.items() if v is not ...}


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmarks", *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_olmo_hybrid", "reference", "olmo_hybrid.py")


def _reference_logits(hf, params, tokens, layer_fns=None):
    """[T, V] float32: the reference's whole forward pass."""
    fns = layer_fns or REF.LAYER_FNS
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for kind, stack, n in REF.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(jnp.float32), stack)
                h = fns[kind](hf, w, h)
        return REF.head(hf, params, h)


@pytest.fixture(scope="module")
def tiny():
    """(hf, cfg, params) at toy widths that do not tile (3 heads of 24 x
    40, a group of one), float32, matrices drawn large enough that every
    mechanism moves the logits."""
    hf = _config(tiny=True)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    return hf, cfg, params


# ------------------------------------------------------------ the config

def test_from_hf_reads_the_published_config_and_the_cut_counts():
    hf = _config(tiny=False)
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is olmo_hybrid
    assert (cfg.num_layers, cfg.full_attention_interval, cfg.num_periods,
            cfg.state_layers, cfg.num_cache_layers, cfg.slot_kind) == (
                16, 4, 4, 12, 4, "recurrent_state")
    assert cfg.layer_kinds == tuple(hf["layer_types"])
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.linear_conv_dim) == (
                30, 30, 96, 192, 4, 11520)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (30, 30, 128)
    assert cfg.linear_allow_neg_eigval and cfg.rope_theta == 0.0
    assert cfg.num_experts == 0 and cfg.intermediate_size == 11008
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_word_embeddings
    assert cfg.vocab_size == 100352
    # ISSUE 51's arithmetic, by the shapes the initialiser would draw
    shapes = jax.eval_shape(
        lambda: olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0)))

    def count(tree):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 4_100_788_944
    assert count(shapes["layers"]["gdn"]) == 12 * 215_570_172
    assert count(shapes["layers"]["full"]) == 4 * 185_809_920
    assert shapes["layers"]["gdn"]["w_qkvz"].shape == (4, 3, 3840, 17280)
    assert shapes["layers"]["full"]["q_norm"].shape == (4, 3840)
    pages = jax.eval_shape(lambda: olmo_hybrid.make_pages(
        cfg, 4096, 16, state_slots=48))
    assert pages["kv"].shape == (4, 4096, 2, 30, 16, 128)
    assert pages["state"].shape == (12, 49, 30, 96, 192)
    assert pages["state"].dtype == jnp.float32      # never the served dtype
    assert pages["conv"].shape == (12, 49, 3, 11520)
    # a sequence's state, and a token of the full layers' cache
    assert 12 * 30 * 96 * 192 * 4 == 26_542_080
    assert 4 * 2 * 30 * 128 * 2 == 61_440
    # the whole model loads too, and the sparse family still does
    whole = ModelConfig.from_hf(dict(
        hf, num_hidden_layers=32, layer_types=hf["layer_types"] * 2))
    assert (whole.num_periods, whole.state_layers) == (8, 24)
    assert get_family(ModelConfig.from_hf(_config(
        tiny=False, name="qwen3-next-80b-a3b-instruct"))) is qwen3_next


@pytest.mark.parametrize("over,names", [
    ({"linear_use_gate_proj": True}, "linear_use_gate_proj"),
    ({"layer_types": ["linear_attention"] * 3 + ["full_attention"]
      + ["linear_attention"] * 4 + ["full_attention"] * 8}, "layer_types"),
    ({"layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 4
      + ["linear_attention"], "num_hidden_layers": 17}, "layer_types"),
    ({"layer_types": ["linear_attention"] * 16}, "layer_types"),
    ({"layer_types": ...}, "layer_types"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    ({"rope_parameters": ..., "rope_theta": 10000.0}, "rope_theta"),
    ({"linear_conv_kernel_dim": ...}, "linear_conv_kernel_dim"),
    ({"attention_bias": True}, "attention_bias"),
    ({"linear_num_value_heads": 45}, "linear_num_value_heads"),
])
def test_a_file_the_loader_cannot_place_is_refused_by_the_keys_name(over,
                                                                    names):
    """A file with ``linear_*`` keys and no interval goes to neither
    family silently: a ``linear_*`` key the family does not know, a
    ``layer_types`` that is not whole periods (or missing), and a rotary
    base set for a family whose attention has no positions each end at the
    key's name."""
    with pytest.raises(NotImplementedError, match=names) as e:
        ModelConfig.from_hf(_config(tiny=False, **over))
    assert "olmo_hybrid" in str(e.value)


# ------------------------------------------------- the rule and its kernels

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


def _rule_inputs(H, Dk, Dv, N, worst):
    rng = np.random.default_rng(0)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    if worst:
        # every key within a thousandth of one direction, every write a
        # reflection, nothing forgotten
        k = unit(rng.normal(size=(1, H, Dk))
                 + 1e-3 * rng.normal(size=(N, H, Dk)))
        g, b = np.zeros((N, H)), np.full((N, H), 2.0)
    else:
        k = unit(rng.normal(size=(N, H, Dk)))
        g = -np.abs(rng.normal(size=(N, H))) * 0.05
        b = rng.uniform(0.1, 1.9, size=(N, H))
    q = unit(rng.normal(size=(N, H, Dk))) * Dk ** -0.5
    return [jnp.asarray(x, jnp.float32)
            for x in (q, k, rng.normal(size=(N, H, Dv)), g, b)]


@pytest.mark.parametrize("worst", [False, True])
@pytest.mark.parametrize("H,Dk,Dv", [(3, 12, 24), (30, 96, 192)])
def test_the_kernels_hold_a_float64_scan_where_nothing_tiles(H, Dk, Dv,
                                                             worst):
    """One whole chunk of 64 tokens through ``gdn_chunk`` and a one-token
    row through ``gdn_step`` (interpret mode), heads and head sizes that are
    no multiple of any tile, against the rule iterated in float64: within
    2e-5 of the scan's largest value. At ``beta`` = 2 over parallel keys
    without decay the state grows to ~25 and the outputs to ~2-6, and a
    float32 ``solve_triangular`` (the XLA form) is itself 5e-5 off in
    absolute terms there; the inverse by powers of 16-wide blocks that the
    kernel had (PR 43) is off by 0.9-3.0 (CHANGES.md, PR 51)."""
    N, new = 65, jnp.array([64, 1])
    rows = gdn.token_rows(N, jnp.array([0, 64]), new, new, jnp.array([1, 2]))
    q, k, v, g, b = _rule_inputs(H, Dk, Dv, N, worst)
    pool = jnp.zeros((2, 3, H, Dk, Dv), jnp.float32)
    ck = gdn.chunk_plan(rows)
    live = ck.valid[..., None]
    o_ck, pool1 = gdn_kernels.gdn_chunk(
        *[jnp.where(live[..., None], a[ck.src], 0) for a in (q, k, v)],
        jnp.where(live, g[ck.src], 0.0), jnp.where(live, b[ck.src], 0.0),
        pool, 1, ck, interpret=True)
    o_st, pool2 = gdn_kernels.gdn_step(
        q[64:], k[64:], v[64:], g[64:], b[64:], pool1, 1, jnp.array([2]),
        jnp.array([True]), interpret=True)
    a = [np.asarray(x, np.float64) for x in (q, k, v, g, b)]
    zero = np.zeros((H, Dk, Dv))
    want, S = _scan(*[x[:64] for x in a], zero)
    tol = 2e-5 * max(1.0, np.abs(want).max())
    np.testing.assert_allclose(np.asarray(o_ck[0]), want, atol=tol)
    np.testing.assert_allclose(np.asarray(pool2[1, 1]), S,
                               atol=2e-5 * max(1.0, np.abs(S).max()))
    want1, S1 = _scan(*[x[64:] for x in a], zero)
    np.testing.assert_allclose(np.asarray(o_st), want1, atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool2[1, 2]), S1, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(pool2[0]), 0.0)
    if worst:
        assert np.abs(want).max() > 1.5 and np.abs(S).max() > 15.0


def test_a_geometry_the_kernels_cannot_lower_serves_on_the_plain_forms():
    assert gdn_kernels.supports(30, 30, 96, 192)       # this family
    assert gdn_kernels.supports(16, 32, 128, 128)      # Qwen3-Next
    assert gdn_kernels.supports(3, 3, 24, 40)
    assert "multiple of 8" in gdn_kernels.why_not(3, 3, 12, 24)
    assert "VMEM" in gdn_kernels.why_not(4, 8, 384, 384)
    assert "whole groups" in gdn_kernels.why_not(4, 6, 128, 128)
    # asked for the kernels at a head of 12, the rule runs without them
    q, k, v, g, b = _rule_inputs(3, 12, 24, 8, False)
    rows = gdn.token_rows(8, jnp.array([0]), jnp.array([8]), jnp.array([8]),
                          jnp.array([1]))
    pool = jnp.zeros((1, 2, 3, 12, 24), jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: gdn.gated_delta_rule(
        *a, pool, 0, rows, use_pallas=True))(q, k, v, g, b))
    assert "pallas_call" not in text
    q, k, v, g, b = _rule_inputs(3, 24, 40, 8, False)
    pool = jnp.zeros((1, 2, 3, 24, 40), jnp.float32)
    text = str(jax.make_jaxpr(lambda *a: gdn.gated_delta_rule(
        *a, pool, 0, rows, use_pallas=True))(q, k, v, g, b))
    assert "pallas_call" in text


def _mixer_as_pr43_wrote_it(cfg, lp, h, cache, gidx, rows):
    """``qwen3_next.gdn_mixer`` before the mixer was shared (PR 43's
    lines, unchanged): what ``beta`` scale 1 has to reproduce."""
    B, S, H = h.shape
    Hk, Dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    f32 = jnp.float32
    x = qwen3_next.zc_norm(h, lp["attn_norm"],
                           cfg.rms_norm_eps).reshape(B * S, H)
    qkvz = x @ lp["w_qkvz"]
    ba = jnp.dot(x, lp["w_ba"], preferred_element_type=f32)
    n_conv = cfg.linear_conv_dim
    mixed, conv = gdn.causal_conv(qkvz[:, :n_conv], lp["conv_w"],
                                  cache["conv"], gidx, rows)
    mixed = jax.nn.silu(mixed)
    z = qkvz[:, n_conv:].reshape(B * S, Hv, Dv)
    q = qwen3_next._l2norm(mixed[:, :Hk * Dk].reshape(-1, Hk, Dk)) \
        * Dk ** -0.5
    k = qwen3_next._l2norm(mixed[:, Hk * Dk:2 * Hk * Dk].reshape(-1, Hk, Dk))
    v = mixed[:, 2 * Hk * Dk:].reshape(-1, Hv, Dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, Hv:] + lp["dt_bias"].astype(f32))
    dt = h.dtype
    o, state = gdn.gated_delta_rule(
        q.astype(dt), k.astype(dt), v.astype(dt), g, beta, cache["state"],
        gidx, rows, use_pallas=False, several=S > 1)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    y = (o * jax.lax.rsqrt(var + cfg.rms_norm_eps)
         * lp["o_norm"].astype(f32) * jax.nn.silu(z.astype(f32)))
    out = y.astype(dt).reshape(B, S, Hv * Dv) @ lp["w_out"]
    return h + out, {**cache, "state": state, "conv": conv}


def test_the_shared_mixer_is_qwen3_nexts_bit_for_bit_at_beta_scale_one():
    import dataclasses
    hf = _config(tiny=True, name="qwen3-next-80b-a3b-instruct")
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    assert not cfg.linear_allow_neg_eigval
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(0), scale=0.3)
    lp = jax.tree_util.tree_map(lambda a: a[1, 2], params["layers"]["gdn"])
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(1, 96, cfg.hidden_size)), jnp.float32)
    new = jnp.array([70, 1, 25])
    rows = gdn.token_rows(96, jnp.cumsum(new) - new, new,
                          jnp.array([70, 9, 40]), jnp.array([1, 2, 3]))
    cache = qwen3_next.make_pages(cfg, 8, 4, state_slots=3)
    cache = {**cache, "state": jnp.asarray(
        rng.normal(size=cache["state"].shape), jnp.float32)}
    want, want_cache = _mixer_as_pr43_wrote_it(cfg, lp, h, cache, 4, rows)
    got, got_cache = qwen3_next.gdn_mixer(cfg, lp, h, cache, 4, rows,
                                          use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for key in ("state", "conv"):
        np.testing.assert_array_equal(np.asarray(got_cache[key]),
                                      np.asarray(want_cache[key]))
    # and the doubled beta is another model
    doubled, _ = qwen3_next.gdn_mixer(
        dataclasses.replace(cfg, linear_allow_neg_eigval=True), lp, h,
        cache, 4, rows, use_pallas=False)
    assert float(jnp.max(jnp.abs(doubled - want))) > 1e-2


def test_the_seeded_beta_spreads_over_its_range():
    """``b``'s columns are drawn at 1.5 / sqrt(hidden): on tokens of unit
    variance ``beta = 2 sigmoid(b)`` has a twentieth of its mass under 0.2
    and a twentieth over 1.8 (measured here at the published width on the
    CPU: a count, not a device metric)."""
    cfg = ModelConfig.from_hf(_config(tiny=False), dtype="float32")
    H, Hv = cfg.hidden_size, cfg.linear_num_value_heads
    w_b = jax.random.normal(jax.random.PRNGKey(1), (H, Hv)) \
        * olmo_hybrid.BETA_LOGIT_STD / H ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (400, H))
    beta = np.asarray(2.0 * jax.nn.sigmoid(x @ w_b))
    lo, mid, hi = np.quantile(beta, [0.05, 0.5, 0.95])
    assert 0.1 < lo < 0.3 and 0.9 < mid < 1.1 and 1.7 < hi < 1.9
    assert (beta > 1.0).mean() == pytest.approx(0.5, abs=0.05)


def test_the_seeded_branch_norms_and_head_gains(tiny):
    """The branch norms' weights are ``layers ** -0.5`` (with ones the stream
    grows to sqrt(1 + 2 layers), the gates saturate and a clean bfloat16 run
    read 0.31 nats at the widest on the chip, PR 51) and the query and key
    heads are drawn a quarter to four times as large in score, so that a
    q/k norm taken a head is another model than the norm over the width."""
    _hf, cfg, params = tiny
    assert olmo_hybrid.branch_norm_init(cfg) == pytest.approx(8 ** -0.5)
    for group in ("gdn", "full"):
        for key in ("mixer_norm", "ffn_norm"):
            np.testing.assert_allclose(
                np.asarray(params["layers"][group][key]), 8 ** -0.5)
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["full"]["q_norm"]), 1.0)
    wq = np.asarray(params["layers"]["full"]["wq"][0]).reshape(
        cfg.hidden_size, cfg.num_heads, cfg.head_dim)
    size = np.sqrt((wq ** 2).mean(axis=(0, 2)))
    assert size[-1] / size[0] == pytest.approx(
        olmo_hybrid.HEAD_GAIN ** 2, rel=0.2)
    big = ModelConfig.from_hf(_config(tiny=False))
    assert olmo_hybrid.branch_norm_init(big) == 0.25


# --------------------------------------------- the forward and the reference

def _table(slot, first_page, n, width=64):
    t = np.zeros((1, width + 1), np.int32)
    t[0, :n] = np.arange(first_page, first_page + n)
    t[0, -1] = slot
    return jnp.asarray(t)


def _program_logits(cfg, params, toks, cuts):
    """The family's own logits after each step of ``cuts`` (prefill in
    chunks into pages and a slot, then decode out of them)."""
    pages = olmo_hybrid.make_pages(cfg, 64, 4, state_slots=3)
    table, out = _table(2, 1, 40), []
    with jax.default_matmul_precision("highest"):
        for lo, hi in cuts:
            logits, pages = olmo_hybrid.forward(
                params, cfg, jnp.asarray(toks[None, lo:hi], jnp.int32),
                jnp.arange(lo, hi)[None], pages, table, jnp.array([hi]),
                jnp.array([hi - lo]))
            out.append(np.asarray(logits[0]))
    return out


CUTS = ((0, 70), (70, 140), (140, 149), (149, 150))


def test_chunked_prefill_then_decode_agrees_with_the_reference(tiny):
    """A prompt of 149 tokens in chunks of 70, 70 and 9 (no multiple of the
    rule's 64), then a decode step: the logits after every step are the
    reference's at that position - the state and the convolution's inputs
    were carried from step to step, and the keys sit in their pages."""
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, hf["vocab_size"], 150)
    want = _reference_logits(hf, params, toks)
    for (lo, hi), got in zip(CUTS, _program_logits(cfg, params, toks, CUTS)):
        np.testing.assert_allclose(got, np.asarray(want[hi - 1]), atol=5e-4)
    assert float(jnp.max(jnp.abs(want))) > 1.0


def _pre_norm(kind):
    """The reference's layer with each branch's norm moved in FRONT of the
    branch (the pre-norm order of Qwen3-Next and Llama)."""
    def layer(hf, w, h):
        eps, real = hf["rms_norm_eps"], REF.rms
        for norm, branch in (("mixer_norm", {"gdn": REF.gated_delta_net,
                                             "full": REF.attention}[kind]),
                             ("ffn_norm", REF.ffn)):
            x = real(h, w[norm], eps)
            REF.rms = lambda a, ww, e, at=w[norm]: (
                a if ww is at else real(a, ww, e))
            try:
                h = h + (branch(hf, w, x) - x)
            finally:
                REF.rms = real
        return h
    return layer


def _attention(per_head=False, rotary=False):
    """The reference's full-attention layer with the q/k norm taken a head
    (Qwen3's) or rotate-half rotary applied at base 10,000."""
    def layer(hf, w, h):
        T = h.shape[0]
        n, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
        d, eps = hf["hidden_size"] // n, hf["rms_norm_eps"]
        q, k = h @ w["wq"], h @ w["wk"]
        if per_head:
            q = REF.rms(q.reshape(T, n, d), w["q_norm"].reshape(n, d), eps)
            k = REF.rms(k.reshape(T, nkv, d), w["k_norm"].reshape(nkv, d),
                        eps)
        else:
            q = REF.rms(q, w["q_norm"], eps).reshape(T, n, d)
            k = REF.rms(k, w["k_norm"], eps).reshape(T, nkv, d)
        if rotary:
            inv = 10000.0 ** (-jnp.arange(d // 2) * 2.0 / d)
            ang = jnp.arange(T)[:, None] * inv[None, :]
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

            def turn(x):
                a, b = x[..., :d // 2], x[..., d // 2:]
                return jnp.concatenate([a * cos - b * sin,
                                        b * cos + a * sin], -1)
            q, k = turn(q), turn(k)
        v = (h @ w["wv"]).reshape(T, nkv, d)
        scores = jnp.einsum("tnd,snd->nts", q, k) * d ** -0.5
        t = jnp.arange(T)
        scores = jnp.where(t[None, :] <= t[:, None], scores, -jnp.inf)
        attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, -1), v)
        return REF.ffn(hf, w, h + REF.rms(
            attn.reshape(T, n * d) @ w["wo"], w["mixer_norm"], eps))
    return layer


@pytest.mark.parametrize("what", ["block_order", "per_head_qk_norm",
                                  "rotary"])
def test_each_convention_moves_the_logits_when_changed(tiny, what):
    """The three conventions the config file does not state - branches
    normed on their way out, the q/k norm over the whole width, no rotary -
    each written the other way in a copy of the reference: the program,
    which holds the reference to 5e-4 above, is then tenths of a nat to
    whole nats away, so a program changed that way fails the test above."""
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, hf["vocab_size"], 150)
    fns = {"block_order": {k: _pre_norm(k) for k in REF.LAYER_FNS},
           "per_head_qk_norm": dict(REF.LAYER_FNS,
                                    full=_attention(per_head=True)),
           "rotary": dict(REF.LAYER_FNS, full=_attention(rotary=True))}[what]
    same = dict(REF.LAYER_FNS, full=_attention())
    want = _reference_logits(hf, params, toks)
    np.testing.assert_allclose(
        np.asarray(_reference_logits(hf, params, toks, same)),
        np.asarray(want), atol=1e-5)            # the copy is a true copy
    other = _reference_logits(hf, params, toks, fns)
    got = _program_logits(cfg, params, toks, CUTS[:1])[0]
    assert float(np.max(np.abs(got - np.asarray(other[69])))) > 0.1
    assert float(jnp.max(jnp.abs(other - want))) > 0.1


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
    pages = olmo_hybrid.make_pages(cfg, 128, 4, state_slots=4)
    rows = [_table(1, 1, 40), _table(3, 60, 20), _table(2, 90, 38),
            jnp.zeros((1, 65), jnp.int32)]
    with jax.default_matmul_precision("highest"):
        _, pages = olmo_hybrid.forward(
            params, cfg, jnp.asarray(toks[None, :149], jnp.int32),
            jnp.arange(149)[None], pages, rows[2], jnp.array([149]),
            jnp.array([149]))
        packed = np.zeros((1, 256), np.int32)
        pos = np.zeros((1, 256), np.int32)
        packed[0, :101], pos[0, :101] = toks[:101], np.arange(101)
        packed[0, 101:134], pos[0, 101:134] = other, np.arange(33)
        packed[0, 134], pos[0, 134] = toks[149], 149
        logits, pages = olmo_hybrid.forward(
            params, cfg, jnp.asarray(packed), jnp.asarray(pos), pages,
            jnp.concatenate(rows, 0), jnp.array([101, 33, 150, 1]),
            jnp.array([101, 33, 1, 0]), packed=True)
    for row, ref in ((0, want[100]), (1, want_other[32]), (2, want[149])):
        np.testing.assert_allclose(np.asarray(logits[row]), np.asarray(ref),
                                   atol=5e-4)


# ------------------------------------------------------ the served path

def _engine(cfg, params, **kw):
    defaults = dict(num_pages=256, page_size=8, max_num_seqs=4,
                    max_prefill_chunk=70, max_context=512,
                    min_prefill_bucket=8, decode_multistep=4,
                    num_top_logprobs=0)
    defaults.update(kw)
    return JaxEngine(cfg, params, JaxEngineConfig(**defaults))


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


@pytest.mark.parametrize("attn_impl", ["scan", "pallas"])
async def test_the_served_path_gives_the_references_greedy_tokens(attn_impl):
    """Five requests on four rows - prompts of 5 to 150 tokens computed in
    chunks of at most 70 beside the rows that decode, fused blocks and
    blocks chained behind a mixed step - stream the reference's greedy
    continuation, token for token: padded steps on the XLA path, and the
    token-packed step with ``gdn_chunk`` / ``gdn_step`` at 3 heads of 24 x
    40 and the paged kernels at a group of one in interpret mode (head_dim
    128 for the attention kernels' tiles)."""
    hf = _config(tiny=True, **({"head_dim": 128}
                               if attn_impl == "pallas" else {}))
    from dynamo_tpu.engine.steptrace import StepRecorder, set_step_recorder

    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0))
    set_step_recorder(StepRecorder(4096))       # this engine's steps alone
    eng = _engine(cfg, params, attn_impl=attn_impl)
    assert (eng.padded_reason is None) == (attn_impl == "pallas")
    assert eng.cache_kinds == (
        f"paged[L=2,Hkv=3,Dh={cfg.head_dim}]+state[L=6,S=4,f32]")
    slot_bytes = 3 * 24 * 40 * 4
    assert eng.state_row_bytes == 6 * 2 * slot_bytes
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
        ring = [r for r in eng.steptrace.snapshot(limit=4096)["records"]
                if r["state_rows"]]
        mixed = [r for r in ring if r["kind"] in ("prefill", "mixed")]
        assert mixed and all(
            r["gdn_tokens"] + r["gdn_step_rows"] == r["tokens_real"]
            and r["state_bytes"] == r["state_rows"] * eng.state_row_bytes
            for r in mixed)
        assert any(r["gdn_tokens"] == 70 for r in mixed)
        blocks = [r for r in ring if r["kind"] == "multistep"]
        assert blocks and all(
            r["gdn_step_rows"] == r["rows"] * r["width"]
            and r["gdn_tokens"] == 0 and r["score_pairs"] > 0
            and r["state_bytes"] == r["gdn_step_rows"] * eng.state_row_bytes
            for r in blocks)
        from dynamo_tpu.worker.metrics import engine_dispatch_stats
        assert engine_dispatch_stats(eng)["state_bytes"] == float(
            sum(r["state_bytes"] for r in ring))
    finally:
        await eng.stop()



@pytest.mark.parametrize("kw,names", [
    (dict(spec_tokens=2), "speculative"),
    (dict(quantize="int8"), "--quantize"),
    (dict(shard_pages_fn=lambda p: p), "mesh"),
])
def test_the_engine_refuses_by_this_familys_name_too(tiny, kw, names):
    """What moves block chains only is refused at start-up for the second
    family with a recurrent state as for the first: off
    ``ModelConfig.slot_kind``, with the model's own name in the words."""
    _hf, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        _engine(cfg, params, **kw)
    assert names in str(e.value) and "olmo_hybrid" in str(e.value)


def test_page_export_tiers_and_scoring_are_refused_for_this_family(tiny):
    from dynamo_tpu.kvbm.manager import TieredEngine

    _hf, cfg, params = tiny
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.gather_pages_host([1, 2])
    with pytest.raises(NotImplementedError, match="host and disk tiers"):
        TieredEngine(eng)
    with pytest.raises(NotImplementedError, match="prompt-scoring"):
        eng._score_batch([[1, 2, 3]])
    assert eng.table_width == 512 // 8 + 1


def test_the_worker_names_the_geometry_and_the_caches(tmp_path):
    """``startup.engine`` carries the rule's geometry with the range of
    ``beta`` and the kinds of cache; ``--disagg`` ends the worker at its
    arguments by the second family's name too."""
    from dynamo_tpu.utils.tracing import StartupTrace
    from dynamo_tpu.worker import main as worker_main

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import modeldir
    model = modeldir.write_model_dir(str(tmp_path / "m"), _config(tiny=True))
    base = ["--model-path", model, "--random-weights", "--dtype", "float32",
            "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
            "--max-context", "128", "--state-slots", "3"]
    parser = worker_main.build_parser()
    with pytest.raises(NotImplementedError, match="recurrent state") as e:
        worker_main.build_engine(parser.parse_args(
            base + ["--disagg", "prefill"]))
    assert "olmo_hybrid" in str(e.value) and "--disagg" in str(e.value)
    startup = StartupTrace()
    eng = worker_main.build_engine(parser.parse_args(base), startup)
    assert eng.state_slots == 3
    attrs = [st[3] for st in startup.stages
             if st[0] == "startup.engine"][0]
    assert attrs["cache.kinds"] == "paged[L=2,Hkv=3,Dh=16]+state[L=6,S=3,f32]"
    assert attrs["linear_attention"] == (
        "gdn[chunk=64,Hk=3,Hv=3,Dk=24,Dv=40,beta<2]")
    assert "moe.experts" not in attrs
    assert attrs["prefill.form"] == "padded:attn_impl"
