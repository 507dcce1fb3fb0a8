"""JoyAI-LLM-Flash through the MLA family (``models/deepseek.py``): the
published config loads by its keys, and prefill then decode through the
paged latent cache agrees with the plain reference's full forward pass
(``benchmarks/reference/joyai.py``) on logits at toy widths, on the XLA path
and through the Pallas kernels (interpret mode here)."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import deepseek, get_family
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import make_pages

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "joyai-llm-flash.json")


def _config(tiny: bool):
    with open(CONFIG) as f:
        hf = json.load(f)
    bench = hf.pop("benchmark")
    if tiny:
        hf.update(bench["tiny"]["config"])
    return hf


def _reference():
    spec = importlib.util.spec_from_file_location(
        "ref_joyai", os.path.join(REPO, "benchmarks", "reference",
                                  "joyai.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_from_hf_reads_the_published_config_by_its_keys():
    """The file's top level is the catalog row's ``config`` verbatim but for
    the depth. No key names a DeepSeek; the family follows from
    ``kv_lora_rank``, the gate from ``topk_method``/``scoring_func``."""
    hf = _config(tiny=False)
    assert hf["model_type"] == "joyai_llm_flash"
    cfg = ModelConfig.from_hf(hf)
    assert get_family(cfg) is deepseek
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 1, 512)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts) == (256, 8,
                                                                 768, 1)
    assert cfg.first_k_dense_replace == 1 and cfg.intermediate_size == 7168
    assert cfg.topk_method == "noaux_tc" and cfg.norm_topk_prob
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    assert cfg.routed_scaling_factor == 2.5
    assert cfg.rope_theta == 32e6 and cfg.rope_interleave
    assert cfg.rope_scaling_factor == 0.0           # rope_scaling: null
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 129280
    assert cfg.moe_backend == "grouped"
    # by count: 5.558 B parameters in the cut the benchmark serves
    shapes = jax.eval_shape(
        lambda: deepseek.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert 5.55e9 < n < 5.57e9


@pytest.mark.parametrize("keys,want", [
    ({"scoring_func": "sigmoid", "topk_method": None}, "noaux_tc"),
    ({"scoring_func": "softmax", "topk_method": None}, "greedy"),
    ({"scoring_func": None, "topk_method": None}, "greedy"),
    ({"scoring_func": "softmax", "topk_method": "group_limited_greedy"},
     "group_limited_greedy"),
    ({"scoring_func": "softmax", "topk_method": "noaux_tc"}, None),
    ({"scoring_func": "sigmoid", "topk_method": "greedy"}, None),
])
def test_the_gate_comes_from_the_configs_keys(keys, want):
    hf = _config(tiny=True)
    for k, v in keys.items():
        if v is None:
            hf.pop(k, None)
        else:
            hf[k] = v
    if want is None:
        with pytest.raises(NotImplementedError):
            ModelConfig.from_hf(hf)
    else:
        assert ModelConfig.from_hf(hf).topk_method == want


def _serve(cfg, params, tokens, split, attn_impl=None, ps=8):
    """Logits for every position from ``split - 1`` on, the way the engine
    gets them: the prompt prefilled in two chunks into the paged latent
    cache (a padded row beside it), then one decode step per token."""
    T = len(tokens)
    P = -(-T // ps) + 1
    pages = make_pages(cfg, 2 * P + 1, ps, dtype=jnp.dtype(cfg.dtype))
    table = jnp.arange(1, 2 * P + 1, dtype=jnp.int32).reshape(2, P)
    out, at = [], 0
    for n in (split // 2, split - split // 2):
        toks = np.zeros((2, n), np.int32)
        toks[0] = tokens[at:at + n]
        pos = np.tile(np.arange(at, at + n, dtype=np.int32), (2, 1))
        logits, pages, aux = deepseek.forward(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos), pages, table,
            jnp.asarray([at + n, 0], jnp.int32),
            jnp.asarray([n, 0], jnp.int32), attn_impl=attn_impl)
        at += n
        # the empty row routes nowhere
        assert int(aux["moe_assignments"]) == (
            n * cfg.num_experts_per_tok
            * (cfg.num_layers - cfg.first_k_dense_replace))
    out.append(logits[0])
    for t in range(split, T):
        logits, pages, _aux = deepseek.forward(
            params, cfg, jnp.asarray([[tokens[t]], [0]], jnp.int32),
            jnp.asarray([[t], [0]], jnp.int32), pages, table,
            jnp.asarray([t + 1, 0], jnp.int32),
            jnp.asarray([1, 0], jnp.int32), attn_impl=attn_impl)
        out.append(logits[0])
    return jnp.stack(out).astype(jnp.float32)


def _reference_logits(hf, params, tokens):
    ref = _reference()
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for kind, stack, n in ref.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(
                    lambda a, i=i: a[i].astype(f32), stack)
                h = ref.LAYER_FNS[kind](hf, w, h)
        return ref.head(hf, params, h)


# float32 against float32: absorbed attention over a paged cache and grouped
# experts against the plain forward differ by summation order alone. Logits
# here are of order 0.1; 2e-4 absolute is what the harness's own check of
# the references allows on log-probabilities. The same path in bfloat16 is
# off by more than 2e-3 (asserted below; 3.6e-3 measured), so the tolerance tells them apart.
TOL = 2e-4


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_prefill_then_decode_agrees_with_the_reference_on_logits(path):
    hf = _config(tiny=True)
    if path == "pallas":
        # the kernels tile 128 lanes: the latent and the widths at their
        # smallest aligned sizes, everything else as in the tiny block
        hf.update(kv_lora_rank=128, hidden_size=128,
                  moe_intermediate_size=128)
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    assert cfg.q_lora_rank and cfg.topk_method == "noaux_tc"
    params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
    # a correction bias that matters: it moves the choice, not the weights
    params["moe_layers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe_layers"]["router_bias"].shape)
    tokens = np.random.default_rng(7).integers(
        0, hf["vocab_size"], size=29).tolist()
    split = 21
    attn = None
    if path == "pallas":
        from dynamo_tpu.ops.pallas.decode import (
            paged_decode_attention_stacked)
        attn = paged_decode_attention_stacked       # the marker, as served
    with jax.default_matmul_precision("highest"):
        served = _serve(cfg, params, tokens, split, attn_impl=attn)
    want = _reference_logits(hf, params, tokens)[split - 1:]
    np.testing.assert_allclose(np.asarray(served), np.asarray(want),
                               atol=TOL, rtol=0)
    if path == "xla":
        low = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
        import dataclasses
        served16 = _serve(dataclasses.replace(cfg, dtype="bfloat16"), low,
                          tokens, split)
        assert float(jnp.max(jnp.abs(served16 - want))) > 10 * TOL


def test_the_reference_streams_an_expert_layer_in_pieces():
    """Iterated, ``layers()`` yields an expert layer as open / blocks /
    close (so that the chip never holds one whole in float32); indexed, the
    layer whole. Both are the same function."""
    hf = _config(tiny=True)
    hf["n_routed_experts"] = 40          # two blocks, the second short
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = deepseek.init_params(cfg, jax.random.PRNGKey(1))
    ref = _reference()
    kinds = [k for k, _s, _n in ref.layers(params)]
    assert kinds == ["dense"] + ["moe_open", "moe_block", "moe_close"] * 2
    assert [k for k, _s, _n in list.__iter__(ref.layers(params))] == [
        "dense", "moe"]
    h = jax.random.normal(jax.random.PRNGKey(2), (9, hf["hidden_size"]))
    kind, stack, n = ref.layers(params)[-1]
    whole = ref.LAYER_FNS[kind](hf, jax.tree_util.tree_map(
        lambda a: a[n - 1], stack), h)
    pieces = h
    for kind, stack, count in list(ref.layers(params))[-3:]:
        for i in range(count):
            pieces = ref.LAYER_FNS[kind](hf, jax.tree_util.tree_map(
                lambda a, i=i: a[i], stack), pieces)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(pieces),
                               atol=1e-6, rtol=0)
