"""q, k and v from ONE stored matrix (ISSUE 54).

The engine lays a layer tree's ``wq | wk | wv`` side by side once at load
(``llama.fuse_qkv``, ``JaxEngine.__init__``) and ``llama.qkv_products``
makes one product of ``wqkv`` and splits it; a tree that still holds the
three - ``init_params``', a loader's, a mesh's, a pipeline stage's - is
served as before. Under test, on the CPU:

- the engine's tree holds ``wqkv`` equal to the concatenation (``bqkv``
  where the family has biases) and none of the three, the tree it was
  handed is untouched, and ``engine.qkv`` says which form serves and why;
- ``init_params``, ``hf_loader`` and ``gguf`` still return the three;
- a padded prefill, a packed step with an empty row and three decode
  steps give the same logits and the same pool fused as split, for the
  dense family (with q/k norm and with biases), the MoE family and gemma,
  in float32 and in bfloat16;
- int8: the fused stack's scales are the three's, concatenated, and so
  are its quantised values - the fused int8 product is the split one to
  the last bit;
- a ``tp`` = 2 mesh and a pipeline stage still serve on the three.

What the TPU compiler makes of either form is read in
``tests/test_pallas_tpu_lowering.py``.
"""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import deepseek, gemma, llama, moe
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import quant
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

from tests.test_packed_step import N, P, PLANS, PS, _plan_arrays

FAMILIES = {
    "llama": (llama, dict()),
    "qwen3": (llama, dict(qk_norm=True)),
    "qwen2_bias": (llama, dict(model_type="qwen2", attention_bias=True)),
    "moe": (moe, dict(model_type="qwen3_moe", qk_norm=True, num_experts=4,
                      num_experts_per_tok=2, moe_intermediate_size=32)),
    "gemma": (gemma, dict(model_type="gemma2", sliding_window=6,
                          attn_logit_softcap=30.0,
                          final_logit_softcap=20.0)),
}
ENGINE_KW = dict(num_pages=32, page_size=4, max_num_seqs=2,
                 max_prefill_chunk=8, max_context=64, min_prefill_bucket=4,
                 attn_impl="scan")


def _cfg(**kw):
    base = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                dtype="float32", max_position_embeddings=256)
    base.update(kw)
    return ModelConfig(**base)


def _params(mod, cfg, seed=1):
    """The family's random tree, with biases that are not zero."""
    params = mod.init_params(cfg, jax.random.PRNGKey(seed))
    layers = dict(params["layers"])
    for i, name in enumerate(("bq", "bk", "bv")):
        if name in layers:
            layers[name] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(10 + i), layers[name].shape,
                jnp.float32).astype(layers[name].dtype)
    return {**params, "layers": layers}


def _fused(params):
    return {**params, "layers": llama.fuse_qkv(params["layers"])}


def _req(tokens, rid="r", max_tokens=5):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


def _stream(eng, tokens):
    async def go():
        out = []
        try:
            async for f in eng.generate(_req(tokens)):
                assert f.error is None, f.error
                out.extend(f.token_ids)
        finally:
            await eng.stop()
        return out
    return asyncio.run(go())


# ---- what the engine holds --------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engine_lays_the_three_side_by_side_once(family):
    mod, extra = FAMILIES[family]
    cfg = _cfg(**extra)
    params = _params(mod, cfg)
    eng = JaxEngine(cfg, params, JaxEngineConfig(**ENGINE_KW))
    assert eng.qkv == "fused"
    held, given = eng.params["layers"], params["layers"]
    assert not {"wq", "wk", "wv", "bq", "bk", "bv"} & set(held)
    assert held["wqkv"].shape == (
        cfg.num_layers, cfg.hidden_size, cfg.q_size + 2 * cfg.kv_size)
    np.testing.assert_array_equal(held["wqkv"], np.concatenate(
        [given["wq"], given["wk"], given["wv"]], axis=-1))
    assert ("bqkv" in held) == cfg.attention_bias
    if cfg.attention_bias:
        np.testing.assert_array_equal(held["bqkv"], np.concatenate(
            [given["bq"], given["bk"], given["bv"]], axis=-1))
    # every other leaf is the one that came in, and the tree handed in
    # still holds the three
    assert set(held) - {"wqkv", "bqkv"} == set(given) - {
        "wq", "wk", "wv", "bq", "bk", "bv"}
    assert all(held[k] is given[k] for k in set(held) & set(given))
    assert {"wq", "wk", "wv"} <= set(given)
    assert len(_stream(eng, range(1, 12))) == 5


def test_an_abstract_tree_is_fused_abstractly():
    """``program_check.step_programs`` builds engines on shapes alone."""
    cfg = _cfg(qk_norm=True)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    eng = JaxEngine(cfg, shapes, JaxEngineConfig(**ENGINE_KW))
    leaf = eng.params["layers"]["wqkv"]
    assert eng.qkv == "fused" and isinstance(leaf, jax.ShapeDtypeStruct)
    assert leaf.shape == (2, 64, cfg.q_size + 2 * cfg.kv_size)
    assert leaf.dtype == jnp.float32


@pytest.mark.parametrize("case", ["family", "tree"])
def test_a_tree_without_the_three_is_left_as_it_is(case):
    """A family whose forward reads no ``wqkv`` (latent attention has a
    ``wq`` of its own), and a tree that lacks one of the three."""
    if case == "family":
        cfg = _cfg(model_type="deepseek_v2", num_layers=2, num_heads=2,
                   num_kv_heads=1, head_dim=32, kv_lora_rank=32,
                   qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
                   num_experts=4, num_experts_per_tok=2,
                   moe_intermediate_size=32, n_shared_experts=1,
                   first_k_dense_replace=1, routed_scaling_factor=1.0)
        params = deepseek.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg = _cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        layers = dict(params["layers"])
        layers["wkv"] = jnp.concatenate(
            [layers.pop("wk"), layers.pop("wv")], axis=-1)
        params = {**params, "layers": layers}
    eng = JaxEngine(cfg, params, JaxEngineConfig(**ENGINE_KW))
    assert eng.qkv == f"split:{case}"
    assert jax.tree_util.tree_structure(eng.params) == \
        jax.tree_util.tree_structure(params)


# ---- what makes the tree still makes the three ------------------------------

def _write_safetensors(path, cfg, params):
    """``params`` as an HF checkpoint, by the loader's own name map."""
    from safetensors.numpy import save_file

    from dynamo_tpu.models.hf_loader import _name_map
    tensors = {}
    for name, (where, transposed) in _name_map(cfg).items():
        leaf = params
        for key in where:
            leaf = leaf[key]
        for i in (range(cfg.num_layers) if "{i}" in name else [None]):
            a = np.asarray(leaf if i is None else leaf[i], np.float32)
            tensors[name.format(i=i)] = np.ascontiguousarray(
                a.T if transposed else a)
    save_file(tensors, str(path / "model.safetensors"))


@pytest.mark.parametrize("source", ["init_llama", "init_moe", "init_gemma",
                                    "hf_loader", "gguf"])
def test_init_params_and_the_loaders_return_the_three(source, tmp_path):
    cfg = _cfg(model_type="qwen2", attention_bias=True,
               tie_word_embeddings=False)
    if source == "hf_loader":
        from dynamo_tpu.models.hf_loader import load_hf_params
        want = _params(llama, cfg)
        _write_safetensors(tmp_path, cfg, want)
        layers = load_hf_params(cfg, str(tmp_path))["layers"]
        for name in ("wq", "wk", "wv", "bq", "bk", "bv"):
            np.testing.assert_array_equal(layers[name],
                                          want["layers"][name])
    elif source == "gguf":
        from dynamo_tpu.models.gguf import GgufFile, load_gguf_params
        from tests.test_gguf import make_file
        path = str(tmp_path / "m.gguf")
        make_file(path)
        cfg = GgufFile(path).to_model_config(dtype="float32")
        layers = load_gguf_params(cfg, path)["layers"]
    else:
        mod, extra = {"init_llama": (llama, {}),
                      "init_moe": FAMILIES["moe"],
                      "init_gemma": FAMILIES["gemma"]}[source]
        cfg = _cfg(**extra)
        layers = mod.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert "wqkv" not in layers and "bqkv" not in layers
    assert layers["wq"].shape == (cfg.num_layers, cfg.hidden_size,
                                  cfg.q_size)
    for name in ("wk", "wv"):
        assert layers[name].shape == (cfg.num_layers, cfg.hidden_size,
                                      cfg.kv_size)


# ---- the same logits, the same pool -----------------------------------------

def _three_decode_steps(mod, cfg, params, pages):
    """Logits of three one-token steps of two rows, each feeding its
    argmax, and the pool behind them."""
    table = jnp.asarray(np.arange(1, 1 + 2 * P).reshape(2, P), jnp.int32)
    tok = jnp.asarray([[7], [23]], jnp.int32)
    lens = jnp.asarray([4, 9], jnp.int32)
    step = jax.jit(lambda p, t, l, pg: mod.forward(
        p, cfg, t, (l - 1)[:, None], pg, table, l, jnp.ones_like(l))[:2])
    out = []
    for _ in range(3):
        logits, pages = step(params, tok, lens, pages)
        out.append(logits)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        lens = lens + 1
    return jnp.stack(out), pages


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("step", ["padded_prefill", "packed_empty_row",
                                  "three_decode_steps"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_logits_are_the_split_ones(family, step, dtype):
    """One product of ``[D, q + 2 kv]`` columns sums each column over the
    same ``D`` values as the product of its own matrix did, in the order
    its tiling gives: on this CPU the packed step and the decode steps
    agree to the last bit in both dtypes, and the padded prefill to the
    rounding of one float32 sum (1.5e-7 - 1.1e-6 on logits of order one:
    a product of 128 columns is blocked otherwise than those of 64 and
    32; in bfloat16 that rounds away but for one logit of the MoE
    family, 1.2e-4)."""
    mod, extra = FAMILIES[family]
    cfg = _cfg(dtype=dtype, **extra)
    params = _params(mod, cfg)
    pages = jax.random.normal(
        jax.random.PRNGKey(2),
        (cfg.num_layers, N, 2, cfg.num_kv_heads, PS, cfg.head_dim)
    ).astype(cfg.dtype)
    if step == "three_decode_steps":
        run = functools.partial(_three_decode_steps, mod, cfg, pages=pages)
    else:
        # the plan of ``tests/test_packed_step.py`` with a resumed chunk
        # ending off a page boundary, a fresh chunk, two decode rows and
        # an EMPTY row (a pad)
        packed = step == "packed_empty_row"
        a = _plan_arrays(PLANS["mixed"])
        inputs = a["packed" if packed else "padded"]
        rows = (a["table"], a["total"], a["new"])
        fwd = jax.jit(lambda p: mod.forward(p, cfg, *inputs, pages, *rows,
                                            packed=packed)[:2])

        def run(p):
            logits, pool = fwd(p)
            # an empty row's logits are whatever slot 0 holds: not a
            # row of the step
            return logits[np.asarray(rows[2]) > 0], pool
    want, got = run(params), run(_fused(params))
    tol = dict(float32=1e-5, bfloat16=2e-2)[dtype]
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


def test_the_engine_streams_the_tokens_the_three_give():
    """Served end to end: the engine (fused) against the forward on the
    tree of ``init_params`` (split), greedy, prompt of two chunks."""
    cfg = _cfg(qk_norm=True)
    params = _params(llama, cfg)
    prompt = list(range(3, 17))
    got = _stream(JaxEngine(cfg, params, JaxEngineConfig(**ENGINE_KW)),
                  prompt)
    pages = llama.make_pages(cfg, 8, 16)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    seq, want = list(prompt), []
    for _ in range(5):
        n = jnp.asarray([len(seq)], jnp.int32)
        logits, _ = llama.forward(
            params, cfg, jnp.asarray([seq], jnp.int32),
            jnp.arange(len(seq), dtype=jnp.int32)[None], pages, table, n, n)
        want.append(int(jnp.argmax(logits[0])))
        seq.append(want[-1])
    assert got == want


# ---- int8 -------------------------------------------------------------------

def test_int8_scales_of_the_fused_stack_are_the_threes_concatenated():
    cfg = _cfg(model_type="qwen2", attention_bias=True,
               tie_word_embeddings=False)
    params = _params(llama, cfg)
    split = quant.quantize_params(params)["layers"]
    fused = quant.quantize_params(_fused(params))["layers"]
    assert "wqkv" not in fused and "wq_q" not in fused
    for suffix in ("_q", "_scale"):
        np.testing.assert_array_equal(fused["wqkv" + suffix], np.concatenate(
            [split[n + suffix] for n in ("wq", "wk", "wv")], axis=-1))
    # integer sums and the same two scales: the product is the same one
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.hidden_size))
    lp_split = {k: v[0] for k, v in split.items()}
    lp_fused = {k: v[0] for k, v in fused.items()}
    for a, b in zip(llama.qkv_products(cfg, lp_fused, x),
                    llama.qkv_products(cfg, lp_split, x)):
        np.testing.assert_array_equal(a, b)


def test_an_int8_engine_quantises_the_fused_stack():
    cfg = _cfg(tie_word_embeddings=False)
    eng = JaxEngine.random_init(cfg, JaxEngineConfig(
        **ENGINE_KW, quantize="int8"))
    held = eng.params["layers"]
    assert eng.qkv == "fused"
    assert held["wqkv_q"].dtype == jnp.int8
    assert held["wqkv_scale"].shape == (2, cfg.q_size + 2 * cfg.kv_size)
    assert not {"wqkv", "wq", "wq_q", "wk_q", "wv_q"} & set(held)
    assert len(_stream(eng, range(1, 12))) == 5


# ---- who still serves on the three ------------------------------------------

@pytest.mark.mesh
def test_a_tp_mesh_serves_on_the_three():
    from dynamo_tpu.parallel.sharding import tp_sharding

    cfg = ModelConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    shard = tp_sharding(cfg, 2)
    kw = dict(ENGINE_KW, attn_impl="auto")
    want = _stream(JaxEngine(cfg, params, JaxEngineConfig(**kw)),
                   range(1, 12))
    eng = JaxEngine(cfg, params, JaxEngineConfig(
        mesh=shard.mesh, shard_params_fn=shard.shard_params,
        shard_pages_fn=shard.shard_pages, **kw))
    assert eng.qkv == "split:mesh"
    assert {"wq", "wk", "wv"} <= set(eng.params["layers"])
    assert "wqkv" not in eng.params["layers"]
    assert _stream(eng, range(1, 12)) == want


@pytest.mark.mesh
def test_a_pipeline_stage_serves_on_the_three():
    from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh
    from dynamo_tpu.parallel.pipeline import (pipeline_forward,
                                              pp_sharding_fns)

    cfg = ModelConfig.tiny(num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    want = _stream(JaxEngine(cfg, params, JaxEngineConfig(**ENGINE_KW)),
                   range(1, 7))
    mesh = make_mesh(MeshSpec(pp=2), devices=jax.devices()[:2])
    shard_params, shard_pages = pp_sharding_fns(mesh)
    eng = JaxEngine(cfg, params, JaxEngineConfig(
        **ENGINE_KW, shard_params_fn=shard_params,
        shard_pages_fn=shard_pages),
        forward_fn=functools.partial(pipeline_forward, mesh=mesh))
    assert eng.qkv == "split:forward"
    assert {"wq", "wk", "wv"} <= set(eng.params["layers"])
    assert _stream(eng, range(1, 7)) == want


# ---- the worker says which --------------------------------------------------

def test_the_worker_says_which_form_serves(tmp_path):
    """``startup.engine`` carries ``qkv``: ``fused`` on one device,
    ``split:mesh`` under ``--tensor-parallel-size``."""
    from dynamo_tpu.utils.testing import make_test_model_dir
    from dynamo_tpu.utils.tracing import StartupTrace
    from dynamo_tpu.worker import main as worker_main

    model = make_test_model_dir(str(tmp_path / "m"))
    base = ["--model-path", model, "--random-weights", "--dtype", "float32",
            "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
            "--max-context", "128"]
    parser = worker_main.build_parser()
    for more, want in (([], "fused"),
                       (["--tensor-parallel-size", "2"], "split:mesh")):
        startup = StartupTrace()
        eng = worker_main.build_engine(parser.parse_args(base + more),
                                       startup)
        attrs = [st[3] for st in startup.stages
                 if st[0] == "startup.engine"][0]
        assert attrs["qkv"] == eng.qkv == want
        assert ("wqkv" in eng.params["layers"]) == (want == "fused")


def test_the_worker_lets_the_three_go_before_the_pools_are_made(
        tmp_path, monkeypatch):
    """An argument lives as long as the call it was passed to, so the
    worker - the tree's owner - lays it out first (``serving_weights``)
    and keeps the result alone: when the engine makes its page pool the
    three are gone (1.13 GB at Qwen3-4B beside a pool cut to fit)."""
    import gc
    import weakref

    from dynamo_tpu.utils.testing import make_test_model_dir
    from dynamo_tpu.worker import main as worker_main

    seen = {}
    build, make_pages = worker_main._build_weights, llama.make_pages

    def built(args):
        out = build(args)
        seen["wq"] = weakref.ref(out[3]["layers"]["wq"])
        return out

    def pool(*a, **kw):
        gc.collect()
        seen["alive_at_the_pool"] = seen["wq"]() is not None
        return make_pages(*a, **kw)

    monkeypatch.setattr(worker_main, "_build_weights", built)
    monkeypatch.setattr(llama, "make_pages", pool)
    model = make_test_model_dir(str(tmp_path / "m"))
    eng = worker_main.build_engine(worker_main.build_parser().parse_args([
        "--model-path", model, "--random-weights", "--dtype", "float32",
        "--num-pages", "64", "--page-size", "4", "--max-num-seqs", "4",
        "--max-context", "128"]))
    assert eng.qkv == "fused" and seen["alive_at_the_pool"] is False


def test_a_tree_already_laid_out_is_taken_as_it_is():
    from dynamo_tpu.engine.jax_engine import qkv_form, serving_weights

    cfg = _cfg(qk_norm=True)
    params = _params(llama, cfg)
    ecfg = JaxEngineConfig(**ENGINE_KW)
    once = serving_weights(cfg, params, ecfg)
    twice = serving_weights(cfg, once, ecfg)
    assert qkv_form(cfg, once, ecfg) == "fused"
    assert sorted(twice["layers"]) == sorted(once["layers"])
    assert twice["layers"]["wqkv"] is once["layers"]["wqkv"]
    eng = JaxEngine(cfg, once, ecfg)
    assert eng.qkv == "fused"
    assert eng.params["layers"]["wqkv"] is once["layers"]["wqkv"]
