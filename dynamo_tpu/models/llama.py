"""Llama-family decoder (llama 2/3, mistral, qwen2/qwen3) — pure-functional jax.

The reference framework never implements a model; it shells out to vLLM/SGLang
on CUDA (SURVEY §2.5). Here the model loop is native and TPU-first, with
one forward over the weights:

- ``forward`` — ONE ``lax.scan`` over stacked per-layer params: a single
  compiled layer body, fast compiles, XLA while-loop buffer aliasing keeps the
  stacked paged KV cache (scan carry) updated in place. The attention op of a
  step is an argument (``attn_impl``): the XLA gather path by default (the
  CPU's, and the reference of the kernel tests), a Pallas kernel that takes
  the stacked cache and the traced layer index on the chip.

Only the last real token's logits are computed ([B, V]); full [B, S, V]
logit materialization would waste HBM on long prefill chunks.

Weight layout matches HF checkpoints after transpose (torch Linear stores
[out, in]; we store [in, out] so the forward is ``x @ w``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import (
    paged_attention,
    ragged_paged_attention,
    selected_attention,
    write_kv,
    write_kv_packed,
)
from dynamo_tpu.ops.rope import apply_rope
from dynamo_tpu.ops import indexer, quant
from dynamo_tpu.ops.sampling import top_candidates

Params = Dict[str, Any]


def _rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _head_rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """qwen3-style per-head norm: x is [B, S, H, Dh], w is [Dh]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def make_pages(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=None) -> jnp.ndarray:
    """Stacked paged KV cache: [L, N, 2, Hkv, page_size, Dh], ``L`` the
    model's cache layers (its layers, or two a layer for a family with two
    attention blocks in each: ``cfg.num_cache_layers``).

    Page-major: one page is a contiguous slab carrying K AND V for all kv
    heads, so page-granular DMAs (Pallas decode kernel, disagg block
    transfer) are single descriptors (see ``ops/attention.py``).

    Page 0 is reserved as the garbage page for padded writes — allocators must
    hand out pages starting at index 1.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    kv = jnp.zeros((cfg.num_cache_layers, num_pages, 2, cfg.num_kv_heads,
                    page_size, cfg.head_dim), dtype=dtype)
    if not cfg.index_topk:
        return kv
    # a model whose layers attend a learned selection: one index key a
    # token a layer beside its keys and values, under the SAME page id -
    # two arrays, one block chain (``write_rows`` writes both, the page
    # table addresses both, ``engine/pages.py`` owns the ids)
    return {"kv": kv,
            "index": indexer.index_pages(cfg.num_cache_layers, num_pages,
                                         page_size, cfg.index_head_dim,
                                         dtype)}


def randn_stack(key, n: int, shape: tuple, scale: float,
                dtype) -> jnp.ndarray:
    """``[n, *shape]`` normal weights drawn a layer at a time inside one
    program, each from its own split key, straight into ``dtype``: no
    float32 copy of the whole stack ever exists (a stacked expert matrix
    of 4 x 256 x 2048 x 768 is 6.4 GB in float32, twice over if drawn in
    one call, beside the 11 GB the finished weights take). The MLA and
    the MoE families' initialisers share it."""
    @jax.jit
    def draw(keys):
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * scale).astype(dtype), keys)
    return draw(jax.random.split(key, n))


# Standard deviation of the sparse families' random weights, times
# sqrt(hidden): 0.012 at their 2,048-wide models, kept per fan-in so that
# toy widths see activations of the same size. Chosen by measurement
# (``deepseek.init_params``), not taken from a paper.
MOE_INIT_GAIN = 0.012 * 2048 ** 0.5


def init_params(cfg: ModelConfig, rng: jax.Array, scale: float = 0.02) -> Params:
    """Random-normal init (for tests/benchmarks; real serving loads HF weights)."""
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(rng, 16))

    def norm(shape):
        return jnp.ones(shape, dtype=dtype)

    def randn(key, shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": norm((L, H)),
        "wq": randn(next(keys), (L, H, cfg.q_size)),
        "wk": randn(next(keys), (L, H, cfg.kv_size)),
        "wv": randn(next(keys), (L, H, cfg.kv_size)),
        "wo": randn(next(keys), (L, cfg.q_size, H)),
        "mlp_norm": norm((L, H)),
        "w_gate": randn(next(keys), (L, H, I)),
        "w_up": randn(next(keys), (L, H, I)),
        "w_down": randn(next(keys), (L, I, H)),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_size), dtype=dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_size), dtype=dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_size), dtype=dtype)
    if cfg.qk_norm:
        layers["q_norm"] = norm((L, cfg.head_dim))
        layers["k_norm"] = norm((L, cfg.head_dim))
    params: Params = {
        "embed": randn(next(keys), (cfg.vocab_size, H)),
        "layers": layers,
        "final_norm": norm((H,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn(next(keys), (H, cfg.vocab_size))
    return params


QKV = ("wq", "wk", "wv")


def fuse_qkv(layers: Dict[str, Any]) -> Dict[str, Any]:
    """The layer tree with ``wq | wk | wv`` laid side by side as ONE stack
    ``wqkv [L, D, q_size + 2 kv_size]`` (``bqkv`` of the biases likewise),
    or the tree as it is where it does not hold the three.

    Three products that share their left operand are what the TPU compiler
    re-lays a layer at a time: each layer's three matrices fetched and
    transposed ahead of three small products, and in a fused block the
    whole stacks transposed once a dispatch (1.13 GB at Qwen3-4B; PERF.md
    section 6, PR 54). One product reads the one stack where it lies, as
    ``wo`` and the FFN's do. A concatenation of the same values, made once
    at load (``jax_engine.serving_weights``) - ``init_params`` and
    the loaders keep making the three, which a mesh and the pipeline
    stages place by name. The tree given keeps its leaves: the three live
    as long as their owner holds it (the worker lays its tree out first
    and keeps the result alone, ``jax_engine.serving_weights``). Abstract
    leaves give abstract leaves."""
    def side_by_side(*parts):
        if isinstance(parts[0], jax.ShapeDtypeStruct):
            return jax.eval_shape(side_by_side, *parts)
        return jnp.concatenate(parts, axis=-1)

    out = dict(layers)
    for fused, names in (("wqkv", QKV), ("bqkv", ("bq", "bk", "bv"))):
        if all(name in out for name in names):
            out[fused] = side_by_side(*(out.pop(name) for name in names))
    return out


def qkv_products(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 x: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``x @ wq, x @ wk, x @ wv`` with their biases, ``[B, S, *]`` each:
    ONE product split by columns where the tree holds ``wqkv``
    (``fuse_qkv``), the three where it holds the three - a mesh with
    ``tp``, the pipeline stages, any caller with the tree of
    ``init_params``. What the tree holds decides, nothing else."""
    if quant.holds(lp, "wqkv"):
        qkv = quant.mm(lp, "wqkv", x)
        if "bqkv" in lp:
            qkv = qkv + lp["bqkv"]
        return tuple(jnp.split(
            qkv, (cfg.q_size, cfg.q_size + cfg.kv_size), axis=-1))
    q, k, v = (quant.mm(lp, name, x) for name in QKV)
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    return q, k, v


def _project_qkv(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 h: jnp.ndarray, positions: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared per-layer pre-attention math: norm, qkv, qk-norm, rope."""
    B, S, _ = h.shape
    eps = cfg.rms_norm_eps
    x = _rms_norm(h, lp["attn_norm"], eps)
    q, k, v = qkv_products(cfg, lp, x)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _head_rms_norm(q, lp["q_norm"], eps)
        k = _head_rms_norm(k, lp["k_norm"], eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _finish_attn(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 h: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """Out-projection residual (shared with the MoE decoder)."""
    B, S, _ = h.shape
    return h + quant.mm(lp, "wo", attn.reshape(B, S, cfg.q_size))


def _ffn(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
         h: jnp.ndarray) -> jnp.ndarray:
    """Gated MLP residual."""
    x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    act = jax.nn.silu(quant.mm(lp, "w_gate", x)) * quant.mm(lp, "w_up", x)
    return h + quant.mm(lp, "w_down", act)


def _finish_layer(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                  h: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """Shared post-attention math: out-proj residual + gated MLP residual."""
    return _ffn(cfg, lp, _finish_attn(cfg, lp, h, attn))


def _select_last(h: jnp.ndarray, new_lens: jnp.ndarray,
                 starts: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Hidden state of each row's last real new token, ``[R, H]``: column
    ``new_lens - 1`` of a padded ``[B, S, H]``, or slot ``starts +
    new_lens - 1`` of a token-packed ``[1, T, H]``."""
    last = jnp.maximum(new_lens - 1, 0).astype(jnp.int32)
    if starts is not None:
        return h[0][starts + last]
    return jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]


def _logits(cfg: ModelConfig, params: Params, h: jnp.ndarray,
            new_lens: jnp.ndarray, window: int = 1,
            starts: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Logits at each row's last ``window`` real new positions.

    window == 1 (every normal step) returns [B, V]; window = W > 1 (the
    speculative-verify step, which samples at all K+1 chunk slots) returns
    [B, W, V]. Only W rows of hidden state hit the lm_head either way —
    full [B, S, V] materialization stays off the table. ``starts`` (a
    token-packed step, ``packed_rows``) is each row's first slot on the
    packed axis of ``h [1, T, H]``.
    """
    h = _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    if window == 1:
        h_sel = _select_last(h, new_lens, starts)
    else:
        offs = jnp.arange(window, dtype=jnp.int32)[None, :]          # [1, W]
        idx = jnp.maximum(new_lens[:, None] - window + offs, 0)      # [B, W]
        h_sel = jnp.take_along_axis(h, idx[..., None], axis=1)       # [B,W,H]
    lm8 = params.get("lm_head_q")
    if lm8 is not None:
        return quant.qdot(h_sel, lm8, params["lm_head_scale"],
                          out_dtype=jnp.float32)
    lm_head = params.get("lm_head")
    if lm_head is None:
        lm_head = params["embed"].T
    # operands stay in the model dtype with f32 ACCUMULATION: casting
    # lm_head to f32 would double its HBM stream (the largest single
    # tensor of a decode step) and push the matmul off the bf16 MXU path
    return jnp.dot(h_sel, lm_head, preferred_element_type=jnp.float32)


def packed_rows(packed: bool, new_lens: jnp.ndarray
                ) -> Optional[jnp.ndarray]:
    """Each row's first slot on the packed axis of a TOKEN-PACKED step (the
    exclusive cumulative sum of ``new_lens``), or None for a padded step.

    A packed step carries ``tokens``/``positions`` ``[1, T]`` — every
    row's new tokens back to back, chunk rows then decode rows, pads at the
    end — beside the per-row ``page_table [R, P]``, ``total_lens`` and
    ``new_lens [R]``. Everything per token (embed, norms, projections,
    rope, FFN) runs on ``[1, T, H]`` as it would on any batch of one; the
    three operations that know rows take the row descriptors:
    ``write_rows``, ``attend_rows`` and ``_logits``. A forward that
    handles both forms says so with ``forward.supports_packed = True``
    (``engine/jax_engine.py`` serves every other forward padded)."""
    if not packed:
        return None
    return (jnp.cumsum(new_lens) - new_lens).astype(jnp.int32)


def write_rows(pages, lidx, k, v, page_table, positions, total_lens,
               new_lens, starts, k_i=None):
    """The cache write of either step form (``starts``: ``packed_rows``).
    ``pages`` a tree of key/value pages and index pages (``make_pages``
    of a model that selects): ``k_i [B, S, D]``, the tokens' index keys,
    go into the index pages through the same page table in the same
    stage - a block never holds the one without the other."""
    if isinstance(pages, dict):
        return {"kv": write_rows(pages["kv"], lidx, k, v, page_table,
                                 positions, total_lens, new_lens, starts),
                "index": indexer.write_index_keys(
                    pages["index"], lidx, k_i, page_table, positions,
                    total_lens, new_lens, starts)}
    if starts is None:
        return write_kv(pages, lidx, k, v, page_table, positions, new_lens)
    return write_kv_packed(pages, lidx, k[0], v[0], page_table, starts,
                           new_lens, total_lens)


def visibility(cfg: ModelConfig) -> Dict[str, int]:
    """What ``attend_rows`` hands the attention op beyond the causal
    arguments: nothing for a causal model (its programs are the ones they
    were), the visibility block of a model that generates by diffusion
    over blocks (``ops.attention.horizon``)."""
    return {"block": cfg.gen_block} if cfg.gen_block > 1 else {}


def attend_rows(attn_impl, q, pages, lidx, page_table, positions,
                total_lens, new_lens, sm_scale, starts, **kw):
    """Attention of either step form. A packed step's ``attn_impl`` has
    ``ops.attention.ragged_paged_attention``'s signature (the default);
    the engine's, ``ops/pallas/ragged.ragged_mixed_attention_packed``,
    reads the row kinds off ``new_lens``: rows of several tokens through
    the ragged kernel, the trailing one-token rows through the decode
    kernel, unless ``kw`` carries a visibility ``block``."""
    if starts is None:
        return (attn_impl or paged_attention)(
            q, pages, lidx, page_table, positions, total_lens, sm_scale,
            **kw)
    return (attn_impl or ragged_paged_attention)(
        q[0], pages, lidx, page_table, starts, new_lens, total_lens,
        sm_scale, **kw)[None]


def index_inputs(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 h: jnp.ndarray, positions: jnp.ndarray):
    """The indexer's three projections of the normed stream (``h [B, S,
    H]``; the norm is ``_project_qkv``'s own, computed once by the
    compiler): ``(q_I [B * S, J, D], k_I [B, S, D], w [B * S, J]
    float32)`` - ``q_I = x W_qI`` and the ONE key a token ``k_I =
    LayerNorm(x W_kI)`` both turned by the token's position over their
    whole ``D`` (``rope_theta``, halves: the family's convention), ``w = x
    W_w``."""
    B, S, _ = h.shape
    J, D = cfg.index_n_heads, cfg.index_head_dim
    x = _rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
    q = apply_rope((x @ lp["wi_q"]).reshape(B, S, J, D), positions,
                   cfg.rope_theta)
    k = apply_rope(indexer.layer_norm(x @ lp["wi_k"], lp["i_norm_w"],
                                      lp["i_norm_b"])[:, :, None, :],
                   positions, cfg.rope_theta)[:, :, 0]
    w = jnp.dot(x, lp["wi_w"], preferred_element_type=jnp.float32)
    return q.reshape(B * S, J, D), k, w.reshape(B * S, J)


def selects_on_kernels(attn_impl, pages) -> bool:
    """Whether a step of a model that selects runs the masked kernels
    (``ops/pallas/ragged.selected_attention_rows``): the engine's Pallas
    marker, unwrapped (a mesh wraps the kernels a shard), at a head size
    and pages they tile. Otherwise the gathered form, in XLA."""
    from dynamo_tpu.ops.pallas.decode import supports

    kv = pages["kv"]
    return bool(getattr(attn_impl, "pallas_paged_kernel", False)
                and not getattr(attn_impl, "per_shard", False)
                and supports(kv.shape[-1], kv.shape[-2]))


def attend_selected(cfg: ModelConfig, attn_impl, q, q_i, w_i, pages, lidx,
                    page_table, total_lens, new_lens, sm_scale, starts):
    """Attention of either step form over a LEARNED SELECTION: each token
    keeps the ``min(index_topk, pos + 1)`` best-scored tokens it can see
    (``ops/indexer.py``, against the index pages this step has written)
    and the grouped-query softmax runs over those alone, one selection
    for every head. ``q [B, S, Hq, Dh]``; returns the same.

    On the kernels the MASKED form - the selection as a bias, a row's
    whole context streamed (``selected_rows`` for the rows of one token,
    ``selected_chunks`` for the rows of several, in a device trace) -
    elsewhere the GATHERED form. Where the table holds no more than
    ``index_topk`` tokens every visible token is selected, nothing is
    scored, and the result is dense attention's."""
    from dynamo_tpu.ops.gdn import token_rows

    B, S, Hq, Dh = q.shape
    N = B * S
    packed = starts is not None
    rows = token_rows(
        N, starts if packed else jnp.arange(B, dtype=jnp.int32) * S,
        new_lens, total_lens, jnp.zeros_like(new_lens))
    walk = dict(width=S, packed=packed)
    kv, index = pages["kv"], pages["index"]
    qf = q.reshape(N, Hq, Dh)
    q_i = q_i.astype(index.dtype)
    if selects_on_kernels(attn_impl, pages):
        from dynamo_tpu.ops.pallas.ragged import selected_attention_rows

        one, bias = indexer.select_split(
            q_i, w_i, index, lidx, page_table, rows, total_lens,
            cfg.index_topk, **walk)
        with stage("sparse"):
            out = selected_attention_rows(
                qf, kv, lidx, page_table, rows.start, new_lens, total_lens,
                one, bias, sm_scale)
    else:
        sel, live = indexer.select(
            q_i, w_i, index, lidx, page_table, rows, total_lens,
            cfg.index_topk, **walk)
        with stage("sparse"):
            out = selected_attention(
                qf, kv, lidx, page_table[rows.row], sel,
                live & rows.valid[:, None], sm_scale)
    return out.reshape(B, S, Hq, Dh).astype(q.dtype)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan-over-layers forward against the stacked paged cache.

    tokens:     [B, S] new token ids (padded; pads masked via new_lens)
    positions:  [B, S] absolute positions of the new tokens
    pages:      stacked paged KV cache (see make_pages); returned updated
    page_table: [B, P] physical page ids per sequence
    total_lens: [B] context length including the new tokens
    new_lens:   [B] real new tokens per sequence (<= S)
    attn_impl:  optional stacked-cache attention override with
                ``paged_attention``'s signature — the engine passes the
                Pallas decode kernel (``paged_decode_attention_stacked``)
                for S == 1 steps on TPU; the traced scan index selects the
                layer inside the kernel's DMA, so decode keeps the
                single-compiled-layer-body scan.
    packed:     the step is token-packed (``packed_rows``): tokens and
                positions are [1, T], the row arrays [R].

    Returns (logits [B, vocab] at each sequence's last real new token, pages).
    """
    sm_scale = cfg.head_dim ** -0.5
    # the stages are the names a device trace shows for the operations
    # traced under them (engine/stages.py, docs/observability.md)
    with stage("step.inputs"):
        starts = packed_rows(packed, new_lens)
    with stage("embed"):
        h = params["embed"][tokens]  # [B, S, H]

    def body(carry, xs):
        h, pages = carry
        lp, lidx = xs
        with stage("layer.attn_in"):
            q, k, v = _project_qkv(cfg, lp, h, positions)
        with stage("layer.kv_write"):
            pages = write_rows(pages, lidx, k, v, page_table, positions,
                               total_lens, new_lens, starts)
        with stage("layer.attn"):
            attn = attend_rows(attn_impl, q, pages, lidx, page_table,
                               positions, total_lens, new_lens, sm_scale,
                               starts, **visibility(cfg))
        with stage("layer.attn_out"):
            h = _finish_attn(cfg, lp, h, attn)
        with stage("layer.ffn"):
            h = _ffn(cfg, lp, h)
        return (h, pages), None

    with stage("step.inputs"):
        layer_ids = jnp.arange(cfg.num_layers)
    (h, pages), _ = jax.lax.scan(
        body, (h, pages), (params["layers"], layer_ids))
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, window=logits_window,
                         starts=starts)
    return logits, pages


forward.supports_packed = True
forward.reads_wqkv = True


def _dense_hidden(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """Causal dense (non-paged) transformer forward shared by the
    one-shot surfaces — ``encode`` (embeddings pooling) and ``score``
    (prompt logprobs). Materializes [B, H, S, S] attention scores per
    layer (under the scan), so callers must bound S. Returns the
    final-norm hidden states [B, S, H]."""
    B, S = tokens.shape
    sm_scale = cfg.head_dim ** -0.5
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
    h = params["embed"][tokens]

    causal = jnp.tril(jnp.ones((S, S), bool))
    attn_mask = causal[None, None] & mask[:, None, None, :]  # [B,1,S,S]

    def body(h, lp):
        q, k, v = _project_qkv(cfg, lp, h, positions)
        if cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        scores = jnp.where(attn_mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
        attn = attn.astype(h.dtype)
        h = _finish_layer(cfg, lp, h, attn)
        return h, None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)


def encode(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
           mask: jnp.ndarray) -> jnp.ndarray:
    """Dense (non-paged) forward for embeddings: mean-pooled final hidden
    state over real tokens. tokens/mask: [B, S]; returns [B, H] float32.

    Serves the /v1/embeddings surface (reference: ``http/service/openai.rs``
    embeddings route; the reference delegates the model to an engine)."""
    h = _dense_hidden(params, cfg, tokens, mask)
    m = mask.astype(jnp.float32)[..., None]
    pooled = jnp.sum(h.astype(jnp.float32) * m, axis=1) / jnp.maximum(
        jnp.sum(m, axis=1), 1.0)
    return pooled


def score(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
          mask: jnp.ndarray, chunk: int = 256, top_n: int = 1
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prompt scoring for the OpenAI ``echo`` + logprobs surface (the
    lm-eval loglikelihood workflow): log P(token[j] | tokens[:j]) for every
    position, plus the ``top_n`` highest alternatives at each position.

    Dense causal forward (no KV cache — shares :func:`_dense_hidden` with
    ``encode``; the caller bounds S, see JaxEngine._score_batch), with the
    LM head applied per S-chunk under ``lax.scan`` so the full [B, S, V]
    logits tensor never materializes.

    tokens/mask: [B, S] (S padded to a multiple of ``chunk``)
    returns (target_lps [B, S] f32 — position 0 is 0 (no context),
             top_ids [B, S, top_n] i32, top_lps [B, S, top_n] f32) —
    tops at position j are the model's best alternatives for position j
    given tokens[:j].
    """
    B, S = tokens.shape
    h = _dense_hidden(params, cfg, tokens, mask)
    lm8 = params.get("lm_head_q")
    lm_head = params.get("lm_head")
    if lm_head is None and lm8 is None:
        lm_head = params["embed"].T

    # chunked LM head: position j-1's logits score token j
    nc = S // chunk
    h_c = h.reshape(B, nc, chunk, -1).swapaxes(0, 1)       # [nc, B, c, H]
    # targets for chunk c, slot k = tokens[:, c*chunk + k + 1]
    tgt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    t_c = tgt.reshape(B, nc, chunk).swapaxes(0, 1)         # [nc, B, c]

    def head_chunk(_, xs):
        hc, tc = xs
        if lm8 is not None:       # int8-quantized serving: same head
            logits = quant.qdot(hc, lm8, params["lm_head_scale"],
                                out_dtype=jnp.float32)
        else:
            logits = jnp.dot(hc, lm_head,
                             preferred_element_type=jnp.float32)  # [B,c,V]
        lsm = jax.nn.log_softmax(logits, axis=-1)
        t_lp = jnp.take_along_axis(lsm, tc[..., None], axis=-1)[..., 0]
        top_lp, top_id = top_candidates(lsm, top_n)   # [B, c, top_n]
        return None, (t_lp, top_id.astype(jnp.int32), top_lp)

    _, (t_lp, top_id, top_lp) = jax.lax.scan(head_chunk, None, (h_c, t_c))
    # [nc, B, c, ...] -> [B, S, ...]; shift: position j-1 scored token j
    def unchunk(a):
        return a.swapaxes(0, 1).reshape((B, S) + a.shape[3:])
    t_lp, top_id, top_lp = unchunk(t_lp), unchunk(top_id), unchunk(top_lp)
    z = jnp.zeros((B, 1), jnp.float32)
    target_lps = jnp.concatenate([z, t_lp[:, :-1]], axis=1)
    top_ids = jnp.concatenate(
        [jnp.zeros((B, 1, top_n), jnp.int32), top_id[:, :-1]], axis=1)
    top_lps = jnp.concatenate(
        [jnp.zeros((B, 1, top_n), jnp.float32), top_lp[:, :-1]], axis=1)
    return target_lps, top_ids, top_lps


__all__ = ["init_params", "forward", "encode", "score", "make_pages"]
