"""Latent attention of two geometries in one model (dots3-note family):
full-attention layers that attend a LEARNED SELECTION of ``index_topk``
tokens through an indexer with a cache of its own, window layers with a
wider latent whose cache stops at the window, a headwise output gate on
both, a sigmoid-routed sparse FFN held as one rank's share. Pure jax.

With ``x = RMSNorm(h)`` a layer is ``h <- h + Attn(x)``, ``h <- h +
FFN(RMSNorm(h))``.

- **Full-attention layer** (``cfg``'s own geometry): ``models/deepseek``'s
  latent attention in the absorbed form (``_mla_qkv``, ``_cache_rows``,
  the latent page layout) with LongCat's two rescales (``cfg.mla_q_scale``
  on the compressed query, ``cfg.mla_kv_scale`` on the normed latent: the
  cache holds the SCALED latent and the unscaled rotary key). **Indexer**:
  ``q_I = c_q W_Iqb`` (``index_n_heads`` heads of ``index_head_dim``,
  rotary on the first ``qk_rope_head_dim``; ``c_q`` the normed, UNSCALED
  compressed query), one key a token ``k_I = LayerNorm(x W_Ik)`` (rotary
  on its first ``qk_rope_head_dim``; cached in index pages), head weights
  ``w = x W_Iw``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` and
  the ``min(index_topk, t + 1)`` best-scored tokens ``s <= t`` are the
  ONLY keys the latent attention of token ``t`` reads, all heads alike
  (``ops/indexer.py``: the exact selection; ``ops/sparse_latent.py``: the
  gather). The
  indexer's constant factors (``index_n_heads ** -0.5 * index_head_dim **
  -0.5``) change no selection and are left out.
- **Window layer** (``cfg.window_cfg()``: other heads, head sizes, ranks,
  theta, rescales): the same latent attention over ``{s : t - swa_window <
  s <= t}``, no indexer, from a ring a sequence.
- **On the kernels** (``Step.kernel``) each row kind of each layer kind
  goes to a latent kernel with a bias - the rows of several tokens to
  ``ops/pallas/mla_ragged.py`` (``mla_selected``, ``mla_window``), the
  rows of one to ``ops/pallas/mla_decode_masked.py``
  (``mla_selected_rows``, ``mla_window_rows``: the ring as pages) - and
  XLA moves nothing of a step's ``T x nh x dkv`` elements around them: the
  queries enter heads-major, scaled and cast by ``W_UK``'s own matmul
  (``_kernel_queries``), the output leaves heads-major into ``W_UV``'s
  (``_finish``), the one-token rows are fetched and laid in place as flat
  rows (``_masked_rows``).
- **Gate**, both kinds: ``gamma = sigmoid(x W_g)`` a head, multiplied onto
  the head's attention output before the out-projection.
- **FFN**: the first ``first_k_dense_replace`` layers a SwiGLU; every
  other ``deepseek._gate`` (the ``noaux_tc`` sigmoid gate) over
  ``moe.grouped_experts`` told which experts it holds
  (``cfg.expert_offset``, ``cfg.experts_held``; a pick held elsewhere adds
  nothing here), plus the shared expert, computed here.

**Three kinds of cache** (``make_pages``, one donated tree through every
step program): ``kv`` the latent pages of the full layers ``[Lf, N, 2, 1,
ps, kv_lora_rank]``; ``index`` their index pages ``[Lf, N, ps,
index_head_dim]``, addressed by the same page table; ``win`` the window
layers' rings ``[Lw, slots + 1, R / ps, 2, 1, ps, swa_kv_lora_rank]``, a
slot a sequence, each a ring of ``R`` positions in pages of the latent
layout (``ops/sparse_latent.py``: token ``p`` at ``p mod R``), whose bytes
do not grow with the context. A
row's slot rides in the LAST column of its page-table row, as the
recurrent state of ``models/qwen3_next.py`` does; slot 0 is no request's.

Layers (``ModelConfig.layer_pattern``): the dense-FFN layers first, one
``lax.scan`` over periods of one full layer and ``G`` window layers (an
inner scan), then at most one more full layer. Weight layout:
``params["dense_layers"]`` leaves ``[K, ...]``; ``params["layers"]["full"]``
leaves ``[P + tail, ...]``; ``params["layers"]["win"]`` leaves ``[P, G,
...]``. No checkpoint loader: the family serves seeded random weights
until its published tensor names are in the repository.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.deepseek import (
    _attn_leaves,
    _cache_rows,
    _dense_mlp,
    _gate,
    _mla_qkv,
    _mla_scale,
    rope_interleaved,
)
from dynamo_tpu.models.llama import (
    MOE_INIT_GAIN,
    _logits,
    _rms_norm,
    packed_rows,
    randn_stack,
    write_rows,
)
from dynamo_tpu.ops import indexer
from dynamo_tpu.ops import sparse_latent as sl
from dynamo_tpu.ops.gdn import token_rows
from dynamo_tpu.ops.indexer import layer_norm as _layer_norm

Params = Dict[str, Any]

# Seeded weights (benchmarks/configs/dots3-note-prev.json ``assumed``, with
# the measurements). At the matrices' common scale a head's attention
# scores have a standard deviation of 0.30 before the softmax (measured at
# the published width, both kinds): every key weighs about the same - the
# largest probability of a query over 384 keys averages 0.006 - and
# neither a selection of 2,048 of 16,000 keys nor a window moves the output
# by more than bfloat16's noise. The queries' up-projection (``wq_b``) of
# BOTH attention kinds is drawn at ``QUERY_GAIN`` times the common scale:
# at 4 the scores' standard deviation is 1.18 and the eight largest
# probabilities hold a fifth of the mass. Not more, because the SERVED
# selection is itself bfloat16's: index keys cached in bfloat16 swap about
# five of a query's 2,048 keys at the 2,048th place against the float32
# reference (counted at these widths; float32 queries against the cached
# keys still three), and the sharper the softmax the more often a swapped
# key carries a head (none of 32,768 heads has a tenth of its mass on one
# at 4, one in three hundred at 10) - on the chip
# a clean run reads 0.12 nats at 4, 0.19 at 6, 0.67 at 8 and 1.6 at 10
# (standard deviation 2.95) against the benchmark's fixed 0.3.
QUERY_GAIN = 4.0
# The indexer's projections stay at the common scale: relu(q_I . k_I) then
# has a standard deviation of 2.7 and the score I one of 8.4 (same
# measurement), so a selection is decided by a score's leading digits.
INDEX_GAIN = 1.0


def make_pages(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=None, state_slots: int = 1,
               max_chunk: int = 1) -> Dict[str, jnp.ndarray]:
    """The family's cache (module docstring): latent pages and index pages
    of the full layers, and the window layers' rings, ``state_slots``
    requests' worth plus slot 0, each of ``ring_size(swa_window,
    max_chunk)`` positions - ``max_chunk`` the most tokens a row brings in
    one step."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    Lf, Lw, n = cfg.num_cache_layers, cfg.window_layers, state_slots + 1
    R = sl.ring_size(cfg.swa_window, max_chunk, page_size)
    return {
        "kv": jnp.zeros((Lf, num_pages, 2, 1, page_size, cfg.kv_lora_rank),
                        dtype),
        "index": jnp.zeros((Lf, num_pages, page_size, cfg.index_head_dim),
                           dtype),
        "win": jnp.zeros((Lw, n, R // page_size, 2, 1, page_size,
                          cfg.swa_kv_lora_rank), dtype),
    }


def window_bytes_per_sequence(cfg: ModelConfig, max_chunk: int,
                              page_size: int, dtype=None) -> int:
    """Bytes one sequence holds for its window layers, whatever its
    context: a ring a layer in the latent page layout (the rotary key
    padded to the latent's width)."""
    size = jnp.dtype(dtype or cfg.dtype).itemsize
    return (cfg.window_layers
            * sl.ring_size(cfg.swa_window, max_chunk, page_size)
            * 2 * cfg.swa_kv_lora_rank * size)


# ------------------------------------------------------------------ params

def _attn_stack(cfg: ModelConfig, key, scale: float, lead: tuple,
                indexer: bool) -> Dict[str, jnp.ndarray]:
    """One attention kind's leaves ``lead + (...)``: ``deepseek``'s MLA
    leaves at ``cfg``'s geometry, the gate, and the indexer's three
    matrices and its key norm."""
    n = 1
    for d in lead:
        n *= d
    dtype = jnp.dtype(cfg.dtype)
    H = cfg.hidden_size
    k_mla, k_q, k_gate, k_iq, k_ik, k_iw = jax.random.split(key, 6)
    leaves = _attn_leaves(cfg, k_mla, scale, n)
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    leaves["wq_b"] = randn_stack(k_q, n, (cfg.q_lora_rank,
                                          cfg.num_heads * qk),
                                 scale * QUERY_GAIN, dtype)
    if cfg.attn_gate:
        leaves["w_og"] = randn_stack(k_gate, n, (H, cfg.num_heads), scale,
                                     dtype)
    if indexer:
        J, D = cfg.index_n_heads, cfg.index_head_dim
        leaves["wi_qb"] = randn_stack(k_iq, n, (cfg.q_lora_rank, J * D),
                                      scale * INDEX_GAIN, dtype)
        leaves["wi_k"] = randn_stack(k_ik, n, (H, D), scale, dtype)
        leaves["wi_w"] = randn_stack(k_iw, n, (H, J), scale, dtype)
        leaves["i_norm_w"] = jnp.ones((n, D), dtype)
        leaves["i_norm_b"] = jnp.zeros((n, D), dtype)
    return {k: v.reshape(lead + v.shape[1:]) for k, v in leaves.items()}


def _ffn_stack(cfg: ModelConfig, key, scale: float,
               lead: tuple) -> Dict[str, jnp.ndarray]:
    """The sparse FFN's leaves ``lead + (...)``: router (+ its float32
    bias), shared expert, the experts this rank holds."""
    n = 1
    for d in lead:
        n *= d
    dtype = jnp.dtype(cfg.dtype)
    H, E, Eh = cfg.hidden_size, cfg.num_experts, cfg.experts_held
    Im = cfg.moe_intermediate_size
    Is = Im * cfg.n_shared_experts
    ks = iter(jax.random.split(key, 8))
    leaves = {"w_router": randn_stack(next(ks), n, (H, E), scale, dtype)}
    if cfg.topk_method == "noaux_tc":
        leaves["router_bias"] = jnp.zeros((n, E), jnp.float32)
    for leaf, shape in (("w_gate", (Eh, H, Im)), ("w_up", (Eh, H, Im)),
                        ("w_down", (Eh, Im, H)), ("ws_gate", (H, Is)),
                        ("ws_up", (H, Is)), ("ws_down", (Is, H))):
        if Is or not leaf.startswith("ws_"):
            leaves[leaf] = randn_stack(next(ks), n, shape, scale, dtype)
    return {k: v.reshape(lead + v.shape[1:]) for k, v in leaves.items()}


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init (tests/benchmarks; the benchmark's worker and its
    reference child both call this, so both hold the same weights). Every
    stack is drawn a layer at a time (``llama.randn_stack``) at ``scale``
    (default ``MOE_INIT_GAIN / sqrt(hidden)``) but the two query-side
    projections (``QUERY_GAIN``, ``INDEX_GAIN``). Only the experts this
    rank holds are drawn."""
    if scale is None:
        scale = MOE_INIT_GAIN / cfg.hidden_size ** 0.5
    dtype = jnp.dtype(cfg.dtype)
    H, F = cfg.hidden_size, cfg.intermediate_size
    K = cfg.first_k_dense_replace
    G, P, tail = cfg.layer_pattern()
    wcfg = cfg.window_cfg()
    k_embed, k_head, k_dense, k_full, k_win = jax.random.split(rng, 5)
    indexer = bool(cfg.index_topk)

    params: Params = {
        "embed": randn_stack(k_embed, 1, (cfg.vocab_size, H), scale,
                             dtype)[0],
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn_stack(k_head, 1, (H, cfg.vocab_size),
                                        scale, dtype)[0]
    if K:
        ka, kf = jax.random.split(k_dense)
        dl = _attn_stack(cfg, ka, scale, (K,), indexer)
        ks = jax.random.split(kf, 3)
        for key, (leaf, shape) in zip(ks, (("w_gate", (H, F)),
                                           ("w_up", (H, F)),
                                           ("w_down", (F, H)))):
            dl[leaf] = randn_stack(key, K, shape, scale, dtype)
        params["dense_layers"] = dl
    ka, kf = jax.random.split(k_full)
    kwa, kwf = jax.random.split(k_win)
    params["layers"] = {
        "full": {**_attn_stack(cfg, ka, scale, (P + tail,), indexer),
                 **_ffn_stack(cfg, kf, scale, (P + tail,))},
        "win": {**_attn_stack(wcfg, kwa, scale, (P, G), False),
                **_ffn_stack(cfg, kwf, scale, (P, G))},
    }
    return params


# -------------------------------------------------------------- the layers

class Step:
    """What every layer of one step shares: the rows on the flat axis
    (``ops/gdn.Rows``), each token's position, the step's form, and
    whether the step runs on the latent kernels (``kernel``: the chip -
    the masked form of a full layer's selection and of a window layer's
    ring for every row) or everything the gathered form (the CPU, the
    ``scan`` path, the oracle)."""

    def __init__(self, tokens, positions, page_table, total_lens, new_lens,
                 slots, starts, kernel: bool):
        B, S = tokens.shape
        self.packed = starts is not None
        self.width = S
        self.starts = starts
        self.positions = positions
        self.page_table = page_table
        self.total_lens, self.new_lens = total_lens, new_lens
        self.kernel = kernel
        self.rows = token_rows(
            B * S, starts if self.packed
            else jnp.arange(B, dtype=jnp.int32) * S,
            new_lens, total_lens, slots)
        self.pos = indexer.token_positions(self.rows, total_lens)
        # the rows the masked form takes: those of several tokens, and in
        # a [B, S > 1] step every row
        least = 1 if self.packed else 0
        self.q_lens = jnp.where(new_lens > least, new_lens, 0)
        # ... and the rows the one-token kernel takes, by their contexts
        self.one_lens = jnp.where(new_lens == 1, total_lens, 0)
        self.first = jnp.clip(self.rows.start, 0, B * S - 1)

    @property
    def walk(self) -> dict:
        return dict(width=self.width, packed=self.packed)

    @property
    def one_token(self) -> bool:
        """Whether the step can hold rows of one token."""
        return self.width == 1 or self.packed

    def write(self, pool, layer, k, v, table, ring: int = 0):
        """``llama.write_rows`` of ``k`` / ``v [B, S, 1, D]`` into a pool
        of the latent layout; ``ring``: positions wrap at ``ring`` (a
        window ring's pages, which ``table`` names twice over)."""
        positions, total = self.positions, self.total_lens
        if ring:
            begin = (total - self.new_lens) % ring
            positions, total = positions % ring, begin + self.new_lens
        return write_rows(pool, layer, k, v, table, positions, total,
                          self.new_lens, self.starts)


def _rope_head(cfg: ModelConfig, x: jnp.ndarray,
               positions: jnp.ndarray) -> jnp.ndarray:
    """Rotary on the first ``qk_rope_head_dim`` of the last axis, in the
    family's convention (``cfg.rope_interleave``, ``cfg.rope_theta``)."""
    dr = cfg.qk_rope_head_dim
    return jnp.concatenate([
        rope_interleaved(x[..., :dr], positions, cfg.rope_theta,
                         interleaved=cfg.rope_interleave), x[..., dr:]], -1)


def _finish(cfg: ModelConfig, lp, h, x, lat, w_uv) -> jnp.ndarray:
    """``lat [nh, N, dkv]`` latent attention output, heads-major as the
    masked kernels write it -> ``W_UV`` expand (a batched matmul over
    heads) -> the headwise gate -> the out-projection residual."""
    B, S, H = h.shape
    out = jnp.einsum("hnk,hkd->nhd", lat, w_uv.astype(jnp.float32))
    if cfg.attn_gate:
        with stage("gate"):
            gamma = jax.nn.sigmoid(jnp.dot(
                x.reshape(B * S, H), lp["w_og"],
                preferred_element_type=jnp.float32))
            out = out * gamma[:, :, None]
    out = out.reshape(B, S, cfg.num_heads * cfg.v_head_dim).astype(h.dtype)
    return h + out @ lp["wo"]


def _kernel_queries(q_lat, q_pe, scale: float, dtype):
    """``_mla_qkv``'s queries ``[B, S, nh, d]`` as the masked kernels take
    them: heads-major ``[nh, B * S, d]`` - the layout ``W_UK``'s batched
    matmul over heads writes, so the absorbed queries are never moved -
    times the softmax scale and in the cache's ``dtype``, in the pass that
    writes them (the kernels are then called with a scale of 1)."""
    B, S, nh, _ = q_lat.shape
    return tuple(
        (jnp.moveaxis(q.reshape(B * S, nh, -1), 1, 0).astype(jnp.float32)
         * scale).astype(dtype) for q in (q_lat, q_pe))


def index_inputs(cfg: ModelConfig, lp, x: jnp.ndarray,
                 positions: jnp.ndarray):
    """The indexer's three projections of the normed stream ``x [B, S,
    H]``: ``(q_I [N, J, D], k_I [N, D], w [N, J] float32)``."""
    B, S, H = x.shape
    J, D = cfg.index_n_heads, cfg.index_head_dim
    eps = cfg.rms_norm_eps
    c_q = _rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps)
    q = _rope_head(cfg, (c_q @ lp["wi_qb"]).reshape(B, S, J, D), positions)
    k = _rope_head(cfg, _layer_norm(x @ lp["wi_k"], lp["i_norm_w"],
                                    lp["i_norm_b"]), positions)
    w = jnp.dot(x, lp["wi_w"], preferred_element_type=jnp.float32)
    return (q.reshape(B * S, J, D), k.reshape(B * S, D),
            w.reshape(B * S, J))


def _masked(st: Step, q, pool, layer, table, kv_lens, bias, name: str):
    """The masked form (``ops/pallas/mla_ragged.py`` with a bias) for the
    step's rows of several tokens, ``q`` the ``_kernel_queries``: ``[nh,
    N, dkv]`` float32, heads-major as ``_finish`` reads it, zero in every
    other slot."""
    from dynamo_tpu.ops.pallas.mla_ragged import mla_masked_attention_packed

    return mla_masked_attention_packed(
        *q, pool, layer, table, st.rows.start, st.q_lens, kv_lens, bias,
        1.0, name=name)


def _masked_rows(st: Step, lat, q, pool, layer, table, kv_lens, bias,
                 name: str):
    """The step's rows of ONE token through the latent decode kernel with
    a bias (``ops/pallas/mla_decode_masked.py``; ``bias [R, S]``; a row of
    ``kv_lens`` 0 streams nothing), laid over the masked form's ``lat
    [nh, N, dkv]`` (None: the step holds no row of several tokens) at each
    row's slot. On the flat axis ``[nh * N]`` a row's heads lie ``N``
    apart: its queries are fetched, and its result laid in place, as
    ``nh`` rows of ``dkv`` - no pass over ``q`` or ``lat``."""
    from dynamo_tpu.ops.pallas.mla_decode_masked import (
        mla_masked_decode_stacked)

    nh, N, dkv = q[0].shape
    heads = jnp.arange(nh, dtype=jnp.int32)[None, :] * N
    at = heads + st.first[:, None]                             # [R, nh]
    res = mla_masked_decode_stacked(
        *(x.reshape(nh * N, -1)[at] for x in q), pool, layer, table,
        kv_lens, bias, 1.0, name=name)
    if lat is None:
        lat = jnp.zeros((nh, N, dkv), jnp.float32)
    to = jnp.where((st.new_lens == 1)[:, None], at, nh * N)   # or nowhere
    return lat.reshape(nh * N, dkv).at[to].set(res, mode="drop").reshape(
        nh, N, dkv)


def full_block(cfg: ModelConfig, lp, h, cache, lidx, st: Step):
    """``h + Attn(norm(h))`` of a full-attention layer against layer
    ``lidx`` of the latent and the index pages. Returns ``(h, cache)``."""
    B, S, H = h.shape
    N, nh = B * S, cfg.num_heads
    kv, index = cache["kv"], cache["index"]
    with stage("layer.attn_in"):
        q_lat, q_pe, c_kv, k_pe, w_uv = _mla_qkv(cfg, lp, h, st.positions)
        k_new, v_new = _cache_rows(cfg, c_kv, k_pe)
        x = _rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q_i, k_i, w_i = index_inputs(cfg, lp, x, st.positions)
    with stage("layer.kv_write"):
        kv = st.write(kv, lidx, k_new, v_new, st.page_table)
        index = indexer.write_index_keys(
            index, lidx, k_i.reshape(B, S, -1), st.page_table,
            st.positions, st.total_lens, st.new_lens, st.starts)
    with stage("layer.attn"):
        scale = _mla_scale(cfg)
        q_i = q_i.astype(index.dtype)
        if st.kernel:
            one, bias = indexer.select_split(
                q_i, w_i, index, lidx, st.page_table, st.rows,
                st.total_lens, cfg.index_topk, **st.walk)
            with stage("sparse"):
                q = _kernel_queries(q_lat, q_pe, scale, kv.dtype)
                lat = None
                if bias is not None:
                    lat = _masked(st, q, kv, lidx, st.page_table,
                                  st.total_lens, bias, "mla_selected")
                if one is not None:
                    rows_bias, _to = one
                    lat = _masked_rows(st, lat, q, kv, lidx, st.page_table,
                                       st.one_lens, rows_bias,
                                       "mla_selected_rows")
        else:
            sel, live = indexer.select(
                q_i, w_i, index, lidx, st.page_table, st.rows,
                st.total_lens, cfg.index_topk, **st.walk)
            with stage("sparse"):
                lat = sl.sparse_attend(
                    q_lat.reshape(N, nh, -1), q_pe.reshape(N, nh, -1), kv,
                    lidx, st.page_table[st.rows.row], sel,
                    live & st.rows.valid[:, None], scale).swapaxes(0, 1)
    with stage("layer.attn_out"):
        h = _finish(cfg, lp, h, x, lat, w_uv)
    return h, {**cache, "kv": kv, "index": index}


def window_block(wcfg: ModelConfig, lp, h, cache, widx, st: Step):
    """``h + Attn(norm(h))`` of a window layer (``wcfg``: the window
    geometry, ``ModelConfig.window_cfg``) against layer ``widx`` of the
    rings. Returns ``(h, cache)``."""
    B, S, H = h.shape
    N, nh = B * S, wcfg.num_heads
    win = cache["win"]
    Lw, n_slots, Rp, _two, _one, ps, dkv = win.shape
    ring = Rp * ps
    with stage("layer.attn_in"):
        q_lat, q_pe, c_kv, k_pe, w_uv = _mla_qkv(wcfg, lp, h, st.positions)
        k_new, v_new = _cache_rows(wcfg, c_kv, k_pe)
        x = _rms_norm(h, lp["attn_norm"], wcfg.rms_norm_eps)
    with stage("layer.kv_write"):
        pool = st.write(win.reshape(Lw, n_slots * Rp, 2, 1, ps, dkv), widx,
                        k_new, v_new,
                        sl.ring_table(st.rows.slot, Rp, twice=True), ring)
        win = pool.reshape(win.shape)
    with stage("layer.attn"):
        with stage("window"):
            scale = _mla_scale(wcfg)
            if st.kernel:
                # the rings as pages: each row's ring streamed with its
                # entries' true positions as the bias, both row kinds
                table = sl.ring_table(st.rows.slot, Rp)
                bias = jnp.where(
                    sl.ring_seen(st.rows, st.pos, st.total_lens, ring,
                                 wcfg.swa_window), 0.0, indexer.NEG_INF)
                q = _kernel_queries(q_lat, q_pe, scale, pool.dtype)
                lat = None
                if st.width > 1:
                    lat = _masked(st, q, pool, widx, table,
                                  jnp.minimum(st.total_lens, ring), bias,
                                  "mla_window")
                if st.one_token:
                    lat = _masked_rows(st, lat, q, pool, widx, table,
                                       jnp.minimum(st.one_lens, ring),
                                       bias[st.first], "mla_window_rows")
            else:
                lat = sl.window_attend(
                    q_lat.reshape(N, nh, -1), q_pe.reshape(N, nh, -1), win,
                    widx, st.rows, st.total_lens, wcfg.swa_window, scale,
                    **st.walk).swapaxes(0, 1)
    with stage("layer.attn_out"):
        h = _finish(wcfg, lp, h, x, lat, w_uv)
    return h, {**cache, "win": win}


def sparse_block(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 x: jnp.ndarray, **kw
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The layer's FFN as this rank computes it: its held experts' part of
    the routed sum plus the shared expert. ``x [B, S, H]`` (normed) ->
    ``([B, S, H], aux)``; ``kw`` goes to ``grouped_experts``."""
    from dynamo_tpu.models.moe import grouped_experts

    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with stage("route"):
        top_w, top_i = _gate(cfg, lp, x)
    out, aux = grouped_experts(
        xt, top_w.reshape(B * S, -1), top_i.reshape(B * S, -1),
        lp["w_gate"], lp["w_up"], lp["w_down"],
        first_expert=cfg.expert_offset, num_routed=cfg.num_experts, **kw)
    if cfg.n_shared_experts:
        with stage("shared"):
            out = out + jnp.dot(
                jax.nn.silu(xt @ lp["ws_gate"]) * (xt @ lp["ws_up"]),
                lp["ws_down"], preferred_element_type=jnp.float32)
    return out.reshape(B, S, H).astype(x.dtype), aux


def _ffn(cfg, lp, h, moe_kw):
    with stage("layer.moe"):
        out, aux = sparse_block(
            cfg, lp, _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps),
            **moe_kw)
        h = h + out
    return h, aux


# ----------------------------------------------------------------- forward

def on_kernels(cfg: ModelConfig, attn_impl: Optional[Callable],
               page_size: int) -> bool:
    """Whether a step handed ``attn_impl`` runs the family's latent
    kernels (``Step.kernel``): the engine's Pallas marker, and both
    attention kinds' latents and the pages at widths the kernels tile."""
    from dynamo_tpu.ops.pallas.mla_decode import supports

    return bool(getattr(attn_impl, "pallas_paged_kernel", False)
                and supports(cfg.kv_lora_rank, page_size)
                and supports(cfg.swa_kv_lora_rank, page_size))


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: Dict[str, jnp.ndarray],
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, packed: bool = False
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], dict]:
    """Scan forward (``llama.forward`` contract, the token-packed form
    included, plus the ``aux`` third return of the MoE families).
    ``pages`` is ``make_pages``'s tree; ``page_table [B, P + 1]`` carries
    each row's window slot in its last column. A passed ``attn_impl`` is
    never called: its ``pallas_paged_kernel`` marker opts the family into
    the masked form of both attention kinds for the rows of several
    tokens (``ops/pallas/mla_ragged.py`` with a bias: ``mla_selected``,
    ``mla_window`` in a device trace) and for the rows of one
    (``ops/pallas/mla_decode_masked.py``: ``mla_selected_rows``,
    ``mla_window_rows``) and into ``moe_grouped``. No ``logits_window``:
    a verify window or a scoring pass would have to take back what a
    rejected token wrote to a ring, so the engine offers neither."""
    from dynamo_tpu.models.moe import (flat_layers, grouped_on_chip,
                                       layer_at, split_experts, sum_aux,
                                       token_slots)

    if cfg.moe_backend != "grouped":
        raise NotImplementedError(
            f"moe_backend {cfg.moe_backend!r}: this family's sparse block "
            "(held range, shared expert) runs the grouped layer only")
    # (a slot past the pool's is held to its last: a write to no slot would
    # be dropped in silence)
    with stage("step.inputs"):
        slots = jnp.minimum(page_table[:, -1], pages["win"].shape[1] - 1)
        page_table = page_table[:, :-1]
        st = Step(tokens, positions, page_table, total_lens, new_lens,
                  slots, packed_rows(packed, new_lens),
                  on_kernels(cfg, attn_impl, pages["kv"].shape[-2]))
    wcfg = cfg.window_cfg()
    K = cfg.first_k_dense_replace
    G, P, tail = cfg.layer_pattern()
    with stage("embed"):
        h = params["embed"][tokens]
    with stage("step.inputs"):
        moe_kw = dict(valid=token_slots(tokens, new_lens, packed),
                      use_pallas=grouped_on_chip(attn_impl))
    lf, lw = params["layers"]["full"], params["layers"]["win"]
    full_scanned, full_experts = split_experts(cfg, lf)
    # the window layers as ONE stack over periods and places: the loops
    # below carry indices alone, each layer's leaves are read where they
    # lie (``moe.flat_layers``) and the grouped layer indexes the experts
    with stage("layer.weights"):
        win_scanned, win_experts = split_experts(cfg, flat_layers(lw))

    def dense(carry, xs):
        h, cache = carry
        lp, lidx = xs
        h, cache = full_block(cfg, lp, h, cache, lidx, st)
        with stage("layer.ffn"):
            h = h + _dense_mlp(lp, _rms_norm(h, lp["mlp_norm"],
                                             cfg.rms_norm_eps))
        return (h, cache), None

    def full(carry, p):
        h, cache = carry
        with stage("layer.weights"):
            fp = layer_at(full_scanned, p)
            lidx = K + p
        h, cache = full_block(cfg, fp, h, cache, lidx, st)
        h, aux = _ffn(cfg, {**fp, **full_experts}, h, dict(moe_kw, layer=p))
        return (h, cache), aux

    def period(carry, p):
        carry, aux_f = full(carry, p)

        def window(carry, j):
            h, cache = carry
            with stage("layer.weights"):
                widx = p * G + j
                lp = layer_at(win_scanned, widx)
            h, cache = window_block(wcfg, lp, h, cache, widx, st)
            h, aux = _ffn(cfg, {**lp, **win_experts}, h,
                          dict(moe_kw, layer=widx))
            return (h, cache), aux

        with stage("step.inputs"):
            places = jnp.arange(G)
        carry, aux_w = jax.lax.scan(window, carry, places)
        with stage("step.counts"):
            aux = {k: aux_f[k] + jnp.sum(aux_w[k]) for k in aux_f}
        return carry, aux

    if K:
        with stage("step.inputs"):
            layer_ids = jnp.arange(K)
        (h, pages), _ = jax.lax.scan(dense, (h, pages),
                                     (params["dense_layers"], layer_ids))
    with stage("step.inputs"):
        periods = jnp.arange(P)
    (h, pages), aux = jax.lax.scan(period, (h, pages), periods)
    with stage("step.counts"):
        aux = sum_aux(aux)
    for t in range(tail):
        (h, pages), aux_t = full((h, pages), P + t)
        with stage("step.counts"):
            aux = {k: aux[k] + aux_t[k].astype(jnp.int32) for k in aux}
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, starts=st.starts)
    return logits, pages, aux


forward.supports_packed = True


__all__ = ["init_params", "forward", "on_kernels", "make_pages",
           "sparse_block", "index_inputs", "full_block", "window_block",
           "window_bytes_per_sequence"]
