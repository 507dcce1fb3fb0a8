"""DeepSeek V2/V3 family: MLA attention + shared/routed MoE, pure jax.

The reference serves DeepSeek only through SGLang's CUDA stack (the wide-EP
DSR1 recipe, ``components/backends/sglang/docs/dsr1-wideep-h100.md``); here
the architecture is native. The TPU-first choice is Multi-head Latent
Attention in its **absorbed** inference form:

- The paged KV cache stores ONLY the compressed latent per token — slot 0
  of the generic page layout holds the rms-normed ``c_kv``
  (``kv_lora_rank`` wide), slot 1 the shared roped key (``qk_rope_head_dim``
  wide, zero-padded to the latent width). At DeepSeek-V3 geometry that is
  ~1 KB/token vs ~16 KB for equivalent MHA — the cache reduction that makes
  long-context R1 serving fit HBM.
- Attention runs IN LATENT SPACE: ``kv_b_proj`` is split into per-head
  ``W_UK``/``W_UV``; queries absorb ``W_UK`` (``q_nope @ W_UK``) so scores
  are ``q_lat · c_kv + q_pe · k_pe``, and the attention output re-expands
  through ``W_UV`` — no per-head K/V ever materializes for the context.
  This is algebraically identical to the HF eager path
  (``transformers/models/deepseek_v2/modeling_deepseek_v2.py:339-430``,
  checked by the parity test).
- RoPE follows ``cfg.rope_interleave``: the complex-pair convention HF
  defaults to for this family, or llama's rotate-half when a checkpoint
  ships de-interleaved weights; V3 additionally folds the yarn mscale
  into the softmax scale (``_mla_scale``).
- Layers are heterogeneous (``first_k_dense_replace`` dense layers, then
  MoE): the scan forward runs TWO scans over two stacked pytrees
  (``dense_layers`` / ``moe_layers``) sharing one paged cache, keeping the
  single-compiled-layer-body property per layer kind.
- The MoE gate matches HF exactly per generation: V2's f32 softmax scores
  with ``greedy`` / ``group_limited_greedy`` top-k (no renorm), and V3's
  aux-loss-free ``noaux_tc`` gate (sigmoid scores, e_score_correction_bias
  group selection, renormalized weights) — both scaled by
  ``routed_scaling_factor``; routed experts run the one exact grouped
  layer (``models/moe.grouped_experts``: sorted by expert, one grouped
  matmul over the groups that exist, no drop) or, by ``cfg.moe_backend``,
  the capacity dispatch, plus the always-on shared experts.

Weight layout matches HF checkpoints after transpose; ``load_params``
assembles the two layer stacks from safetensors.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    MOE_INIT_GAIN as INIT_GAIN,
    _logits,
    _rms_norm,
    randn_stack as _randn_stack,
    make_pages,
    packed_rows,
    write_rows,
)
from dynamo_tpu.ops.attention import NEG_INF, _pad_table, packed_token_rows

Params = Dict[str, Any]


def yarn_freqs(cfg: ModelConfig) -> Tuple[np.ndarray, float]:
    """(inv_freq [dr/2], attention_factor) — HF's
    ``_compute_yarn_parameters`` (``modeling_rope_utils.py:246``) for the
    rope head dim; identity when the config carries no yarn scaling."""
    import math

    dr = cfg.qk_rope_head_dim
    base = cfg.rope_theta
    pos_freqs = base ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    if not cfg.rope_scaling_factor:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    factor = cfg.rope_scaling_factor
    orig = cfg.rope_orig_max_position or cfg.max_position_embeddings

    def get_mscale(scale, mscale=1.0):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    if cfg.rope_attention_factor:
        attention_factor = cfg.rope_attention_factor
    elif cfg.rope_mscale and cfg.rope_mscale_all_dim:
        attention_factor = (get_mscale(factor, cfg.rope_mscale)
                            / get_mscale(factor, cfg.rope_mscale_all_dim))
    else:
        attention_factor = get_mscale(factor)

    def correction_dim(num_rot):
        return (dr * math.log(orig / (num_rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dr - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dr // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = ((1.0 / (factor * pos_freqs))
                * (1 - extrapolation_factor)
                + (1.0 / pos_freqs) * extrapolation_factor)
    return inv_freq.astype(np.float32), float(attention_factor)


def rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                     theta: float,
                     inv_freq: Optional[np.ndarray] = None,
                     scale: float = 1.0,
                     interleaved: bool = True) -> jnp.ndarray:
    """RoPE in either deepseek convention, the result scaled by the yarn
    ``attention_factor`` (HF multiplies the cos/sin magnitude):

    - ``interleaved=True`` — complex-pair (HF ``apply_rotary_emb`` /
      ``rope_interleave=True``): consecutive PAIRS (x[2i], x[2i+1]) rotate;
    - ``interleaved=False`` — llama rotate-half over (x[:D/2], x[D/2:]).

    x: [B, S, ..., D]; positions: [B, S]."""
    D = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2,
                                               dtype=jnp.float32) / D))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, S, D/2]
    while ang.ndim < x.ndim:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    if interleaved:
        xr = x[..., 0::2].astype(jnp.float32)
        xi = x[..., 1::2].astype(jnp.float32)
        out = jnp.stack([xr * cos - xi * sin, xr * sin + xi * cos],
                        axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1 = x[..., :D // 2].astype(jnp.float32)
    x2 = x[..., D // 2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- params

def _attn_leaves(cfg: ModelConfig, key, scale: float,
                 n: int) -> Dict[str, jnp.ndarray]:
    dtype = jnp.dtype(cfg.dtype)
    H = cfg.hidden_size
    qk_head = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = iter(jax.random.split(key, 8))

    def randn(shape):
        return _randn_stack(next(keys), n, shape, scale, dtype)

    leaves = {
        "attn_norm": jnp.ones((n, H), dtype),
        "wkv_a": randn((H, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
        "kv_a_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
        "wkv_b": randn((cfg.kv_lora_rank,
                        cfg.num_heads * (cfg.qk_nope_head_dim
                                         + cfg.v_head_dim))),
        "wo": randn((cfg.num_heads * cfg.v_head_dim, H)),
        "mlp_norm": jnp.ones((n, H), dtype),
    }
    if cfg.q_lora_rank:
        leaves["wq_a"] = randn((H, cfg.q_lora_rank))
        leaves["q_a_norm"] = jnp.ones((n, cfg.q_lora_rank), dtype)
        leaves["wq_b"] = randn((cfg.q_lora_rank, cfg.num_heads * qk_head))
    else:
        leaves["wq"] = randn((H, cfg.num_heads * qk_head))
    return leaves


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init with the two-stack layer layout (tests/benchmarks; the
    benchmark's worker and its reference child both call this, so both
    hold the same weights). Every stacked tensor is drawn a layer at a
    time (``_randn_stack``), with standard deviation ``scale`` (default
    ``INIT_GAIN / sqrt(hidden)``).

    Why 0.012: renormalised sigmoid scores weigh the 8 chosen of 256
    experts 1/8 each, and bfloat16 rounding swaps one at the top-k
    boundary in some tokens against a float32 run. Swaps and real faults
    both move log-probabilities as the square of the scale, so the scale
    only places the benchmark's fixed 0.3 nats between them. On the chip
    (PERF.md section 6, PR 34): at 0.02 a clean run reads 0.41 (refused);
    at 0.006 a clean run 0.043, but every expert swapped for its neighbour
    only 0.18 (passes); at 0.012 clean 0.145, one expert layer's routed
    output dropped 0.37, experts swapped 0.78 (both refused). Experts in
    3-bit mantissas read 0.22 there: the check does not see that."""
    if scale is None:
        scale = INIT_GAIN / cfg.hidden_size ** 0.5
    dtype = jnp.dtype(cfg.dtype)
    H, E = cfg.hidden_size, cfg.num_experts
    Im = cfg.moe_intermediate_size or cfg.intermediate_size
    K = cfg.first_k_dense_replace
    M = cfg.num_layers - K
    k_dense, k_moe, k_embed, k_head = jax.random.split(rng, 4)

    params: Params = {
        "embed": _randn_stack(k_embed, 1, (cfg.vocab_size, H), scale,
                              dtype)[0],
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = _randn_stack(
            k_head, 1, (H, cfg.vocab_size), scale, dtype)[0]
    if K:
        dl = _attn_leaves(cfg, k_dense, scale, K)
        ks = iter(jax.random.split(jax.random.fold_in(k_dense, 1), 3))
        for leaf, shape in (("w_gate", (H, cfg.intermediate_size)),
                            ("w_up", (H, cfg.intermediate_size)),
                            ("w_down", (cfg.intermediate_size, H))):
            dl[leaf] = _randn_stack(next(ks), K, shape, scale, dtype)
        params["dense_layers"] = dl
    if M:
        ml = _attn_leaves(cfg, k_moe, scale, M)
        ks = iter(jax.random.split(jax.random.fold_in(k_moe, 1), 8))
        ml["w_router"] = _randn_stack(next(ks), M, (H, E), scale, dtype)
        if cfg.topk_method == "noaux_tc":
            ml["router_bias"] = jnp.zeros((M, E), jnp.float32)
        for leaf, shape in (("w_gate", (E, H, Im)), ("w_up", (E, H, Im)),
                            ("w_down", (E, Im, H))):
            ml[leaf] = _randn_stack(next(ks), M, shape, scale, dtype)
        if cfg.n_shared_experts:
            Is = Im * cfg.n_shared_experts
            for leaf, shape in (("ws_gate", (H, Is)), ("ws_up", (H, Is)),
                                ("ws_down", (Is, H))):
                ml[leaf] = _randn_stack(next(ks), M, shape, scale, dtype)
        params["moe_layers"] = ml
    return params


# ---------------------------------------------------------------- attention

def _mla_qkv(cfg: ModelConfig, lp: Dict[str, jnp.ndarray], h: jnp.ndarray,
             positions: jnp.ndarray):
    """Pre-attention MLA math: queries (latent-absorbed + rope) and the new
    tokens' cache rows. Returns (q_lat [B,S,nh,dkv], q_pe [B,S,nh,dr],
    c_kv [B,S,dkv], k_pe [B,S,dr], w_uv [nh,dkv,dv])."""
    B, S, H = h.shape
    nh = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dkv, dv = cfg.kv_lora_rank, cfg.v_head_dim
    eps = cfg.rms_norm_eps
    x = _rms_norm(h, lp["attn_norm"], eps)
    if cfg.q_lora_rank:
        with stage("q_compress"):
            q = _rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps) @ lp["wq_b"]
    else:
        q = x @ lp["wq"]
    if cfg.mla_q_scale != 1.0:          # LongCat's mla_scale_q_lora
        q = (q.astype(jnp.float32) * cfg.mla_q_scale).astype(q.dtype)
    q = q.reshape(B, S, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    inv_freq, att_scale = yarn_freqs(cfg)
    q_pe = rope_interleaved(q_pe, positions, cfg.rope_theta,
                            inv_freq=inv_freq, scale=att_scale,
                            interleaved=cfg.rope_interleave)

    ckv = x @ lp["wkv_a"]                                  # [B,S,dkv+dr]
    c_kv = _rms_norm(ckv[..., :dkv], lp["kv_a_norm"], eps)
    if cfg.mla_kv_scale != 1.0:
        # LongCat's mla_scale_kv_lora: the cache holds the SCALED latent,
        # so keys and values both expand from it as published
        # (multiplied in float32: bfloat16 holds sqrt(12) to 0.13 % only)
        c_kv = (c_kv.astype(jnp.float32)
                * cfg.mla_kv_scale).astype(c_kv.dtype)
    k_pe = rope_interleaved(ckv[..., dkv:], positions, cfg.rope_theta,
                            inv_freq=inv_freq, scale=att_scale,
                            interleaved=cfg.rope_interleave)

    w_kb = lp["wkv_b"].reshape(dkv, nh, dn + dv)
    w_uk = w_kb[..., :dn].transpose(1, 0, 2)               # [nh, dkv, dn]
    w_uv = w_kb[..., dn:].transpose(1, 0, 2)               # [nh, dkv, dv]
    # absorb W_UK into the queries: scores run in latent space
    q_lat = jnp.einsum("bsnd,nkd->bsnk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    return q_lat, q_pe, c_kv, k_pe, w_uv


def _cache_rows(cfg: ModelConfig, c_kv: jnp.ndarray, k_pe: jnp.ndarray):
    """(k_new, v_new) for the generic paged write: slot 0 = latent,
    slot 1 = rope key padded to the latent width. Both [B, S, 1, dkv]."""
    pad = cfg.kv_lora_rank - cfg.qk_rope_head_dim
    k_pe_padded = jnp.pad(k_pe, ((0, 0), (0, 0), (0, pad)))
    return c_kv[:, :, None, :], k_pe_padded[:, :, None, :]


# pages per streamed chunk on the blockwise path (matches ops/attention)
PAGES_PER_CHUNK = 8


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale. V3 folds the yarn mscale into the SCORE scale
    (``modeling_deepseek_v3.py:371-377``: scaling *= mscale^2 when
    rope_scaling carries mscale_all_dim); V2 expresses it through the
    rope attention_factor instead (handled in ``yarn_freqs``)."""
    import math

    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if (cfg.model_type == "deepseek_v3" and cfg.rope_scaling_factor
            and cfg.rope_mscale_all_dim):
        m = (0.1 * cfg.rope_mscale_all_dim
             * math.log(cfg.rope_scaling_factor) + 1.0
             if cfg.rope_scaling_factor > 1 else 1.0)
        scale *= m * m
    return scale


def _expand_and_project(cfg: ModelConfig, lp, h, lat, w_uv) -> jnp.ndarray:
    """lat [B,S,nh,dkv] latent attention output -> W_UV expand -> wo
    residual."""
    B, S, H = h.shape
    out = jnp.einsum("bsnk,nkd->bsnd", lat, w_uv.astype(jnp.float32))
    out = out.reshape(B, S, cfg.num_heads * cfg.v_head_dim).astype(h.dtype)
    return h + out @ lp["wo"]


def _mla_latent(cfg: ModelConfig, q_lat, q_pe, ckv_ctx: jnp.ndarray,
                kpe_ctx: jnp.ndarray, positions: jnp.ndarray,
                total_lens: jnp.ndarray) -> jnp.ndarray:
    """Latent-space attention (direct path: decode steps / small tables —
    the full [B,nh,S,T] scores fit). ckv_ctx/kpe_ctx: [B, T, dkv] /
    [B, T, dr] gathered context. Returns the latent output
    [B, S, nh, dkv]."""
    sm_scale = _mla_scale(cfg)
    T = ckv_ctx.shape[1]
    scores = (jnp.einsum("bsnk,btk->bnst", q_lat,
                         ckv_ctx.astype(jnp.float32))
              + jnp.einsum("bsnd,btd->bnst", q_pe.astype(jnp.float32),
                           kpe_ctx.astype(jnp.float32))) * sm_scale
    t_pos = jnp.arange(T)[None, None, None, :]
    mask = ((t_pos <= positions[:, None, :, None])
            & (t_pos < total_lens[:, None, None, None]))
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                # [B,nh,S,T]
    return jnp.einsum("bnst,btk->bsnk", probs,
                      ckv_ctx.astype(jnp.float32))         # [B,S,nh,dkv]


def _mla_attend(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                h: jnp.ndarray, q_lat, q_pe, w_uv,
                ckv_ctx: jnp.ndarray, kpe_ctx: jnp.ndarray,
                positions: jnp.ndarray, total_lens: jnp.ndarray
                ) -> jnp.ndarray:
    """``_mla_latent`` + output projection residual."""
    lat = _mla_latent(cfg, q_lat, q_pe, ckv_ctx, kpe_ctx, positions,
                      total_lens)
    return _expand_and_project(cfg, lp, h, lat, w_uv)


def _mla_attend_blockwise(cfg: ModelConfig, lp, h, q_lat, q_pe, w_uv,
                          gather_chunk, num_table_pages: int, ps: int,
                          positions: jnp.ndarray, total_lens: jnp.ndarray
                          ) -> jnp.ndarray:
    """``_latent_blockwise`` + output projection residual: the XLA path
    of a prefill chunk batch (S > 1)."""
    lat = _latent_blockwise(q_lat, q_pe, gather_chunk, num_table_pages, ps,
                            positions, total_lens, _mla_scale(cfg))
    return _expand_and_project(cfg, lp, h, lat, w_uv)


def _latent_blockwise(q_lat, q_pe, gather_chunk, num_table_pages: int,
                      ps: int, positions: jnp.ndarray,
                      total_lens: jnp.ndarray, sm_scale: float
                      ) -> jnp.ndarray:
    """Flash-style chunked latent attention: the context streams in page
    chunks with an online softmax, so the peak intermediate is
    ``[B, nh, S, span]`` scores + a fixed ``[B, nh, S, dkv]`` latent
    accumulator regardless of context length — the full-gather path's
    ``[B, nh, S, T]`` scores are GBs per layer at DeepSeek-V3 head counts
    (same failure mode ``ops/attention._attend_blockwise`` exists for).
    ``gather_chunk(c)`` gives chunk ``c`` of the (padded) table as
    ``_gather_ctx`` does. Returns the latent output [B, S, nh, dkv]."""
    B, S, nh, dkv = q_lat.shape
    span = PAGES_PER_CHUNK * ps
    n_static = -(-num_table_pages // PAGES_PER_CHUNK)
    n_chunks = jnp.minimum(
        (jnp.max(total_lens) + span - 1) // span, n_static)
    q_pe32 = q_pe.astype(jnp.float32)

    def body(c, carry):
        num, den, mx = carry
        ckv, kpe = gather_chunk(c)            # [B, span, dkv] / [B, span, dr]
        s = (jnp.einsum("bsnk,btk->bnst", q_lat, ckv.astype(jnp.float32))
             + jnp.einsum("bsnd,btd->bnst", q_pe32,
                          kpe.astype(jnp.float32))) * sm_scale
        t_pos = c * span + jnp.arange(span)
        mask = ((t_pos[None, None, None, :] <= positions[:, None, :, None])
                & (t_pos[None, None, None, :]
                   < total_lens[:, None, None, None]))
        s = jnp.where(mask, s, NEG_INF)
        mx_new = jnp.maximum(mx, jnp.max(s, axis=-1))      # [B,nh,S]
        p = jnp.exp(s - mx_new[..., None])
        p = jnp.where((mx_new > NEG_INF / 2)[..., None], p, 0.0)
        scale = jnp.where(mx > NEG_INF / 2, jnp.exp(mx - mx_new), 0.0)
        pv = jnp.einsum("bnst,btk->bnsk", p, ckv.astype(jnp.float32))
        num = num * scale[..., None] + pv
        den = den * scale + jnp.sum(p, axis=-1)
        return num, den, mx_new

    num0 = jnp.zeros((B, nh, S, dkv), jnp.float32)
    den0 = jnp.zeros((B, nh, S), jnp.float32)
    mx0 = jnp.full((B, nh, S), NEG_INF, jnp.float32)
    num, den, _mx = jax.lax.fori_loop(0, n_chunks, body, (num0, den0, mx0))
    return (num / jnp.maximum(den, 1e-20)[..., None]) \
        .transpose(0, 2, 1, 3)                             # [B,S,nh,dkv]


def mla_ragged_attention(cfg: ModelConfig, q_lat: jnp.ndarray,
                         q_pe: jnp.ndarray, pages: jnp.ndarray, layer_idx,
                         page_table: jnp.ndarray, q_starts: jnp.ndarray,
                         q_lens: jnp.ndarray, kv_lens: jnp.ndarray
                         ) -> jnp.ndarray:
    """Latent attention of a TOKEN-PACKED step (``llama.packed_rows``):
    the pure-JAX reference of ``ops/pallas/mla_ragged.py``, the CPU-test
    oracle, and what a packed forward runs when it is handed no kernel.

    q_lat [T, nh, dkv] / q_pe [T, nh, dr] hold every row's query tokens
    back to back, row ``r`` at slots ``q_starts[r] .. q_starts[r] +
    q_lens[r]`` and positions ``kv_lens[r] - q_lens[r] ..``; page_table
    [R, P]. Each token attends to its row's pages as a [T, 1]-query batch
    of ``_latent_blockwise`` (``ops.attention.ragged_paged_attention``'s
    construction). Returns the latent output [T, nh, dkv] in f32, zero in
    the slots of no row."""
    T = q_lat.shape[0]
    P = page_table.shape[1]
    valid, pos, tok_table, tok_total = packed_token_rows(
        T, page_table, q_starts, q_lens, kv_lens)
    table = _pad_table(tok_table, PAGES_PER_CHUNK)

    def gather_chunk(c):
        tbl = jax.lax.dynamic_slice(table, (0, c * PAGES_PER_CHUNK),
                                    (T, PAGES_PER_CHUNK))
        return _gather_ctx(cfg, pages[layer_idx, tbl])

    lat = _latent_blockwise(q_lat[:, None].astype(jnp.float32),
                            q_pe[:, None], gather_chunk, P,
                            pages.shape[-2], pos[:, None], tok_total,
                            _mla_scale(cfg))[:, 0]
    return jnp.where(valid[:, None, None], lat, 0.0)


def _gather_ctx(cfg: ModelConfig, gathered: jnp.ndarray):
    """[B, P, 2, 1, ps, dkv] gathered pages -> latent/rope context."""
    B, P, _two, _one, ps, dkv = gathered.shape
    ckv = gathered[:, :, 0, 0].reshape(B, P * ps, dkv)
    kpe = gathered[:, :, 1, 0].reshape(B, P * ps, dkv)[
        ..., :cfg.qk_rope_head_dim]
    return ckv, kpe


# --------------------------------------------------------------------- MoE

def _gate(cfg: ModelConfig, lp: Dict[str, jnp.ndarray], x: jnp.ndarray):
    """HF-exact DeepSeek gate, per generation: V2 = f32 softmax scores
    with greedy / group-limited top-k (no renorm); V3 (``noaux_tc``) =
    the sigmoid + e_score_correction_bias gate (``_gate_noaux``). Both
    scale by routed_scaling_factor."""
    if cfg.topk_method == "noaux_tc":
        return _gate_noaux(cfg, lp, x)
    scores = jax.nn.softmax(
        (x.astype(jnp.float32) @ lp["w_router"].astype(jnp.float32)),
        axis=-1)                                           # [B,S,E]
    k = cfg.num_experts_per_tok
    if cfg.topk_method == "group_limited_greedy":
        B, S, E = scores.shape
        g = cfg.n_group
        group_scores = scores.reshape(B, S, g, E // g).max(axis=-1)
        _gv, gi = jax.lax.top_k(group_scores, cfg.topk_group)
        group_mask = jnp.sum(
            jax.nn.one_hot(gi, g, dtype=scores.dtype), axis=2)  # [B,S,g]
        score_mask = jnp.repeat(group_mask, E // g, axis=-1)
        masked = jnp.where(score_mask > 0, scores, 0.0)
        top_w, top_i = jax.lax.top_k(masked, k)
    elif cfg.topk_method == "greedy":
        top_w, top_i = jax.lax.top_k(scores, k)
    else:
        raise NotImplementedError(f"topk_method {cfg.topk_method!r}")
    return top_w * cfg.routed_scaling_factor, top_i


def _gate_noaux(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                x: jnp.ndarray):
    """V3 aux-loss-free gate (``DeepseekV3TopkRouter``): sigmoid scores,
    bias-corrected group-limited selection (group score = sum of its top-2
    corrected scores), weights taken from the UNCORRECTED scores,
    normalized (+1e-20) when norm_topk_prob, scaled."""
    scores = jax.nn.sigmoid(
        x.astype(jnp.float32) @ lp["w_router"].astype(jnp.float32))
    sfc = scores + lp["router_bias"].astype(jnp.float32)   # [B,S,E]
    B, S, E = scores.shape
    g, k = cfg.n_group, cfg.num_experts_per_tok
    masked = sfc
    if g > 1:       # one group is no limit
        group_scores = jnp.sum(
            jax.lax.top_k(sfc.reshape(B, S, g, E // g), 2)[0], axis=-1)
        _gv, gi = jax.lax.top_k(group_scores, cfg.topk_group)
        group_mask = jnp.sum(jax.nn.one_hot(gi, g, dtype=sfc.dtype), axis=2)
        score_mask = jnp.repeat(group_mask, E // g, axis=-1)
        masked = jnp.where(score_mask > 0, sfc, 0.0)
    _w, top_i = jax.lax.top_k(masked, k)
    top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_w * cfg.routed_scaling_factor, top_i


def _moe_mlp(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
             x: jnp.ndarray, ep_mesh=None, **kw
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Routed experts + shared experts. The routed part is the one exact
    grouped layer (``models/moe.grouped_experts``; ``kw`` — the layer
    index into stacked experts, the valid-slot mask, the kernel switch —
    goes to it) unless ``cfg.moe_backend`` asks for the capacity-factor
    token dispatch (``expert_dispatch``, the wide-EP path; ``ep_mesh``
    pins its buffers to the ep axis). Returns ``(out, aux)``: the grouped
    layer's counts or the dispatch's dropped assignments."""
    from dynamo_tpu.models.moe import expert_dispatch, grouped_experts

    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with stage("route"):
        top_w, top_i = _gate(cfg, lp, x)
        top_w, top_i = top_w.reshape(B * S, -1), top_i.reshape(B * S, -1)
    if cfg.moe_backend == "dispatch":
        routed, dropped = expert_dispatch(
            xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"],
            cfg.num_experts, cfg.moe_capacity_factor, ep_mesh=ep_mesh)
        aux = {"moe_dropped_assignments": dropped}
    else:
        routed, aux = grouped_experts(
            xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"], **kw)
    routed = routed.reshape(B, S, H).astype(x.dtype)
    if cfg.n_shared_experts:
        with stage("shared"):
            routed = routed + (jax.nn.silu(x @ lp["ws_gate"])
                               * (x @ lp["ws_up"])) @ lp["ws_down"]
    return routed, aux


def _dense_mlp(lp: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


# ----------------------------------------------------------------- forward

def _attend(cfg: ModelConfig, q_lat, q_pe, positions, total_lens, new_lens,
            page_table, pages, lidx, *, use_pallas: bool, starts=None):
    """The attention stage of ``_layer_step``: latent attention over the
    paged cache, by the path the step form (``starts``:
    ``llama.packed_rows``) and the geometry pick. Returns the latent
    output ``[B, S, nh, dkv]`` that ``_expand_and_project`` takes."""
    S = q_lat.shape[1]
    P = page_table.shape[1]
    ps = pages.shape[-2]
    if starts is not None:
        rows = (pages, lidx, page_table, starts, new_lens, total_lens)
        if use_pallas:
            from dynamo_tpu.ops.pallas.mla_ragged import (
                mla_ragged_attention_packed)

            lat = mla_ragged_attention_packed(q_lat[0], q_pe[0], *rows,
                                              _mla_scale(cfg))
        else:
            lat = mla_ragged_attention(cfg, q_lat[0], q_pe[0], *rows)
        return lat[None]
    if use_pallas and S == 1:
        from dynamo_tpu.ops.pallas.mla_decode import (
            mla_paged_decode_stacked)

        return mla_paged_decode_stacked(q_lat, q_pe, pages, lidx,
                                        page_table, total_lens,
                                        _mla_scale(cfg))
    if use_pallas:
        from dynamo_tpu.ops.pallas.mla_prefill import (
            mla_paged_prefill_stacked)

        return mla_paged_prefill_stacked(q_lat, q_pe, pages, lidx,
                                         page_table, positions, total_lens,
                                         _mla_scale(cfg))
    if S > 1 and P > PAGES_PER_CHUNK:
        table = _pad_table(page_table, PAGES_PER_CHUNK)

        def gather_chunk(c):
            tbl = jax.lax.dynamic_slice(
                table, (0, c * PAGES_PER_CHUNK),
                (table.shape[0], PAGES_PER_CHUNK))
            return _gather_ctx(cfg, pages[lidx, tbl])

        return _latent_blockwise(q_lat, q_pe, gather_chunk, P, ps,
                                 positions, total_lens, _mla_scale(cfg))
    ckv_ctx, kpe_ctx = _gather_ctx(cfg, pages[lidx, page_table])
    return _mla_latent(cfg, q_lat, q_pe, ckv_ctx, kpe_ctx, positions,
                       total_lens)


def _layer_step(cfg: ModelConfig, lp, h, positions, total_lens, new_lens,
                page_table, pages, lidx, *, moe: bool,
                use_pallas: bool = False, ep_mesh=None, moe_kw=None,
                starts=None):
    """One decoder layer against the stacked paged latent cache.
    ``use_pallas`` routes a token-packed step (``starts``:
    ``llama.packed_rows``, ``h`` ``[1, T, H]``) through the ragged MLA
    kernel (``ops/pallas/mla_ragged.py``), S==1 through the decode kernel
    (``mla_decode.py``) and a padded S>1 through the prefill kernel when
    the geometry supports them; ``moe_kw`` goes to the grouped
    expert layer. Returns ``(h, pages, aux)``, ``aux`` the expert layer's
    counts (empty for a dense layer)."""
    with stage("layer.attn_in"):
        q_lat, q_pe, c_kv, k_pe, w_uv = _mla_qkv(cfg, lp, h, positions)
        k_new, v_new = _cache_rows(cfg, c_kv, k_pe)
    with stage("layer.kv_write"):
        pages = write_rows(pages, lidx, k_new, v_new, page_table,
                           positions, total_lens, new_lens, starts)
    with stage("layer.attn"):
        lat = _attend(cfg, q_lat, q_pe, positions, total_lens, new_lens,
                      page_table, pages, lidx, use_pallas=use_pallas,
                      starts=starts)
    with stage("layer.attn_out"):
        h = _expand_and_project(cfg, lp, h, lat, w_uv)
    with stage("layer.moe" if moe else "layer.ffn"):
        x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        if moe:
            mlp, aux = _moe_mlp(cfg, lp, x, ep_mesh=ep_mesh,
                                **(moe_kw or {}))
        else:
            mlp, aux = _dense_mlp(lp, x), {}
        h = h + mlp
    return h, pages, aux


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, ep_mesh=None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Scan forward (llama.forward contract, the token-packed form
    included, plus the ``aux`` third return
    carrying the expert layer's counts summed over layers, like
    models/moe.py: ``moe_experts_touched`` and ``moe_assignments``, or the
    dispatch backend's ``moe_dropped_assignments``). The GQA
    Pallas kernels the engine passes as ``attn_impl`` cannot run latent
    attention, so they are never CALLED here — but an impl carrying the
    ``pallas_paged_kernel`` marker (every stacked kernel sets it) opts
    the family into its OWN latent kernels when the geometry supports it
    (kv_lora_rank % 128 == 0 — true for real V2/V3 checkpoints): a
    token-packed step rides ``ops/pallas/mla_ragged.py``, S==1
    steps ``ops/pallas/mla_decode.py``, padded S>1 chunks
    ``ops/pallas/mla_prefill.py``, and the expert layer its
    ``moe_grouped`` kernel. Any other non-None impl is ignored
    (the XLA paths serve), matching gemma's marker pattern."""
    from dynamo_tpu.models.moe import (grouped_on_chip, split_experts,
                                       sum_aux, token_slots)
    from dynamo_tpu.ops.pallas.mla_decode import supports as mla_supports

    use_pallas = (getattr(attn_impl, "pallas_paged_kernel", False)
                  and mla_supports(cfg.kv_lora_rank, pages.shape[-2]))
    K = cfg.first_k_dense_replace
    with stage("step.inputs"):
        starts = packed_rows(packed, new_lens)
    with stage("embed"):
        h = params["embed"][tokens]
    aux = {}

    def body(moe, experts=None, **moe_kw):
        def step(carry, xs):
            h, pages = carry
            lp, lidx = xs
            kw = {}
            if experts:     # stacked, indexed by the MoE layer's number
                with stage("layer.moe"):
                    kw = dict(moe_kw, layer=lidx - K)
                lp = {**lp, **experts}
            h, pages, aux = _layer_step(
                cfg, lp, h, positions, total_lens, new_lens, page_table,
                pages, lidx, moe=moe, use_pallas=use_pallas, ep_mesh=ep_mesh,
                moe_kw=kw, starts=starts)
            return (h, pages), aux
        return step

    if K and "dense_layers" in params:
        with stage("step.inputs"):
            layer_ids = jnp.arange(K)
        (h, pages), _ = jax.lax.scan(
            body(False), (h, pages), (params["dense_layers"], layer_ids))
    if "moe_layers" in params:
        scanned, experts = split_experts(cfg, params["moe_layers"])
        with stage("step.inputs"):
            valid = token_slots(tokens, new_lens, packed)
            layer_ids = K + jnp.arange(cfg.num_layers - K)
        (h, pages), aux = jax.lax.scan(
            body(True, experts, valid=valid,
                 use_pallas=grouped_on_chip(attn_impl)), (h, pages),
            (scanned, layer_ids))
        with stage("step.counts"):
            aux = sum_aux(aux)
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, window=logits_window,
                         starts=starts)
    return logits, pages, aux


forward.supports_packed = True


# ------------------------------------------------------------------ loader

def load_params(cfg: ModelConfig, path: str,
                shardings: Optional[Dict[str, Any]] = None) -> Params:
    """Assemble the two-stack pytree from an HF deepseek checkpoint."""
    from safetensors import safe_open

    from dynamo_tpu.models.hf_loader import _checkpoint_files

    K = cfg.first_k_dense_replace
    attn = {
        "input_layernorm.weight": ("attn_norm", False),
        "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", True),
        "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
        "self_attn.kv_b_proj.weight": ("wkv_b", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("mlp_norm", False),
    }
    if cfg.q_lora_rank:
        attn.update({
            "self_attn.q_a_proj.weight": ("wq_a", True),
            "self_attn.q_a_layernorm.weight": ("q_a_norm", False),
            "self_attn.q_b_proj.weight": ("wq_b", True),
        })
    else:
        attn["self_attn.q_proj.weight"] = ("wq", True)
    dense_mlp = {
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
    }
    moe_mlp_names = {
        "mlp.gate.weight": ("w_router", True),
        "mlp.gate.e_score_correction_bias": ("router_bias", False),
        "mlp.shared_experts.gate_proj.weight": ("ws_gate", True),
        "mlp.shared_experts.up_proj.weight": ("ws_up", True),
        "mlp.shared_experts.down_proj.weight": ("ws_down", True),
    }
    expert_names = {
        "gate_proj.weight": "w_gate",
        "up_proj.weight": "w_up",
        "down_proj.weight": "w_down",
    }
    top = {
        "model.embed_tokens.weight": (("embed",), False),
        "model.norm.weight": (("final_norm",), False),
    }
    if not cfg.tie_word_embeddings:
        top["lm_head.weight"] = (("lm_head",), True)

    staged: Dict[tuple, Any] = {}
    by_layer: Dict[Tuple[str, str], Dict[int, np.ndarray]] = {}
    by_expert: Dict[str, Dict[Tuple[int, int], np.ndarray]] = {}
    for f in _checkpoint_files(path):
        with safe_open(f, framework="np") as sf:
            for name in sf.keys():
                if name in top:
                    tree_path, tr = top[name]
                    t = sf.get_tensor(name)
                    staged[tree_path] = (np.ascontiguousarray(t.T)
                                         if tr else t)
                    continue
                if not name.startswith("model.layers."):
                    continue
                rest = name[len("model.layers."):]
                idx, _, tail = rest.partition(".")
                layer = int(idx)
                stack = "dense_layers" if layer < K else "moe_layers"
                if tail in attn or (stack == "dense_layers"
                                    and tail in dense_mlp) \
                        or (stack == "moe_layers"
                            and tail in moe_mlp_names):
                    leaf, tr = (attn.get(tail) or dense_mlp.get(tail)
                                or moe_mlp_names.get(tail))
                    t = sf.get_tensor(name)
                    if tr:
                        t = np.ascontiguousarray(t.T)
                    by_layer.setdefault((stack, leaf), {})[layer] = t
                    continue
                if tail.startswith("mlp.experts."):
                    sub = tail[len("mlp.experts."):]
                    j, _, wname = sub.partition(".")
                    leaf = expert_names.get(wname)
                    if leaf is not None:
                        t = np.ascontiguousarray(sf.get_tensor(name).T)
                        by_expert.setdefault(leaf, {})[
                            (layer, int(j))] = t

    for (stack, leaf), d in by_layer.items():
        if stack == "dense_layers":
            idxs = list(range(K))
        else:
            idxs = list(range(K, cfg.num_layers))
        missing = set(idxs) - set(d)
        if missing:
            raise ValueError(f"missing layers {sorted(missing)} for "
                             f"{stack}.{leaf}")
        staged[(stack, leaf)] = np.stack([d[i] for i in idxs])
    for leaf, d in by_expert.items():
        want = {(i, j) for i in range(K, cfg.num_layers)
                for j in range(cfg.num_experts)}
        missing = want - set(d)
        if missing:
            raise ValueError(
                f"checkpoint missing {len(missing)} expert tensors for "
                f"moe_layers.{leaf} (e.g. {sorted(missing)[:3]})")
        staged[("moe_layers", leaf)] = np.stack([
            np.stack([d[(i, j)] for j in range(cfg.num_experts)])
            for i in range(K, cfg.num_layers)])

    params: Params = {}
    dtype = jnp.dtype(cfg.dtype)
    for tree_path, arr in staged.items():
        node = params
        for k in tree_path[:-1]:
            node = node.setdefault(k, {})
        # the V3 gate's e_score_correction_bias stays f32: rounding it to
        # bf16 flips expert selections near group/top-k boundaries
        leaf_dtype = (jnp.float32 if tree_path[-1] == "router_bias"
                      else dtype)
        leaf = jnp.asarray(arr).astype(leaf_dtype)
        if shardings is not None:
            spec = shardings
            for k in tree_path:
                spec = spec.get(k) if isinstance(spec, dict) else None
                if spec is None:
                    break
            if spec is not None:
                leaf = jax.device_put(leaf, spec)
        node[tree_path[-1]] = leaf
    return params


__all__ = ["init_params", "forward", "load_params", "rope_interleaved",
           "make_pages"]
