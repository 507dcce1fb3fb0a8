"""Gemma-2 family decoder — pure-functional jax over the paged KV cache.

Same serving contract as ``models/llama.py`` (``init_params`` / the
``forward`` scan), covering the gemma-2 architecture differences
(verified against transformers' ``Gemma2ForCausalLM`` in tests):

- GeGLU MLP: ``gelu_tanh(x@gate) * (x@up) @ down``;
- sandwich norms: pre+post norms around BOTH attention and the MLP
  (4 RMSNorms per layer), with gemma's ``x * (1 + w)`` RMSNorm;
- embedding scaled by ``sqrt(hidden_size)``;
- attention-logit and final-logit soft-capping;
- alternating sliding-window layers (even layers sliding, odd global —
  HF gemma-2 convention), expressed as a per-layer window arg to the
  paged attention mask so the SAME paged cache serves both kinds;
- query scale from ``query_pre_attn_scalar`` instead of ``head_dim``.

Both stacked Pallas kernels (decode AND prefill, ``ops/pallas/``) carry
the per-layer window + softcap operands, so the scan forward serves this
family fully on kernels under ``attn_impl="pallas"``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    _select_last,
    attend_rows,
    make_pages,
    packed_rows,
    qkv_products,
    write_rows,
)
from dynamo_tpu.ops.rope import apply_rope
from dynamo_tpu.ops import quant

Params = Dict[str, Any]


def _rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """gemma RMSNorm: f32 compute, ``x * (1 + w)`` (weights zero-init)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention window: even layers sliding, odd global (0)."""
    if not cfg.sliding_window:
        return jnp.zeros((cfg.num_layers,), jnp.int32)
    return jnp.asarray([cfg.sliding_window if (i % 2 == 0) else 0
                        for i in range(cfg.num_layers)], jnp.int32)


def _sm_scale(cfg: ModelConfig) -> float:
    base = cfg.query_pre_attn_scalar or cfg.head_dim
    return base ** -0.5


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: float = 0.02) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(rng, 16))

    def zeros(shape):
        return jnp.zeros(shape, dtype=dtype)  # gemma norms are zero-init

    def randn(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": zeros((L, H)),
        "post_attn_norm": zeros((L, H)),
        "pre_ffw_norm": zeros((L, H)),
        "post_ffw_norm": zeros((L, H)),
        "wq": randn(next(keys), (L, H, cfg.q_size)),
        "wk": randn(next(keys), (L, H, cfg.kv_size)),
        "wv": randn(next(keys), (L, H, cfg.kv_size)),
        "wo": randn(next(keys), (L, cfg.q_size, H)),
        "w_gate": randn(next(keys), (L, H, I)),
        "w_up": randn(next(keys), (L, H, I)),
        "w_down": randn(next(keys), (L, I, H)),
    }
    params: Params = {
        "embed": randn(next(keys), (cfg.vocab_size, H)),
        "layers": layers,
        "final_norm": zeros((H,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn(next(keys), (H, cfg.vocab_size))
    return params


def _project_qkv(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 h: jnp.ndarray, positions: jnp.ndarray):
    B, S, _ = h.shape
    x = _rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = qkv_products(cfg, lp, x)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _finish_attn(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 h: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    """Out-projection, its norm and the residual."""
    B, S, _ = h.shape
    attn_out = quant.mm(lp, "wo", attn.reshape(B, S, cfg.q_size))
    return h + _rms_norm(attn_out, lp["post_attn_norm"], cfg.rms_norm_eps)


def _ffn(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
         h: jnp.ndarray) -> jnp.ndarray:
    """Gated MLP between its two norms, and the residual."""
    eps = cfg.rms_norm_eps
    x = _rms_norm(h, lp["pre_ffw_norm"], eps)
    act = (jax.nn.gelu(quant.mm(lp, "w_gate", x), approximate=True)
           * quant.mm(lp, "w_up", x))
    mlp = quant.mm(lp, "w_down", act)
    return h + _rms_norm(mlp, lp["post_ffw_norm"], eps)


def _finish_layer(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                  h: jnp.ndarray, attn: jnp.ndarray) -> jnp.ndarray:
    return _ffn(cfg, lp, _finish_attn(cfg, lp, h, attn))


def _logits(cfg: ModelConfig, params: Params, h: jnp.ndarray,
            new_lens: jnp.ndarray, window: int = 1,
            starts=None) -> jnp.ndarray:
    """Logits at each row's last ``window`` real new positions ([B, V], or
    [B, W, V] for the speculative-verify step — see llama._logits)."""
    h = _rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    if window == 1:
        h_sel = _select_last(h, new_lens, starts)
    else:
        offs = jnp.arange(window, dtype=jnp.int32)[None, :]
        idx = jnp.maximum(new_lens[:, None] - window + offs, 0)
        h_sel = jnp.take_along_axis(h, idx[..., None], axis=1)
    lm8 = params.get("lm_head_q")
    if lm8 is not None:
        logits = quant.qdot(h_sel, lm8, params["lm_head_scale"],
                            out_dtype=jnp.float32)
    else:
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        # model-dtype operands + f32 accumulation (see llama._logits)
        logits = jnp.dot(h_sel, lm_head,
                         preferred_element_type=jnp.float32)
    cap = cfg.final_logit_softcap
    if cap:
        logits = jnp.tanh(logits / cap) * cap
    return logits


def _embed(cfg: ModelConfig, params: Params,
           tokens: jnp.ndarray) -> jnp.ndarray:
    h = params["embed"][tokens]
    # gemma scales embeddings by sqrt(H), cast through the model dtype the
    # way HF does (the normalizer is rounded to bf16 there)
    normalizer = jnp.asarray(math.sqrt(cfg.hidden_size), h.dtype)
    return h * normalizer


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan-over-layers forward, padded or token-packed
    (``llama.packed_rows``). ``attn_impl`` is honored only when it
    advertises ``supports_window_softcap`` (the stacked Pallas kernels —
    decode, prefill and ragged — carry gemma's per-layer sliding window +
    logit soft-capping) — otherwise the XLA paths serve, with identical
    math."""
    if not getattr(attn_impl, "supports_window_softcap", False):
        attn_impl = None
    sm_scale = _sm_scale(cfg)
    softcap = cfg.attn_logit_softcap or None  # static: both paths accept
    # the stages of ``llama.forward`` (engine/stages.py)
    with stage("step.inputs"):
        starts = packed_rows(packed, new_lens)
        windows = layer_windows(cfg)
    with stage("embed"):
        h = _embed(cfg, params, tokens)

    def body(carry, xs):
        h, pages = carry
        lp, lidx, win = xs
        with stage("layer.attn_in"):
            q, k, v = _project_qkv(cfg, lp, h, positions)
        with stage("layer.kv_write"):
            pages = write_rows(pages, lidx, k, v, page_table, positions,
                               total_lens, new_lens, starts)
        with stage("layer.attn"):
            attn = attend_rows(attn_impl, q, pages, lidx, page_table,
                               positions, total_lens, new_lens, sm_scale,
                               starts, window=win, softcap=softcap)
        with stage("layer.attn_out"):
            h = _finish_attn(cfg, lp, h, attn)
        with stage("layer.ffn"):
            h = _ffn(cfg, lp, h)
        return (h, pages), None

    with stage("step.inputs"):
        layer_ids = jnp.arange(cfg.num_layers)
    (h, pages), _ = jax.lax.scan(
        body, (h, pages), (params["layers"], layer_ids, windows))
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, window=logits_window,
                         starts=starts)
    return logits, pages


forward.supports_packed = True
forward.reads_wqkv = True


__all__ = ["init_params", "forward", "make_pages", "layer_windows"]
