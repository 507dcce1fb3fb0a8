"""Int8 quantized serving (W8A8 dynamic) for the decode-bound hot path.

Decode throughput on TPU is HBM-bandwidth-bound: every step streams the
full parameter set (BASELINE.md roofline model; at the bench's Llama-3B
config the params are ~6.4 GB of the ~7.5 GB step traffic). Symmetric
int8 weights halve that stream, and int8×int8 ``lax.dot_general`` with
``preferred_element_type=int32`` lowers onto the MXU's double-rate int8
path on v5e — bandwidth AND compute both improve, which is why this is
the standard TPU serving quantization (the reference's H100 recipes lean
on FP8 for the same reason: ``docs/architecture/architecture.md``'s
R1-Distill-Llama-70B **FP8** baselines, served by vLLM/SGLang quantized
engines; TPU MXUs have no FP8, int8 is the native equivalent).

Scheme (calibration-free, load-time):
- **Weights**: symmetric per-out-channel absmax over the contraction
  axis: ``w8[k, n] = round(w[k, n] / s_w[n])``, ``s_w = absmax_k / 127``.
  Stored stacked ``[L, K, N] int8`` + ``[L, N] f32`` — the layer dicts
  scan exactly like the bf16 ones.
- **Activations**: dynamic symmetric per-token absmax (computed inside
  the step, fused by XLA; no calibration pass): W8A8-dynamic, the same
  trade vLLM ships as "w8a8 dynamic" int8.
- Accumulation in int32, rescale ``y * s_x * s_w`` in f32, cast back.

Tied embeddings are NOT quantized (the embed table doubles as lm_head;
the gather path wants the bf16 rows), and norms/biases stay bf16 — they
are O(H) a step, noise next to the matmuls.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# stacked [L, in, out] layer weights that quantize (llama family tree —
# llama 2/3, mistral, qwen2/3 — which shares these exact names; the MoE
# and MLA families keep bf16 until their expert/latent paths opt in);
# ``wqkv`` is the engine's ``wq | wk | wv`` side by side
# (``llama.fuse_qkv``, before this transform: a concatenation's per-column
# scales are the three's, concatenated)
LAYER_WEIGHTS = ("wq", "wk", "wv", "wqkv", "wo", "w_gate", "w_up", "w_down")

_EPS = 1e-30


def quantize_weight(w: jnp.ndarray, axis: int):
    """Symmetric per-channel int8: absmax over ``axis`` (the contraction
    dim), one f32 scale per remaining channel. Returns ``(w8, scale)``
    with ``scale`` shaped like ``w`` minus ``axis``."""
    a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(a, _EPS) / 127.0
    w8 = jnp.round(w.astype(jnp.float32)
                   / jnp.expand_dims(scale, axis)).astype(jnp.int8)
    return w8, scale


def qdot(x: jnp.ndarray, w8: jnp.ndarray, w_scale: jnp.ndarray,
         out_dtype=None) -> jnp.ndarray:
    """``x @ w`` with int8 weights and dynamic per-token int8 activations.

    x: [..., K] (any float dtype); w8: [K, N] int8; w_scale: [N] f32.
    The int8×int8 contraction accumulates in int32 on the MXU; the two
    scales re-enter in f32 and the result is cast to ``out_dtype``
    (default ``x.dtype``). The logits call sites pass f32 so the final
    projection keeps full-precision accumulation like the bf16 path.
    """
    xf = x.astype(jnp.float32)
    s_x = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                      _EPS) / 127.0                       # [..., 1]
    x8 = jnp.round(xf / s_x).clip(-127, 127).astype(jnp.int8)
    y = jax.lax.dot_general(
        x8, w8, (((x8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                 # [..., N] i32
    return (y.astype(jnp.float32) * s_x * w_scale).astype(
        out_dtype or x.dtype)


def quantize_params(params: Params) -> Params:
    """Load-time transform of a llama-family param tree to int8 weights.

    Each stacked layer matrix ``name [L, K, N]`` is replaced by
    ``name+"_q" [L, K, N] int8`` and ``name+"_scale" [L, N] f32``; the
    bf16 original is dropped (that is the memory/bandwidth win). An
    untied ``lm_head [K, N]`` quantizes the same way. Norms, biases,
    qk-norms and the embedding table pass through unchanged — the
    forward helpers dispatch on the ``_q`` suffix per weight, so partial
    trees (e.g. tied embeddings) stay correct.
    """
    out = dict(params)
    layers = dict(params["layers"])
    for name in LAYER_WEIGHTS:
        w = layers.pop(name, None)
        if w is None:
            continue
        w8, scale = quantize_weight(w, axis=1)            # [L, K, N] over K
        layers[name + "_q"] = w8
        layers[name + "_scale"] = scale
    out["layers"] = layers
    lm = params.get("lm_head")
    if lm is not None:
        w8, scale = quantize_weight(lm, axis=0)           # [K, N] over K
        out.pop("lm_head")
        out["lm_head_q"] = w8
        out["lm_head_scale"] = scale
    return out


def holds(lp: Dict[str, jnp.ndarray], name: str) -> bool:
    """Whether the layer tree holds the weight ``name``, as it was loaded
    or as its int8 pair."""
    return name in lp or name + "_q" in lp


def mm(lp: Dict[str, jnp.ndarray], name: str, x: jnp.ndarray
       ) -> jnp.ndarray:
    """``x @ lp[name]``, transparently using the int8 pair when the tree
    was quantized. The single call site shape the llama-family forwards
    share (``models/llama.py``)."""
    w8 = lp.get(name + "_q")
    if w8 is not None:
        return qdot(x, w8, lp[name + "_scale"])
    return x @ lp[name]


__all__ = ["LAYER_WEIGHTS", "holds", "mm", "qdot", "quantize_params",
           "quantize_weight"]
