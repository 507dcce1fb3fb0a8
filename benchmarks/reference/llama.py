"""Plain reference of the Llama family tree (Llama, Mistral, Qwen2, Qwen3):
the forward pass of a decoder-only transformer in float32 ``jax.numpy``, one
whole sequence at a time, with no cache, kernel or batching.

Written from the published equations: pre-norm residual blocks with RMSNorm
(Zhang & Sennrich 2019), rotary position embedding in the rotate-half
convention of the released checkpoints (Su et al. 2021), grouped-query
causal attention (Ainslie et al. 2023), SwiGLU feed-forward (Shazeer 2020),
and for ``model_type`` qwen3 an RMSNorm over each head's query and key
before the rotation (Qwen3 technical report). No departures.

It shares no code with ``dynamo_tpu/models``. Weights are data: the arrays
the worker serves (``init_params``), one layer at a time, cast to float32.
Layout of one layer (input-major matrices): ``attn_norm [H]``, ``wq [H,
nq*dh]``, ``wk``/``wv [H, nkv*dh]``, ``wo [nq*dh, H]``, ``mlp_norm [H]``,
``w_gate``/``w_up [H, I]``, ``w_down [I, H]``, for qwen3 ``q_norm``/
``k_norm [dh]``, for qwen2 ``bq``/``bk``/``bv``.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, theta):
    """x [T, heads, dh]; token t is rotated by angle t * theta^(-2i/dh) in
    the planes (i, i + dh/2)."""
    T, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(hf, w, h):
    """One block on h [T, H]."""
    T = h.shape[0]
    nq, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = hf.get("head_dim") or hf["hidden_size"] // nq
    eps = hf["rms_norm_eps"]
    x = rms_norm(h, w["attn_norm"], eps)
    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(T, nq, dh)
    k = k.reshape(T, nkv, dh)
    v = v.reshape(T, nkv, dh)
    if "q_norm" in w:
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    q, k = rope(q, hf["rope_theta"]), rope(k, hf["rope_theta"])
    # query head j reads key/value head j // (nq / nkv)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    h = h + attn.reshape(T, nq * dh) @ w["wo"]
    x = rms_norm(h, w["mlp_norm"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def layers(params):
    """(kind, stacked layer weights, count) in model order."""
    n = params["layers"]["wq"].shape[0]
    return [("block", params["layers"], n)]


LAYER_FNS = {"block": layer}


def head(hf, params, h):
    """Final norm and vocabulary projection: logits [T, V]."""
    f32 = jnp.float32
    h = rms_norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    if "lm_head" in params:
        return h @ params["lm_head"].astype(f32)
    return h @ params["embed"].astype(f32).T
