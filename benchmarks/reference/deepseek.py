"""Plain reference of the DeepSeek-V2 family: multi-head latent attention
and a mixture of routed and shared experts, in float32 ``jax.numpy``, one
whole sequence at a time, with no cache, kernel, batching or weight
absorption.

Written from the DeepSeek-V2 paper (arXiv:2405.04434, section 2.1 and 2.2)
and the YaRN paper (arXiv:2309.00071):

- MLA as the paper states it, *not* absorbed: the keys and values of every
  head are materialised from the compressed latent. c = RMSNorm(h W_DKV);
  per head k_nope = c W_UK, v = c W_UV; one rotary key k_rope = RoPE(h W_KR)
  shared by all heads; q = h W_Q (V2-Lite has no query compression), split
  into q_nope and q_rope = RoPE(.); score = (q_nope.k_nope + q_rope.k_rope)
  / sqrt(d_nope + d_rope).
- RoPE rotates consecutive pairs (x[2i], x[2i+1]) - the convention of the
  released checkpoints - with YaRN's blended frequencies; the cosine and
  sine are scaled by mscale(s, m) / mscale(s, m_all), which is 1 for the
  published V2-Lite config (both 0.707).
- Gate: softmax over all routed experts in float32, the top k scores kept
  as they are (``norm_topk_prob`` false: no renormalisation), times
  ``routed_scaling_factor``; output = sum of the chosen experts' SwiGLU
  outputs weighted by their scores, plus the shared experts (one SwiGLU of
  width ``n_shared_experts`` x ``moe_intermediate_size``). The first
  ``first_k_dense_replace`` layers have a dense SwiGLU instead.

Departure, stated: deepseek-ai's own ``modeling_deepseek.py`` also
multiplies the softmax scale by mscale(s, m_all)^2 (1.59 for V2-Lite);
Hugging Face's port ``modeling_deepseek_v2.py``, which the program cites and
follows, does not. This reference follows the port, since the question is
whether the served path computes what the program says it computes; which of
the two a real checkpoint needs is an open question in PERF.md (neither
source can be fetched here).

It shares no code with ``dynamo_tpu/models``. Weights are data: the arrays
the worker serves, a layer at a time, cast to float32. One layer:
``attn_norm [H]``, ``wq [H, n*(dn+dr)]``, ``wkv_a [H, dc+dr]``,
``kv_a_norm [dc]``, ``wkv_b [dc, n*(dn+dv)]``, ``wo [n*dv, H]``,
``mlp_norm [H]``; dense: ``w_gate``/``w_up [H, I]``, ``w_down [I, H]``;
MoE: ``w_router [H, E]``, ``w_gate``/``w_up [E, H, Im]``, ``w_down [E, Im,
H]``, ``ws_gate``/``ws_up [H, Is]``, ``ws_down [Is, H]``.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn(hf):
    """(inverse frequencies [dr/2], scale of cos and sin) after YaRN
    section 3.2 ("NTK-by-parts") and 3.4 (attention scaling)."""
    dr, base = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    freq = [base ** (-2.0 * i / dr) for i in range(dr // 2)]
    rs = hf.get("rope_scaling")
    if not rs:
        return jnp.asarray(freq, jnp.float32), 1.0
    s, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def dim_of(rotations):
        # the dimension whose wavelength makes `rotations` turns over the
        # original context
        return dr * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(freq):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        # ramp 0: high frequency, kept; ramp 1: low frequency, interpolated
        out.append(f * (1 - ramp) + (f / s) * ramp)

    def mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    m, m_all = rs.get("mscale", 0), rs.get("mscale_all_dim", 0)
    att = (mscale(s, m) / mscale(s, m_all)) if (m and m_all) else mscale(s, 1)
    return jnp.asarray(out, jnp.float32), att


def rope_pairs(x, inv, scale):
    """x [T, ..., dr]: pair (2i, 2i+1) of token t turns by t * inv[i]."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(hf, w, h):
    T = h.shape[0]
    n = hf["num_attention_heads"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, dc = hf["v_head_dim"], hf["kv_lora_rank"]
    inv, att = yarn(hf)
    x = rms_norm(h, w["attn_norm"], hf["rms_norm_eps"])
    q = (x @ w["wq"]).reshape(T, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], inv, att)
    down = x @ w["wkv_a"]
    c = rms_norm(down[:, :dc], w["kv_a_norm"], hf["rms_norm_eps"])
    k_rope = rope_pairs(down[:, dc:], inv, att)               # [T, dr]
    up = (c @ w["wkv_b"]).reshape(T, n, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    scores = (jnp.einsum("tnd,snd->nts", q_nope, k_nope)
              + jnp.einsum("tnd,sd->nts", q_rope, k_rope)) \
        / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return h + out.reshape(T, n * dv) @ w["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def dense_layer(hf, w, h):
    h = attention(hf, w, h)
    x = rms_norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def moe_layer(hf, w, h):
    h = attention(hf, w, h)
    x = rms_norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    E, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    if hf.get("topk_method", "greedy") != "greedy":
        raise NotImplementedError("only the greedy gate of V2-Lite")
    scores = jax.nn.softmax(x @ w["w_router"], axis=-1)        # [T, E]
    top_w, top_i = jax.lax.top_k(scores, k)
    if hf.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * hf.get("routed_scaling_factor", 1.0)
    # weight of expert e for token t, zero where e was not chosen
    weight = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)

    def one_expert(acc, ew):
        gate, up, down, col = ew
        return acc + col[:, None] * swiglu(x, gate, up, down), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    shared = swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"]) \
        if hf.get("n_shared_experts") else 0.0
    return h + routed + shared


def layers(params):
    """(kind, stacked layer weights, count) in model order."""
    out = []
    if "dense_layers" in params:
        out.append(("dense", params["dense_layers"],
                    params["dense_layers"]["wq"].shape[0]))
    if "moe_layers" in params:
        out.append(("moe", params["moe_layers"],
                    params["moe_layers"]["wq"].shape[0]))
    return out


LAYER_FNS = {"dense": dense_layer, "moe": moe_layer}


def head(hf, params, h):
    f32 = jnp.float32
    h = rms_norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    if "lm_head" in params:
        return h @ params["lm_head"].astype(f32)
    return h @ params["embed"].astype(f32).T
