"""Plain reference of JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``):
multi-head latent attention with a compressed query, one leading dense
layer, then layers of sigmoid-routed experts plus a shared expert, in
float32 ``jax.numpy``, one whole sequence at a time, with no cache, kernel,
batching or weight absorption.

Written from the model's published ``config.json``
(huggingface.co/jdopensource/JoyAI-LLM-Flash), whose keys are those of the
DeepSeek-V3 architecture (arXiv:2412.19437, sections 2.1.1 and 2.1.2):

- MLA, *not* absorbed: c_q = RMSNorm(x W_qa); q = c_q W_qb, per head split
  into q_nope (128) and q_rope (64). [c_kv | k_rope] = x W_kva; c_kv =
  RMSNorm(c_kv); per head [k_nope | v] = c_kv W_kvb. One rotary key shared
  by all heads. RoPE turns consecutive pairs (x[2i], x[2i+1])
  (``rope_interleave`` true) at theta = 32e6; ``rope_scaling`` is null, so
  no YaRN blend and no mscale. score = (q_nope.k_nope + q_rope.k_rope) /
  sqrt(192), causal, softmax.
- Gate (``topk_method`` ``noaux_tc``, ``scoring_func`` ``sigmoid``): s =
  sigmoid(x W_r) in float32; the 8 experts with the largest s + b are
  chosen (b the ``e_score_correction_bias``; ``n_group`` = ``topk_group`` =
  1, so there is no group limit); their weights are s (without b), divided
  by their sum + 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``
  2.5. Output = sum of the chosen experts' SwiGLU outputs times their
  weights, plus the shared expert's SwiGLU. The first
  ``first_k_dense_replace`` layers have a dense SwiGLU instead.

Departure, stated: the multi-token-prediction layer
(``num_nextn_predict_layers`` 1) is left out. It takes no part in the
next-token forward pass. Nothing else.

It shares no code with ``dynamo_tpu/models``. Weights are data: the arrays
the worker serves, cast to float32 a piece at a time. ``score.py`` upcasts
one whole entry of ``layers()`` at once, and a whole expert layer is 4.96 GB
in float32 beside the 11.1 GB the child already holds; so an expert layer is
yielded as its attention-and-gate part (``moe_open``), then its experts in
blocks of ``EXPERT_BLOCK`` (``moe_block``, 0.6 GB in float32 each, every
expert of the block run on every token in a plain loop and weighted by the
gate's column, zero where it was not chosen), then the shared expert and the
residual (``moe_close``); ``(h, x, weight, acc)`` is carried between them.
"""

import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_pairs(x, theta):
    """x [T, ..., dr]: pair (2i, 2i+1) of token t turns by t * theta^(-2i/dr)."""
    T, dr = x.shape[0], x.shape[-1]
    inv = jnp.asarray([theta ** (-2.0 * i / dr) for i in range(dr // 2)],
                      jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(hf, w, h):
    if hf.get("rope_scaling") or not hf.get("rope_interleave", True):
        raise NotImplementedError("rope_scaling null, interleaved pairs")
    T = h.shape[0]
    n, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, dc = hf["v_head_dim"], hf["kv_lora_rank"]
    theta = float(hf["rope_theta"])
    x = rms_norm(h, w["attn_norm"], eps)
    c_q = rms_norm(x @ w["wq_a"], w["q_a_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(T, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], theta)
    down = x @ w["wkv_a"]
    c_kv = rms_norm(down[:, :dc], w["kv_a_norm"], eps)
    k_rope = rope_pairs(down[:, dc:], theta)                    # [T, dr]
    up = (c_kv @ w["wkv_b"]).reshape(T, n, dn + dv)
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


def gate(hf, w, x):
    """[T, E] weight of expert e for token t, zero where e was not chosen."""
    if (hf.get("topk_method") != "noaux_tc"
            or hf.get("scoring_func") != "sigmoid"
            or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1):
        raise NotImplementedError("the sigmoid noaux_tc gate, one group")
    s = jax.nn.sigmoid(x @ w["w_router"])                       # [T, E]
    _, top_i = jax.lax.top_k(s + w["router_bias"], hf["num_experts_per_tok"])
    top_w = jnp.take_along_axis(s, top_i, axis=-1)
    if hf.get("norm_topk_prob"):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * hf.get("routed_scaling_factor", 1.0)
    return jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)


def moe_open(hf, w, h):
    h = attention(hf, w, h)
    x = rms_norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    return h, x, gate(hf, w, x), jnp.zeros_like(x)


def moe_block(hf, w, carry):
    """The experts ``first .. first + EXPERT_BLOCK`` of the layer, each on
    every token, in a plain loop."""
    h, x, weight, acc = carry
    cols = jax.lax.dynamic_slice_in_dim(
        weight, w["first"].astype(jnp.int32), w["w_gate"].shape[0], axis=1)

    def one_expert(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * swiglu(x, g, u, d), None

    acc, _ = jax.lax.scan(one_expert, acc,
                          (w["w_gate"], w["w_up"], w["w_down"], cols.T))
    return h, x, weight, acc


def moe_close(hf, w, carry):
    h, x, _weight, acc = carry
    shared = swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"]) \
        if hf.get("n_shared_experts") else 0.0
    return h + acc + shared


def moe_layer(hf, w, h):
    """One whole expert layer, its experts ``[E, ...]`` in ``w``: the three
    pieces in turn."""
    carry = moe_open(hf, w, h)
    for first in range(0, w["w_gate"].shape[0], EXPERT_BLOCK):
        block = {k: w[k][first:first + EXPERT_BLOCK] for k in EXPERTS}
        carry = moe_block(hf, dict(block, first=jnp.asarray(first)), carry)
    return moe_close(hf, w, carry)


EXPERTS = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")


class ExpertBlocks:
    """One layer's stacked expert matrix ``[E, ...]`` as its blocks of
    ``EXPERT_BLOCK`` experts: ``blocks[i]`` cuts block ``i`` out of the
    served array, so ``score.py``'s ``a[i].astype(float32)`` holds one
    block in float32 at a time and no second copy of the layer."""

    def __init__(self, stacked, layer: int):
        self.stacked, self.layer = stacked, layer

    def __getitem__(self, i):
        return self.stacked[self.layer,
                            i * EXPERT_BLOCK:(i + 1) * EXPERT_BLOCK]


class Layers(list):
    """``(kind, stacked layer weights, count)`` in model order. Indexed, it
    is the model's layers whole, as the other references list theirs
    (``dense``, ``moe``). Iterated - which is how ``score.py`` walks it on
    the chip - an expert layer comes as its three kinds of piece instead
    (module docstring), so that no whole layer is ever upcast at once."""

    def __iter__(self):
        for kind, stack, n in list.__iter__(self):
            if kind != "moe":
                yield kind, stack, n
                continue
            for layer in range(n):
                yield "moe_open", {k: v[layer:layer + 1]
                                   for k, v in stack.items()
                                   if k not in EXPERTS + SHARED}, 1
                E = stack["w_gate"].shape[1]
                blocks = {k: ExpertBlocks(stack[k], layer) for k in EXPERTS}
                blocks["first"] = jnp.arange(0, E, EXPERT_BLOCK)
                yield "moe_block", blocks, -(-E // EXPERT_BLOCK)
                yield "moe_close", {k: stack[k][layer:layer + 1]
                                    for k in SHARED if k in stack}, 1


def layers(params):
    out = Layers()
    if "dense_layers" in params:
        out.append(("dense", params["dense_layers"],
                    params["dense_layers"]["wq_a"].shape[0]))
    if "moe_layers" in params:
        out.append(("moe", params["moe_layers"],
                    params["moe_layers"]["wq_a"].shape[0]))
    return out


LAYER_FNS = {"dense": dense_layer, "moe": moe_layer, "moe_open": moe_open,
             "moe_block": moe_block, "moe_close": moe_close}


def head(hf, params, h):
    f32 = jnp.float32
    h = rms_norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)
