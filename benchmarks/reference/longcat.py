"""Plain reference of LongCat-Flash-Omni's language model: double layers with
a shortcut-connected expert branch, zero-compute experts in one softmax
router, scaled multi-head latent attention - in float32 ``jax.numpy``, one
whole sequence at a time, with no cache, kernel, batching or weight
absorption.

Written from the model's published ``config.json``
(huggingface.co/meituan-longcat/LongCat-Flash-Omni, the language model's
keys as ``architectures.jsonl`` holds them) and, for the order of the
operations, from the family's public port (``transformers``'
``modeling_longcat_flash.py``) as remembered; what is remembered and not in
the file is listed under ``assumed`` in ``configs/longcat-flash-omni.json``.

One layer (``h`` the residual stream, RMSNorm eps ``rms_norm_eps``)::

    a0 = h  + MLA0(norm(h;  g_in0))
    x0 = norm(a0; g_post0)
    s  = MoE(x0)                    # the shortcut: used only at the end
    b0 = a0 + FFN0(x0)              # SwiGLU, ffn_hidden_size wide
    a1 = b0 + MLA1(norm(b0; g_in1))
    x1 = norm(a1; g_post1)
    h' = a1 + FFN1(x1) + s

- MLA, *not* absorbed: c_q = RMSNorm(x W_qa); q = (c_q W_qb) a_q, a_q =
  sqrt(hidden / q_lora_rank) (``mla_scale_q_lora``), per head split into
  q_nope (128) and q_rope (64). [c_kv | k_rope] = x W_kva; c = RMSNorm(c_kv)
  a_kv, a_kv = sqrt(hidden / kv_lora_rank) (``mla_scale_kv_lora``); per head
  [k_nope | v] = c W_kvb. One rotary key shared by all heads, NOT scaled.
  RoPE turns consecutive pairs (x[2i], x[2i+1]) at theta = 1e7, no scaling.
  score = (q_nope.k_nope + q_rope.k_rope) / sqrt(192), causal, softmax.
- MoE: p = softmax(x W_r) in float32 over ``n_routed_experts +
  zero_expert_num`` outputs; the ``moe_topk`` largest of p + b are picked
  (b the correction bias); weight w_j = ``routed_scaling_factor`` p_j, not
  renormalised. s = sum over picked computing experts of w_j E_j(x) (SwiGLU,
  ``expert_ffn_hidden_size`` wide) + sum over picked zero-compute experts of
  w_j x (``zero_expert_type`` ``identity``). No shared expert.

Departures, stated:

- The file describes ONE RANK of an expert-parallel deployment (``ep_rank``
  of ``ep_size``): ``n_routed_experts`` counts the experts held here,
  ``n_routed_experts * ep_size`` the model's; the router keeps its whole
  width. This rank's result is the reference: the sum runs over the picked
  experts ``ep_rank * held .. (ep_rank + 1) * held`` only, picks of experts
  held elsewhere add nothing, and the identity picks are computed here
  (where the token lives). Without ``ep_rank`` every expert is held.
- The multi-token-prediction layer, the audio and vision encoders and the
  codec decoder are left out (the configuration's ``left_out``).

It shares no code with ``dynamo_tpu/models``. Weights are data: the arrays
the worker serves, cast to float32 a piece at a time. ``score.py`` upcasts
one whole entry of ``layers()`` at once, and a whole layer is 5 GB in
float32 beside the 10.4 GB the child already holds; so a layer is yielded
as ``open`` (the first attention block and the router: the carry becomes
``(a0, x0, weight, acc)``, ``acc`` starting as the identity picks' term),
its held experts in blocks of ``EXPERT_BLOCK`` (``experts``: every expert of
the block on every token in a plain loop, weighted by the gate's column,
zero where it was not picked), ``mid`` (the first FFN and the second
attention block) and ``close`` (the second FFN and the shortcut's return).
"""

import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4


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


def held_range(hf):
    """(first held expert, experts held, computing experts of the model)."""
    held = hf["n_routed_experts"]
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf.get("ep_rank", 0) * held, held, held * ep


def attention(hf, w, h):
    """h + MLA(norm(h)), unabsorbed."""
    if hf.get("rope_scaling") or not hf.get("rope_interleave", True):
        raise NotImplementedError("rope_scaling null, interleaved pairs")
    T, H = h.shape
    n, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, dc = hf["v_head_dim"], hf["kv_lora_rank"]
    a_q = math.sqrt(H / hf["q_lora_rank"]) if hf.get("mla_scale_q_lora") \
        else 1.0
    a_kv = math.sqrt(H / dc) if hf.get("mla_scale_kv_lora") else 1.0
    theta = float(hf["rope_theta"])
    x = rms_norm(h, w["attn_norm"], eps)
    c_q = rms_norm(x @ w["wq_a"], w["q_a_norm"], eps)
    q = ((c_q @ w["wq_b"]) * a_q).reshape(T, n, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_pairs(q[..., dn:], theta)
    down = x @ w["wkv_a"]
    c = rms_norm(down[:, :dc], w["kv_a_norm"], eps) * a_kv
    k_rope = rope_pairs(down[:, dc:], theta)                    # [T, dr]
    up = (c @ w["wkv_b"]).reshape(T, n, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    scores = (jnp.einsum("tnd,snd->nts", q_nope, k_nope)
              + jnp.einsum("tnd,sd->nts", q_rope, k_rope)) \
        / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return h + out.reshape(T, n * dv) @ w["wo"]


def swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def gate(hf, w, x):
    """[T, E + Z] weight of the router's output j for token t, zero where j
    was not picked."""
    if hf.get("zero_expert_num") and hf.get("zero_expert_type") != "identity":
        raise NotImplementedError("identity zero-compute experts")
    p = jax.nn.softmax(x @ w["w_router"], axis=-1)
    _, top_i = jax.lax.top_k(p + w["router_bias"], hf["moe_topk"])
    top_w = jnp.take_along_axis(p, top_i, axis=-1) \
        * hf.get("routed_scaling_factor", 1.0)
    return jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)


def layer_open(hf, w, h):
    a0 = attention(hf, w["attn0"], h)
    x0 = rms_norm(a0, w["attn0"]["mlp_norm"], hf["rms_norm_eps"])
    weight = gate(hf, w, x0)
    _first, _held, routed = held_range(hf)
    # the zero-compute experts: the token itself times their weights
    acc = jnp.sum(weight[:, routed:], axis=-1, keepdims=True) * x0
    return a0, x0, weight, acc


def layer_experts(hf, w, carry):
    """The held experts ``first .. first + EXPERT_BLOCK`` (numbered within
    the held range), each on every token, in a plain loop."""
    a0, x0, weight, acc = carry
    cols = jax.lax.dynamic_slice_in_dim(
        weight, held_range(hf)[0] + w["first"].astype(jnp.int32),
        w["w_gate"].shape[0], axis=1)

    def one_expert(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * swiglu(
            x0, {"w_gate": g, "w_up": u, "w_down": d}), None

    acc, _ = jax.lax.scan(one_expert, acc,
                          (w["w_gate"], w["w_up"], w["w_down"], cols.T))
    return a0, x0, weight, acc


def layer_mid(hf, w, carry):
    a0, x0, _weight, s = carry
    b0 = a0 + swiglu(x0, w["ffn0"])
    return attention(hf, w["attn1"], b0), s


def layer_close(hf, w, carry):
    a1, s = carry
    x1 = rms_norm(a1, w["attn1"]["mlp_norm"], hf["rms_norm_eps"])
    return a1 + swiglu(x1, w["ffn1"]) + s


def layer(hf, w, h):
    """One whole double layer, its held experts ``[E_held, ...]`` in ``w``:
    the pieces in turn."""
    carry = layer_open(hf, w, h)
    for first in range(0, w["w_gate"].shape[0], EXPERT_BLOCK):
        block = {k: w[k][first:first + EXPERT_BLOCK] for k in EXPERTS}
        carry = layer_experts(hf, dict(block, first=jnp.asarray(first)),
                              carry)
    return layer_close(hf, w, layer_mid(hf, w, carry))


EXPERTS = ("w_gate", "w_up", "w_down")


class ExpertBlocks:
    """One layer's stacked held experts ``[E_held, ...]`` as blocks of
    ``EXPERT_BLOCK``: ``blocks[i]`` cuts block ``i`` out of the served
    array, so ``score.py``'s ``a[i].astype(float32)`` holds one block in
    float32 at a time and no second copy of the layer."""

    def __init__(self, stacked, layer: int):
        self.stacked, self.layer = stacked, layer

    def __getitem__(self, i):
        return self.stacked[self.layer,
                            i * EXPERT_BLOCK:(i + 1) * EXPERT_BLOCK]


class Layers(list):
    """``(kind, stacked layer weights, count)`` in model order. Indexed, it
    is the model's layers whole (``layer``). Iterated - which is how
    ``score.py`` walks it on the chip - a layer comes as its pieces
    instead (module docstring), so that no whole layer is ever upcast at
    once."""

    def __iter__(self):
        for _kind, stack, n in list.__iter__(self):
            for i in range(n):
                def cut(*names):
                    return {k: jax.tree_util.tree_map(
                        lambda a: a[i:i + 1], stack[k]) for k in names}
                yield "open", cut("attn0", "w_router", "router_bias"), 1
                E = stack["w_gate"].shape[1]
                blocks = {k: ExpertBlocks(stack[k], i) for k in EXPERTS}
                blocks["first"] = jnp.arange(0, E, EXPERT_BLOCK)
                yield "experts", blocks, -(-E // EXPERT_BLOCK)
                yield "mid", cut("ffn0", "attn1"), 1
                # (attn1 here for its post-attention norm alone)
                yield "close", {**cut("ffn1"), "attn1": {
                    "mlp_norm": stack["attn1"]["mlp_norm"][i:i + 1]}}, 1


def layers(params):
    stack = params["layers"]
    return Layers([("layer", stack, stack["w_router"].shape[0])])


LAYER_FNS = {"layer": layer, "open": layer_open, "experts": layer_experts,
             "mid": layer_mid, "close": layer_close}


def head(hf, params, h):
    f32 = jnp.float32
    h = rms_norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)
