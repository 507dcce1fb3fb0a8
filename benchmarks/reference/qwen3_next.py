"""Plain reference of Qwen3-Next's language model: Gated DeltaNet
linear-attention layers, gated full attention every fourth layer, a sparse
block with a gated shared expert in every layer - in float32 ``jax.numpy``,
one whole sequence at a time, with no cache, state pool, chunking, kernel or
batching.

Written from the model's published ``config.json``
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, as ``architectures.jsonl``
holds it) and, for the order of the operations, from the family's public
port (``transformers``' ``modeling_qwen3_next.py``) as remembered; what is
remembered and not in the file is listed under ``assumed`` in
``configs/qwen3-next-80b-a3b-instruct.json``.

``norm(x; w) = x / rms(x) * (1 + w)`` (eps ``rms_norm_eps``): the stream's
two norms a layer, the final norm and the per-head q/k norms. A layer is
``h <- h + mixer(norm(h))``, ``h <- h + moe(norm(h))``; layer ``i`` is full
attention where ``(i + 1) % full_attention_interval == 0``.

- Gated DeltaNet mixer (``Hk`` key heads, ``Hv`` value heads of ``D``; key
  head ``j`` serves value heads ``j * Hv / Hk ..``): ``[q | k | v | z] = x
  W_qkvz``, ``[b | a] = x W_ba``; ``(q, k, v) <- SiLU(conv(q | k | v))``, a
  causal depthwise convolution of width ``linear_conv_kernel_dim`` from
  zeros, no bias; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q <- q / |q| / sqrt(D)``, ``k <- k / |k|``; with the state
  ``S [D, D]`` a value head from zeros, for each token ``t``::

      S <- exp(g_t) S
      u  = beta_t (v_t - S^T k_t)
      S <- S + k_t u^T
      o_t = S^T q_t

  (a ``lax.scan`` over the tokens: these lines, no chunking); ``y = o /
  rms(o) * w_o * SiLU(z)`` a head, ``w_o`` a plain weight; ``out = y W_out``.
- Gated full attention: ``[q | gate] = x W_q`` a head; ``q <- norm(q)``,
  ``k <- norm(k)`` a head; rotate-half RoPE at ``rope_theta`` on the first
  ``partial_rotary_factor`` of the head's dimensions; causal softmax, scale
  ``head_dim ** -0.5``, by blocks of ``QUERY_BLOCK`` queries so that twelve
  thousand tokens fit; ``out = (attn * sigmoid(gate)) W_o``.
- Sparse block: ``p = softmax(x W_r)`` in float32; the ``num_experts_per_tok``
  largest, renormalised to sum 1 (``norm_topk_prob``); ``sum w_e SwiGLU_e(x)
  + sigmoid(x . w_sg) SwiGLU_shared(x)``.

Departures, stated:

- The file describes ONE RANK of an expert-parallel deployment (``ep_rank``
  of ``ep_size``): ``num_experts`` counts the experts held here,
  ``num_experts * ep_size`` the model's, and the router keeps its whole
  width. This rank's result is the reference: the routed sum runs over the
  picked experts ``ep_rank * held .. (ep_rank + 1) * held`` only - every
  held expert on every token in a plain loop, weighted by the gate's
  column, zero where it was not picked - and the shared expert is computed
  here. Without ``ep_rank`` every expert is held.
- The columns of ``W_qkvz`` and ``W_ba`` are ``q | k | v | z`` and ``b |
  a``; the checkpoint interleaves them by key head (a permutation of
  columns, which seeded weights do not see).
- The multi-token-prediction layer is left out (``left_out``).

It shares no code with ``dynamo_tpu/models`` or ``dynamo_tpu/ops``. Weights
are data: the arrays the worker serves, cast to float32 a layer at a time.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rope_half(x, theta):
    """x [T, n, d]: rotate-half rotary over all ``d`` dimensions of token
    ``t`` at position ``t``."""
    T, d = x.shape[0], x.shape[-1]
    inv = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                      jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def held_range(hf):
    """(first held expert, experts held, experts of the model)."""
    held = hf["num_experts"]
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf.get("ep_rank", 0) * held, held, held * ep


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def sparse_block(hf, w, h):
    """h + moe(norm(h)): this rank's share."""
    x = norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    p = jax.nn.softmax(x @ w["w_router"], axis=-1)
    top_w, top_i = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob", True):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)
    first, held, _routed = held_range(hf)
    cols = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=1)

    def one_expert(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * swiglu(x, g, u, d), None

    shared = jax.nn.sigmoid(x @ w["w_sg"])[:, None] * swiglu(
        x, w["ws_gate"], w["ws_up"], w["ws_down"])
    acc, _ = jax.lax.scan(one_expert, shared,
                          (w["w_gate"], w["w_up"], w["w_down"], cols.T))
    return h + acc


def gated_delta_net(hf, w, h):
    """h + GatedDeltaNet(norm(h))."""
    T = h.shape[0]
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K = hf["linear_conv_kernel_dim"]
    x = norm(h, w["attn_norm"], hf["rms_norm_eps"])
    qkvz, ba = x @ w["w_qkvz"], x @ w["w_ba"]
    n_conv = 2 * Hk * Dk + Hv * Dv
    padded = jnp.pad(qkvz[:, :n_conv], ((K - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[i:i + T] * w["conv_w"][i]
                            for i in range(K)))
    z = qkvz[:, n_conv:].reshape(T, Hv, Dv)
    q = mixed[:, :Hk * Dk].reshape(T, Hk, Dk)
    k = mixed[:, Hk * Dk:2 * Hk * Dk].reshape(T, Hk, Dk)
    v = mixed[:, 2 * Hk * Dk:].reshape(T, Hv, Dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / Dk ** 0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hdv,hd->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                        (q, k, v, g, beta))
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + hf["rms_norm_eps"]) * w["o_norm"] \
        * jax.nn.silu(z)
    return h + y.reshape(T, Hv * Dv) @ w["w_out"]


def gated_attention(hf, w, h):
    """h + GatedAttention(norm(h)), by blocks of queries."""
    if hf.get("rope_scaling"):
        raise NotImplementedError("rope_scaling null")
    T = h.shape[0]
    n, nkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                 hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    rd = int(d * hf.get("partial_rotary_factor", 1.0))
    x = norm(h, w["attn_norm"], eps)
    qg = (x @ w["wq"]).reshape(T, n, 2 * d)
    q, gate = norm(qg[..., :d], w["q_norm"], eps), qg[..., d:]
    k = norm((x @ w["wk"]).reshape(T, nkv, d), w["k_norm"], eps)
    v = (x @ w["wv"]).reshape(T, nkv, d)
    q = jnp.concatenate([rope_half(q[..., :rd], theta), q[..., rd:]], -1)
    k = jnp.concatenate([rope_half(k[..., :rd], theta), k[..., rd:]], -1)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))
    blocks = -(-T // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, blocks * QUERY_BLOCK - T), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        scores = jnp.einsum("tnd,snd->nts", qb, k) * d ** -0.5
        t = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)[:, None]
        scores = jnp.where(jnp.arange(T)[None, :] <= t, scores, -jnp.inf)
        return jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, -1), v)

    attn = jax.lax.map(block, jnp.arange(blocks)).reshape(
        blocks * QUERY_BLOCK, n, d)[:T]
    return h + (attn * jax.nn.sigmoid(gate)).reshape(T, n * d) @ w["wo"]


def gdn_layer(hf, w, h):
    return sparse_block(hf, w, gated_delta_net(hf, w, h))


def full_layer(hf, w, h):
    return sparse_block(hf, w, gated_attention(hf, w, h))


class _Of:
    """A stacked leaf seen from one period: ``leaf[i]`` is layer ``i`` of
    the period (``at`` given: the leaf has a place axis behind the period's)
    or the period's one layer. ``score.py`` upcasts ``a[i]``, so one layer
    is in float32 at a time and no period is ever copied whole."""

    def __init__(self, leaf, period: int, places: bool):
        self.leaf, self.period, self.places = leaf, period, places

    def __getitem__(self, i):
        return (self.leaf[self.period, i] if self.places
                else self.leaf[self.period])


def layers(params):
    """``(kind, stacked layer weights, count)`` in the published order: a
    period's linear layers, then its full-attention layer. The stacks are
    views (``_Of``): nothing is cut out of the served arrays here."""
    gdn, full = params["layers"]["gdn"], params["layers"]["full"]
    out = []
    for p in range(full["wq"].shape[0]):
        out.append(("gdn", {k: _Of(v, p, True) for k, v in gdn.items()},
                    gdn["w_out"].shape[1]))
        out.append(("full", {k: _Of(v, p, False) for k, v in full.items()},
                    1))
    return out


LAYER_FNS = {"gdn": gdn_layer, "full": full_layer}


def head(hf, params, h):
    f32 = jnp.float32
    h = norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)
