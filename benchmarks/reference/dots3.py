"""Plain reference of dots3-note-prev's language model: latent attention of
two geometries - full layers that attend a learned selection of
``index_topk`` tokens, window layers of ``sliding_window_size`` tokens with a
wider latent - a headwise output gate on both, a sigmoid-routed sparse FFN
with a shared expert - in float32 ``jax.numpy``, one whole sequence at a
time, with no cache, no absorption, no kernel and no batching.

Written from the model's published ``config.json``
(huggingface.co/dots-studio/dots3-note-prev, as ``architectures.jsonl``
holds it: the sizes and ``described_as``); what the file does not say is
listed under ``assumed`` in ``configs/dots3-note-prev.json``.

``norm(x; w) = x / rms(x) * w`` (eps ``rms_norm_eps``). A layer is ``h <- h
+ Attn(norm(h))``, ``h <- h + FFN(norm(h))``; layer ``i`` is of kind
``layer_types[i]``; the first ``first_k_dense_replace`` layers' FFN is a
SwiGLU of ``intermediate_size``, every other the sparse block.

- Latent attention, either kind (``n`` heads of ``dn`` + ``dr`` query/key
  and ``dv`` value dimensions, ranks ``rq`` and ``rkv``, ``alpha_q =
  sqrt(hidden / rq)``, ``alpha_kv = sqrt(hidden / rkv)`` where
  ``apply_mla_qkv_lora_rescale``): ``c_q = norm(x W_qa)``; ``[q_n | q_r] =
  (alpha_q c_q) W_qb`` a head; ``[c_kv | k_r] = x W_kva``; ``c = alpha_kv
  norm(c_kv)``; ``[k_n | v] = c W_kvb`` a head, MATERIALISED; rotary
  (interleaved pairs, ``theta`` of the kind) on ``q_r`` and on the one
  ``k_r`` all heads share; ``a[h, t, s] = (q_n . k_n + q_r . k_r) / sqrt(dn
  + dr)``; softmax over the keys the kind lets token ``t`` see; ``o =
  softmax(a) v``; ``o <- sigmoid(x W_g)[h] o`` a head; ``out = o W_o``.
- What a full layer's token sees: the indexer's dense score matrix ``I[t,
  s] = sum_j (x W_w)[t, j] relu(q_I[t, j] . k_I[s])`` with ``q_I = c_q
  W_Iqb`` (``index_n_heads`` heads of ``index_head_dim``, rotary on each
  head's first ``dr``), ``k_I = LayerNorm(x W_Ik)`` (weight, bias, eps 1e-6;
  rotary on its first ``dr``); ``s > t`` masked; ``lax.top_k`` keeps the
  ``min(index_topk, T)`` best of each row, and a mask built from those
  indices (less the masked ones) is the softmax's. Computed a block of
  ``QUERY_BLOCK`` queries at a time so that sixteen thousand tokens fit.
- What a window layer's token sees: the band ``t - sliding_window_size < s
  <= t``.
- Sparse block (``noaux_tc``): ``p = sigmoid(x W_r)`` in float32; the
  ``num_experts_per_tok`` largest of ``p + bias`` (one group); their ``p``
  renormalised to sum 1 (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``sum w_e SwiGLU_e(x) + SwiGLU_shared(x)``.

Departures, stated:

- The file describes ONE RANK of an expert-parallel deployment (``ep_rank``
  of ``ep_size``): ``n_routed_experts`` counts the experts held here,
  ``n_routed_experts * ep_size`` the model's, and the router keeps its
  whole width. This rank's result is the reference: the routed sum runs
  over the picked experts ``ep_rank * held .. (ep_rank + 1) * held`` only -
  every held expert on every token in a plain loop, weighted by the gate's
  column, zero where it was not picked - and the shared expert is computed
  here. Without ``ep_rank`` every expert is held.
- The indexer is DeepSeek-V3.2's lightning indexer as publicly described.
  Its constant factors (``index_n_heads ** -0.5 * index_head_dim ** -0.5``)
  change no selection and are left out; its Hadamard rotation of ``q_I`` and
  ``k_I`` is orthogonal (it changes no score) and its FP8 key cache is a
  precision this configuration does not state: both left out.
- The vision and audio towers and the multi-token-prediction module are
  left out (``left_out``).

It shares no code with ``dynamo_tpu/models`` or ``dynamo_tpu/ops``. Weights
are data: the arrays the worker serves, cast to float32 a layer at a time.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 64


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope_pairs(x, theta):
    """x [T, ..., d]: rotary over consecutive pairs ``(x[2i], x[2i+1])``
    of token ``t`` at position ``t``."""
    T, d = x.shape[0], x.shape[-1]
    inv = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                      jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rope_first(x, dr, theta):
    return jnp.concatenate([rope_pairs(x[..., :dr], theta), x[..., dr:]], -1)


def geometry(hf, kind):
    """(heads, dn, dr, dv, rq, rkv, theta) of a layer kind."""
    p = "swa_" if kind == "sliding_attention" else ""
    return (hf[p + "num_attention_heads"], hf[p + "qk_nope_head_dim"],
            hf[p + "qk_rope_head_dim"], hf[p + "v_head_dim"],
            hf[p + "q_lora_rank"], hf[p + "kv_lora_rank"],
            float(hf[p + "rope_theta"]))


def index_scores(hf, w, x, c_q, dr, theta):
    """The dense ``[T, T]`` index score matrix of a full layer, future keys
    at ``-inf``."""
    T = x.shape[0]
    J, D = hf["index_n_heads"], hf["index_head_dim"]
    q = rope_first((c_q @ w["wi_qb"]).reshape(T, J, D), dr, theta)
    k = rope_first(layer_norm(x @ w["wi_k"], w["i_norm_w"], w["i_norm_b"]),
                   dr, theta)
    wt = x @ w["wi_w"]
    blocks = -(-T // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - T
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    wp = jnp.pad(wt, ((0, pad), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        wb = jax.lax.dynamic_slice_in_dim(wp, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("tjd,sd->tjs", qb, k)
        return jnp.sum(wb[:, :, None] * jax.nn.relu(s), axis=1)

    scores = jax.lax.map(block, jnp.arange(blocks)).reshape(
        blocks * QUERY_BLOCK, T)[:T]
    t = jnp.arange(T)
    return jnp.where(t[None, :] <= t[:, None], scores, -jnp.inf)


def selection_mask(hf, scores):
    """``[T, T]`` bool from ``lax.top_k`` of each row of the index scores:
    the ``min(index_topk, T)`` best, less those that were masked."""
    T = scores.shape[0]
    vals, idx = jax.lax.top_k(scores, min(hf["index_topk"], T))
    rows = jnp.arange(T)[:, None]
    return jnp.zeros((T, T), bool).at[rows, idx].set(vals > -jnp.inf)


def attention(hf, w, h, kind):
    """h + Attn(norm(h)) of a layer of ``kind``."""
    T, H = h.shape
    n, dn, dr, dv, rq, rkv, theta = geometry(hf, kind)
    eps = hf["rms_norm_eps"]
    rescale = hf.get("apply_mla_qkv_lora_rescale")
    a_q = (H / rq) ** 0.5 if rescale else 1.0
    a_kv = (H / rkv) ** 0.5 if rescale else 1.0
    x = norm(h, w["attn_norm"], eps)
    c_q = norm(x @ w["wq_a"], w["q_a_norm"], eps)
    q = ((a_q * c_q) @ w["wq_b"]).reshape(T, n, dn + dr)
    q_n, q_r = q[..., :dn], rope_pairs(q[..., dn:], theta)
    ckv = x @ w["wkv_a"]
    c = a_kv * norm(ckv[:, :rkv], w["kv_a_norm"], eps)
    k_r = rope_pairs(ckv[:, rkv:], theta)
    kv = (c @ w["wkv_b"]).reshape(T, n, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    t = jnp.arange(T)
    if kind == "sliding_attention":
        sees = ((t[None, :] <= t[:, None])
                & (t[None, :] > t[:, None] - hf["sliding_window_size"]))
    else:
        sees = selection_mask(hf, index_scores(hf, w, x, c_q, dr, theta))
    blocks = -(-T // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - T
    qn_p = jnp.pad(q_n, ((0, pad), (0, 0), (0, 0)))
    qr_p = jnp.pad(q_r, ((0, pad), (0, 0), (0, 0)))
    sees_p = jnp.pad(sees, ((0, pad), (0, 0)))

    def block(i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(
            a, i * QUERY_BLOCK, QUERY_BLOCK)
        a = (jnp.einsum("tnd,snd->nts", cut(qn_p), k_n)
             + jnp.einsum("tnd,sd->nts", cut(qr_p), k_r)) * (dn + dr) ** -0.5
        m = cut(sees_p)[None]
        a = jnp.where(m, a, -jnp.inf)
        p = jnp.where(m, jnp.exp(a - jnp.max(a, -1, keepdims=True)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        return jnp.einsum("nts,snd->tnd", p, v)

    o = jax.lax.map(block, jnp.arange(blocks)).reshape(
        blocks * QUERY_BLOCK, n, dv)[:T]
    gate_key = ("swa_" if kind == "sliding_attention" else "") \
        + "attention_gate_type"
    if hf.get(gate_key) == "headwise":
        o = o * jax.nn.sigmoid(x @ w["w_og"])[:, :, None]
    return h + o.reshape(T, n * dv) @ w["wo"]


def held_range(hf):
    """(first held expert, experts held, experts of the model)."""
    held = hf["n_routed_experts"]
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf.get("ep_rank", 0) * held, held, held * ep


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def sparse_block(hf, w, h):
    """h + moe(norm(h)): this rank's share."""
    x = norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    p = jax.nn.sigmoid(x @ w["w_router"])
    _v, top_i = jax.lax.top_k(p + w["router_bias"],
                              hf["num_experts_per_tok"])
    top_w = jnp.take_along_axis(p, top_i, axis=-1)
    if hf.get("norm_topk_prob", True):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * float(hf.get("routed_scaling_factor") or 1.0)
    weight = jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)
    first, held, _routed = held_range(hf)
    cols = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=1)

    def one_expert(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * swiglu(x, g, u, d), None

    acc = jnp.zeros_like(h)
    if hf.get("n_shared_experts"):
        acc = swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"])
    acc, _ = jax.lax.scan(one_expert, acc,
                          (w["w_gate"], w["w_up"], w["w_down"], cols.T))
    return h + acc


def dense_block(hf, w, h):
    x = norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def dense_layer(hf, w, h):
    return dense_block(hf, w, attention(hf, w, h, "full_attention"))


def full_layer(hf, w, h):
    return sparse_block(hf, w, attention(hf, w, h, "full_attention"))


def window_layer(hf, w, h):
    return sparse_block(hf, w, attention(hf, w, h, "sliding_attention"))


class _Of:
    """A stacked leaf seen from one place: ``leaf[i]`` is the layer at
    ``at + (i,)``. ``score.py`` upcasts ``a[i]``, so one layer is in
    float32 at a time and no stack is ever copied whole."""

    def __init__(self, leaf, at: tuple):
        self.leaf, self.at = leaf, at

    def __getitem__(self, i):
        return self.leaf[self.at + (i,)]


def layers(params):
    """``(kind, stacked layer weights, count)`` in the published order: the
    dense-FFN layers, then each period's full layer and its window layers,
    then what full layers are left. The stacks are views (``_Of``)."""
    full, win = params["layers"]["full"], params["layers"]["win"]
    out = []
    if "dense_layers" in params:
        dl = params["dense_layers"]
        out.append(("dense", dl, dl["wo"].shape[0]))
    P, G = win["wo"].shape[:2]
    for p in range(P):
        out.append(("full", {k: _At(v, p) for k, v in full.items()}, 1))
        out.append(("win", {k: _Of(v, (p,)) for k, v in win.items()}, G))
    for p in range(P, full["wo"].shape[0]):
        out.append(("full", {k: _At(v, p) for k, v in full.items()}, 1))
    return out


class _At:
    """One layer of a stack, whatever index it is asked for."""

    def __init__(self, leaf, p: int):
        self.leaf, self.p = leaf, p

    def __getitem__(self, _i):
        return self.leaf[self.p]


LAYER_FNS = {"dense": dense_layer, "full": full_layer, "win": window_layer}


def head(hf, params, h):
    f32 = jnp.float32
    h = norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)
