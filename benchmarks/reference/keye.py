"""Plain reference of Keye-VL-2.0-30B-A3B's language model: the Qwen3-MoE
block - grouped-query attention with a per-head q/k norm, 128 routed SwiGLU
experts - whose every layer attends a LEARNED SELECTION of ``sa_config.topk``
tokens chosen by an indexer, in float32 ``jax.numpy``, one whole sequence at
a time, with no cache, no kernel and no batching.

Written from the model's published ``config.json``
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, as ``architectures.jsonl``
holds it: the sizes and ``described_as``); what the file does not say is
listed under ``assumed`` in ``configs/keye-vl-2.0-30b-a3b.json``.

``norm(x; w) = x / rms(x) * w`` (eps ``rms_norm_eps``). Every layer is ``h
<- h + Attn(norm(h))``, ``h <- h + MoE(norm(h))``; a final norm, an untied
head. With ``x = norm(h)``:

- ``q = x W_q`` (``num_attention_heads`` heads of ``head_dim``), ``k = x
  W_k``, ``v = x W_v`` (``num_key_value_heads``); q and k normed a head;
  rotary over the whole head at ``rope_theta``, position ``t`` turning the
  planes ``(i, i + head_dim / 2)``.
- The indexer: ``q_I = x W_qI`` (``indexer_num_heads`` heads of
  ``indexer_head_dim``), ONE key a token ``k_I = LayerNorm(x W_kI)``
  (weight, bias, eps 1e-6), both turned by the token's position over their
  whole width, ``w = x W_w``; the dense score matrix ``I[t, s] = sum_j w[t,
  j] relu(q_I[t, j] . k_I[s])``, ``s > t`` masked; ``lax.top_k`` keeps the
  ``min(topk, T)`` best of each row and a mask built from those indices
  (less the masked ones) is the softmax's: one selection a token for every
  head. Computed a block of ``QUERY_BLOCK`` queries at a time, so that
  twelve thousand tokens fit.
- ``a[t, h, s] = q[t, h] . k[s, g(h)] / sqrt(head_dim)`` over the selected
  ``s`` alone, softmax, times ``v``, then ``W_o``.
- MoE: softmax over the router's logits, the ``num_experts_per_tok``
  largest, renormalised (``norm_topk_prob``); every expert on every token
  in a plain loop, weighted by the gate's column, zero where not picked.

Departures, stated:

- The indexer is DeepSeek-V3.2's lightning indexer as publicly described,
  which ``described_as`` names. Its constant factors change no selection
  and are left out; its Hadamard rotation of ``q_I`` and ``k_I`` is
  orthogonal (it changes no score) and its FP8 key cache is a precision
  this configuration does not state: both left out. ``q_chunk_size`` /
  ``kv_chunk_size`` are the tiling in which the published code evaluates
  the scores and change no selection.
- Text alone: the three position streams of ``mrope_section`` are equal,
  and multimodal rotary is then plain rotary (``mrope``, below, is the
  general form, held equal to ``rope`` by ``tests/test_keye.py``).
- The vision tower is left out (``left_out``).

It shares no code with ``dynamo_tpu/models`` or ``dynamo_tpu/ops``. Weights
are data: the arrays the worker serves, cast to float32 a layer at a time.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
f32 = jnp.float32


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, pos, theta):
    """x [T, heads, d] at positions pos [T]: position t turns the planes
    (i, i + d/2) by t * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    ang = pos.astype(f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mrope(x, pos3, theta, section):
    """Multimodal rotary: ``pos3 [3, T]`` the temporal, height and width
    positions; plane ``i`` of a head turns by the stream its section
    names (``section`` planes of each stream in turn). With the three
    streams equal it is ``rope``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(section),
                        total_repeat_length=d // 2)
    ang = pos3.astype(f32)[stream, :].T * inv[None, :]          # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def blocks_of(T):
    n = -(-T // QUERY_BLOCK)
    return n, n * QUERY_BLOCK - T


def index_scores(hf, w, x, pos):
    """The dense ``[T, T]`` index score matrix, future keys at ``-inf``."""
    T = x.shape[0]
    sa = hf["sa_config"]
    J, D = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta = float(hf["rope_theta"])
    q = rope((x @ w["wi_q"]).reshape(T, J, D), pos, theta)
    k = rope(layer_norm(x @ w["wi_k"], w["i_norm_w"],
                        w["i_norm_b"])[:, None, :], pos, theta)[:, 0]
    wt = x @ w["wi_w"]
    n, pad = blocks_of(T)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    wp = jnp.pad(wt, ((0, pad), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        wb = jax.lax.dynamic_slice_in_dim(wp, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("tjd,sd->tjs", qb, k)
        return jnp.sum(wb[:, :, None] * jax.nn.relu(s), axis=1)

    scores = jax.lax.map(block, jnp.arange(n)).reshape(
        n * QUERY_BLOCK, T)[:T]
    return jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)


def selection_mask(hf, scores):
    """``[T, T]`` bool from ``lax.top_k`` of each row of the index scores:
    the ``min(topk, T)`` best, less those that were masked."""
    T = scores.shape[0]
    vals, idx = jax.lax.top_k(scores, min(hf["sa_config"]["topk"], T))
    rows = jnp.arange(T)[:, None]
    return jnp.zeros((T, T), bool).at[rows, idx].set(vals > -jnp.inf)


def attention(hf, w, h):
    """h + Attn(norm(h)) over each token's selection."""
    T = h.shape[0]
    nq, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = hf.get("head_dim") or hf["hidden_size"] // nq
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    pos = jnp.arange(T)
    x = norm(h, w["attn_norm"], eps)
    q = rope(norm((x @ w["wq"]).reshape(T, nq, dh), w["q_norm"], eps),
             pos, theta)
    k = rope(norm((x @ w["wk"]).reshape(T, nkv, dh), w["k_norm"], eps),
             pos, theta)
    v = (x @ w["wv"]).reshape(T, nkv, dh)
    sees = selection_mask(hf, index_scores(hf, w, x, pos))
    k, v = (jnp.repeat(a, nq // nkv, axis=1) for a in (k, v))
    n, pad = blocks_of(T)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    sp = jnp.pad(sees, ((0, pad), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        m = jax.lax.dynamic_slice_in_dim(sp, i * QUERY_BLOCK,
                                         QUERY_BLOCK)[None]
        a = jnp.einsum("tnd,snd->nts", qb, k) * dh ** -0.5
        a = jnp.where(m, a, -jnp.inf)
        p = jnp.where(m, jnp.exp(a - jnp.max(a, -1, keepdims=True)), 0.0)
        p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
        return jnp.einsum("nts,snd->tnd", p, v)

    o = jax.lax.map(block, jnp.arange(n)).reshape(
        n * QUERY_BLOCK, nq * dh)[:T]
    return h + o @ w["wo"]


def experts(hf, w, h):
    """h + MoE(norm(h)): every expert on every token, weighted by the
    gate's column."""
    x = norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    p = jax.nn.softmax(x @ w["w_router"], axis=-1)
    top_w, top_i = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob", True):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)

    def one(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * ((jax.nn.silu(x @ g) * (x @ u)) @ d), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return h + acc


def layer(hf, w, h):
    return experts(hf, w, attention(hf, w, h))


def layers(params):
    """(kind, stacked layer weights, count) in model order."""
    return [("block", params["layers"], params["layers"]["wq"].shape[0])]


LAYER_FNS = {"block": layer}


def head(hf, params, h):
    h = norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)


def forward(hf, params, tokens):
    """Logits ``[T, V]`` of one whole sequence, a layer at a time (the
    tests' form of ``score.next_token_rule``)."""
    h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(f32)
    for _kind, stack, n in layers(params):
        for i in range(n):
            h = layer(hf, jax.tree_util.tree_map(
                lambda a, i=i: a[i].astype(f32), stack), h)
    return head(hf, params, h)
