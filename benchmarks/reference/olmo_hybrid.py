"""Plain reference of Olmo-Hybrid's language model: a dense hybrid of Gated
DeltaNet linear-attention layers whose delta rule may reflect and full
attention without positions - in float32 ``jax.numpy``, one whole sequence
at a time, with no cache, state pool, chunking, kernel or batching.

Written from the model's published ``config.json``
(huggingface.co/allenai/Olmo-Hybrid-7B, as ``architectures.jsonl`` holds
it) and, for what the file does not say, from the family's conventions as
remembered - the Olmo 2 / Olmo 3 block and q/k norm, the Gated DeltaNet
layer of flash-linear-attention that the ``linear_*`` keys are named after
- each listed under ``assumed`` in ``configs/olmo-hybrid-7b.json``.

``rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w``, a plain weight. A
layer is ``h <- h + rms(mixer(h); w_1)``, ``h <- h + rms(ffn(h); w_2)``:
each branch reads the stream as it is and is normed on its way out. Layer
``i`` is what ``layer_types[i]`` says.

- Gated DeltaNet mixer (``H`` heads of ``Dk x Dv``, a key head a value
  head): ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x W_ba``; ``(q, k, v)
  <- SiLU(conv(q | k | v))``, a causal depthwise convolution of width
  ``linear_conv_kernel_dim`` from zeros, no bias; ``beta = 2 sigmoid(b)``
  where ``linear_allow_neg_eigval`` (else ``sigmoid(b)``), ``g =
  -exp(A_log) softplus(a + dt_bias)``; ``q <- q / |q| / sqrt(Dk)``, ``k <-
  k / |k|``; with the state ``S [Dk, Dv]`` a head from zeros, for each
  token ``t``::

      S <- exp(g_t) S
      u  = beta_t (v_t - S^T k_t)
      S <- S + k_t u^T
      o_t = S^T q_t

  (a ``lax.scan`` over the tokens: these lines, no chunking); ``y = rms(o;
  w_o) * SiLU(z)`` a head; ``out = y W_out``.
- Full attention: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; ``q <-
  rms(q; w_qn)``, ``k <- rms(k; w_kn)`` over the WHOLE projected vector,
  then the split into heads; no rotary embedding (``rope_theta`` null);
  causal softmax, scale ``head_dim ** -0.5``, one full masked score matrix
  a head; ``out = attn W_o``.
- FFN: ``(SiLU(x W_gate) * (x W_up)) W_down``.

Departures, stated: the checkpoint keeps the mixer's six projections apart
(``q, k, v, g, a, b``); here their columns lie side by side as ``W_qkvz``
and ``W_ba``, as the served weights hold them (a concatenation of columns,
which seeded weights do not see). Nothing of the language model is left
out.

It shares no code with ``dynamo_tpu/models`` or ``dynamo_tpu/ops``. Weights
are data: the arrays the worker serves, cast to float32 a layer at a time.
"""

import jax
import jax.numpy as jnp


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def ffn(hf, w, h):
    """h + rms(SwiGLU(h); w_2)."""
    out = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return h + rms(out, w["ffn_norm"], hf["rms_norm_eps"])


def gated_delta_net(hf, w, h):
    """h + rms(GatedDeltaNet(h); w_1)."""
    T = h.shape[0]
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K = hf["linear_conv_kernel_dim"]
    qkvz, ba = h @ w["w_qkvz"], h @ w["w_ba"]
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
    if hf.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"])

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hdv,hd->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                        (q, k, v, g, beta))
    y = rms(o, w["o_norm"], hf["rms_norm_eps"]) * jax.nn.silu(z)
    return h + rms(y.reshape(T, Hv * Dv) @ w["w_out"], w["mixer_norm"],
                   hf["rms_norm_eps"])


def attention(hf, w, h):
    """h + rms(Attention(h); w_1): no positions anywhere."""
    if (hf.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise NotImplementedError("rope_theta null: no rotary embedding")
    T = h.shape[0]
    n, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = hf.get("head_dim") or hf["hidden_size"] // n
    eps = hf["rms_norm_eps"]
    q = rms(h @ w["wq"], w["q_norm"], eps).reshape(T, n, d)
    k = rms(h @ w["wk"], w["k_norm"], eps).reshape(T, nkv, d)
    v = (h @ w["wv"]).reshape(T, nkv, d)
    k, v = (jnp.repeat(a, n // nkv, axis=1) for a in (k, v))
    scores = jnp.einsum("tnd,snd->nts", q, k) * d ** -0.5
    t = jnp.arange(T)
    scores = jnp.where(t[None, :] <= t[:, None], scores, -jnp.inf)
    attn = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, -1), v)
    return h + rms(attn.reshape(T, n * d) @ w["wo"], w["mixer_norm"], eps)


def gdn_layer(hf, w, h):
    return ffn(hf, w, gated_delta_net(hf, w, h))


def full_layer(hf, w, h):
    return ffn(hf, w, attention(hf, w, h))


class _Of:
    """A stacked leaf seen from one period: ``leaf[i]`` is layer ``i`` of
    the period (``places``: the leaf has a place axis behind the period's)
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
    h = rms(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ params["lm_head"].astype(f32)
