"""The dense hybrid family (Olmo-Hybrid): Gated DeltaNet linear-attention
layers with a recurrent state beside the paged cache, full attention
without positions every ``full_attention_interval``-th layer, a dense SwiGLU
FFN in every layer. Pure jax.

With ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w`` (a plain weight) a layer
is ``h <- h + rms(mixer(h); w_1)``, ``h <- h + rms(ffn(h); w_2)``: each
branch reads the stream as it is and is normed on its way OUT (the Olmo 2 /
Olmo 3 order). ``logits = rms(h; w_f) W_head``.

- **Gated DeltaNet mixer**: ``qwen3_next.gated_delta_net``, the one mixer of
  both families with linear layers (projections ``q | k | v | z`` and ``b |
  a``, the causal convolution over ``q | k | v``, the gated delta rule over
  the row's state, the gated RMSNorm on its output). Here
  ``linear_allow_neg_eigval`` makes ``beta = 2 sigmoid(b)``, a key head
  serves one value head, and a head is ``Dk x Dv`` = 96 x 192: neither a
  multiple of the kernels' 128-wide tiles (``ops/pallas/gdn.py`` pads them
  in VMEM, never in the pool).
- **Full attention**: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; ``q <-
  rms(q; w_qn)``, ``k <- rms(k; w_kn)`` over the WHOLE projected vector
  before it is split into heads (the Olmo family's q/k norm, not the
  per-head norm of Qwen3); NO rotary embedding (``cfg.rope_theta`` is 0.0:
  there is no base to rotate by, the linear layers carry the order); causal
  softmax attention against the paged cache (``llama.write_rows`` /
  ``attend_rows``: the kernels of the GQA families, here at one query head a
  key/value head); ``out = attn W_o``. No bias, no gate.
- **FFN**: ``(SiLU(x W_gate) * (x W_up)) W_down``.

The cache (``qwen3_next.make_pages``: paged pool, state pool, convolution
pool), the slot in the last column of a row's page table and the ONE
``lax.scan`` over periods are that family's (the linear layers of a period
run in a loop that indexes their weights in the whole stack, so that no
period's weights are sliced out and copied). Weight layout:
``params["layers"]["gdn"]`` leaves ``[P, interval - 1, ...]``,
``params["layers"]["full"]`` leaves ``[P, ...]``. No checkpoint loader: the
family serves seeded random weights until its published tensor names are in
the repository (the checkpoint keeps six separate projections ``q, k, v, g,
a, b`` a mixer; here their columns are laid side by side as ``W_qkvz`` and
``W_ba``, which seeded weights do not see).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    MOE_INIT_GAIN,
    _logits,
    _rms_norm,
    attend_rows,
    packed_rows,
    randn_stack,
    write_rows,
)
from dynamo_tpu.models.moe import flat_layers, grouped_on_chip, layer_at
from dynamo_tpu.models.qwen3_next import (
    conv_init_std,
    decay_init,
    gated_delta_net,
    make_pages,
)
from dynamo_tpu.ops import gdn

Params = Dict[str, Any]

# Standard deviation of ``b = x . w_b`` on tokens of unit variance under
# seeded weights: ``beta = 2 sigmoid(b)`` then spreads over (0, 2) - a
# twentieth of the tokens under 0.16, a twentieth over 1.84, a third past 1.5
# either way (benchmarks/configs/olmo-hybrid-7b.json, ``assumed``, with the
# measured spread). At the matrices' common scale ``b`` would stay within
# +-0.5 of 0 and ``beta`` within 0.8-1.2, and a ``beta`` left at
# ``sigmoid(b)`` would be a change of scale a probe barely sees.
BETA_LOGIT_STD = 1.5
# the embedding's: the first layer's branches read the stream as it is, and
# at the matrices' scale (0.009) every one of them would come out of its
# norm as ``eps`` makes it, not as the weights do
EMBED_STD = 1.0


# Each branch leaves its norm at the norm's weight, so with weights of one
# the stream grows to sqrt(1 + 2 layers) and the mixers' gates - which read
# it as it is - saturate: on the chip a clean bfloat16 run then read 0.31
# nats at the widest against the float32 reference, over the harness's 0.3
# (PERF.md, PR 51). The branch norms' weights are drawn as ``layers ** -0.5``:
# the branches together add twice the embedding's variance and the stream
# stays between 1 and sqrt(3).
def branch_norm_init(cfg: ModelConfig) -> float:
    return cfg.num_layers ** -0.5


# Heads of equal size under seeded weights would make the q/k norm over the
# whole width and a norm a head the same thing to a few percent. Head ``i``
# of the query and key projections is drawn ``HEAD_GAIN ** u_i`` times as
# large, ``u`` evenly spaced over (-1, 1): the whole-width norm keeps those
# proportions (score temperatures from a quarter to four), a norm a head
# would level them.
HEAD_GAIN = 2.0


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init (tests/benchmarks; the benchmark's worker and its
    reference child both call this, so both hold the same weights), drawn
    as ``qwen3_next.init_params`` draws: every stack a layer at a time
    (``llama.randn_stack``), matrices at ``scale`` (default ``MOE_INIT_GAIN
    / sqrt(hidden)``), the decay through ``decay_init``, the convolution's
    taps at ``conv_init_std``; ``b``'s columns of ``W_ba`` at
    ``BETA_LOGIT_STD / sqrt(hidden)`` and the embedding at ``EMBED_STD``
    (both above), the query and key heads at ``HEAD_GAIN``'s spread. The
    branch norms' weights are ``branch_norm_init``, the other norms' ones."""
    if scale is None:
        scale = MOE_INIT_GAIN / cfg.hidden_size ** 0.5
    dtype = jnp.dtype(cfg.dtype)
    P, G = cfg.num_periods, cfg.full_attention_interval - 1
    H, I = cfg.hidden_size, cfg.intermediate_size
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    key_dim = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    k_embed, k_head, k_gdn, k_full = jax.random.split(rng, 4)

    def stack(key, lead: tuple, shape: tuple, std: float = scale):
        n = 1
        for d in lead:
            n *= d
        return randn_stack(key, n, shape, std, dtype).reshape(lead + shape)

    def branches(keys, lead: tuple) -> Dict[str, jnp.ndarray]:
        return {
            "mixer_norm": jnp.full(lead + (H,), branch_norm_init(cfg), dtype),
            "ffn_norm": jnp.full(lead + (H,), branch_norm_init(cfg), dtype),
            "w_gate": stack(next(keys), lead, (H, I)),
            "w_up": stack(next(keys), lead, (H, I)),
            "w_down": stack(next(keys), lead, (I, H)),
        }

    kg = iter(jax.random.split(k_gdn, 16))
    a_log, dt_bias = decay_init(cfg, (P, G))
    layers_gdn = {
        "w_qkvz": stack(next(kg), (P, G), (H, 2 * key_dim + 2 * Hv * Dv)),
        "w_ba": jnp.concatenate(
            [stack(next(kg), (P, G), (H, Hv), BETA_LOGIT_STD / H ** 0.5),
             stack(next(kg), (P, G), (H, Hv))], axis=-1),
        "conv_w": stack(next(kg), (P, G), (cfg.linear_conv_kernel_dim,
                                            cfg.linear_conv_dim),
                        conv_init_std(cfg)),
        "A_log": a_log,
        "dt_bias": dt_bias,
        "o_norm": jnp.ones((P, G, Dv), dtype),
        "w_out": stack(next(kg), (P, G), (Hv * Dv, H)),
        **branches(kg, (P, G)),
    }
    kf = iter(jax.random.split(k_full, 16))

    def heads(w, n):            # head i's columns HEAD_GAIN ** u_i as large
        gain = HEAD_GAIN ** jnp.linspace(-1.0, 1.0, n)
        return (w.reshape(w.shape[:-1] + (n, cfg.head_dim))
                * gain[:, None].astype(dtype)).reshape(w.shape)

    layers_full = {
        "wq": heads(stack(next(kf), (P,), (H, cfg.q_size)), cfg.num_heads),
        "wk": heads(stack(next(kf), (P,), (H, cfg.kv_size)),
                    cfg.num_kv_heads),
        "wv": stack(next(kf), (P,), (H, cfg.kv_size)),
        "wo": stack(next(kf), (P,), (cfg.q_size, H)),
        "q_norm": jnp.ones((P, cfg.q_size), dtype),
        "k_norm": jnp.ones((P, cfg.kv_size), dtype),
        **branches(kf, (P,)),
    }
    params: Params = {
        "embed": randn_stack(k_embed, 1, (cfg.vocab_size, H), EMBED_STD,
                             dtype)[0],
        "layers": {"gdn": layers_gdn, "full": layers_full},
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn_stack(k_head, 1, (H, cfg.vocab_size),
                                        scale, dtype)[0]
    return params


# -------------------------------------------------------------- the layers

def _ffn(cfg: ModelConfig, lp, h):
    """``h + rms(SwiGLU(h); w_2)``."""
    with stage("layer.ffn"):
        act = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
        return h + _rms_norm(act @ lp["w_down"], lp["ffn_norm"],
                             cfg.rms_norm_eps)


def full_mixer(cfg: ModelConfig, lp, h, positions, total_lens, new_lens,
               page_table, cache, lidx, *, attn_impl, starts):
    """``h + rms(Attention(h); w_1)`` against the paged pool's layer
    ``lidx``: the q/k norm over the whole width, no rotary embedding.
    Returns ``(h, cache)``."""
    B, S, _ = h.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    with stage("layer.attn_in"):
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        with stage("qk_norm"):
            q = _rms_norm(q, lp["q_norm"], eps).reshape(B, S, Hq, Dh)
            k = _rms_norm(k, lp["k_norm"], eps).reshape(B, S, Hkv, Dh)
        v = v.reshape(B, S, Hkv, Dh)
    with stage("layer.kv_write"):
        kv = write_rows(cache["kv"], lidx, k, v, page_table, positions,
                        total_lens, new_lens, starts)
    with stage("layer.attn"):
        attn = attend_rows(attn_impl, q, kv, lidx, page_table, positions,
                           total_lens, new_lens, Dh ** -0.5, starts)
    with stage("layer.attn_out"):
        h = h + _rms_norm(attn.reshape(B, S, Hq * Dh) @ lp["wo"],
                          lp["mixer_norm"], eps)
    return h, {**cache, "kv": kv}


# ----------------------------------------------------------------- forward

def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: Dict[str, jnp.ndarray],
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, packed: bool = False
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Scan-over-periods forward (``llama.forward``'s contract, the
    token-packed form included). ``pages`` is ``make_pages``'s dict;
    ``page_table [B, P + 1]`` carries each row's state slot in its last
    column. A passed ``attn_impl`` with the ``pallas_paged_kernel`` marker
    also opts the family into ``gdn_chunk`` / ``gdn_step`` where they lower
    at its geometry (``ops/pallas/gdn.supports``). ``positions`` place the
    keys and values in their pages and nothing else: no layer rotates by
    them. No ``logits_window``, as in ``qwen3_next.forward``."""
    on_chip = grouped_on_chip(attn_impl)
    B, S = tokens.shape
    with stage("step.inputs"):
        slots, page_table = page_table[:, -1], page_table[:, :-1]
        starts = packed_rows(packed, new_lens)
        rows = gdn.token_rows(
            B * S, starts if packed else jnp.arange(B, dtype=jnp.int32) * S,
            new_lens, total_lens, slots)
    with stage("embed"):
        h = params["embed"][tokens]
    G = cfg.full_attention_interval - 1
    eps = cfg.rms_norm_eps
    # the linear layers as ONE stack over periods and places, each layer's
    # leaves read where they lie inside the loop (``moe.flat_layers``; as
    # the periods' scanned slices they were 1.3 GB of temporaries at the
    # published widths: the sandbox's compile for the v5e, PERF.md PR 51)
    with stage("layer.weights"):
        lg = flat_layers(params["layers"]["gdn"])

    def period(carry, xs):
        fp, p = xs

        def linear(j, carry):
            h, cache = carry
            with stage("layer.weights"):
                gidx = p * G + j
                lp = layer_at(lg, gidx)
            with stage("layer.gdn_in"):
                x = h.reshape(B * S, -1)
            out, cache = gated_delta_net(
                cfg, lp, x, cache, gidx, rows, use_pallas=on_chip,
                several=S > 1)
            with stage("layer.gdn_out"):
                h = h + _rms_norm(out.reshape(h.shape), lp["mixer_norm"],
                                  eps)
            return _ffn(cfg, lp, h), cache

        h, cache = jax.lax.fori_loop(0, G, linear, carry)
        h, cache = full_mixer(cfg, fp, h, positions, total_lens, new_lens,
                              page_table, cache, p, attn_impl=attn_impl,
                              starts=starts)
        return (_ffn(cfg, fp, h), cache), None

    with stage("step.inputs"):
        periods = jnp.arange(cfg.num_periods)
    (h, pages), _ = jax.lax.scan(
        period, (h, pages), (params["layers"]["full"], periods))
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, starts=starts)
    return logits, pages


forward.supports_packed = True


__all__ = ["init_params", "forward", "make_pages", "full_mixer"]
