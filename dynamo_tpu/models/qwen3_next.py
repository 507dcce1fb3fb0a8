"""Qwen3-Next family: Gated DeltaNet linear-attention layers with a
recurrent state beside the paged cache, gated full attention every
``full_attention_interval``-th layer, a sparse FFN with a gated shared
expert in every layer. Pure jax.

With ``norm(x; w) = x / rms(x) * (1 + w)`` (zero-centred weights) a layer is
``h <- h + mixer(norm(h))``, ``h <- h + moe(norm(h))``.

- **Gated DeltaNet mixer** (``gated_delta_net``, the one mixer of both
  families with linear layers; ``Hk`` key heads, ``Hv`` value heads, each key
  head serving ``Hv / Hk`` value heads): ``[q | k | v | z] = x W_qkvz``,
  ``[b | a] = x W_ba``; ``(q, k, v) <- SiLU(conv(q | k | v))``, a causal
  depthwise convolution of width ``linear_conv_kernel_dim`` without bias
  that continues from the row's last inputs (``ops/gdn.causal_conv``);
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)`` in
  float32, one each a value head; ``q <- q / |q| / sqrt(Dk)``, ``k <- k /
  |k|``; the gated delta rule over the row's state (``ops/gdn.py``: the
  equations, and the two forms they run in); ``y = o / rms(o) * w_o *
  SiLU(z)`` a head (a plain weight); ``out = y W_out``.
- **Gated full attention**: ``[q | gate] = x W_q`` a head, ``k``, ``v``;
  per-head ``norm`` on ``q`` and ``k``; rotate-half rotary on the first
  ``partial_rotary_factor`` of the head's dimensions; causal softmax
  attention against the paged cache (``llama.write_rows`` /
  ``attend_rows``: the kernels of the GQA families); ``out = (attn *
  sigmoid(gate)) W_o``.
- **Sparse block**: ``moe._router_topk`` (softmax in float32, top-k,
  renormalised) over the router's whole width and
  ``moe.grouped_experts`` over the experts HELD here (``cfg.expert_offset``,
  ``cfg.experts_held``: rank ``cfg.ep_rank`` of ``cfg.ep_size``; a pick of
  an expert held elsewhere adds nothing), plus ``sigmoid(x . w_sg) *
  SwiGLU_shared(x)``, computed here.

**Two kinds of cache.** ``make_pages`` returns ``{"kv": the paged pool of
the full-attention layers, "state": [Lg, slots, Hv, Dk, Dv] float32, "conv":
[Lg, slots, K - 1, Ch]}`` - one donated value through every step program.
A row's slot rides in the LAST column of its page-table row (the engine
writes it there, ``forward`` cuts it off): a row's table is where its cache
lives, pages and state alike, and no step program takes another argument.
Slot 0 is no request's (rows that carry no token point at it).

The forward is ONE ``lax.scan`` over periods (``interval - 1`` linear
layers, an inner scan, then one full layer), so a step program's size does
not grow with depth. Weight layout: ``params["layers"]["gdn"]`` leaves
``[P, interval - 1, ...]``, ``params["layers"]["full"]`` leaves ``[P,
...]``, both holding their own FFN leaves (``mlp_norm``, ``w_router``,
``ws_gate``/``ws_up``/``ws_down``, ``w_sg`` and the held experts
``w_gate``/``w_up``/``w_down``). No checkpoint loader: the family serves
seeded random weights until its published tensor names are in the
repository (the columns of ``W_qkvz`` / ``W_ba`` are laid out ``q | k | v |
z`` and ``b | a``; the checkpoint interleaves them by key head).
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
    attend_rows,
    packed_rows,
    randn_stack,
    write_rows,
)
from dynamo_tpu.ops import gdn
from dynamo_tpu.ops.rope import apply_rope

Params = Dict[str, Any]

# The per-token decay exp(g) of a value head under seeded weights. The
# public port draws A uniform in (0, 16), under which a head forgets within
# a token and a dropped or uncarried state changes nothing a probe sees.
# Here exp(g) at a = 0 is spread log-uniformly in 1 - decay over the value
# heads, from DECAY_SLOW to DECAY_FAST (benchmarks/configs/
# qwen3-next-80b-a3b-instruct.json, ``assumed``, with the measured spread).
DECAY_FAST, DECAY_SLOW = 0.9, 0.9999


def conv_init_std(cfg: ModelConfig) -> float:
    """Standard deviation of the convolution's taps under seeded weights:
    the public port's uniform(+-K ** -0.5), which is (3 K) ** -0.5. At the
    matrices' common scale the convolution's output, and with it every
    value the rule writes, would be a twentieth of that, and the mixer a
    fifth of what it adds here (measured at the published width: 0.04
    against 0.22 RMS on a stream of 0.3)."""
    return (3.0 * cfg.linear_conv_kernel_dim) ** -0.5


def zc_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with a zero-centred weight: ``x / rms(x) * (1 + w)``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def make_pages(cfg: ModelConfig, num_pages: int, page_size: int,
               dtype=None, state_slots: int = 1,
               max_chunk: int = 1) -> Dict[str, jnp.ndarray]:
    """The family's cache: the paged pool of its full-attention layers
    (``llama.make_pages``'s layout) and the two pools a linear layer's
    rows carry, ``state_slots`` requests' worth plus slot 0 (a state's
    size does not depend on ``max_chunk``, the most tokens a row brings
    in one step)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    Lg, n = cfg.state_layers, state_slots + 1
    return {
        "kv": jnp.zeros((cfg.num_cache_layers, num_pages, 2,
                         cfg.num_kv_heads, page_size, cfg.head_dim), dtype),
        "state": jnp.zeros((Lg, n, cfg.linear_num_value_heads,
                            cfg.linear_key_head_dim,
                            cfg.linear_value_head_dim), jnp.float32),
        "conv": jnp.zeros((Lg, n, cfg.linear_conv_kernel_dim - 1,
                           cfg.linear_conv_dim), dtype),
    }


def decay_init(cfg: ModelConfig, shape: tuple) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """``(A_log, dt_bias)`` of ``shape + (Hv,)``: ``dt_bias`` 0 (softplus
    ln 2 at ``a = 0``) and ``A_log`` such that head ``i``'s decay there is
    ``1 - d_i``, ``d`` log-uniform from ``1 - DECAY_FAST`` down to ``1 -
    DECAY_SLOW``; the same in every layer."""
    Hv = cfg.linear_num_value_heads
    lo, hi = jnp.log(1.0 - DECAY_SLOW), jnp.log(1.0 - DECAY_FAST)
    d = jnp.exp(jnp.linspace(hi, lo, Hv))
    a_log = jnp.log(-jnp.log1p(-d) / jnp.log(2.0))
    return (jnp.broadcast_to(a_log, shape + (Hv,)).astype(jnp.float32),
            jnp.zeros(shape + (Hv,), jnp.float32))


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init (tests/benchmarks; the benchmark's worker and its
    reference child both call this, so both hold the same weights). Every
    stack is drawn a layer at a time (``llama.randn_stack``); ``scale``
    defaults to the sparse families' ``MOE_INIT_GAIN / sqrt(hidden)``. Norm
    weights are zeros (zero-centred) but the mixer's output norm (ones);
    the decay is drawn by ``decay_init``. Only the experts this rank holds
    are drawn."""
    if scale is None:
        scale = MOE_INIT_GAIN / cfg.hidden_size ** 0.5
    dtype = jnp.dtype(cfg.dtype)
    P, G = cfg.num_periods, cfg.full_attention_interval - 1
    H, E, Eh = cfg.hidden_size, cfg.num_experts, cfg.experts_held
    Im, Is = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    key_dim = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    k_embed, k_head, k_gdn, k_full = jax.random.split(rng, 4)

    def stack(key, lead: tuple, shape: tuple, std: float = scale):
        n = 1
        for d in lead:
            n *= d
        return randn_stack(key, n, shape, std, dtype).reshape(lead + shape)

    def ffn(keys, lead: tuple) -> Dict[str, jnp.ndarray]:
        return {
            "mlp_norm": jnp.zeros(lead + (H,), dtype),
            "w_router": stack(next(keys), lead, (H, E)),
            "ws_gate": stack(next(keys), lead, (H, Is)),
            "ws_up": stack(next(keys), lead, (H, Is)),
            "ws_down": stack(next(keys), lead, (Is, H)),
            "w_sg": stack(next(keys), lead, (H,)),
            "w_gate": stack(next(keys), lead, (Eh, H, Im)),
            "w_up": stack(next(keys), lead, (Eh, H, Im)),
            "w_down": stack(next(keys), lead, (Eh, Im, H)),
        }

    kg = iter(jax.random.split(k_gdn, 16))
    a_log, dt_bias = decay_init(cfg, (P, G))
    layers_gdn = {
        "attn_norm": jnp.zeros((P, G, H), dtype),
        "w_qkvz": stack(next(kg), (P, G), (H, 2 * key_dim + 2 * Hv * Dv)),
        "w_ba": stack(next(kg), (P, G), (H, 2 * Hv)),
        "conv_w": stack(next(kg), (P, G), (cfg.linear_conv_kernel_dim,
                                            cfg.linear_conv_dim),
                        conv_init_std(cfg)),
        "A_log": a_log,
        "dt_bias": dt_bias,
        "o_norm": jnp.ones((P, G, Dv), dtype),
        "w_out": stack(next(kg), (P, G), (Hv * Dv, H)),
        **ffn(kg, (P, G)),
    }
    kf = iter(jax.random.split(k_full, 16))
    layers_full = {
        "attn_norm": jnp.zeros((P, H), dtype),
        "wq": stack(next(kf), (P,), (H, 2 * cfg.q_size)),
        "wk": stack(next(kf), (P,), (H, cfg.kv_size)),
        "wv": stack(next(kf), (P,), (H, cfg.kv_size)),
        "wo": stack(next(kf), (P,), (cfg.q_size, H)),
        "q_norm": jnp.zeros((P, cfg.head_dim), dtype),
        "k_norm": jnp.zeros((P, cfg.head_dim), dtype),
        **ffn(kf, (P,)),
    }
    params: Params = {
        "embed": randn_stack(k_embed, 1, (cfg.vocab_size, H), scale,
                             dtype)[0],
        "layers": {"gdn": layers_gdn, "full": layers_full},
        "final_norm": jnp.zeros((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn_stack(k_head, 1, (H, cfg.vocab_size),
                                        scale, dtype)[0]
    return params


# -------------------------------------------------------------- the layers

def sparse_block(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 x: jnp.ndarray, **kw
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The layer's FFN as this rank computes it: its held experts' part of
    the routed sum plus the gated shared expert. ``x [B, S, H]`` (normed)
    -> ``([B, S, H], aux)``; ``kw`` goes to ``grouped_experts``."""
    from dynamo_tpu.models.moe import _router_topk, grouped_experts

    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with stage("route"):
        top_w, top_i = _router_topk(cfg, lp, xt)
    out, aux = grouped_experts(
        xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"],
        first_expert=cfg.expert_offset, num_routed=cfg.num_experts, **kw)
    with stage("shared"):
        act = jax.nn.silu(xt @ lp["ws_gate"]) * (xt @ lp["ws_up"])
        gate = jax.nn.sigmoid(jnp.dot(
            xt, lp["w_sg"], preferred_element_type=jnp.float32))
        out = out + gate[:, None] * jnp.dot(
            act, lp["ws_down"], preferred_element_type=jnp.float32)
    return out.reshape(B, S, H).astype(x.dtype), aux


def _ffn(cfg, lp, h, moe_kw):
    with stage("layer.moe"):
        out, aux = sparse_block(
            cfg, lp, zc_norm(h, lp["mlp_norm"], cfg.rms_norm_eps), **moe_kw)
        h = h + out
    return h, aux


def _l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_net(cfg: ModelConfig, lp, x, cache, gidx, rows: gdn.Rows,
                    *, use_pallas: bool, several: bool):
    """The Gated DeltaNet mixer of every family that has one (this one and
    ``models/olmo_hybrid.py``): ``x [N, H]``, the mixer's input on the
    step's flat axis of ``N`` slots, against the state pools' layer
    ``gidx`` -> ``(out [N, H], cache)``. What the residual stream is
    normed with, before or behind the mixer, is the caller's. ``lp`` holds
    ``w_qkvz`` (columns ``q | k | v | z``), ``w_ba`` (``b | a``),
    ``conv_w``, ``A_log``, ``dt_bias``, ``o_norm`` and ``w_out``; ``beta =
    sigmoid(b)``, doubled where ``cfg.linear_allow_neg_eigval``;
    ``several`` (static) is off where every row carries one slot."""
    N = x.shape[0]
    Hk, Dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    f32 = jnp.float32
    with stage("layer.gdn_in"):
        qkvz = x @ lp["w_qkvz"]
        ba = jnp.dot(x, lp["w_ba"], preferred_element_type=f32)
        n_conv = cfg.linear_conv_dim
        mixed, conv = gdn.causal_conv(qkvz[:, :n_conv], lp["conv_w"],
                                      cache["conv"], gidx, rows)
        mixed = jax.nn.silu(mixed)
        z = qkvz[:, n_conv:].reshape(N, Hv, Dv)
        q = _l2norm(mixed[:, :Hk * Dk].reshape(-1, Hk, Dk)) * Dk ** -0.5
        k = _l2norm(mixed[:, Hk * Dk:2 * Hk * Dk].reshape(-1, Hk, Dk))
        v = mixed[:, 2 * Hk * Dk:].reshape(-1, Hv, Dv)
        beta = jax.nn.sigmoid(ba[:, :Hv])
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
            ba[:, Hv:] + lp["dt_bias"].astype(f32))
    with stage("layer.gdn"):
        dt = x.dtype
        o, state = gdn.gated_delta_rule(
            q.astype(dt), k.astype(dt), v.astype(dt), g, beta,
            cache["state"], gidx, rows, use_pallas=use_pallas,
            several=several)
    with stage("layer.gdn_out"):
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        y = (o * jax.lax.rsqrt(var + cfg.rms_norm_eps)
             * lp["o_norm"].astype(f32) * jax.nn.silu(z.astype(f32)))
        out = y.astype(dt).reshape(N, Hv * Dv) @ lp["w_out"]
    return out, {**cache, "state": state, "conv": conv}


def gdn_mixer(cfg: ModelConfig, lp, h, cache, gidx, rows: gdn.Rows, *,
              use_pallas: bool):
    """``h + GatedDeltaNet(norm(h))`` against the state pools' layer
    ``gidx``. ``h [B, S, H]``; the rule and the convolution see the step's
    flat axis of ``B * S`` slots (``S == 1``: a decode step, a slot a
    row). Returns ``(h, cache)``."""
    B, S, H = h.shape
    with stage("layer.gdn_in"):
        x = zc_norm(h, lp["attn_norm"], cfg.rms_norm_eps).reshape(B * S, H)
    out, cache = gated_delta_net(cfg, lp, x, cache, gidx, rows,
                                 use_pallas=use_pallas, several=S > 1)
    with stage("layer.gdn_out"):
        h = h + out.reshape(B, S, H)
    return h, cache


def full_mixer(cfg: ModelConfig, lp, h, positions, total_lens, new_lens,
               page_table, cache, lidx, *, attn_impl, starts):
    """``h + GatedAttention(norm(h))`` against the paged pool's layer
    ``lidx``. Returns ``(h, cache)``."""
    B, S, _ = h.shape
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps, rd = cfg.rms_norm_eps, cfg.rotary_dim
    with stage("layer.attn_in"):
        x = zc_norm(h, lp["attn_norm"], eps)
        qg = (x @ lp["wq"]).reshape(B, S, Hq, 2 * Dh)
        q, gate = qg[..., :Dh], qg[..., Dh:]
        k = (x @ lp["wk"]).reshape(B, S, Hkv, Dh)
        v = (x @ lp["wv"]).reshape(B, S, Hkv, Dh)
        q = zc_norm(q, lp["q_norm"], eps)
        k = zc_norm(k, lp["k_norm"], eps)
        if rd < Dh:
            q = jnp.concatenate([apply_rope(q[..., :rd], positions,
                                            cfg.rope_theta), q[..., rd:]], -1)
            k = jnp.concatenate([apply_rope(k[..., :rd], positions,
                                            cfg.rope_theta), k[..., rd:]], -1)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    with stage("layer.kv_write"):
        kv = write_rows(cache["kv"], lidx, k, v, page_table, positions,
                        total_lens, new_lens, starts)
    with stage("layer.attn"):
        attn = attend_rows(attn_impl, q, kv, lidx, page_table, positions,
                           total_lens, new_lens, Dh ** -0.5, starts)
    with stage("layer.attn_out"):
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            attn.dtype)
        h = h + attn.reshape(B, S, Hq * Dh) @ lp["wo"]
    return h, {**cache, "kv": kv}


# ----------------------------------------------------------------- forward

def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: Dict[str, jnp.ndarray],
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, packed: bool = False
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], dict]:
    """Scan-over-periods forward (``llama.forward`` contract, the
    token-packed form included, plus the ``aux`` third return of the MoE
    families). ``pages`` is ``make_pages``'s dict; ``page_table [B, P +
    1]`` carries each row's state slot in its last column. A passed
    ``attn_impl`` with the ``pallas_paged_kernel`` marker also opts the
    family into ``gdn_chunk`` / ``gdn_step`` and ``moe_grouped``. No
    ``logits_window``: a verify window or a scoring pass over a recurrent
    state would have to roll it back, so the engine offers neither."""
    from dynamo_tpu.models.moe import (flat_layers, grouped_on_chip,
                                       layer_at, split_experts, sum_aux,
                                       token_slots)

    if cfg.moe_backend != "grouped":
        raise NotImplementedError(
            f"moe_backend {cfg.moe_backend!r}: this family's sparse block "
            "(held range, shared expert) runs the grouped layer only")
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
    with stage("step.inputs"):
        moe_kw = dict(valid=token_slots(tokens, new_lens, packed),
                      use_pallas=on_chip)
    lg, lf = params["layers"]["gdn"], params["layers"]["full"]

    # the linear layers as ONE stack over periods and places: the loops
    # below carry indices alone, each layer's leaves are read where they
    # lie (``moe.flat_layers``) and the grouped layer indexes the experts
    with stage("layer.weights"):
        gdn_scanned, gdn_experts = split_experts(cfg, flat_layers(lg))
    full_scanned, full_experts = split_experts(cfg, lf)

    def period(carry, p):
        def linear(carry, j):
            h, cache = carry
            with stage("layer.weights"):
                gidx = p * G + j
                lp = layer_at(gdn_scanned, gidx)
            h, cache = gdn_mixer(cfg, lp, h, cache, gidx, rows,
                                 use_pallas=on_chip)
            h, aux = _ffn(cfg, {**lp, **gdn_experts}, h,
                          dict(moe_kw, layer=gidx))
            return (h, cache), aux

        with stage("step.inputs"):
            places = jnp.arange(G)
        (h, cache), aux_g = jax.lax.scan(linear, carry, places)
        with stage("layer.weights"):
            fp = layer_at(full_scanned, p)
        h, cache = full_mixer(cfg, fp, h, positions, total_lens, new_lens,
                              page_table, cache, p, attn_impl=attn_impl,
                              starts=starts)
        h, aux_f = _ffn(cfg, {**fp, **full_experts}, h,
                        dict(moe_kw, layer=p))
        with stage("step.counts"):
            aux = {k: aux_f[k] + jnp.sum(aux_g[k]) for k in aux_f}
        return (h, cache), aux

    with stage("step.inputs"):
        periods = jnp.arange(cfg.num_periods)
    (h, pages), aux = jax.lax.scan(period, (h, pages), periods)
    with stage("logits"):
        # the final norm's weight is zero-centred like the stream's;
        # ``_logits`` multiplies by the weight it is handed
        w = params["final_norm"]
        logits = _logits(
            cfg, {**params, "final_norm": (
                1.0 + w.astype(jnp.float32)).astype(w.dtype)},
            h, new_lens, starts=starts)
    with stage("step.counts"):
        aux = sum_aux(aux)
    return logits, pages, aux


forward.supports_packed = True


__all__ = ["init_params", "forward", "make_pages", "sparse_block",
           "decay_init", "zc_norm", "gated_delta_net", "conv_init_std"]
