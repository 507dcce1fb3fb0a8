"""LongCat-Flash family: double layers with a shortcut-connected expert
branch, zero-compute experts, scaled latent attention. Pure jax.

One layer holds TWO latent-attention blocks and TWO dense SwiGLU FFNs
around ONE expert branch that leaves after the first attention block and
rejoins at the layer's end (``h`` the residual stream)::

    a0 = h  + MLA0(norm(h;  g_in0))
    x0 = norm(a0; g_post0)
    s  = MoE(x0)                    # the shortcut: used only at the end
    b0 = a0 + FFN0(x0)
    a1 = b0 + MLA1(norm(b0; g_in1))
    x1 = norm(a1; g_post1)
    h' = a1 + FFN1(x1) + s

so inside a layer the expert read (bound by memory), the dense FFNs (bound
by compute at a wide batch) and the second attention have no data
dependence on each other. Nothing here orders them: no barrier, XLA is free
to overlap the branch with the dense path.

What is shared and what is this family's own:

- Latent attention is ``models/deepseek.py``'s, in the absorbed form over
  the latent page layout (``_mla_qkv``, ``_cache_rows``, ``_attend``), with
  the two scales of ``mla_scale_q_lora`` / ``mla_scale_kv_lora``
  (``cfg.mla_q_scale`` on the query, ``cfg.mla_kv_scale`` on the normed
  latent: the cache holds the SCALED latent). Two blocks a layer means two
  cache layers a layer: block ``j`` of layer ``l`` owns cache layer
  ``2 l + j`` (``cfg.num_cache_layers``).
- The router is one softmax in float32 over ``num_experts +
  zero_expert_num`` outputs; the ``moe_topk`` largest of ``p + bias`` are
  picked, weighted ``routed_scaling_factor * p`` and NOT renormalised. A
  pick below ``num_experts`` is a SwiGLU expert; a pick at or above it is a
  zero-compute expert (``identity``): the token itself times the weight.
- The expert branch is ``models/moe.grouped_experts`` told which experts
  it holds (``cfg.expert_offset``, ``cfg.experts_held``: rank
  ``cfg.ep_rank`` of ``cfg.ep_size``): picks of experts held elsewhere add
  nothing here — one rank's share of the layer, without the exchange — and
  the identity picks are computed where the token lives, which is here.

Weight layout: ``params["layers"]`` holds ``attn0`` / ``attn1`` (the MLA
leaves of ``deepseek``, ``mlp_norm`` being the block's post-attention
norm), ``ffn0`` / ``ffn1`` (``w_gate``/``w_up``/``w_down``), ``w_router``
``[L, H, E + Z]``, ``router_bias`` ``[L, E + Z]`` float32 and the HELD
experts ``w_gate``/``w_up`` ``[L, E_held, H, I]``, ``w_down`` ``[L, E_held,
I, H]``, every leaf stacked over the layers for the scan. No checkpoint
loader: the family serves seeded random weights (the benchmark's) until
its published tensor names are in the repository.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.deepseek import (
    _attend,
    _attn_leaves,
    _cache_rows,
    _dense_mlp,
    _expand_and_project,
    _mla_qkv,
)
from dynamo_tpu.models.llama import (
    MOE_INIT_GAIN,
    _logits,
    _rms_norm,
    make_pages,
    packed_rows,
    randn_stack,
    write_rows,
)

Params = Dict[str, Any]

# Standard deviation of the router's logits under seeded weights (its
# matrix is drawn at this over sqrt(hidden); a normed token has unit RMS).
# A softmax over 768 near-uniform logits would weigh a pick 6/768 and the
# whole expert branch a hundredth of the stream; at 2.0 the twelve picked
# scores of a token sum to 0.40 at the median (0.31-0.54 from the tenth to
# the ninetieth percentile, the largest single score 0.09 at the median;
# measured at the published width under PRNGKey(0):
# benchmarks/configs/longcat-flash-omni.json, ``assumed``).
ROUTER_LOGIT_STD = 2.0
# The experts' matrices are drawn at this times the other matrices' scale:
# a SwiGLU's output goes as the cube of its weights' scale, and at the
# common scale a 2,048-wide expert's output times its weight of ~0.2 is a
# hundredth of the stream, where a dense FFN's is a tenth; at 2.2 a held
# pick adds about what a dense FFN adds, so a fault in the held experts
# moves the logits as one in the dense path does.
EXPERT_GAIN = 2.2


def router_stack(cfg: ModelConfig, key, n: int) -> jnp.ndarray:
    """``[n, H, E + Z]``: the routers of ``n`` layers, drawn as
    ``init_params`` draws them (the measurement of ``ROUTER_LOGIT_STD``
    calls this with ``init_params``'s key, the seventh of the eight it
    splits its own into)."""
    H = cfg.hidden_size
    return randn_stack(key, n, (H, cfg.num_experts + cfg.zero_expert_num),
                       ROUTER_LOGIT_STD / H ** 0.5, jnp.dtype(cfg.dtype))


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init (tests/benchmarks; the benchmark's worker and its
    reference child both call this, so both hold the same weights). Every
    stack is drawn a layer at a time (``llama.randn_stack``). ``scale``
    (default ``MOE_INIT_GAIN / sqrt(hidden)``, the sparse families'
    measured scale) is every matrix's standard deviation but the router's
    (``ROUTER_LOGIT_STD``) and the experts' (``EXPERT_GAIN``). Only the
    experts this rank holds are drawn: expert ``e`` of the layer is row
    ``e - cfg.expert_offset``."""
    if scale is None:
        scale = MOE_INIT_GAIN / cfg.hidden_size ** 0.5
    dtype = jnp.dtype(cfg.dtype)
    L, H = cfg.num_layers, cfg.hidden_size
    F, Im, E = cfg.intermediate_size, cfg.moe_intermediate_size, \
        cfg.experts_held
    k_embed, k_head, k_a0, k_a1, k_f0, k_f1, k_router, k_exp = \
        jax.random.split(rng, 8)

    def ffn(key):
        ks = jax.random.split(key, 3)
        return {"w_gate": randn_stack(ks[0], L, (H, F), scale, dtype),
                "w_up": randn_stack(ks[1], L, (H, F), scale, dtype),
                "w_down": randn_stack(ks[2], L, (F, H), scale, dtype)}

    ks = jax.random.split(k_exp, 3)
    es = scale * EXPERT_GAIN
    layers = {
        "attn0": _attn_leaves(cfg, k_a0, scale, L),
        "attn1": _attn_leaves(cfg, k_a1, scale, L),
        "ffn0": ffn(k_f0),
        "ffn1": ffn(k_f1),
        "w_router": router_stack(cfg, k_router, L),
        "router_bias": jnp.zeros((L, cfg.num_experts + cfg.zero_expert_num),
                                 jnp.float32),
        "w_gate": randn_stack(ks[0], L, (E, H, Im), es, dtype),
        "w_up": randn_stack(ks[1], L, (E, H, Im), es, dtype),
        "w_down": randn_stack(ks[2], L, (E, Im, H), es, dtype),
    }
    params: Params = {
        "embed": randn_stack(k_embed, 1, (cfg.vocab_size, H), scale,
                             dtype)[0],
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = randn_stack(k_head, 1, (H, cfg.vocab_size),
                                        scale, dtype)[0]
    return params


# ------------------------------------------------------------ expert branch

def _gate(cfg: ModelConfig, lp: Dict[str, jnp.ndarray], x: jnp.ndarray):
    """``(top_w, top_i) [T, k]`` over the router's whole width: softmax in
    float32, the ``k`` largest of ``p + bias`` picked, weights the
    UNCORRECTED ``p`` times ``routed_scaling_factor``, not renormalised."""
    p = jax.nn.softmax(x.astype(jnp.float32)
                       @ lp["w_router"].astype(jnp.float32), axis=-1)
    _v, top_i = jax.lax.top_k(p + lp["router_bias"].astype(jnp.float32),
                              cfg.num_experts_per_tok)
    top_w = jnp.take_along_axis(p, top_i, axis=-1)
    return top_w * cfg.routed_scaling_factor, top_i


def expert_branch(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                  x: jnp.ndarray, **kw
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``s = MoE(x)`` as this rank computes it: its held experts' part
    plus the identity picks. ``x [B, S, H]`` -> ``([B, S, H], aux)``;
    ``kw`` (the layer index into stacked experts, the valid-slot mask, the
    kernel switch) goes to ``grouped_experts``."""
    from dynamo_tpu.models.moe import grouped_experts

    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with stage("route"):
        top_w, top_i = _gate(cfg, lp, xt)
    out, aux = grouped_experts(
        xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"],
        first_expert=cfg.expert_offset, num_routed=cfg.num_experts, **kw)
    return out.reshape(B, S, H).astype(x.dtype), aux


# ----------------------------------------------------------------- forward

def _attention_block(cfg: ModelConfig, lp, h, positions, total_lens,
                     new_lens, page_table, pages, lidx, which: int, *,
                     use_pallas: bool, starts):
    """``h + MLA(norm(h))`` of attention block ``which`` (0 or 1) of double
    layer ``lidx``, against cache layer ``2 lidx + which``, under the
    stages ``layer.attn<which>/{in,kv_write,attn,out}``: where every
    family cuts a token mixer (``engine/stages.py``). Returns
    ``(h, pages)``."""
    name = f"layer.attn{which}"
    with stage(f"{name}/in"):
        # (no ``+ 0`` traced for the first block: the programs are the
        # parent's, operation for operation)
        cache_layer = 2 * lidx + 1 if which else 2 * lidx
        q_lat, q_pe, c_kv, k_pe, w_uv = _mla_qkv(cfg, lp, h, positions)
        k_new, v_new = _cache_rows(cfg, c_kv, k_pe)
    with stage(f"{name}/kv_write"):
        pages = write_rows(pages, cache_layer, k_new, v_new, page_table,
                           positions, total_lens, new_lens, starts)
    with stage(f"{name}/attn"):
        lat = _attend(cfg, q_lat, q_pe, positions, total_lens, new_lens,
                      page_table, pages, cache_layer, use_pallas=use_pallas,
                      starts=starts)
    with stage(f"{name}/out"):
        h = _expand_and_project(cfg, lp, h, lat, w_uv)
    return h, pages


def _layer_step(cfg: ModelConfig, lp, h, positions, total_lens, new_lens,
                page_table, pages, lidx, *, use_pallas: bool = False,
                moe_kw=None, starts=None):
    """One double layer (module docstring) against the stacked latent
    cache, whose layers ``2 lidx`` and ``2 lidx + 1`` are this layer's.
    Returns ``(h, pages, aux)``, ``aux`` the expert branch's counts."""
    eps = cfg.rms_norm_eps
    rows = dict(use_pallas=use_pallas, starts=starts)
    a0, pages = _attention_block(
        cfg, lp["attn0"], h, positions, total_lens, new_lens,
        page_table, pages, lidx, 0, **rows)
    with stage("layer.moe"):
        # the one norm both branches of the first half read
        x0 = _rms_norm(a0, lp["attn0"]["mlp_norm"], eps)
        s, aux = expert_branch(cfg, lp, x0, **(moe_kw or {}))
    with stage("layer.ffn0"):
        b0 = a0 + _dense_mlp(lp["ffn0"], x0)
    a1, pages = _attention_block(
        cfg, lp["attn1"], b0, positions, total_lens, new_lens,
        page_table, pages, lidx, 1, **rows)
    with stage("layer.ffn1"):
        x1 = _rms_norm(a1, lp["attn1"]["mlp_norm"], eps)
        h = a1 + _dense_mlp(lp["ffn1"], x1) + s
    return h, pages, aux


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, ep_mesh=None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Scan forward (``llama.forward`` contract, the token-packed form
    included, plus the ``aux`` third return: the expert branch's counts
    summed over layers, ``models/moe.grouped_experts``). ``pages`` has
    ``cfg.num_cache_layers`` layers. As in ``deepseek.forward`` a passed
    ``attn_impl`` is never called: its ``pallas_paged_kernel`` marker opts
    the family into the latent kernels and ``moe_grouped``."""
    from dynamo_tpu.models.moe import (grouped_on_chip, split_experts,
                                       sum_aux, token_slots)
    from dynamo_tpu.ops.pallas.mla_decode import supports as mla_supports

    if cfg.moe_backend != "grouped":
        raise NotImplementedError(
            f"moe_backend {cfg.moe_backend!r}: this family's expert branch "
            "(held range, zero-compute experts) runs the grouped layer only")
    use_pallas = (getattr(attn_impl, "pallas_paged_kernel", False)
                  and mla_supports(cfg.kv_lora_rank, pages.shape[-2]))
    with stage("step.inputs"):
        starts = packed_rows(packed, new_lens)
    with stage("embed"):
        h = params["embed"][tokens]
    scanned, experts = split_experts(cfg, params["layers"])
    # slots that hold no token route to no expert, identity ones included
    with stage("step.inputs"):
        moe_kw = dict(valid=token_slots(tokens, new_lens, packed),
                      use_pallas=grouped_on_chip(attn_impl))

    def step(carry, xs):
        h, pages = carry
        lp, lidx = xs
        h, pages, aux = _layer_step(
            cfg, {**lp, **experts}, h, positions, total_lens, new_lens,
            page_table, pages, lidx, use_pallas=use_pallas,
            moe_kw=dict(moe_kw, layer=lidx), starts=starts)
        return (h, pages), aux

    with stage("step.inputs"):
        layer_ids = jnp.arange(cfg.num_layers)
    (h, pages), aux = jax.lax.scan(step, (h, pages), (scanned, layer_ids))
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, window=logits_window,
                         starts=starts)
    with stage("step.counts"):
        aux = sum_aux(aux)
    return logits, pages, aux


forward.supports_packed = True


__all__ = ["init_params", "forward", "make_pages", "expert_branch",
           "router_stack"]
