"""Mixture-of-Experts decoder (mixtral, qwen3-moe, deepseek-style top-k).

The reference serves MoE models only through external engines (wide-EP
DeepSeek-R1 via SGLang DeepEP, SURVEY §2.7); here the MoE layer is native
jax, sharing the Llama attention path (``models/llama.py`` helpers) and
swapping the dense MLP for routed experts:

- router: softmax over expert logits, top-k selection, optional
  renormalization (``norm_topk_prob``).
- one exact expert layer, ``grouped_experts``: assignments sorted by
  expert, one grouped matmul over the groups that exist (the
  ``moe_grouped`` Mosaic kernel on the chip, ``lax.ragged_dot`` elsewhere),
  no capacity, no drop — what runs unless ``cfg.moe_backend`` says
  "dispatch" (``moe_mlp_dispatch``), the capacity-factor wide-EP path that
  pins fixed ``[E, C, H]`` buffers to the ``ep`` axis and drops past
  capacity.

- **a learned selection in front of the attention** where the config has
  one (``cfg.index_topk``: ``sa_config`` of a file of the llama tree,
  Keye-VL-2.0's language model): in EVERY layer an indexer - ``q_I = x
  W_qI`` (``index_n_heads`` heads of ``index_head_dim``), one cached key a
  token ``k_I = LayerNorm(x W_kI)``, both rotated over their whole width,
  head weights ``w = x W_w``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
  k_I[s])`` - keeps the ``min(index_topk, t + 1)`` best-scored tokens a
  query can see, EXACTLY, and the grouped-query softmax runs over those
  alone, one selection for every head (``llama.index_inputs``,
  ``attend_selected``; the indexer's arithmetic is ``ops/indexer.py``'s,
  shared with ``models/dots3.py``). The cache is then a tree
  (``llama.make_pages``): the key/value pages and index pages under the
  SAME page id, written in one stage (``llama.write_rows``) - one block
  chain, no slot, so the prefix cache stays on and a hit, an eviction and
  a preemption move both pools. The same ``forward``: every branch is
  static on the config, and a model without ``index_topk`` traces the
  program it always did.

Weight layout (stacked for scan): ``w_router [L, H, E]``,
``w_gate/w_up [L, E, H, I]``, ``w_down [L, E, I, H]``; with a selection
``wi_q [L, H, J * D]``, ``wi_k [L, H, D]``, ``wi_w [L, H, J]`` and the
index key's LayerNorm ``i_norm_w`` / ``i_norm_b [L, D]``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    Params,
    _finish_attn,
    _logits,
    _project_qkv,
    _rms_norm,
    attend_rows,
    attend_selected,
    index_inputs,
    make_pages,
    packed_rows,
    randn_stack,
    visibility,
    write_rows,
)
from dynamo_tpu.models import llama


def _unsort(order: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """``values`` (in sorted order) back in the order ``order`` sorted:
    ``out[order[i]] = values[i]``, as one more sort - XLA's scatter costs
    the TPU 70-80 ns an index (PERF.md section 6, PR 25), a sort of 65,536
    keys far less."""
    return jax.lax.sort((order, values), num_keys=1)[1]


def _windows(values: jnp.ndarray, start: jnp.ndarray,
             width: int) -> jnp.ndarray:
    """``out[i] = values[start[i] : start[i] + width]`` (zeros past the
    end), ``width`` a power of two, ``0 <= start[i] <= len(values)``.

    Not as a gather of ``width``-long slices: XLA's TPU compiler expands
    that into a loop of one ``dynamic-slice`` an index, and what it does
    run as one operation, a gather of single entries, costs ~8 ns an entry
    whatever it fetches (PERF.md section 6, PR 44). It fetches whole rows
    of a 2-D table at the same price an index, so: the list as rows of
    ``width``, the two rows a window straddles (``2 * len(start)``
    indices), and the window's start brought to lane 0 by ``log2(width)``
    static rotations, each taken where ``start % width`` has that bit."""
    n = values.shape[0]
    rows = n // width + 2
    table = jnp.pad(values, (0, rows * width - n)).reshape(rows, width)
    first_row, lane = start // width, start % width
    pair = table[jnp.stack([first_row, first_row + 1], axis=1)]
    pair = pair.reshape(-1, 2 * width)
    step = 1
    while step < width:
        pair = jnp.where((lane & step != 0)[:, None],
                         jnp.roll(pair, -step, axis=1), pair)
        step *= 2
    return pair[:, :width]


def _sorted_picks(flat_e: jnp.ndarray, E: int, k: int):
    """The ``T * k`` assignments sorted by expert (stable; ``flat_e`` is
    ``E`` for a pick that is not computed here, which sorts behind every
    group): ``(sorted_t [A], order [A], first [E + 1])`` - the token of
    each sorted assignment, the permutation, and ``first[e]``, the
    assignments of experts below ``e`` (``first[E]``: all that are computed
    here). The experts in order are the sort's own first output: no
    ``flat_e[order]`` gather."""
    i32 = jnp.int32
    A = flat_e.shape[0]
    sorted_e, order = jax.lax.sort(
        (flat_e, jnp.arange(A, dtype=i32)), num_keys=1, is_stable=True)
    # compare_all: one fused comparison of every pair; the default binary
    # search is a loop of a dozen small device operations
    first = jnp.searchsorted(sorted_e, jnp.arange(E + 1, dtype=i32),
                             method="compare_all").astype(i32)
    return order // k, order, first


def _tile_plan(sorted_t: jnp.ndarray, order: jnp.ndarray,
               first: jnp.ndarray, *, tm: int, n_tiles: int):
    """Which token each row of the grouped call holds, built from the
    tile's side. The call has ``n_tiles * tm`` rows; a group starts on a
    tile boundary, so a tile has one expert, and a group's rows are
    consecutive entries of the sorted assignment list: row ``r`` of expert
    ``e`` holds entry ``r - shift[e]``, and entry ``a`` of expert ``e``
    sits in row ``a + shift[e]``, with ``shift[e] = row_first[e] -
    first[e]`` - one table of ``E`` offsets serves both directions.

    Looked up a TILE (``n_tiles`` indices each): its expert's ``shift`` and
    the row its group ends at. Computed a row, under a broadcast over
    ``[n_tiles, tm]``: whether it is live (a compare) and which entry it
    holds (an add) - and the entries themselves come as ``n_tiles`` whole
    windows of the sorted list (``_windows``), not as ``n_tiles * tm``
    single lookups. An assignment looks nothing up either: its expert's
    ``shift`` comes from comparing its place in the sorted list with the
    groups' starts.

    Returns ``(row_tok [M], row_live [M], pos [A], tile_expert [n_tiles],
    num_tiles)``: each row's token (0 where dead), whether a group owns
    the row, each assignment's row in token-major order (meaningless for a
    pick no group holds), each tile's expert (tiles past the last keep its
    expert: nothing is fetched for them) and the number of live tiles."""
    i32 = jnp.int32
    A, E = sorted_t.shape[0], first.shape[0] - 1
    counts = first[1:] - first[:-1]                         # [E]
    tiles = -(-counts // tm)
    tile_end = jnp.cumsum(tiles)
    num_tiles = tile_end[-1]
    row_first = (tile_end - tiles) * tm                     # [E]
    shift = row_first - first[:-1]
    tile = jnp.arange(n_tiles, dtype=i32)
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, tile, side="right", method="compare_all"),
        E - 1).astype(i32)
    alive, row0, row_end = tile < num_tiles, tile * tm, row_first + counts
    src0 = jnp.where(alive, row0 - shift[tile_expert], 0)
    left = jnp.where(alive, row_end[tile_expert] - row0, 0)
    row_live = jnp.arange(tm, dtype=i32)[None, :] < left[:, None]
    row_tok = jnp.where(row_live, _windows(sorted_t, src0, tm), 0)
    tile_expert = jnp.where(
        alive, tile_expert, tile_expert[jnp.maximum(num_tiles - 1, 0)])
    # an assignment's shift without a lookup: over the sorted list it steps
    # at each group's start (groups of no pick share a start, their steps
    # add up there) - every entry compared with every start, the fused
    # form ``first`` itself comes from; picks no group holds take the last
    entry = jnp.arange(A, dtype=i32)
    entry_shift = jnp.sum(jnp.where(
        entry[:, None] >= first[None, :-1],
        jnp.diff(shift, prepend=0)[None, :], 0), axis=1)
    pos = _unsort(order, entry + entry_shift)
    return (row_tok.reshape(n_tiles * tm), row_live.reshape(n_tiles * tm),
            pos, tile_expert, num_tiles)


def grouped_experts(xt: jnp.ndarray, top_w: jnp.ndarray,
                    top_i: jnp.ndarray, w_gate, w_up, w_down, *,
                    layer=None, valid=None, use_pallas: bool = False,
                    first_expert: int = 0, num_routed: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The exact expert layer every MoE family runs (routing-agnostic: the
    caller brings its gate's ``top_w``/``top_i``). The ``T * k``
    assignments are sorted by expert (stable), the tokens gathered into
    that order, ONE grouped matmul over the groups that exist computes
    gate/up and one the down projection, and each token sums its ``k``
    expert outputs times the routing weights in float32. No capacity and
    no drop: the result equals the every-expert-on-every-token mask form
    up to summation order, at ``k / E`` of its FLOPs and without its
    ``[T, E, I]`` temporaries. Sorts, searches and gathers only: no
    scatter. In front of the kernel the plan of which row holds which
    token is built a tile at a time (``_tile_plan``): two lookups a tile,
    a compare and an add a row, the sorted list fetched as whole windows -
    a row or an assignment looks nothing up by itself.

    ``xt [T, H]``; ``top_w``/``top_i [T, k]``; weights ``[E, H, I]`` /
    ``[E, I, H]``, or stacked ``[L, E, ...]`` with ``layer`` the (traced)
    index — the stacked form is what a scan over layers hands over, so no
    per-layer slice of the expert weights is ever materialised. ``valid
    [T]`` bool masks slots that hold no token (padding of a ``[B, S]``
    step, dead rows of a fused block): they route nowhere, cost nothing and
    come back zero. The stages are named for the device trace (``sort``,
    ``experts``, ``combine``, under the caller's ``layer.moe``).
    ``use_pallas`` runs the grouped matmuls in the
    ``moe_grouped`` Mosaic kernel (``ops/pallas/moe_grouped.py``), which
    reads only the experts that own a row; otherwise ``lax.ragged_dot``,
    the plain form (the CPU, meshes, widths the kernel cannot tile).

    A pick is one of three things. The weights hold the ``E`` experts
    ``first_expert .. first_expert + E`` of the router's ``num_routed``
    computing experts (default: all of them, from 0): a pick among those
    is computed here; a pick of another computing expert is held
    elsewhere and adds nothing here (one rank's share of an
    expert-parallel layer, without its exchange); a pick at or above
    ``num_routed`` is a zero-compute expert and adds ``w * x`` in the
    combine, in float32, with no row, no fetch and no FLOP. Both kinds
    that are not computed here sort behind every group, where an
    unrouted slot's already go. Of a token's ``k`` distinct picks at most
    ``min(k, E)`` are held, so the static row bound of the grouped call
    is ``T * min(k, E)``.

    Returns ``(out [T, H] float32, aux)``; ``aux`` holds the counts the
    step programs hand on: ``moe_experts_touched`` (held experts with at
    least one row), ``moe_assignments`` (every pick of a valid token),
    ``moe_held_assignments`` (picks computed here) and
    ``moe_zero_assignments`` (picks of zero-compute experts)."""
    T, H = xt.shape
    k = top_i.shape[1]
    E = w_gate.shape[-3]
    A = T * k
    i32 = jnp.int32
    picked = top_i.reshape(A).astype(i32)
    live = None if valid is None else jnp.repeat(valid, k)
    whole = first_expert == 0 and num_routed is None
    is_zero = None
    if whole:
        flat_e = picked
    else:
        local = picked - first_expert
        flat_e = jnp.where((local >= 0) & (local < E), local, E)
        if num_routed is not None:
            is_zero = picked >= num_routed
            if live is not None:
                is_zero &= live
    if live is not None:
        # an unrouted assignment sorts behind every expert's
        flat_e = jnp.where(live, flat_e, E)
    with stage("sort"):
        sorted_t, order, first = _sorted_picks(flat_e, E, k)
        counts = first[1:] - first[:-1]                     # [E]
    if whole:               # every pick of a valid token is computed here
        picks = first[E]
    else:
        picks = (jnp.asarray(A, i32) if live is None
                 else jnp.sum(live).astype(i32))
    aux = {"moe_experts_touched": jnp.sum(counts > 0).astype(i32),
           "moe_assignments": picks,
           "moe_held_assignments": first[E],
           "moe_zero_assignments": (jnp.sum(is_zero).astype(i32)
                                    if is_zero is not None
                                    else jnp.zeros((), i32))}
    if w_gate.ndim == 3:        # one layer's experts: a stack of one
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    if use_pallas:
        from dynamo_tpu.ops.pallas.moe_grouped import moe_grouped, supports
        use_pallas = supports(H, w_gate.shape[-1])
    if use_pallas:
        # row tiles: a group starts on a tile boundary, so a tile has one
        # expert. 16 rows (one bf16 sublane tile) while the assignments
        # are few and most groups hold a row or two; 128 (the MXU's edge)
        # once groups are long enough to fill them
        tm = 16 if A <= 2048 else 128
        held = T * min(k, E)                    # bound on sum c
        n_tiles = -(-held // tm) + min(E, held)  # ... on sum ceil(c / tm)
        with stage("sort"):
            row_tok, _, pos, tile_expert, num_tiles = _tile_plan(
                sorted_t, order, first, tm=tm, n_tiles=n_tiles)
        with stage("experts"):
            ys = moe_grouped(
                xt[row_tok], tile_expert, num_tiles.reshape(1),
                jnp.asarray(layer, i32).reshape(1), w_gate, w_up, w_down,
                tm=tm)
    else:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
        with stage("experts"):
            xs = xt[sorted_t]                              # [A, H]
            act = (jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, counts))
                   * jax.lax.ragged_dot(xs, w_up, counts))
            ys = jax.lax.ragged_dot(
                act.astype(xt.dtype), w_down, counts,
                preferred_element_type=jnp.float32)        # [A, H]
        with stage("sort"):
            pos = _unsort(order, jnp.arange(A, dtype=i32))
    with stage("combine"):
        routed = (flat_e < E).reshape(T, k, 1)
        # rows no group owns are never written: select, do not multiply
        y = jnp.where(routed, ys[jnp.minimum(pos, ys.shape[0] - 1)]
                      .reshape(T, k, H), 0.0)
        out = jnp.sum(y * top_w.astype(jnp.float32)[..., None], axis=1)
        if is_zero is not None:
            # the identity experts: the token itself times their weights
            zero_w = jnp.where(is_zero.reshape(T, k),
                               top_w.astype(jnp.float32), 0.0)
            out = out + (jnp.sum(zero_w, axis=1, keepdims=True)
                         * xt.astype(jnp.float32))
    return out, aux


def moe_mlp(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
            x: jnp.ndarray) -> jnp.ndarray:
    """Routed expert MLP. x: [B, S, H] (already normed) -> [B, S, H]."""
    return moe_mlp_counted(cfg, lp, x)[0]


def moe_mlp_counted(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                    x: jnp.ndarray, **kw
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``moe_mlp`` with the grouped layer's counts: ``([B, S, H], aux)``;
    ``kw`` goes to ``grouped_experts``."""
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    with stage("route"):
        top_w, top_i = _router_topk(cfg, lp, xt)           # [T, k]
    out, aux = grouped_experts(
        xt, top_w, top_i, lp["w_gate"], lp["w_up"], lp["w_down"], **kw)
    return out.reshape(B, S, H).astype(x.dtype), aux


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
                    h: jnp.ndarray, attn: jnp.ndarray, ep_mesh=None, **kw
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns ``(h, aux)``: the grouped layer's counts (experts touched,
    assignments), or the dispatch backend's dropped assignments. ``kw``
    goes to ``grouped_experts``."""
    with stage("layer.attn_out"):
        h = _finish_attn(cfg, lp, h, attn)
    with stage("layer.moe"):
        x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        if cfg.moe_backend == "dispatch":
            mlp, dropped = moe_mlp_dispatch(cfg, lp, x, ep_mesh=ep_mesh)
            aux = {"moe_dropped_assignments": dropped}
        else:
            mlp, aux = moe_mlp_counted(cfg, lp, x, **kw)
        h = h + mlp
    return h, aux


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def split_experts(cfg: ModelConfig, layers: Dict[str, jnp.ndarray]):
    """``(scanned leaves, stacked expert leaves)`` of a layer stack: the
    grouped layer takes the experts whole, indexed by the layer (see
    ``grouped_experts``); the dispatch backend scans them like the
    rest."""
    if cfg.moe_backend == "dispatch":
        return layers, {}
    return ({k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
            {k: layers[k] for k in EXPERT_LEAVES})


def flat_layers(stack):
    """A tree of layers stacked over periods and places ``[P, G, ...]`` as
    ONE stack ``[P * G, ...]`` (a bitcast, made once outside every loop).
    A forward that scans over periods takes a layer's leaves from it with
    ``layer_at`` inside the inner body and never hands a loop a slice of
    a stack as an operand or as ``xs``: a loop's operand has to be a
    buffer, so XLA materialised each period's ``[G, ...]`` slice - every
    non-expert matrix read and written once a period a step before any
    matmul read it (1.00 s of an 8 s slice of ``dots3-note-prev.longctx``,
    PERF.md section 6, PR 49 and PR 52; 1.3 GB of temporaries at
    Olmo-Hybrid's widths, PR 51)."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), stack)


def layer_at(stack, i):
    """Layer ``i`` (traced or static) of a tree of stacked layers: each
    leaf read where it lies, by the matmul that consumes it."""
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def token_slots(tokens: jnp.ndarray, new_lens: jnp.ndarray,
                packed: bool) -> jnp.ndarray:
    """``[B * S]`` bool: the slots of a step that hold a token. The others
    (padding of either step form, dead rows of a fused block) route to no
    expert: ``grouped_experts``'s ``valid``."""
    B, S = tokens.shape
    if packed:
        return jnp.arange(S) < jnp.sum(new_lens)
    return (jnp.arange(S)[None, :] < new_lens[:, None]).reshape(B * S)


def sum_aux(aux: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Per-layer (or per-step) counts stacked by a scan -> their sums."""
    return {k: jnp.sum(v.astype(jnp.int32)) for k, v in aux.items()}


def grouped_on_chip(attn_impl) -> bool:
    """Whether the expert layer may run its Mosaic kernel: where the
    engine handed the family its own Pallas attention kernels, unwrapped
    (on a mesh they come wrapped per shard, and GSPMD cannot partition a
    Mosaic call over sharded experts)."""
    return (getattr(attn_impl, "pallas_paged_kernel", False)
            and not getattr(attn_impl, "per_shard", False))


def init_params(cfg: ModelConfig, rng: jax.Array,
                scale: Optional[float] = None) -> Params:
    """Random init (tests/benchmarks; the benchmark's worker and its
    reference child both call this, so both hold the same weights).
    Attention and embedding weights come from ``llama.init_params`` (with
    a dense FFN one column wide in place of the one it would draw and
    drop); the expert stacks are drawn a layer at a time inside one
    program (``llama.randn_stack``), so no float32 copy of a whole stack
    ever exists: ``w_gate`` of 7 layers x 128 experts x 2048 x 768 is 5.6
    GB in float32 beside the 10 GB the finished weights take.

    ``scale`` defaults to ``MOE_INIT_GAIN / sqrt(hidden)`` (0.012 at a
    hidden size of 2,048), the sparse families' measured scale
    (``deepseek.init_params``): bfloat16 against float32 swaps one of the
    chosen experts at the top-k boundary in some tokens, and the scale
    sets what a swap costs against what a real fault costs."""
    if scale is None:
        scale = llama.MOE_INIT_GAIN / cfg.hidden_size ** 0.5
    import dataclasses
    params = llama.init_params(
        dataclasses.replace(cfg, intermediate_size=1), rng, scale)
    layers = params["layers"]
    for k in EXPERT_LEAVES:
        del layers[k]
    dtype = jnp.dtype(cfg.dtype)
    L, H, E = cfg.num_layers, cfg.hidden_size, cfg.num_experts
    I = cfg.moe_intermediate_size or cfg.intermediate_size
    keys = iter(jax.random.split(jax.random.fold_in(rng, 7), 4))
    layers["w_router"] = randn_stack(next(keys), L, (H, E), scale, dtype)
    layers["w_gate"] = randn_stack(next(keys), L, (E, H, I), scale, dtype)
    layers["w_up"] = randn_stack(next(keys), L, (E, H, I), scale, dtype)
    layers["w_down"] = randn_stack(next(keys), L, (E, I, H), scale, dtype)
    if cfg.index_topk:
        # the indexer (``llama.index_inputs``), at the common scale: the
        # key is LayerNormed and the attention's q and k are normed a
        # head, so relu's argument has a standard deviation of 4 and a
        # head's attention scores one of 1 - a selection is decided by a
        # score's leading digits and leaving it out moves the output
        # (benchmarks/configs/keye-vl-2.0-30b-a3b.json ``assumed``)
        J, D = cfg.index_n_heads, cfg.index_head_dim
        ki = iter(jax.random.split(jax.random.fold_in(rng, 11), 3))
        layers["wi_q"] = randn_stack(next(ki), L, (H, J * D), scale, dtype)
        layers["wi_k"] = randn_stack(next(ki), L, (H, D), scale, dtype)
        layers["wi_w"] = randn_stack(next(ki), L, (H, J), scale, dtype)
        layers["i_norm_w"] = jnp.ones((L, D), dtype)
        layers["i_norm_b"] = jnp.zeros((L, D), dtype)
    return params


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, pages: jnp.ndarray,
            page_table: jnp.ndarray, total_lens: jnp.ndarray,
            new_lens: jnp.ndarray,
            attn_impl: Optional[Callable] = None, ep_mesh=None,
            logits_window: int = 1, packed: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Scan-over-layers MoE forward (llama.forward contract, the
    token-packed form included, plus a third ``aux`` return: the expert
    layer's counts summed over layers — ``moe_experts_touched`` and
    ``moe_assignments``, or the dispatch backend's
    ``moe_dropped_assignments`` — which the engine forwards to worker
    stats). ``pages`` is ``llama.make_pages``'s: the pool, or the tree of
    key/value and index pages of a model that selects (module
    docstring)."""
    sm_scale = cfg.head_dim ** -0.5
    # every layer attends a learned selection (``cfg.index_topk``,
    # ``sa_config``): ``pages`` is then the tree of ``make_pages``
    selects = bool(cfg.index_topk)
    with stage("step.inputs"):
        starts = packed_rows(packed, new_lens)
    with stage("embed"):
        h = params["embed"][tokens]
    with stage("step.inputs"):
        valid = token_slots(tokens, new_lens, packed)
    scanned, experts = split_experts(cfg, params["layers"])
    kw = (dict(valid=valid, use_pallas=grouped_on_chip(attn_impl))
          if experts else {})

    def body(carry, xs):
        h, pages = carry
        lp, lidx = xs
        with stage("layer.attn_in"):
            q, k, v = _project_qkv(cfg, lp, h, positions)
            if selects:
                q_i, k_i, w_i = index_inputs(cfg, lp, h, positions)
        with stage("layer.kv_write"):
            pages = write_rows(pages, lidx, k, v, page_table, positions,
                               total_lens, new_lens, starts,
                               **({"k_i": k_i} if selects else {}))
        with stage("layer.attn"):
            if selects:
                attn = attend_selected(cfg, attn_impl, q, q_i, w_i, pages,
                                       lidx, page_table, total_lens,
                                       new_lens, sm_scale, starts)
            else:
                attn = attend_rows(attn_impl, q, pages, lidx, page_table,
                                   positions, total_lens, new_lens,
                                   sm_scale, starts, **visibility(cfg))
        grouped = dict(kw, layer=lidx) if experts else {}
        h, aux = _moe_layer_tail(cfg, {**lp, **experts}, h, attn,
                                 ep_mesh=ep_mesh, **grouped)
        return (h, pages), aux

    with stage("step.inputs"):
        layer_ids = jnp.arange(cfg.num_layers)
    (h, pages), aux = jax.lax.scan(body, (h, pages), (scanned, layer_ids))
    with stage("logits"):
        logits = _logits(cfg, params, h, new_lens, window=logits_window,
                         starts=starts)
    with stage("step.counts"):
        aux = sum_aux(aux)
    return logits, pages, aux


forward.supports_packed = True
forward.reads_wqkv = True


__all__ = ["forward", "init_params", "make_pages", "moe_mlp",
           "moe_mlp_dispatch", "expert_dispatch", "grouped_experts"]
