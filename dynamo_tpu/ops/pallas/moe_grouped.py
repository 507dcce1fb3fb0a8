"""Grouped expert SwiGLU on TPU — Pallas kernel ``moe_grouped``.

The exact expert layer (``models/moe.grouped_experts``) sorts the ``T * k``
token-to-expert assignments by expert and lays them out in row tiles of
``tm`` rows, each group starting on a tile boundary, so every tile belongs
to ONE expert. The layout is planned from the tile's side
(``models/moe._tile_plan``): what is constant over a tile - its expert,
how far its group's rows sit past the group's place in the sorted list,
the row the group ends at - is looked up once a TILE; a ROW only compares
(is it live) and adds (which sorted assignment it holds), and the tokens
arrive as one window of ``tm`` consecutive sorted assignments a tile. This
kernel walks the tiles and computes, per tile,

    out = (silu(x @ W_gate[e]) * (x @ W_up[e])) @ W_down[e]        (f32)

with the tile -> expert map as a scalar-prefetch operand: the weight
``BlockSpec``s index the expert axis through it, so only experts that own a
tile are ever read from HBM — at decode (16 rows, top-8 of 256: ~100
experts with a row or two each) that is the whole cost, and an expert whose
rows fill several tiles is read once (consecutive tiles with the same block
index are not fetched again). The number of tiles is dynamic under a static
grid: tiles past ``num_tiles`` repeat the last live tile's block indices
(no DMA) and skip the compute.

The weights enter STACKED over layers, ``[L, E, H, I]`` / ``[L, E, I, H]``,
with the layer as a third prefetched scalar: under the engine's
``lax.scan`` over layers a per-layer slice handed to a custom call would be
copied out of the stack first (2.4 GB a layer at 256 experts of 2048 x
768), which is the whole point of not reading every expert.

Wide experts are walked in chunks of ``tn`` columns of ``I`` (inner grid
axis, the down-projection accumulating into the resident f32 output
block), so the double-buffered weight blocks stay inside VMEM.

Alignment: ``H % 128 == 0`` and ``I % 128 == 0`` (``supports``); tests run
interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import _resolve_interpret

# double-buffered weight blocks (gate, up, down chunks) may take this much
# VMEM; the rest of the limit below is the row tiles and temporaries
_WEIGHT_VMEM = 40 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024


def supports(hidden: int, inter: int) -> bool:
    """Geometries this kernel can lower for (else ``lax.ragged_dot``)."""
    return hidden % 128 == 0 and inter % 128 == 0 and _pick_tn(
        hidden, inter, 2) is not None


def _pick_tn(hidden: int, inter: int, itemsize: int):
    """Largest multiple-of-128 divisor of ``inter`` whose three
    double-buffered weight chunks fit ``_WEIGHT_VMEM``."""
    best = None
    for tn in range(128, inter + 1, 128):
        if inter % tn == 0 and 6 * hidden * tn * itemsize <= _WEIGHT_VMEM:
            best = tn
    return best


def _kernel(tile_expert_ref, num_tiles_ref, layer_ref, x_ref, wg_ref,
            wu_ref, wd_ref, o_ref):
    del tile_expert_ref, layer_ref          # consumed by the index maps
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < num_tiles_ref[0])
    def _():
        x = x_ref[...]                                     # [tm, H]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * u).astype(x.dtype)           # [tm, tn]
        part = jnp.dot(a, wd_ref[0, 0], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            o_ref[...] = part

        @pl.when(j > 0)
        def _():
            o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_grouped(xs: jnp.ndarray, tile_expert: jnp.ndarray,
                num_tiles: jnp.ndarray, layer: jnp.ndarray,
                w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
                *, tm: int, interpret=None) -> jnp.ndarray:
    """xs ``[n_tiles * tm, H]`` rows grouped by expert on tile boundaries;
    ``tile_expert [n_tiles]`` int32 (tiles past ``num_tiles [1]`` must
    repeat the last live tile's expert); ``layer [1]`` int32 into the
    stacked weights ``[L, E, H, I]`` / ``[L, E, I, H]``. Returns ``[n_tiles
    * tm, H]`` float32; rows of tiles past ``num_tiles`` are never
    written."""
    M, H = xs.shape
    n_tiles = M // tm
    inter = w_gate.shape[-1]
    tn = _pick_tn(H, inter, w_gate.dtype.itemsize)

    def row_map(i, j, te, nt, ly):
        return (jnp.maximum(jnp.minimum(i, nt[0] - 1), 0), 0)

    def up_map(i, j, te, nt, ly):
        # a dead tile keeps the last live chunk too: nothing is fetched
        return (ly[0], te[i], 0, jnp.where(i < nt[0], j, inter // tn - 1))

    def down_map(i, j, te, nt, ly):
        return (ly[0], te[i], jnp.where(i < nt[0], j, inter // tn - 1), 0)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, inter // tn),
            in_specs=[
                pl.BlockSpec((tm, H), row_map),
                pl.BlockSpec((1, 1, H, tn), up_map),
                pl.BlockSpec((1, 1, H, tn), up_map),
                pl.BlockSpec((1, 1, tn, H), down_map),
            ],
            out_specs=pl.BlockSpec((tm, H), row_map)),
        out_shape=jax.ShapeDtypeStruct((M, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_resolve_interpret(interpret),
        name="moe_grouped",
    )(tile_expert, num_tiles, layer, xs, w_gate, w_up, w_down)


__all__ = ["moe_grouped", "supports"]
