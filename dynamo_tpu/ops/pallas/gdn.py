"""The gated delta rule on TPU - Pallas kernels ``gdn_chunk`` and
``gdn_step`` (the equations and the plain forms: ``ops/gdn.py``).

Both take the state pool STACKED over the linear layers, ``[L, slots, Hv,
Dk, Dv]`` float32, with the layer and each row's slot as prefetched scalars
that the pool's ``BlockSpec`` indexes by, and alias it to their output: a
row's state is read from its slot and written back there, nothing else of
the pool moves, and under the forward's ``lax.scan`` no layer is sliced out.

``gdn_chunk`` walks a grid of (blocks of value heads, chunks). A row's
chunks follow one another, so the state of the heads in flight stays in a
VMEM scratch from one chunk to the next: it is loaded at a row's first
chunk (times 0 where the row starts at position 0) and the output block -
indexed by the row's slot, so written back when the slot changes - holds it
after every chunk. The number of live chunks is dynamic under a static
grid: a chunk past the last repeats the last one's block indices (nothing
is fetched), computes nothing, and copies slot 0 of the pool onto itself.
Inside a chunk everything is a matrix product: ``(I + A)^-1`` of the
64 x 64 strictly lower-triangular ``A`` comes from its 16 x 16 diagonal
blocks ``D`` (nilpotent: ``(I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I +
D^8)``) and the rest ``L`` (``(I + D + L)^-1 = (I - N)(I + N^2)(I + D)^-1``
with ``N = (I + D)^-1 L``, whose fourth power is zero), in float32 at the
highest precision: the alternating sums stay small because no block is
wider than 16. Keys, queries and values enter the products in the dtype
they come in (bfloat16 on the chip, float32 in the CPU tests), the state as
well, and every product accumulates in float32, which is what the state is
kept and updated in.

``gdn_step`` walks (rows, blocks of value heads): one token a row, the
five lines of the rule on the vector unit (the products are outer products
and matrix-vector products of one row: the kernel is bound by reading and
writing the state, 2 x ``Hv Dk Dv`` x 4 bytes a row).

Tests run both in interpret mode on the CPU against ``ops/gdn.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.gdn import CHUNK, Chunks
from dynamo_tpu.ops.pallas.decode import _resolve_interpret

_BLOCK = 16          # the diagonal blocks of the chunk's triangular solve
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _head_block(Hv: int, rep: int, cap: int) -> int:
    """Value heads a grid step computes: the most under ``cap`` that
    divide ``Hv`` and hold whole groups of ``rep`` (one key head's)."""
    best = rep
    for hb in range(rep, min(cap, Hv) + 1, rep):
        if Hv % hb == 0:
            best = hb
    return best


def _mm32(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32, precision=_HIGHEST)


def _unit_lower_inverse(A, t, s):
    """``(I + A)^-1`` of a strictly lower-triangular ``A [C, C]`` float32
    (``t``/``s``: its row and column numbers), module docstring."""
    eye = (t == s).astype(_F32)
    D = jnp.where(t // _BLOCK == s // _BLOCK, A, 0.0)
    L = A - D
    D2 = _mm32(D, D)
    D4 = _mm32(D2, D2)
    D8 = _mm32(D4, D4)
    Td = _mm32(_mm32(eye - D, eye + D2), _mm32(eye + D4, eye + D8))
    N = _mm32(Td, L)
    return _mm32(_mm32(eye - N, eye + _mm32(N, N)), Td)


def _chunk_kernel(slot_ref, flags_ref, live_ref, layer_ref, q_ref, k_ref,
                  v_ref, g_ref, b_ref, sin_ref, o_ref, sout_ref, s_scr, *,
                  kb: int, rep: int, C: int):
    del slot_ref, layer_ref                  # consumed by the index maps
    j = pl.program_id(1)
    dt = q_ref.dtype
    prec = _HIGHEST if dt == _F32 else None

    def mm(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a.astype(dt), b.astype(dt), dims,
                                   preferred_element_type=_F32,
                                   precision=prec)
    nt = (((1,), (1,)), ((), ()))            # a @ b.T
    tn = (((0,), (0,)), ((), ()))            # a.T @ b

    @pl.when(j >= live_ref[0])
    def _():
        sout_ref[...] = sin_ref[...]

    @pl.when(j < live_ref[0])
    def _():
        flags = flags_ref[j]

        @pl.when(flags % 2 == 1)             # the row's first chunk
        def _():
            keep = jnp.where(flags >= 2, 0.0, 1.0)   # ... from zeros
            s_scr[...] = sin_ref[0, 0] * keep

        t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

        def column(row):                     # [1, C] -> [C, 1]
            return jnp.sum(jnp.where(t == s, jnp.broadcast_to(row, (C, C)),
                                     0.0), axis=1, keepdims=True)

        for i in range(kb):
            q, k = q_ref[0, i], k_ref[0, i]                  # [C, Dk]
            kk, qk = mm(k, k, nt), mm(q, k, nt)              # [C, C]
            for r in range(rep):
                h = i * rep + r
                G_row, b_row = g_ref[0, h], b_ref[0, h]      # [1, C]
                G, beta = column(G_row), column(b_row)       # [C, 1]
                decay = jnp.exp(jnp.where(t >= s, G - G_row, -1e30))
                T = _unit_lower_inverse(
                    jnp.where(t > s, beta * decay * kk, 0.0), t, s)
                S0 = s_scr[h]                                # [Dk, Dv]
                eg = jnp.exp(G)
                kf, qf = k.astype(_F32), q.astype(_F32)
                u = _mm32(T, beta * (v_ref[0, h].astype(_F32)
                                     - mm(eg * kf, S0)))     # [C, Dv]
                o_ref[0, h] = mm(eg * qf, S0) + mm(decay * qk, u)
                g_end = jnp.min(G_row, axis=1, keepdims=True)    # [1, 1]
                s_scr[h] = jnp.exp(g_end) * S0 + mm(
                    jnp.exp(g_end - G) * kf, u, tn)
        sout_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_chunk(q, k, v, g, beta, pool, layer, ck: Chunks, *, interpret=None):
    """``ops/gdn.gdn_chunk_xla``'s contract as one Mosaic call: ``q``/``k
    [NC, C, Hk, Dk]``, ``v [NC, C, Hv, Dv]``, ``g``/``beta [NC, C, Hv]``
    float32, ``pool [L, slots, Hv, Dk, Dv]`` float32 (donated: the result
    aliases it). Returns ``(o [NC, C, Hv, Dv] float32, pool)``; chunks
    past ``ck.live`` are never written."""
    NC, C, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    rep = Hv // Hk
    if C != CHUNK or C != 4 * _BLOCK:
        raise ValueError(f"gdn_chunk is built for chunks of {CHUNK} tokens")
    hb = _head_block(Hv, rep, 4)
    kb = hb // rep
    i32 = jnp.int32
    heads_first = lambda a: jnp.swapaxes(a, 1, 2)      # noqa: E731
    G = jnp.cumsum(g.astype(_F32), axis=1)
    rows = lambda a: jnp.swapaxes(a, 1, 2)[:, :, None, :]   # noqa: E731
    flags = (ck.first.astype(i32) + 2 * ck.fresh.astype(i32))
    live = ck.live.reshape(1).astype(i32)

    def chunk_map(b, j, slot, fl, lv, ly):
        return (jnp.maximum(jnp.minimum(j, lv[0] - 1), 0), b, 0, 0)

    def pool_map(b, j, slot, fl, lv, ly):
        return (ly[0], slot[j], b, 0, 0)

    o, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, kb=kb, rep=rep, C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(Hv // hb, NC),
            in_specs=[
                pl.BlockSpec((1, kb, C, Dk), chunk_map),
                pl.BlockSpec((1, kb, C, Dk), chunk_map),
                pl.BlockSpec((1, hb, C, Dv), chunk_map),
                pl.BlockSpec((1, hb, 1, C), chunk_map),
                pl.BlockSpec((1, hb, 1, C), chunk_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, C, Dv), chunk_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ],
            scratch_shapes=[pltpu.VMEM((hb, Dk, Dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((NC, Hv, C, Dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_resolve_interpret(interpret),
        name="gdn_chunk",
    )(ck.slot.astype(i32), flags, live, jnp.asarray(layer, i32).reshape(1),
      heads_first(q), heads_first(k), heads_first(v), rows(G),
      rows(beta.astype(_F32)), pool)
    return jnp.swapaxes(o, 1, 2), pool


def _step_kernel(slot_ref, keep_ref, layer_ref, q_ref, k_ref, v_ref, eg_ref,
                 b_ref, sin_ref, o_ref, sout_ref, *, hb: int, rep: int):
    del slot_ref, layer_ref
    keep = keep_ref[pl.program_id(0)].astype(_F32)
    for i in range(hb):
        kh = i // rep
        kc = k_ref[0, 0][:, kh:kh + 1]                       # [Dk, 1]
        qc = q_ref[0, 0][:, kh:kh + 1]
        S = sin_ref[0, 0, i] * keep * eg_ref[0, i:i + 1, :]  # [Dk, Dv]
        u = b_ref[0, i:i + 1, :] * (
            v_ref[0, i:i + 1, :] - jnp.sum(kc * S, axis=0, keepdims=True))
        S = S + kc * u
        o_ref[0, i:i + 1, :] = jnp.sum(qc * S, axis=0, keepdims=True)
        sout_ref[0, 0, i] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_step(q, k, v, g, beta, pool, layer, slot, fresh, *, interpret=None):
    """``ops/gdn.gdn_step_xla``'s contract as one Mosaic call: ``q``/``k
    [R, Hk, Dk]``, ``v [R, Hv, Dv]``, ``g``/``beta [R, Hv]`` float32,
    ``slot``/``fresh [R]``. Returns ``(o [R, Hv, Dv] float32, pool)``."""
    R, Hk, Dk = q.shape
    Hv, Dv = v.shape[1:]
    rep = Hv // Hk
    hb = _head_block(Hv, rep, 8)
    kb, nb = hb // rep, Hv // hb
    i32 = jnp.int32

    def columns(a):             # [R, Hk, Dk] -> [R, nb, Dk, kb] float32
        return jnp.swapaxes(a.astype(_F32).reshape(R, nb, kb, Dk), 2, 3)

    def wide(a):                # [R, Hv] -> [R, Hv, Dv]
        return jnp.broadcast_to(a.astype(_F32)[..., None], (R, Hv, Dv))

    def key_map(r, b, slot, keep, ly):
        return (r, b, 0, 0)

    def row_map(r, b, slot, keep, ly):
        return (r, b, 0)

    def pool_map(r, b, slot, keep, ly):
        return (ly[0], slot[r], b, 0, 0)

    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, nb),
            in_specs=[
                pl.BlockSpec((1, 1, Dk, kb), key_map),
                pl.BlockSpec((1, 1, Dk, kb), key_map),
                pl.BlockSpec((1, hb, Dv), row_map),
                pl.BlockSpec((1, hb, Dv), row_map),
                pl.BlockSpec((1, hb, Dv), row_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, Dv), row_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ]),
        out_shape=[jax.ShapeDtypeStruct((R, Hv, Dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_resolve_interpret(interpret),
        name="gdn_step",
    )(slot.astype(i32), (~fresh).astype(i32),
      jnp.asarray(layer, i32).reshape(1), columns(q), columns(k),
      v.astype(_F32), wide(jnp.exp(g)), wide(beta), pool)
    return o, pool


__all__ = ["gdn_chunk", "gdn_step"]
