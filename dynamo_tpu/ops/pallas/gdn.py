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
64 x 64 strictly lower-triangular ``A`` is built by doubling. The inverse
of its 2 x 2 diagonal blocks is ``I - A`` there; two inverted neighbours
``T_a``, ``T_b`` of width ``b`` and the block ``J`` of ``A`` that joins
them invert together as ``[[T_a, 0], [-T_b J T_a, T_b]]``, which over the
whole matrix is ``T <- T - T J T`` with ``J`` the joining blocks of that
width - five such steps, ten products, in float32 at the highest
precision. Every intermediate is a block of the true inverse, whose
entries the rule bounds (the transitions ``I - beta k k^T`` do not expand
for ``beta`` in [0, 2]); a sum of powers of ``A`` is not: at ``beta = 2``
over parallel keys (``linear_allow_neg_eigval``) the powers of a 16-wide
block reach 2e6 before they cancel to +-2, and float32 keeps a tenth of
that answer (tests/test_olmo_hybrid.py). Keys, queries and values enter
the products in the dtype
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

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


# what the state blocks in flight may take of the 16 MiB a kernel's VMEM is
# held to: ``gdn_step`` keeps two blocks in and two out
_STATE_VMEM = 12 << 20


def why_not(Hk: int, Hv: int, Dk: int, Dv: int):
    """None where ``gdn_chunk`` and ``gdn_step`` lower for this geometry,
    else the reason they do not (the rule then runs on ``ops/gdn.py``'s
    ``gdn_chunk_xla`` / ``gdn_step_xla``). Head counts and ``Dv`` are
    free: a block is the whole of an array's last two axes, which Mosaic
    pads in VMEM (30 heads of 96 x 192 lower as they are)."""
    if Hk < 1 or Hv % Hk:
        return f"{Hk} key heads do not serve {Hv} value heads in whole groups"
    if Dk % 8:
        return (f"a key head of {Dk} is no multiple of 8: the state's "
                f"[{Dk}, {Dv}] float32 tiles would be padded in the pool in "
                "HBM and the pool copied around every call")
    hb = _head_block(Hv, Hv // Hk, 8)
    need = 4 * hb * Dk * -(-Dv // 128) * 128 * 4
    if need > _STATE_VMEM:
        return (f"{hb} states of [{Dk}, {Dv}] float32, two in and two out, "
                f"take {need} bytes of VMEM (over {_STATE_VMEM})")
    return None


def supports(Hk: int, Hv: int, Dk: int, Dv: int) -> bool:
    """Geometries these kernels can lower for (else use the XLA forms)."""
    return why_not(Hk, Hv, Dk, Dv) is None


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
    C = A.shape[0]
    T = (t == s).astype(_F32) - jnp.where(t // 2 == s // 2, A, 0.0)
    b = 2
    while b < C:
        # the part of A that joins two inverted blocks of b into one of 2b
        join = jnp.where((t // (2 * b) == s // (2 * b))
                         & (t // b != s // b), A, 0.0)
        T = T - _mm32(_mm32(T, join), T)
        b *= 2
    return T


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
    if C != CHUNK or C & (C - 1):
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
        S = sin_ref[0, 0, i] * keep * eg_ref[0, 0, i:i + 1, :]  # [Dk, Dv]
        u = b_ref[0, 0, i:i + 1, :] * (
            v_ref[0, 0, i:i + 1, :] - jnp.sum(kc * S, axis=0, keepdims=True))
        S = S + kc * u
        o_ref[0, 0, i:i + 1, :] = jnp.sum(qc * S, axis=0, keepdims=True)
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

    # a block is the WHOLE of an array's last two axes, whatever the head
    # count and the head's size (30 heads cut into blocks of 6, a head of
    # 96 x 192): Mosaic pads such a tile in VMEM and the pool stays as it is
    def blocks(a):              # [R, Hv, Dv] -> [R, nb, hb, Dv] float32
        return a.astype(_F32).reshape(R, nb, hb, Dv)

    def wide(a):                # [R, Hv] -> [R, nb, hb, Dv]
        return blocks(jnp.broadcast_to(a.astype(_F32)[..., None],
                                       (R, Hv, Dv)))

    def block_map(r, b, slot, keep, ly):
        return (r, b, 0, 0)

    def pool_map(r, b, slot, keep, ly):
        return (ly[0], slot[r], b, 0, 0)

    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, nb),
            in_specs=[
                pl.BlockSpec((1, 1, Dk, kb), block_map),
                pl.BlockSpec((1, 1, Dk, kb), block_map),
                pl.BlockSpec((1, 1, hb, Dv), block_map),
                pl.BlockSpec((1, 1, hb, Dv), block_map),
                pl.BlockSpec((1, 1, hb, Dv), block_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, Dv), block_map),
                pl.BlockSpec((1, 1, hb, Dk, Dv), pool_map),
            ]),
        out_shape=[jax.ShapeDtypeStruct((R, nb, hb, Dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_resolve_interpret(interpret),
        name="gdn_step",
    )(slot.astype(i32), (~fresh).astype(i32),
      jnp.asarray(layer, i32).reshape(1), columns(q), columns(k),
      blocks(v), wide(jnp.exp(g)), wide(beta), pool)
    return o.reshape(R, Hv, Dv), pool


__all__ = ["gdn_chunk", "gdn_step", "supports", "why_not"]
