"""Ragged mixed-batch paged attention on TPU — one kernel for prefill
chunks AND decode steps (the Ragged Paged Attention kernel shape,
PAPERS.md).

The engine's mixed step packs prefill chunks and single-token decode rows
into one ``[B, S]`` dispatch (``engine/scheduler.MixedStepBatch``). The
prefill kernel (``ops/pallas/prefill.py``) already computes such a batch
correctly — pad rows mask out causally — but it pays the FULL query-block
grid for every row: a decode row (1 real query token) costs the same
``ceil(S/SB)`` programs as a 512-token chunk, each streaming the row's
whole paged context. This kernel is the prefill kernel plus the ragged
row descriptors:

- Per row, ``q_len = ctx - q_start`` (positions are row-contiguous and end
  at ``ctx - 1``, so the descriptor rides the arrays the engine already
  ships — no new operands).
- Grid programs wholly past their row's real queries
  (``j*SB >= q_len``) SKIP everything — no page DMAs, no matmuls. On the
  sequential TPU grid a decode row costs ONE program streaming its own
  context instead of ``ceil(S/SB)``; mixed batches run at ~ragged cost,
  not padded cost.
- Everything else (page-streaming double buffer, SMEM layer index for the
  ``lax.scan`` forward, causal online softmax in f32, window/softcap) is
  the prefill kernel's machinery unchanged.

The pure-JAX flattened-layout reference lives in
``ops.attention.ragged_paged_attention`` (the CPU-test oracle); CPU tests
of this kernel run in interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import _resolve_interpret, supports  # noqa: F401
from dynamo_tpu.ops.pallas.prefill import (
    PAGES_PER_CHUNK,
    _fit_query_block,
)

NEG_INF = -1e30


def _ragged_kernel(q_ref, kv_hbm, layer_ref, window_ref, table_ref,
                   qstart_ref, lens_ref, out_ref, buf, sem, *,
                   page_size: int, n_kv: int, chunk: int, q_block: int,
                   softcap: float):
    """One program per (row, query-block); blocks wholly past the row's
    ragged ``q_len`` degenerate to near no-ops: the chunk loop's trip
    count collapses to ZERO (so no page DMAs are armed — nothing for the
    next program's semaphores to trip over — and no matmuls run), leaving
    only the cheap vector-unit epilogue writing zeros into the pad block.
    Mosaic cannot lower the layout transposes inside a ``pl.when``
    branch, so the skip is expressed through the loop bounds instead of a
    guarded body."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    layer = layer_ref[0]
    win = window_ref[0]
    ctx = lens_ref[b]
    q_start = qstart_ref[b]
    # the ragged descriptor: row b contributes q_len real query tokens at
    # positions q_start .. ctx-1 (a decode row is q_len == 1)
    q_len = ctx - q_start
    active = j * q_block < q_len

    SB = q_block
    Hq, Dh = q_ref.shape[2], q_ref.shape[3]
    G = Hq // n_kv
    span = chunk * page_size

    # kv this block can see: causal bound clamped to the live context
    block_last = q_start + (j + 1) * SB - 1
    visible = jnp.minimum(ctx, block_last + 1)
    num_chunks = jnp.maximum(jax.lax.div(visible + span - 1, span), 1)
    block_first = q_start + j * SB
    first_pos = jnp.where(win > 0,
                          jnp.maximum(block_first - win + 1, 0), 0)

    P = table_ref.shape[1]

    def page_dma(slot, i, c):
        jj = jnp.minimum(c * chunk + i, P - 1)
        return pltpu.make_async_copy(
            kv_hbm.at[layer, table_ref[b, jj]],
            buf.at[slot, :, :, pl.ds(i * page_size, page_size)],
            sem.at[slot, i])

    def start_chunk(slot, c):
        def start_one(i, _):
            page_dma(slot, i, c).start()
            return 0

        jax.lax.fori_loop(0, chunk, start_one, 0, unroll=True)

    def wait_chunk(slot, c):
        def wait_one(i, _):
            page_dma(slot, i, c).wait()
            return 0

        jax.lax.fori_loop(0, chunk, wait_one, 0, unroll=True)

    c0 = jnp.minimum(jax.lax.div(first_pos, span), num_chunks - 1)
    # THE ragged skip: an inactive block runs the chunk loop zero times
    n_end = jnp.where(active, num_chunks, c0)

    @pl.when(active)
    def _():
        start_chunk(jax.lax.rem(c0, 2), c0)

    q = q_ref[0].reshape(SB, n_kv, G, Dh).transpose(1, 2, 0, 3) \
        .reshape(n_kv, G * SB, Dh)
    qpos = q_start + j * SB + jax.lax.broadcasted_iota(
        jnp.int32, (1, G, SB, 1), 2)                       # [1, G, SB, 1]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_end)
        def _():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        k = buf[slot, 0]                                   # [Hkv, span, Dh]
        v = buf[slot, 1]

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, G*SB, span]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s4 = s.reshape(n_kv, G, SB, span)
        t_pos = c * span + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, span), 3)
        # pad rows of the block (local row >= q_len - j*SB) carry
        # qpos >= ctx; the `t_pos < ctx` bound keeps their work finite
        # and their outputs are never read downstream (the engine
        # samples at each row's last REAL token only)
        mask = (t_pos <= qpos) & (t_pos < ctx)             # [1, G, SB, span]
        mask &= (win <= 0) | (t_pos > qpos - win)
        s4 = jnp.where(mask, s4, NEG_INF)
        s = s4.reshape(n_kv, G * SB, span)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))        # [Hkv, G*SB]
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where((m_new > NEG_INF / 2)[..., None], p, 0.0)
        scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        l_new = l * scale + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, G*SB, Dh]
        acc = acc * scale[..., None] + pv
        return m_new, l_new, acc

    m0 = jnp.full((n_kv, G * SB), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, G * SB), jnp.float32)
    acc0 = jnp.zeros((n_kv, G * SB, Dh), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(c0, n_end, body, (m0, l0, acc0))
    # inactive blocks kept acc == 0, l == 0: the epilogue writes zeros
    # into the pad block — deterministic output for the parity oracle
    out = acc / jnp.maximum(l, 1e-20)[..., None]           # [Hkv, G*SB, Dh]
    out = out.reshape(n_kv, G, SB, Dh).transpose(2, 0, 1, 3) \
        .reshape(SB, Hq, Dh)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "softcap", "interpret"))
def _ragged_mixed(q, kv_pages, layer_idx, window, page_table, q_start,
                  total_lens, sm_scale: float, softcap: float = 0.0,
                  interpret: bool = False):
    B, S, Hq, Dh = q.shape
    _L, _N, _two, Hkv, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(PAGES_PER_CHUNK, P)
    span = chunk * page_size
    slab_bytes = 2 * 2 * Hkv * span * Dh * kv_pages.dtype.itemsize
    SB = _fit_query_block(S, Hq, Dh, span, slab_bytes)
    n_q_blocks = -(-S // SB)

    kernel = functools.partial(_ragged_kernel, page_size=page_size,
                               n_kv=Hkv, chunk=chunk, q_block=SB,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=(B, n_q_blocks),
        in_specs=[
            pl.BlockSpec((1, SB, Hq, Dh), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, SB, Hq, Dh), lambda b, j: (b, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, Hkv, chunk * page_size, Dh), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
        out_shape=jax.ShapeDtypeStruct((B, S, Hq, Dh), q.dtype),
        interpret=interpret,
        name="ragged_mixed",
    )((q * sm_scale).astype(q.dtype), kv_pages, layer_idx, window,
      page_table, q_start, total_lens)


def ragged_mixed_attention_stacked(q: jnp.ndarray, pages: jnp.ndarray,
                                   layer_idx, page_table: jnp.ndarray,
                                   positions: jnp.ndarray,
                                   total_lens: jnp.ndarray, sm_scale: float,
                                   window=None, softcap=None,
                                   interpret: bool | None = None
                                   ) -> jnp.ndarray:
    """Drop-in for ``ops.attention.paged_attention`` on MIXED steps
    (S > 1, rows ragged: each row's real query tokens are its leading
    ``total_lens[b] - positions[b, 0]`` slots — a prefill chunk, or a
    single decode token).

    q:          [B, S, Hq, Dh] (S = padded widest chunk in the batch)
    pages:      [L, N, 2, Hkv, page_size, Dh]
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [B, P]
    positions:  [B, S] absolute positions (row-contiguous; only column 0
                enters the kernel — the ragged length is derived as
                ``total_lens - positions[:, 0]``)
    total_lens: [B] context length including the new tokens
    window:     optional scalar (python int or traced, 0 = unlimited)
    softcap:    optional STATIC float (gemma logit soft-capping)
    """
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    win = (jnp.zeros((1,), jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))
    return _ragged_mixed(q, pages, layer, win,
                         page_table.astype(jnp.int32),
                         positions[:, 0].astype(jnp.int32),
                         total_lens.astype(jnp.int32), sm_scale,
                         softcap=float(softcap or 0.0),
                         interpret=_resolve_interpret(interpret))


# the family forwards consult these markers before handing an impl their
# per-layer window/softcap kwargs (see ops/pallas/prefill.py)
ragged_mixed_attention_stacked.supports_window_softcap = True
ragged_mixed_attention_stacked.pallas_paged_kernel = True


__all__ = ["ragged_mixed_attention_stacked", "supports"]
