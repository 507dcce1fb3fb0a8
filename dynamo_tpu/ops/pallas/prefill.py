"""Chunked-prefill paged attention on TPU — flash-style Pallas kernel.

Covers the serving engine's prefill steps (S = chunk of new tokens per
sequence) against the paged KV cache, the shape class where the XLA
blockwise path (``ops/attention._attend_blockwise``) still materializes a
``[B, Hkv, S, G, span]`` score block per chunk in XLA-managed buffers. Here
the whole layer runs as one kernel per (sequence, query-block):

- Same page-streaming machinery as the decode kernel
  (``ops/pallas/decode.py``): pages stay in HBM (``memory_space=ANY``) in
  the page-major slab layout ``[L, N, 2, Hkv, ps, Dh]``, an SMEM layer
  index rides the DMA descriptors (so the kernel works under ``lax.scan``
  over layers), and chunks of ``PAGES_PER_CHUNK`` pages double-buffer into
  VMEM — the next chunk's burst issued while the current chunk computes.
- Flash-style online softmax in f32 with a CAUSAL mask on absolute
  positions: query row ``s`` of the block attends to kv positions
  ``t <= q_start + j*SB + s`` and ``t < ctx``. Prefix-cache hits fall out:
  queries attend to whatever the page table already holds.
- The query block is ``[SB, Hq, Dh]`` with SB = 256 (or S when shorter):
  large enough to fill the MXU via the grouped ``[Hkv, G*SB, span]``
  matmuls, small enough that scores + accumulator + kv slabs fit VMEM at
  Llama-3-class geometry (~11 MB at Hkv=8, G=3, Dh=128).
- Each (b, j) program streams only the chunks its queries can SEE
  (``ceil(min(ctx, block_end+1) / span)``) — early query blocks of a long
  context skip the tail, and queries past ``ctx`` cost nothing.

Alignment: ``head_dim % 128 == 0`` and ``page_size % 8 == 0`` (same
``supports`` predicate as decode). CPU tests run in interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import _resolve_interpret, supports  # noqa: F401

NEG_INF = -1e30

PAGES_PER_CHUNK = 8

# query rows per grid program: SB * Hq * Dh bf16 + f32 scores/acc must fit
# scoped VMEM next to the double-buffered kv slabs. The ceiling is the
# 16 MiB scoped-vmem stack limit, and Mosaic's materialized temporaries
# (exp input, p cast, acc update, the q/out transposes) roughly DOUBLE the
# naive scores+acc accounting: on a real v5e, SB=128 at Llama-3B geometry
# (Hq=24, Dh=128, span=128) measured 16.79 MiB of stack — 804 KiB OVER.
# ``_fit_query_block`` shrinks SB per-geometry with an estimator
# calibrated against that measurement; QUERY_BLOCK is only the upper bound.
QUERY_BLOCK = 128

# scoped-vmem stack budget the estimator targets: the hardware limit is
# 16 MiB; 14 MiB leaves margin for the ~5% the calibrated estimator
# underpredicts plus Mosaic's small fixed overheads
VMEM_STACK_BUDGET = 14 * 2**20


def shrink_query_block(sb: int, floor: int, row_heads: int,
                       bytes_per_row: int, slab_bytes: int) -> int:
    """Halve ``sb`` until ``row_heads * sb * bytes_per_row + slab_bytes``
    fits ``VMEM_STACK_BUDGET`` (never below ``floor``). Shared by this
    kernel and the MLA prefill kernel — each supplies its own calibrated
    per-row byte cost."""
    while sb > floor and row_heads * sb * bytes_per_row + slab_bytes \
            > VMEM_STACK_BUDGET:
        # clamp the halving so a non-power-of-two start (sb seeds from the
        # prompt length S) cannot step BELOW the floor: 12 -> 6 would
        # violate the kernel's minimum-rows contract
        sb = max(floor, sb // 2)
    return sb


def _fit_query_block(S: int, Hq: int, Dh: int, span: int,
                     slab_bytes: int) -> int:
    """Largest query block (power-of-two rows ≥ 8) whose estimated scoped
    VMEM stack fits the budget.

    Estimator: the f32 score/prob/exp temporaries are ``Hq*SB*span`` (≈3
    copies live) and the f32 accumulator chain is ``Hq*SB*Dh`` (≈4 copies),
    plus bf16 q/out copies — ``Hq*SB*(14*span + 24*Dh)`` bytes total.
    Calibrated on v5e: predicts 15.9 MiB where the chip measured 16.79 MiB
    (Hq=24, SB=128, span=128, Dh=128), hence the conservative budget.

    Beside the double-buffered slab the stack holds the chunk in flight
    read OUT of it, keys and values once more each and in float32: twice
    the slab again. At the calibration's 8 key/value heads that is 2 MiB,
    which the fitted per-row cost already carries; at 30 heads without
    grouping (Olmo-Hybrid: ``[2, 2, 30, 128, 128]`` bf16, 3.75 MiB) the TPU
    compiler measured a constant 11.1 MiB beside 0.10 MiB a query row
    (17.63 MiB at SB=64, 24.18 at 128: PERF.md, PR 51), so what the
    copies take over those 2 MiB is counted (SB=32 there: 14.4 MiB).
    """
    copies = max(0, 2 * slab_bytes - 2 * 2**20)
    return shrink_query_block(min(QUERY_BLOCK, S), 8, Hq,
                              14 * span + 24 * Dh, slab_bytes + copies)


def _horizon(qpos, block: int):
    """The last key position a query at ``qpos`` sees: itself under the
    causal mask (``block`` 1: the expression is ``qpos``, the program the
    one it was), the end of its block of ``block`` positions under the
    block-wise visibility of generation by diffusion over blocks
    (``ops.attention.horizon``)."""
    if block <= 1:
        return qpos
    return jax.lax.div(qpos, block) * block + (block - 1)


def _prefill_kernel(q_ref, kv_hbm, layer_ref, window_ref, table_ref,
                    qstart_ref, lens_ref, out_ref, buf, sem, *,
                    page_size: int, n_kv: int, chunk: int, q_block: int,
                    softcap: float, block: int = 1):
    """One program per (sequence, query-block): stream visible page chunks,
    causal online-softmax attend.

    q_ref/out_ref: [1, SB, Hq, Dh] block of the padded chunk batch.
    buf: [2, 2, Hkv, chunk*page_size, Dh] double-buffered kv slabs.
    sem: [2, chunk] DMA semaphores.

    ``window_ref`` (SMEM scalar, 0 = unlimited) restricts each query row to
    the last ``window`` kv positions (gemma-2 alternating sliding-window
    layers) — chunks wholly before the BLOCK's earliest window are never
    DMA'd. ``softcap`` (static; 0 = disabled) applies gemma-style logit
    soft-capping ``cap * tanh(s / cap)`` before the mask, matching the XLA
    paths.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    layer = layer_ref[0]
    win = window_ref[0]
    ctx = lens_ref[b]
    q_start = qstart_ref[b]

    SB = q_block
    Hq, Dh = q_ref.shape[2], q_ref.shape[3]
    G = Hq // n_kv
    span = chunk * page_size

    # kv this block can see: causal bound (its last query's position + 1)
    # clamped to the live context
    block_last = _horizon(q_start + (j + 1) * SB - 1, block)
    visible = jnp.minimum(ctx, block_last + 1)
    num_chunks = jnp.maximum(jax.lax.div(visible + span - 1, span), 1)
    # first kv position the block's EARLIEST query can see (the window
    # mask is per-row below; this only bounds the chunk range)
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

    # skip chunks before the window, clamped so at least one loop
    # iteration consumes the unconditional start_chunk below — an
    # unconsumed DMA would leave its semaphores armed for the NEXT grid
    # program's wait (scratch persists across the sequential grid); the
    # clamped chunk is fully masked and the m_new guard zeroes it
    c0 = jnp.minimum(jax.lax.div(first_pos, span), num_chunks - 1)
    start_chunk(jax.lax.rem(c0, 2), c0)

    # queries in [Hkv, G*SB, Dh] so scores/PV are single-contraction
    # batched matmuls (Mosaic takes one contracting dim)
    q = q_ref[0].reshape(SB, n_kv, G, Dh).transpose(1, 2, 0, 3) \
        .reshape(n_kv, G * SB, Dh)
    qpos = q_start + j * SB + jax.lax.broadcasted_iota(
        jnp.int32, (1, G, SB, 1), 2)                       # [1, G, SB, 1]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
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
        mask = (t_pos <= _horizon(qpos, block)) & (t_pos < ctx)  # [1,G,SB,span]
        # per-row sliding window: row at position p sees t > p - win
        mask &= (win <= 0) | (t_pos > qpos - win)
        s4 = jnp.where(mask, s4, NEG_INF)
        s = s4.reshape(n_kv, G * SB, span)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))        # [Hkv, G*SB]
        p = jnp.exp(s - m_new[..., None])
        # a block whose first chunks are all-masked keeps m at -inf:
        # exp(-inf - -inf) = 1 would leak weight — zero those rows
        p = jnp.where((m_new > NEG_INF / 2)[..., None], p, 0.0)
        scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        l = l * scale + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, G*SB, Dh]
        acc = acc * scale[..., None] + pv
        return m_new, l, acc

    m0 = jnp.full((n_kv, G * SB), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, G * SB), jnp.float32)
    acc0 = jnp.zeros((n_kv, G * SB, Dh), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(c0, num_chunks, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)[..., None]           # [Hkv, G*SB, Dh]
    out = out.reshape(n_kv, G, SB, Dh).transpose(2, 0, 1, 3) \
        .reshape(SB, Hq, Dh)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "softcap", "interpret",
                                    "block"))
def _paged_prefill(q, kv_pages, layer_idx, window, page_table, q_start,
                   total_lens, sm_scale: float, softcap: float = 0.0,
                   interpret: bool = False, block: int = 1):
    B, S, Hq, Dh = q.shape
    _L, _N, _two, Hkv, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(PAGES_PER_CHUNK, P)
    span = chunk * page_size
    slab_bytes = 2 * 2 * Hkv * span * Dh * kv_pages.dtype.itemsize
    SB = _fit_query_block(S, Hq, Dh, span, slab_bytes)
    # S need not divide SB: pallas pads the ragged last block (its garbage
    # query rows attend to finite clamped pages and their outputs land in
    # the discarded pad region of out_ref)
    n_q_blocks = -(-S // SB)

    kernel = functools.partial(_prefill_kernel, page_size=page_size,
                               n_kv=Hkv, chunk=chunk, q_block=SB,
                               softcap=softcap, block=block)
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
        name="paged_prefill",
    )((q * sm_scale).astype(q.dtype), kv_pages, layer_idx, window,
      page_table, q_start, total_lens)


def paged_prefill_attention_stacked(q: jnp.ndarray, pages: jnp.ndarray,
                                    layer_idx, page_table: jnp.ndarray,
                                    positions: jnp.ndarray,
                                    total_lens: jnp.ndarray, sm_scale: float,
                                    window=None, softcap=None,
                                    interpret: bool | None = None,
                                    block: int = 1) -> jnp.ndarray:
    """Drop-in for ``ops.attention.paged_attention`` on prefill steps
    (S > 1, positions contiguous per row — the engine's chunk batches).

    q:          [B, S, Hq, Dh] (S = padded chunk length)
    pages:      [L, N, 2, Hkv, page_size, Dh]
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [B, P]
    positions:  [B, S] absolute positions (row-contiguous; only column 0
                enters the kernel — pad rows/slots mask out downstream)
    total_lens: [B] context length including the new tokens
    window:     optional scalar (python int or traced, 0 = unlimited) —
                gemma-2 alternating sliding-window layers
    softcap:    optional STATIC float (gemma logit soft-capping)
    block:      STATIC visibility block: a query sees every key of its own
                and earlier blocks of ``block`` positions (1 = causal);
                what a pass over a block of diffusion generation and its
                block-wise prefill run
    """
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    win = (jnp.zeros((1,), jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))
    out = _paged_prefill(q, pages, layer, win,
                         page_table.astype(jnp.int32),
                         positions[:, 0].astype(jnp.int32),
                         total_lens.astype(jnp.int32), sm_scale,
                         softcap=float(softcap or 0.0),
                         interpret=_resolve_interpret(interpret),
                         block=int(block))
    return out


# gemma's forward checks this marker before handing the impl its per-layer
# window / softcap kwargs (closes VERDICT r4 item 4: gemma-2 prefill now
# rides the Pallas kernel instead of falling back to the XLA path)
paged_prefill_attention_stacked.supports_window_softcap = True
# see ops/pallas/decode.py: deepseek's MLA opt-in marker
paged_prefill_attention_stacked.pallas_paged_kernel = True


__all__ = ["paged_prefill_attention_stacked", "supports"]
