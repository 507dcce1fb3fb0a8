"""Chunked-prefill latent (MLA) paged attention on TPU — Pallas kernel.

The S>1 counterpart of ``ops/pallas/mla_decode.py``: DeepSeek V2/V3
prefill in the absorbed MLA form against the 2-slot latent page cache
``[L, N, 2, 1, ps, dkv]``. Same flash structure as the GQA prefill kernel
(``ops/pallas/prefill.py`` — page-chunk streaming into double-buffered
VMEM slabs, causal online softmax over absolute positions, SMEM layer
index so the kernel runs under the engine's layer scan), with the MLA
score/value substitution:

    s[q, t] = q_lat[q] . c_kv[t]  +  q_pe[q] . k_pe[t]   (slot-batched dot)
    out[q]  = softmax(s)[q] . c_kv                        (value = latent)

Shape strategy: MLA has ONE kv head but many query heads against a WIDE
latent (V3: nh=128, dkv=512), so the per-program working set scales with
``nh * SB * dkv`` — the query block SB adapts (``_query_block``) to keep
q2 + f32 accumulator + kv slabs inside VMEM while the matmul M dim
(``nh*SB`` rows) stays MXU-wide. No sliding window / softcap: no MLA
family uses them.

Reference role: SGLang's CUDA MLA prefill kernels behind the DSR1 recipe
(``components/backends/sglang/docs/dsr1-wideep-h100.md``); the XLA
blockwise latent path (``models/deepseek._mla_attend_blockwise``) remains
the portable fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import _resolve_interpret
from dynamo_tpu.ops.pallas.mla_decode import supports  # noqa: F401
from dynamo_tpu.ops.pallas.prefill import shrink_query_block

NEG_INF = -1e30

PAGES_PER_CHUNK = 8

# target query rows per program: chosen so nh * SB stays a wide matmul M
# dim while the f32 accumulator [nh*SB, dkv] (the dominant buffer at V3
# geometry) stays a few MB of VMEM
_TARGET_M_ROWS = 2048


def _query_block(S: int, nh: int, dkv: int, span: int,
                 slab_bytes: int) -> int:
    """Query block bounded by MXU row target AND the scoped-VMEM stack.

    The stack estimator mirrors ``prefill._fit_query_block``'s on-chip
    calibration (v5e measured Mosaic temporaries at ~2× the naive
    accounting): per query row, f32 score/prob/exp temporaries cost
    ``~22*span`` bytes (the slot-batched s2 is [2, rows, span]) and the
    f32 accumulator chain + q2/out copies cost ``~32*dkv`` bytes. At V3
    geometry (nh=128, dkv=512) the old fixed 2048-row target estimated
    ~39 MiB — far past the 16 MiB scoped limit the chip enforces."""
    sb = max(1, min(S, max(8, _TARGET_M_ROWS // nh)))
    return shrink_query_block(sb, 1, nh, 22 * span + 32 * dkv, slab_bytes)


def _mla_prefill_kernel(q2_ref, kv_hbm, layer_ref, table_ref, qstart_ref,
                        lens_ref, out_ref, buf, sem, *, page_size: int,
                        chunk: int, q_block: int):
    """One program per (sequence, query-block).

    q2_ref:  [1, 2, SB, nh, dkv] — slot 0 = absorbed latent queries,
             slot 1 = roped queries zero-padded to dkv; pre-scaled.
    kv_hbm:  [L, N, 2, 1, ps, dkv] stacked latent cache (ANY).
    buf:     [2, 2, 1, chunk*ps, dkv] double-buffered slabs.
    sem:     [2, chunk] DMA semaphores.
    out_ref: [1, SB, nh, dkv] latent attention output (f32 downstream
             re-expansion through W_UV happens outside).
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    layer = layer_ref[0]
    ctx = lens_ref[b]
    q_start = qstart_ref[b]

    SB = q_block
    nh, dkv = q2_ref.shape[3], q2_ref.shape[4]
    span = chunk * page_size

    block_last = q_start + (j + 1) * SB - 1
    visible = jnp.minimum(ctx, block_last + 1)
    num_chunks = jnp.maximum(jax.lax.div(visible + span - 1, span), 1)

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

    start_chunk(0, 0)

    # [2, nh*SB, dkv]: heads-major rows so the slot-batched dot has one
    # contracting dim (Mosaic) and M = nh*SB fills the MXU
    q2 = q2_ref[0].transpose(0, 2, 1, 3).reshape(2, nh * SB, dkv)
    qpos = q_start + j * SB + jax.lax.broadcasted_iota(
        jnp.int32, (1, SB, 1), 1)                          # [1, SB, 1]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        kv = buf[slot, :, 0]                               # [2, span, dkv]

        s2 = jax.lax.dot_general(
            q2, kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [2, nh*SB, span]
        s = s2[0] + s2[1]
        s3 = s.reshape(nh, SB, span)
        t_pos = c * span + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, span), 2)
        mask = (t_pos <= qpos) & (t_pos < ctx)             # [1, SB, span]
        # chunk 0 always holds position 0, which every row's causal mask
        # admits (ctx >= 1) — no fully-masked-row guard needed
        s3 = jnp.where(mask, s3, NEG_INF)
        s = s3.reshape(nh * SB, span)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # [nh*SB, span]
        scale = jnp.exp(m - m_new)
        l = l * scale + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [nh*SB, dkv]
        acc = acc * scale + pv
        return m_new, l, acc

    m0 = jnp.full((nh * SB, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nh * SB, 1), jnp.float32)
    acc0 = jnp.zeros((nh * SB, dkv), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)                      # [nh*SB, dkv]
    out_ref[0] = out.reshape(nh, SB, dkv).transpose(1, 0, 2) \
        .astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _mla_paged_prefill(q2, kv_pages, layer_idx, page_table, q_start,
                       total_lens, sm_scale: float,
                       interpret: bool = False):
    B, _two, S, nh, dkv = q2.shape
    _L, _N, _2, _one, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(PAGES_PER_CHUNK, P)
    span = chunk * page_size
    slab_bytes = 2 * 2 * span * dkv * kv_pages.dtype.itemsize
    SB = _query_block(S, nh, dkv, span, slab_bytes)
    n_q_blocks = -(-S // SB)

    kernel = functools.partial(_mla_prefill_kernel, page_size=page_size,
                               chunk=chunk, q_block=SB)
    return pl.pallas_call(
        kernel,
        grid=(B, n_q_blocks),
        in_specs=[
            pl.BlockSpec((1, 2, SB, nh, dkv),
                         lambda b, j: (b, 0, j, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, SB, nh, dkv),
                               lambda b, j: (b, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, chunk * page_size, dkv), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
        out_shape=jax.ShapeDtypeStruct((B, S, nh, dkv), jnp.float32),
        interpret=interpret,
        name="mla_prefill",
    )((q2 * sm_scale).astype(kv_pages.dtype), kv_pages, layer_idx,
      page_table, q_start, total_lens)


def mla_paged_prefill_stacked(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                              pages: jnp.ndarray, layer_idx,
                              page_table: jnp.ndarray,
                              positions: jnp.ndarray,
                              total_lens: jnp.ndarray, sm_scale: float,
                              interpret: bool | None = None
                              ) -> jnp.ndarray:
    """Latent paged PREFILL attention over the stacked MLA cache.

    q_lat:      [B, S, nh, dkv] absorbed latent queries (f32 ok; cast in)
    q_pe:       [B, S, nh, dr] roped queries
    pages:      [L, N, 2, 1, ps, dkv] latent cache
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [B, P]
    positions:  [B, S] absolute positions (row-contiguous; column 0 is
                the block base — the engine's chunk batches)
    total_lens: [B] context length including the new tokens

    Returns the latent attention output [B, S, nh, dkv] in f32 — feed to
    ``models.deepseek._expand_and_project``.
    """
    B, S, nh, dkv = q_lat.shape
    dr = q_pe.shape[-1]
    q_pe_pad = jnp.pad(q_pe, ((0, 0), (0, 0), (0, 0), (0, dkv - dr)))
    q2 = jnp.stack([q_lat, q_pe_pad], axis=1)      # [B, 2, S, nh, dkv]
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    return _mla_paged_prefill(q2, pages, layer,
                              page_table.astype(jnp.int32),
                              positions[:, 0].astype(jnp.int32),
                              total_lens.astype(jnp.int32), sm_scale,
                              interpret=_resolve_interpret(interpret))


__all__ = ["mla_paged_prefill_stacked", "supports"]
