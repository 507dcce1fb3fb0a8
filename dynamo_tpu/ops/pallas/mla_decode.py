"""Decode-step latent (MLA) paged attention on TPU — Pallas kernel.

DeepSeek V2/V3 decode in the **absorbed** MLA form (``models/deepseek.py``):
the paged cache stores, per token, only the compressed latent ``c_kv``
(slot 0) and the shared roped key ``k_pe`` zero-padded to the latent width
(slot 1) — ``[L, N, 2, 1, ps, dkv]`` with ``dkv = kv_lora_rank``. Scores are

    s[t] = q_lat . c_kv[t]  +  q_pe . k_pe[t]

and the attention value IS the latent itself (``out = softmax(s) . c_kv``;
the per-head ``W_UV`` re-expansion happens OUTSIDE the kernel, once, as a
dense einsum the MXU loves). The reference has no in-house MLA kernel at
all — it serves DeepSeek-R1 through SGLang's CUDA MLA path
(``components/backends/sglang/docs/dsr1-wideep-h100.md:8``); this kernel is
that role, TPU-native.

Design notes (shared with ``ops/pallas/decode.py`` — same page-DMA
machinery, same SMEM-scalar layer index so the kernel runs under the
engine's ``lax.scan`` over layers):

- One grid program per sequence; pages stream HBM -> double-buffered VMEM
  slabs in chunks of ``PAGES_PER_CHUNK``, one DMA descriptor per page (a
  page's ``[2, 1, ps, dkv]`` slab is contiguous, K-rope and latent
  together).
- The two query parts enter pre-scaled and stacked as ``q2 [B, 2, nh,
  dkv]`` (``q_pe`` zero-padded to ``dkv``): the slot axis of the cache
  (latent / padded rope key) batches against the slot axis of the query, so
  the score is ONE batched ``dot_general`` over the slab plus an add —
  zero-padding makes ``q_pe_pad . k_pe_pad == q_pe . k_pe`` exactly.
- Flash-style online softmax in f32; the PV product contracts the
  positions against the SLOT-0 slab only (the latent is the value).
- GQA degenerates to Hkv=1 here, so the head axis (nh up to 128 on V3) is
  the matmul M dim — MXU-shaped without transposes.

Alignment: ``dkv % 128 == 0`` (V2/V3 real checkpoints: 512) and
``page_size % 8 == 0``; tests run interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import (
    NEG_INF,
    PAGES_PER_CHUNK,
    _resolve_interpret,
)


def supports(kv_lora_rank: int, page_size: int) -> bool:
    """Geometries this kernel can lower for (else use the XLA path)."""
    return kv_lora_rank % 128 == 0 and page_size % 8 == 0


def _mla_decode_kernel(q2_ref, kv_hbm, layer_ref, table_ref, lens_ref,
                       out_ref, buf, sem, *, page_size: int, chunk: int):
    """One program per sequence: stream latent page chunks, online-softmax
    attend in latent space.

    q2_ref:  [1, 2, nh, dkv] — slot 0 = absorbed latent query, slot 1 =
             roped query zero-padded to dkv; both pre-scaled by sm_scale.
    kv_hbm:  [L, N, 2, 1, ps, dkv] stacked latent cache (memory_space ANY).
    buf:     [2, 2, 1, chunk*ps, dkv] double-buffered slabs (cache slot
             axis kept: 0 = latent, 1 = padded rope key; same slab DMA
             pattern as decode.py with Hkv == 1).
    sem:     [2, chunk] DMA semaphores (slot, page-in-chunk).
    """
    b = pl.program_id(0)
    layer = layer_ref[0]
    ctx = lens_ref[b]
    num_pages = jax.lax.div(ctx + page_size - 1, page_size)
    num_chunks = jax.lax.div(num_pages + chunk - 1, chunk)

    nh, dkv = q2_ref.shape[2], q2_ref.shape[3]
    q2 = q2_ref[0]                                         # [2, nh, dkv]

    P = table_ref.shape[1]

    def page_dma(slot, i, j):
        # One descriptor per page: the [2, 1, ps, dkv] slab lands in both
        # slot rows of the chunk buffer at this page's position range.
        # Pad pages of a partial last chunk clamp to a real table entry
        # (masked to zero weight later; see decode.py's rationale).
        jj = jnp.minimum(j, P - 1)
        return pltpu.make_async_copy(
            kv_hbm.at[layer, table_ref[b, jj]],
            buf.at[slot, :, :, pl.ds(i * page_size, page_size)],
            sem.at[slot, i])

    def start_chunk(slot, c):
        def start_one(i, _):
            page_dma(slot, i, c * chunk + i).start()
            return 0

        jax.lax.fori_loop(0, chunk, start_one, 0, unroll=True)

    def wait_chunk(slot, c):
        def wait_one(i, _):
            page_dma(slot, i, c * chunk + i).wait()
            return 0

        jax.lax.fori_loop(0, chunk, wait_one, 0, unroll=True)

    span = chunk * page_size
    start_chunk(0, 0)

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        kv = buf[slot, :, 0]                               # [2, span, dkv]

        # scores [2, nh, span]: batch the slot axis, contract dkv — slot 0
        # is q_lat . c_kv, slot 1 is q_pe_pad . k_pe_pad (== q_pe . k_pe)
        s2 = jax.lax.dot_general(
            q2, kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s2[0] + s2[1]                                  # [nh, span]
        pos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # [nh, span]
        scale = jnp.exp(m - m_new)                         # [nh, 1]
        l = l * scale + jnp.sum(p, axis=-1, keepdims=True)
        # PV [nh, dkv]: the latent slab IS the value
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc * scale + pv
        return m_new, l, acc

    # chunk 0 always holds position 0 (no sliding window in MLA models),
    # so m never stays at -inf and needs no fully-masked-row guard
    m0 = jnp.full((nh, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    acc0 = jnp.zeros((nh, dkv), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _mla_paged_decode(q2, kv_pages, layer_idx, page_table, total_lens,
                      sm_scale: float, interpret: bool = False):
    B, _two, nh, dkv = q2.shape
    _L, _N, _2, _one, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(PAGES_PER_CHUNK, P)

    kernel = functools.partial(_mla_decode_kernel, page_size=page_size,
                               chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 2, nh, dkv), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, nh, dkv), lambda b: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, chunk * page_size, dkv), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
        out_shape=jax.ShapeDtypeStruct((B, nh, dkv), jnp.float32),
        interpret=interpret,
        name="mla_decode",
    )((q2 * sm_scale).astype(kv_pages.dtype), kv_pages, layer_idx,
      page_table, total_lens)


def mla_paged_decode_stacked(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                             pages: jnp.ndarray, layer_idx,
                             page_table: jnp.ndarray,
                             total_lens: jnp.ndarray, sm_scale: float,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Latent paged decode attention over the stacked MLA cache.

    q_lat:      [B, 1, nh, dkv] absorbed latent queries (f32 ok; cast in)
    q_pe:       [B, 1, nh, dr] roped queries
    pages:      [L, N, 2, 1, ps, dkv] latent cache (slot 0 = c_kv, slot 1
                = k_pe zero-padded to dkv)
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [B, P]; total_lens: [B] (context incl. the query token)

    Returns the latent attention output [B, 1, nh, dkv] in f32 — feed it
    to ``models.deepseek._expand_and_project`` for the W_UV re-expansion.
    """
    B, S, nh, dkv = q_lat.shape
    if S != 1:
        raise ValueError(f"MLA decode kernel requires S=1, got S={S}")
    dr = q_pe.shape[-1]
    q_pe_pad = jnp.pad(q_pe, ((0, 0), (0, 0), (0, 0), (0, dkv - dr)))
    q2 = jnp.stack([q_lat[:, 0], q_pe_pad[:, 0]], axis=1)  # [B, 2, nh, dkv]
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    out = _mla_paged_decode(q2, pages, layer,
                            page_table.astype(jnp.int32),
                            total_lens.astype(jnp.int32), sm_scale,
                            interpret=_resolve_interpret(interpret))
    return out[:, None]                                    # [B, 1, nh, dkv]


__all__ = ["mla_paged_decode_stacked", "supports"]
