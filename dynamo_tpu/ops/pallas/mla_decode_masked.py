"""Latent (MLA) paged attention of ONE-TOKEN rows over a MASK of their
context on TPU — the decode-step kernel of a model whose queries attend a
learned selection of their keys, or the entries of a window ring by their
true positions (``models/dots3.py``: both layer kinds, ``mla_selected_rows``
over a row's pages and ``mla_window_rows`` over its ring handed over as
pages).

The shape is ``ops/pallas/mla_decode.py``'s: one grid program a row, the
row's pages of the 2-slot latent cache ``[L, N, 2, 1, ps, dkv]`` streamed
once HBM -> double-buffered VMEM slabs, one DMA descriptor a page, scores
and values in latent space, an online softmax in f32. What differs is what
``ops/pallas/mla_ragged.py`` has with a bias:

- **The bias.** ``bias [R, S]`` float32 is added to every head's scores of
  a row against its keys - 0 on the keys it attends, ``NEG_INF`` on the
  others - so the softmax runs over the selection while the cache is read
  in the order it lies in. Fetching a row's 2,048 selected latents by
  ``(page, offset)`` costs XLA's gather 29 ns each, what streaming 25 k
  tokens costs here, and needs the selection as a sorted list first (a sort
  of the table's width); the mask is what the indexer's ``topk_mask`` makes
  anyway. A chunk may hold no attended key, so the running max may stand at
  ``NEG_INF`` (``mla_ragged``'s guard).
- **A chunk is 32 pages** (``mla_ragged.BIASED_PAGES_PER_CHUNK``: 512 keys
  at pages of 16), so the rescale of the ``[nh, dkv]`` accumulator runs
  once for four times the keys, and the rotary slot is read over the
  columns that are not padding.
- **A row of length 0 streams nothing** and comes back zero: the rows of a
  token-packed step that bring a prompt chunk (the ragged kernel's) enter
  so, and a step pays for the one-token rows it carries, not for its
  table's.
- **Two query operands**, the absorbed latent query ``[R, nh, dkv]`` and
  the rotary query ``[R, nh, rope]`` over the rotary slot's columns that
  are not padding (whole lanes): nothing is stacked and nothing padded to
  the latent's width on the way in.

The kernel's name in a device trace is the caller's (``name``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import NEG_INF, _resolve_interpret
from dynamo_tpu.ops.pallas.mla_decode import supports  # noqa: F401
from dynamo_tpu.ops.pallas.mla_ragged import (BIASED_PAGES_PER_CHUNK,
                                              pad_rope)


def _kernel(ql_ref, qp_ref, kv_hbm, layer_ref, table_ref, lens_ref,
            bias_ref, out_ref, buf, sem, *, page_size: int, chunk: int):
    """One program a row.

    ql_ref:   [1, nh, dkv] the absorbed latent query; qp_ref: [1, nh,
              rope] the rotary query over the rotary slot's columns that
              are not padding; both pre-scaled.
    kv_hbm:   [L, N, 2, 1, ps, dkv] stacked latent cache (ANY).
    bias_ref: [1, chunks, span] float32, the row's bias a chunk a line.
    buf:      [2, 2, 1, span, dkv] double-buffered slabs; sem [2, chunk].
    """
    b = pl.program_id(0)
    layer = layer_ref[0]
    ctx = lens_ref[b]
    span = chunk * page_size
    num_chunks = jax.lax.div(ctx + span - 1, span)
    _one, nh, dkv = ql_ref.shape
    rope_dim = qp_ref.shape[2]
    q_lat = ql_ref[0]                                      # [nh, dkv]
    q_pe = qp_ref[0]
    P = table_ref.shape[1]

    def page_dma(slot, i, j):
        # pad pages of a partial last chunk clamp to a real table entry
        # (masked out below)
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

    @pl.when(num_chunks > 0)
    def _():
        start_chunk(0, 0)

    dims = (((1,), (1,)), ((), ()))

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        kv = buf[slot, :, 0]                               # [2, span, dkv]
        s = (jax.lax.dot_general(q_lat, kv[0], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_pe, kv[1][:, :rope_dim], dims,
                                   preferred_element_type=jnp.float32)
             + bias_ref[0, pl.ds(c, 1), :])                # [nh, span]
        pos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        # no key of the chunk (or of every chunk so far) may be attended:
        # the max then stands at NEG_INF and nothing is added
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        l = l * scale + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [nh, dkv]
        return m_new, l, acc * scale + pv

    m0 = jnp.full((nh, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    acc0 = jnp.zeros((nh, dkv), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))
    out_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "name"))
def _mla_decode_masked(q_lat, q_pe, kv_pages, layer_idx, page_table, lens,
                       bias, sm_scale: float, interpret: bool, name: str):
    R, nh, dkv = q_lat.shape
    _L, _N, _2, _one, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(BIASED_PAGES_PER_CHUNK, P)
    span = chunk * page_size
    n_chunks = -(-P // chunk)
    # [R, S] -> [R, chunks, span]: keys past the table's read NEG_INF
    b = jnp.pad(bias.astype(jnp.float32),
                ((0, 0), (0, n_chunks * span - P * page_size)),
                constant_values=NEG_INF).reshape(R, n_chunks, span)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, chunk=chunk),
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, nh, dkv), lambda r: (r, 0, 0)),
            pl.BlockSpec((1, nh, q_pe.shape[2]), lambda r: (r, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            smem, smem, smem,
            pl.BlockSpec((1, n_chunks, span), lambda r: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh, dkv), lambda r: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, span, dkv), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
        out_shape=jax.ShapeDtypeStruct((R, nh, dkv), jnp.float32),
        interpret=interpret,
        name=name,
    )(*((x.astype(jnp.float32) * sm_scale).astype(kv_pages.dtype)
        for x in (q_lat, q_pe)), kv_pages, layer_idx, page_table, lens, b)


def mla_masked_decode_stacked(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                              pages: jnp.ndarray, layer_idx,
                              page_table: jnp.ndarray, lens: jnp.ndarray,
                              bias: jnp.ndarray, sm_scale: float,
                              interpret: bool | None = None,
                              name: str = "mla_decode_masked"
                              ) -> jnp.ndarray:
    """Latent paged attention of one query a row over the keys its bias
    leaves open.

    q_lat:      [R, nh, dkv] absorbed latent queries (f32 ok; cast in)
    q_pe:       [R, nh, dr] roped queries
    pages:      [L, N, 2, 1, ps, dkv] latent cache
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [R, P]
    lens:       [R] the row's context, its query's token included; 0: the
                row streams nothing and its output is zero
    bias:       [R, P * ps] float32, added to every head's scores (0 on
                the keys the row attends, ``NEG_INF`` on the others)
    name:       the kernel's name in a device trace

    Returns the latent attention output [R, nh, dkv] in f32, zero for a
    row that attends no key.
    """
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    return _mla_decode_masked(
        q_lat, pad_rope(q_pe, q_lat.shape[-1]), pages, layer,
        page_table.astype(jnp.int32), lens.astype(jnp.int32), bias, sm_scale,
        interpret=_resolve_interpret(interpret), name=name)


__all__ = ["mla_masked_decode_stacked", "supports"]
