"""Decode-step paged attention on TPU — our own Pallas kernel.

Replaces the reference's CUDA paged-attention kernels (vLLM's, reached via
``components/backends/vllm``) with a TPU-native Pallas kernel. (jax ships a
paged-attention kernel under ``jax.experimental``, but its output block
specs fail Mosaic's tiling checks under jax 0.9 — and owning the kernel
lets us fuse exactly our cache layout.)

Design (one grid program per sequence, chunked page streaming):

- The page table and context lengths enter as plain SMEM-resident inputs.
  NOT ``PrefetchScalarGridSpec``: on this toolchain the scalar-prefetch
  grid machinery costs ~1.7 ms per invocation (measured 80x slowdown on an
  otherwise identical kernel); plain SMEM inputs issue dynamic-index DMAs
  at sub-microsecond cost.
- K/V pages stay in HBM (``memory_space=ANY``) in the page-major stacked
  layout ``[L, N, 2, Hkv, ps, Dh]`` — one page is one contiguous slab with
  K and V for all heads, so each page is fetched by ONE DMA descriptor.
  The WHOLE pool enters the kernel and the layer index rides in SMEM (a
  layer-slice taken outside would make XLA copy the layer around the
  opaque custom call). Pages are
  streamed in chunks of ``PAGES_PER_CHUNK`` into a double-buffered VMEM
  slab, the next chunk's burst issued while the current chunk computes.
- Flash-style online softmax in f32 over a ``lax.fori_loop`` whose trip
  count is the sequence's true chunk count (short sequences stop early).
  Pad pages of the last chunk / stale slab contents are masked to -inf
  before the softmax update, so they contribute zero.
- GQA without transposes: scores and the PV product are batched
  ``dot_general``s over the kv-head axis with the chunk/slot dims left in
  place (``[Hkv,G,Dh] x [C,Hkv,ps,Dh] -> [Hkv,G,C,ps]``), bf16 in, f32
  accumulation on the MXU.

Alignment: Mosaic tiles the two minor dims to (8, 128) — the kernel
requires ``head_dim % 128 == 0`` (Llama-3-8B / 3.2-3B class; the engine
falls back to the XLA gather path otherwise) and ``page_size % 8 == 0``.

CPU tests run the same kernel in interpreter mode against the XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# pages per streamed chunk: with 16-token pages this is 128 positions per
# burst — one chunk's matmul fills the MXU's 128 lanes
PAGES_PER_CHUNK = 8
# ... and of the masked form (a ``bias``): the rescale of the accumulator
# and the loop's own cost run once for four times the keys
# (``mla_ragged.BIASED_PAGES_PER_CHUNK``, where it was measured)
BIASED_PAGES_PER_CHUNK = 32


def supports(head_dim: int, page_size: int) -> bool:
    """Geometries this kernel can lower for (else use the XLA path)."""
    return head_dim % 128 == 0 and page_size % 8 == 0


def _resolve_interpret(interpret) -> bool:
    """``None`` -> interpreter mode off-TPU (so CPU tests exercise the
    engine's exact TPU code path), native Mosaic on TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _decode_kernel(q_ref, kv_hbm, layer_ref, window_ref, table_ref,
                   lens_ref, *rest, page_size: int, n_kv: int, chunk: int,
                   softcap: float, biased: bool = False):
    """One program per sequence: stream page chunks, online-softmax attend.

    ``rest``: ``bias_ref`` where ``biased`` (``[1, chunks, span]`` float32,
    the row's bias a chunk a line, added to every head's scores: 0 on the
    keys the row attends, ``NEG_INF`` on the others - a learned selection
    of the context, ``models/moe.py`` with ``cfg.index_topk``), then
    ``out_ref, buf, sem``.

    kv_hbm is the STACKED cache ``[L, N, 2, Hkv, ps, Dh]`` and ``layer_ref``
    an SMEM scalar selecting the layer — the dynamic layer index rides the
    DMA descriptor, so the same compiled kernel serves every layer. That is
    what lets the engine run decode under ``lax.scan`` over layers (one
    compiled layer body, ~L× cheaper cold compile) instead of a python
    unroll: the kernel receives the WHOLE cache array (no layer slicing at
    the XLA level — slicing a stacked cache outside an opaque custom call
    is what forced the defensive whole-cache copies, measured ~10x).

    buf: [2, 2, Hkv, chunk*page_size, Dh] double-buffered slabs — pages DMA
    straight into their position range, so the chunk is ALREADY in the
    merged [Hkv, span, Dh] layout the matmuls want (no in-kernel transpose,
    and Mosaic's matmul only takes a single contracting dim).
    sem: [2, chunk] DMA semaphores (slot, page-in-chunk).

    ``window_ref`` (SMEM scalar, 0 = unlimited) restricts the query to the
    last ``window`` kv positions (gemma-2 alternating sliding-window
    layers) — chunks wholly before the window are never even DMA'd.
    ``softcap`` (static; 0 = disabled) applies gemma-style logit
    soft-capping ``cap * tanh(s / cap)`` before the softmax.

    A sequence of length 0 streams nothing and writes zeros: a
    token-packed step runs this kernel over all of its rows with a length
    for the one-token rows only (``ops/pallas/ragged.py``).
    """
    bias_ref = rest[0] if biased else None
    out_ref, buf, sem = rest[biased:]
    b = pl.program_id(0)
    layer = layer_ref[0]
    win = window_ref[0]
    ctx = lens_ref[b]
    num_pages = jax.lax.div(ctx + page_size - 1, page_size)
    num_chunks = jax.lax.div(num_pages + chunk - 1, chunk)
    # first kv position the (single, at ctx-1) query can see
    first_pos = jnp.where(win > 0, jnp.maximum(ctx - win, 0), 0)

    Hq, Dh = q_ref.shape[1], q_ref.shape[2]
    G = Hq // n_kv
    q = q_ref[0].reshape(n_kv, G, Dh)                      # [Hkv, G, Dh]

    P = table_ref.shape[1]

    def page_dma(slot, i, j):
        # One descriptor fetches the page's full slab (K+V, all heads) into
        # the chunk slab's position range for this page. Pad pages of a
        # partial last chunk DMA a clamped (real) table entry instead of
        # branching: conditionals cost more than the extra ~page of
        # bandwidth, and the slab must hold FINITE memory everywhere — the
        # softmax masks pad positions to weight 0, but 0 x garbage-NaN
        # would still poison the PV matmul.
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
    c0 = jax.lax.div(first_pos, span)  # skip chunks before the window

    # a row of length 0 has no chunk: start no copy that nothing waits for
    @pl.when(num_chunks > c0)
    def _():
        start_chunk(jax.lax.rem(c0, 2), c0)

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < num_chunks)
        def _():
            start_chunk(jax.lax.rem(c + 1, 2), c + 1)

        wait_chunk(slot, c)
        k = buf[slot, 0]                                   # [Hkv, span, Dh]
        v = buf[slot, 1]

        # scores [Hkv, G, span]: batch Hkv, contract Dh
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        if biased:
            s = s + bias_ref[0, pl.ds(c, 1), :][None]
        pos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where((pos < ctx) & (pos >= first_pos), s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))        # [Hkv, G]
        p = jnp.exp(s - m_new[..., None])
        # a fully-masked first chunk would leave m at -inf and leak
        # exp(0)=1 weights — zero those rows (cannot happen without a
        # window or a bias, where chunk c0=0 always holds position 0)
        p = jnp.where((m_new > NEG_INF / 2)[..., None], p, 0.0)
        scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        l = l * scale + jnp.sum(p, axis=-1)
        # PV [Hkv, G, Dh]: batch Hkv, contract span
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc = acc * scale[..., None] + pv
        return m_new, l, acc

    m0 = jnp.full((n_kv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_kv, G), jnp.float32)
    acc0 = jnp.zeros((n_kv, G, Dh), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(c0, num_chunks, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, Dh).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "softcap", "interpret",
                                    "name"))
def _paged_decode(q, kv_pages, layer_idx, window, page_table, total_lens,
                  sm_scale: float, softcap: float = 0.0,
                  interpret: bool = False, bias=None,
                  name: str = "paged_decode"):
    """``bias [B, P * ps]`` float32 (optional): the masked form, under the
    caller's ``name`` in a device trace."""
    B, Hq, Dh = q.shape
    _L, _N, _two, Hkv, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    biased = bias is not None
    chunk = min(BIASED_PAGES_PER_CHUNK if biased else PAGES_PER_CHUNK, P)
    extra, extra_specs = (), []
    if biased:
        # [B, S] -> [B, chunks, span]: keys past the table's read NEG_INF
        span = chunk * page_size
        n_chunks = -(-P // chunk)
        extra = (jnp.pad(bias.astype(jnp.float32),
                         ((0, 0), (0, n_chunks * span - P * page_size)),
                         constant_values=NEG_INF).reshape(B, n_chunks, span),)
        extra_specs = [pl.BlockSpec((1, n_chunks, span),
                                    lambda b: (b, 0, 0))]

    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               n_kv=Hkv, chunk=chunk, softcap=softcap,
                               biased=biased)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, Dh), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ] + extra_specs,
        out_specs=pl.BlockSpec((1, Hq, Dh), lambda b: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, Hkv, chunk * page_size, Dh), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dh), q.dtype),
        interpret=interpret,
        name=name,
    )((q * sm_scale).astype(q.dtype), kv_pages, layer_idx, window,
      page_table, total_lens, *extra)


def paged_decode_attention_stacked(q: jnp.ndarray, pages: jnp.ndarray,
                                   layer_idx, page_table: jnp.ndarray,
                                   positions: jnp.ndarray,
                                   total_lens: jnp.ndarray, sm_scale: float,
                                   window=None, softcap=None,
                                   interpret: bool | None = None,
                                   bias=None, name: str = "paged_decode"
                                   ) -> jnp.ndarray:
    """Drop-in for ``ops.attention.paged_attention`` when S == 1: the whole
    stacked cache enters the kernel and the (possibly TRACED) ``layer_idx``
    selects the layer inside the DMA — usable as the attention op inside a
    ``lax.scan`` over layers, giving one compiled decode layer body.

    q:          [B, 1, Hq, Dh]
    pages:      [L, N, 2, Hkv, page_size, Dh] (page-major slabs)
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [B, P]
    total_lens: [B] context length including the query token
    window:     optional scalar (python int or traced, 0 = unlimited) —
                gemma-2 alternating sliding-window layers
    softcap:    optional STATIC float (gemma logit soft-capping)
    bias:       optional [B, P * ps] float32 added to every head's scores
                (0 on the keys a row attends, ``NEG_INF`` on the others:
                the masked form of a learned selection); a row of
                ``total_lens`` 0 streams nothing and comes back zero
    name:       the kernel's name in a device trace
    """
    B, S, Hq, Dh = q.shape
    if S != 1:
        raise ValueError(f"decode kernel requires S=1, got S={S}")
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    win = (jnp.zeros((1,), jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))
    out = _paged_decode(q[:, 0], pages, layer, win,
                        page_table.astype(jnp.int32),
                        total_lens.astype(jnp.int32), sm_scale,
                        softcap=float(softcap or 0.0),
                        interpret=_resolve_interpret(interpret), bias=bias,
                        name=name)
    return out[:, None]                                    # [B, 1, Hq, Dh]


# marker the gemma forward checks before handing this impl its per-layer
# window / softcap kwargs
paged_decode_attention_stacked.supports_window_softcap = True
# marker for families whose attention the GQA kernels cannot run directly
# (deepseek MLA): a passed impl carrying it opts the family into its own
# Pallas kernels (ops/pallas/mla_decode.py) instead of being called
paged_decode_attention_stacked.pallas_paged_kernel = True


__all__ = ["paged_decode_attention_stacked", "supports"]
