"""Latent (MLA) paged attention on TPU over a TOKEN-PACKED step — one kernel
for the prompt chunks AND the decode rows of DeepSeek-family models.

The shape is ``ops/pallas/ragged.py``'s, the mathematics
``ops/pallas/mla_prefill.py``'s:

- The engine's prefill-carrying step lays every row's new tokens back to
  back on one ``[T]`` axis; row ``r`` owns slots ``q_starts[r] ..
  q_starts[r] + q_lens[r]`` (a decode row one slot, a pad row none). The
  grid runs over ALIGNED blocks of ``SB`` packed slots; a block loops over
  the rows that have slots in it (first and one past the last arrive as
  scalars) and, per row, streams the pages those slots can see into
  double-buffered VMEM slabs, masking the block's other slots out. Every
  slot belongs to one row, so one running softmax state per slot serves the
  whole loop. A block wholly past the packed tokens loops over no row and
  writes zeros.
- Scores and values run in latent space against the 2-slot latent cache
  ``[L, N, 2, 1, ps, dkv]``:

      s[q, t] = q_lat[q] . c_kv[t]  +  q_pe[q] . k_pe[t]   (slot-batched dot)
      out[q]  = softmax(s)[q] . c_kv                        (value = latent)

  one kv "head" against many query heads and a wide latent, so the query
  block comes from the MLA prefill kernel's VMEM accounting
  (``mla_prefill._query_block``: the f32 accumulator ``[nh*SB, dkv]`` is
  the large buffer). No window, softcap or visibility block: no MLA family
  has them.
- **With a bias** (``bias [T, S]`` float32: what ``models/dots3.py``'s two
  attention kinds hand over) the kernel knows no positions: every slot
  attends EVERY key of its row's ``kv_lens`` and the bias, added to the
  scores of all heads alike, says which it sees (0) and which not
  (``NEG_INF``) - a learned selection of the context, or the entries of a
  window ring in whatever order the ring holds them. The bias travels as
  ``[chunks, T, span]`` so that a chunk's block is one leading index, and
  a chunk is 32 pages where the unbiased kernel's is 8
  (``BIASED_PAGES_PER_CHUNK``: 21.3 -> 10.8 ms a layer for 512 queries of
  128 heads over 8,192 keys on a v5e). Queries and output are
  HEADS-MAJOR there, the layout their neighbours hold: the absorbed
  queries ``[nh, T, dkv]`` as ``W_UK``'s batched matmul writes them and
  the rotary queries ``[nh, T, rope]`` as two operands (no stack, the
  rotary half padded to whole lanes and not to the latent's width), the
  output ``[nh, T, dkv]`` as the accumulator lies and as ``W_UV``'s
  batched matmul reads it - no pass over ``T * nh * dkv`` elements in XLA
  on either side of the call.

The pure-JAX reference over the same layout, and the CPU-test oracle, is
``models.deepseek.mla_ragged_attention``; CPU tests of this kernel run in
interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import _resolve_interpret
from dynamo_tpu.ops.pallas.mla_decode import supports  # noqa: F401
from dynamo_tpu.ops.pallas.mla_prefill import PAGES_PER_CHUNK, _query_block

NEG_INF = -1e30

# the masked form (``bias``): pages a chunk, query rows a block and the
# scoped-VMEM limit the call asks for. A chunk of 32 pages (512 keys where
# the unbiased kernel streams 128) runs the rescale of the accumulator
# ``[nh*SB, dkv]`` and of the running max and sum once for four times the
# keys: at 128 keys those were more vector work than the scores' softmax
# itself and the MXU stood at 30 % of its peak. What the wider chunk
# needs of VMEM is past the 16 MiB the compiler scopes by default.
BIASED_PAGES_PER_CHUNK = 32
BIASED_M_ROWS = 1024
BIASED_VMEM_STACK = 48 * 2**20
BIASED_VMEM_LIMIT = 96 * 2**20


def _mla_ragged_kernel(*refs, page_size: int, chunk: int, q_block: int,
                       biased: bool = False):
    """One program per block of ``SB`` packed slots.

    ``refs``: the queries, the cache and the rows' scalars, ``bias_ref``
    (``biased``: ``[chunks, SB, span]`` float32, added to every head's
    scores; the causal mask is then the bias's), the output and the
    scratch buffers.

    q2_ref:  [2, SB, nh, dkv] - slot 0 = absorbed latent queries, slot 1 =
             roped queries zero-padded to dkv; pre-scaled. ``biased``: two
             refs, heads-major: ``ql_ref [nh, SB, dkv]`` and ``qp_ref [nh,
             SB, rope]``, the rotary slot's columns that are not padding.
    kv_hbm:  [L, N, 2, 1, ps, dkv] stacked latent cache (ANY).
    rows_ref [2, n_blocks]: the first row with a slot in the block and one
             past the last; qstart/qlen/lens [R]: a row's first slot, its
             slots, its context including them.
    buf:     [2, 2, 1, chunk*ps, dkv] double-buffered slabs.
    m/l [nh*SB, 1], acc [nh*SB, dkv]: the running softmax state of the
             block's slots, heads-major.
    out_ref: [SB, nh, dkv] latent attention output in f32; ``biased``:
             ``[nh, SB, dkv]``, as the accumulator lies.

    A row without slots in the block runs its chunk loop zero times, so no
    page DMA is armed and no matmul runs (the skip rides the loop bounds:
    Mosaic cannot lower the layout transposes inside a ``pl.when``)."""
    q_refs, refs = refs[:1 + biased], refs[1 + biased:]
    (kv_hbm, layer_ref, table_ref, rows_ref, qstart_ref, qlen_ref,
     lens_ref) = refs[:7]
    bias_ref = refs[7] if biased else None
    out_ref, buf, sem, m_ref, l_ref, acc_ref = refs[7 + biased:]
    i = pl.program_id(0)
    layer = layer_ref[0]
    SB = q_block
    span = chunk * page_size
    P = table_ref.shape[1]
    t0 = i * SB

    # heads-major rows so the slot-batched dot has one contracting dim
    # (Mosaic) and M = nh*SB fills the MXU
    if biased:
        ql_ref, qp_ref = q_refs
        nh, _sb, dkv = ql_ref.shape
        rope_dim = qp_ref.shape[2]
        q_lat = ql_ref[...].reshape(nh * SB, dkv)
        q_pe = qp_ref[...].reshape(nh * SB, rope_dim)
    else:
        q2_ref, = q_refs
        nh, dkv = q2_ref.shape[2], q2_ref.shape[3]
        # [2, nh*SB, dkv]
        q2 = q2_ref[...].transpose(0, 2, 1, 3).reshape(2, nh * SB, dkv)
    # the packed slot of each query of the block
    slot_t = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, SB, 1), 1)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def one_row(r, _):
        ctx = lens_ref[r]
        q_start = qstart_ref[r]
        q_len = qlen_ref[r]
        # the row's slots inside this block, and their positions: slot t
        # sits at absolute position pos0 + t
        lo = jnp.maximum(q_start, t0)
        hi = jnp.minimum(q_start + q_len, t0 + SB)
        active = hi > lo
        pos0 = ctx - q_len - q_start
        # kv the row's slots of this block can see: the causal bound,
        # inside the live context by construction (hi <= q_start + q_len)
        visible = ctx if biased else pos0 + hi
        num_chunks = jnp.maximum(jax.lax.div(visible + span - 1, span), 1)
        n_end = jnp.where(active, num_chunks, 0)

        def page_dma(slot, k, c):
            jj = jnp.minimum(c * chunk + k, P - 1)
            return pltpu.make_async_copy(
                kv_hbm.at[layer, table_ref[r, jj]],
                buf.at[slot, :, :, pl.ds(k * page_size, page_size)],
                sem.at[slot, k])

        def start_chunk(slot, c):
            def start_one(k, _):
                page_dma(slot, k, c).start()
                return 0

            jax.lax.fori_loop(0, chunk, start_one, 0, unroll=True)

        def wait_chunk(slot, c):
            def wait_one(k, _):
                page_dma(slot, k, c).wait()
                return 0

            jax.lax.fori_loop(0, chunk, wait_one, 0, unroll=True)

        @pl.when(active)
        def _():
            start_chunk(0, 0)

        qpos = pos0 + slot_t                               # [1, SB, 1]
        in_row = (slot_t >= q_start) & (slot_t < q_start + q_len)

        def body(c, _):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_end)
            def _():
                start_chunk(jax.lax.rem(c + 1, 2), c + 1)

            wait_chunk(slot, c)
            kv = buf[slot, :, 0]                           # [2, span, dkv]

            if biased:
                # the rotary slot's first ``rope_dim`` columns hold all
                # of it: two dots, the second a quarter as deep or less
                dims = (((1,), (1,)), ((), ()))
                s3 = (jax.lax.dot_general(
                    q_lat, kv[0], dims, preferred_element_type=jnp.float32)
                    + jax.lax.dot_general(
                        q_pe, kv[1][:, :rope_dim], dims,
                        preferred_element_type=jnp.float32)
                      ).reshape(nh, SB, span)
            else:
                s2 = jax.lax.dot_general(
                    q2, kv, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)    # [2, nh*SB, span]
                s3 = (s2[0] + s2[1]).reshape(nh, SB, span)
            t_pos = c * span + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, span), 2)
            if biased:
                s3 = s3 + bias_ref[c][None]
                mask = in_row & (t_pos < ctx)
            else:
                mask = in_row & (t_pos <= qpos)            # [1, SB, span]
            s = jnp.where(mask, s3, NEG_INF).reshape(nh * SB, span)

            # slots of other rows see nothing here: their max stays, their
            # p is 0 (or masked below while they have seen nothing at all)
            m = m_ref[...]                                 # [nh*SB, 1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
            scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
            l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(kv.dtype), kv[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [nh*SB, dkv]
            acc_ref[...] = acc_ref[...] * scale + pv
            m_ref[...] = m_new
            return 0

        jax.lax.fori_loop(0, n_end, body, 0)
        return 0

    jax.lax.fori_loop(rows_ref[0, i], rows_ref[1, i], one_row, 0)
    # slots of no row kept acc == 0, l == 0: zeros, a deterministic output
    # for the parity oracle
    out = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)   # [nh*SB, dkv]
           ).reshape(nh, SB, dkv)
    if not biased:
        out = out.transpose(1, 0, 2)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "name"))
def _mla_ragged(q, kv_pages, layer_idx, page_table, q_starts, q_lens,
                kv_lens, sm_scale: float, interpret: bool = False,
                bias=None, name: str = "mla_ragged"):
    """``q``: the stacked queries ``[2, T, nh, dkv]``; with a ``bias`` the
    pair ``(q_lat [nh, T, dkv], q_pe [nh, T, rope])``, and the output is
    ``[nh, T, dkv]``."""
    biased = bias is not None
    if biased:
        nh, T, dkv = q[0].shape
    else:
        _two, T, nh, dkv = q.shape
    _L, _N, _2, _one, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    chunk = min(BIASED_PAGES_PER_CHUNK if biased else PAGES_PER_CHUNK, P)
    span = chunk * page_size
    slab_bytes = 2 * 2 * span * dkv * kv_pages.dtype.itemsize
    n_chunks = -(-P // chunk)
    if not biased:
        SB = _query_block(T, nh, dkv, span, slab_bytes)
    else:
        # a slot's bias block, double-buffered, beside the rest
        SB = max(1, min(T, max(8, BIASED_M_ROWS // nh)))
        per_row = 22 * span + 32 * dkv + 8 * n_chunks * span // nh
        while SB > 8 and nh * SB * per_row + slab_bytes > BIASED_VMEM_STACK:
            SB = max(8, SB // 2)
    n_blocks = -(-T // SB)
    pad = n_blocks * SB - T
    # sm_scale rides the packed queries (the kernel's matmuls see it once)
    if biased:
        # ... in the pass that writes them: each operand scaled and cast
        # where it is produced, nothing stacked, nothing moved
        qs = tuple((x.astype(jnp.float32) * sm_scale).astype(kv_pages.dtype)
                   for x in q)
        if pad:
            qs = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in qs)
        q_specs = [pl.BlockSpec((nh, SB, x.shape[2]), lambda i: (0, i, 0))
                   for x in qs]
    else:
        qs = (q * sm_scale).astype(kv_pages.dtype)
        if pad:
            qs = jnp.pad(qs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qs = (qs,)
        q_specs = [pl.BlockSpec((2, SB, nh, dkv), lambda i: (0, i, 0, 0))]
    # the rows with slots in each block: rows are packed in order, so
    # those whose end lies past the block's start and whose start lies
    # before its end
    t0 = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * SB
    rows = jnp.stack([
        jnp.sum(((q_starts + q_lens)[None, :] <= t0), axis=1),
        jnp.sum((q_starts[None, :] < t0 + SB), axis=1)]).astype(jnp.int32)

    kernel = functools.partial(_mla_ragged_kernel, page_size=page_size,
                               chunk=chunk, q_block=SB, biased=biased)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    extra, extra_specs = (), []
    if biased:
        # [T, S] -> [chunks, T, span]: keys past the table's read NEG_INF
        S = P * page_size
        b = jnp.pad(bias.astype(jnp.float32),
                    ((0, pad), (0, n_chunks * span - S)),
                    constant_values=NEG_INF)
        extra = (b.reshape(n_blocks * SB, n_chunks, span)
                 .transpose(1, 0, 2),)
        extra_specs = [pl.BlockSpec((n_chunks, SB, span),
                                    lambda i: (0, i, 0))]
        out_spec = pl.BlockSpec((nh, SB, dkv), lambda i: (0, i, 0))
        out_shape = (nh, n_blocks * SB, dkv)
    else:
        out_spec = pl.BlockSpec((SB, nh, dkv), lambda i: (i, 0, 0))
        out_shape = (n_blocks * SB, nh, dkv)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=q_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            smem, smem, smem, smem, smem, smem,
        ] + extra_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, 2, 1, chunk * page_size, dkv), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
            pltpu.VMEM((nh * SB, 1), jnp.float32),
            pltpu.VMEM((nh * SB, 1), jnp.float32),
            pltpu.VMEM((nh * SB, dkv), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        name=name,
        **({} if not biased else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=BIASED_VMEM_LIMIT)}),
    )(*qs, kv_pages, layer_idx, page_table, rows, q_starts, q_lens, kv_lens,
      *extra)
    return out[:, :T] if biased else out[:T]


def mla_ragged_attention_packed(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                                pages: jnp.ndarray, layer_idx,
                                page_table: jnp.ndarray,
                                q_starts: jnp.ndarray, q_lens: jnp.ndarray,
                                kv_lens: jnp.ndarray, sm_scale: float,
                                interpret: bool | None = None,
                                name: str = "mla_ragged") -> jnp.ndarray:
    """Latent paged attention of a token-packed step over the stacked MLA
    cache (drop-in for ``models.deepseek.mla_ragged_attention``).

    q_lat:      [T, nh, dkv] absorbed latent queries, every row's tokens
                back to back (f32 ok; cast in); slots of no row are pad
                (their output is zero)
    q_pe:       [T, nh, dr] roped queries
    pages:      [L, N, 2, 1, ps, dkv] latent cache
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [R, P]
    q_starts:   [R] a row's first slot (ascending, packed: the exclusive
                cumulative sum of ``q_lens``)
    q_lens:     [R] real query tokens per row (a decode row is 1, a pad
                row 0)
    kv_lens:    [R] context per row including its new tokens
    name:       the kernel's name in a device trace

    Returns the latent attention output [T, nh, dkv] in f32 — feed to
    ``models.deepseek._expand_and_project``.
    """
    dkv, dr = q_lat.shape[-1], q_pe.shape[-1]
    q_pe_pad = jnp.pad(q_pe, ((0, 0), (0, 0), (0, dkv - dr)))
    q2 = jnp.stack([q_lat, q_pe_pad.astype(q_lat.dtype)])  # [2, T, nh, dkv]
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    return _mla_ragged(q2, pages, layer, page_table.astype(jnp.int32),
                       q_starts.astype(jnp.int32), q_lens.astype(jnp.int32),
                       kv_lens.astype(jnp.int32), sm_scale,
                       interpret=_resolve_interpret(interpret), name=name)


def pad_rope(q_pe: jnp.ndarray, dkv: int) -> jnp.ndarray:
    """``q_pe [..., dr]`` zero-padded to the rotary slot's columns the
    masked kernels read: whole lanes (128), never past the latent's
    width."""
    dr = q_pe.shape[-1]
    rope = min(dkv, -(-dr // 128) * 128)
    return jnp.pad(q_pe, ((0, 0),) * (q_pe.ndim - 1) + ((0, rope - dr),))


def mla_masked_attention_packed(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                                pages: jnp.ndarray, layer_idx,
                                page_table: jnp.ndarray,
                                q_starts: jnp.ndarray, q_lens: jnp.ndarray,
                                kv_lens: jnp.ndarray, bias: jnp.ndarray,
                                sm_scale: float,
                                interpret: bool | None = None,
                                name: str = "mla_masked") -> jnp.ndarray:
    """The masked form (module docstring): ``mla_ragged_attention_packed``
    with a bias and HEADS-MAJOR queries and output.

    q_lat:      [nh, T, dkv] absorbed latent queries (f32 ok; scaled and
                cast in, in the pass that produces them)
    q_pe:       [nh, T, dr] roped queries
    bias:       [T, P * ps] float32, added to every head's scores of a
                slot against its row's keys: every key below ``kv_lens``
                is attended and the bias alone masks
    (the rest as ``mla_ragged_attention_packed``)

    Returns the latent attention output [nh, T, dkv] in f32, zero for the
    slots of no row: ``W_UV``'s expand is a batched matmul over heads.
    """
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    return _mla_ragged((q_lat, pad_rope(q_pe, q_lat.shape[-1])), pages,
                       layer, page_table.astype(jnp.int32),
                       q_starts.astype(jnp.int32), q_lens.astype(jnp.int32),
                       kv_lens.astype(jnp.int32), sm_scale,
                       interpret=_resolve_interpret(interpret), bias=bias,
                       name=name)


__all__ = ["mla_ragged_attention_packed", "mla_masked_attention_packed",
           "supports"]
