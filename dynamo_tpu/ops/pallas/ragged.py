"""Paged attention on TPU over a TOKEN-PACKED step: the ragged kernel for
the rows that bring several tokens (prompt chunks; the Ragged Paged
Attention kernel shape, PAPERS.md) and the decode kernel for the rows that
bring one.

The engine's prefill-carrying step lays every row's new tokens back to back
on one ``[T]`` axis (``engine/jax_engine._packed_step_impl``): chunk rows
first, then the decode rows, one token each; row ``r`` owns slots
``q_starts[r] .. q_starts[r] + q_lens[r]``. Nothing around the kernels is
padded to ``[rows, longest chunk]`` any more.

**Which kernel serves which row** (``ragged_mixed_attention_packed``, read
off the step itself): the ragged kernel multiplies a whole block of ``SB``
slots' queries with every chunk of a row's keys and masks the other rows'
slots out — right for a chunk that fills the block, ``SB`` times the work
for a row that owns one slot of it. So the trailing run of one-token rows
(the decode rows; their slots are contiguous) goes through
``ops/pallas/decode.py``'s ``paged_decode``, one grid program and a
``[Hkv, G, Dh]`` query each: their queries are one ``dynamic_slice`` of the
packed axis, their outputs one select and ``dynamic_update_slice`` back,
and the ragged kernel gets them as rows without slots and row bounds that
end before them. A one-token row among the chunk rows stays with the ragged
kernel. Under a visibility ``block`` > 1 (generation by diffusion over
blocks: a one-slot row sees to its block's end, and ``paged_decode`` knows
no ``block``) every row takes the ragged kernel, the program it always was.

The ragged kernel reads the queries where they lie:

- The grid runs over ALIGNED blocks of ``SB`` packed slots (plain
  ``BlockSpec``s on ``q`` and the output: no row is aligned to anything,
  a decode row takes one slot). A block loops over the rows that have
  slots in it (first and last row per block arrive as scalars) and, per
  row, streams the pages those slots can SEE, masking the block's other
  slots out. Every slot belongs to one row, so one running softmax state
  per slot serves the whole loop: a row's pass leaves the other rows'
  slots as they were.
- A block wholly past the packed tokens, or one that holds nothing but
  rows the decode kernel took, loops over no row and writes zeros. A
  one-token row that does stay here costs a whole block's matmuls over its
  context, in the one block that holds its slot.
- The page-streaming double buffer, the SMEM layer index for the
  ``lax.scan`` forward, the causal online softmax in f32 and window /
  softcap are the prefill kernel's (``ops/pallas/prefill.py``).

**With a bias** (``selected_attention_rows``: a model whose layers attend a
LEARNED SELECTION of their context, ``models/moe.py`` with
``cfg.index_topk``) both kernels add ``ops/indexer.select_split``'s bias -
0 on the keys a query attends, ``NEG_INF`` on the others - to every head's
scores while the row's whole context streams through: the decode kernel a
line a row (``selected_rows`` in a device trace), the ragged kernel a line a
slot, ``[chunks, SB, span]`` a block (``selected_chunks``), both at chunks
of 32 pages. The routing is not ``ragged_mixed_attention_packed``'s run of
trailing rows: every row of one token goes to the decode kernel, fetched by
its first slot and laid back in place. Fetching a query's 2,048 selected
tokens instead - 8 rows of 256 B a token in the page layout - costs XLA's
gather 14.0 ms a layer for 48 rows of 24.7 k where the stream takes 3.4
(PERF.md section 6, PR 56): the masked form is the one form.

The pure-JAX reference over the same layout, and the CPU-test oracle, is
``ops.attention.ragged_paged_attention`` (with a selection:
``ops.attention.selected_attention``); CPU tests of this kernel run in
interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.decode import (  # noqa: F401
    BIASED_PAGES_PER_CHUNK,
    _paged_decode,
    _resolve_interpret,
    supports,
)
from dynamo_tpu.ops.pallas.prefill import (
    PAGES_PER_CHUNK,
    _fit_query_block,
    _horizon,
)

NEG_INF = -1e30

# the masked form (``bias``): query slots a block, and the scoped-VMEM
# limit the call asks for - a block's bias ``[chunks, SB, span]`` float32
# (``SB`` lines of the table's whole width, double-buffered) lies beside
# the slabs and the scores of a chunk of 32 pages, past the 16 MiB the
# compiler scopes by default (``mla_ragged``'s masked form, the same way)
BIASED_Q_BLOCK = 32
BIASED_VMEM_LIMIT = 96 * 2**20


def _ragged_kernel(q_ref, kv_hbm, layer_ref, window_ref, table_ref,
                   rows_ref, qstart_ref, qlen_ref, lens_ref, *rest,
                   page_size: int, n_kv: int, chunk: int, q_block: int,
                   softcap: float, block: int = 1, biased: bool = False):
    """One program per block of ``SB`` packed slots.

    ``rest``: ``bias_ref`` where ``biased`` (``[chunks, SB, span]``
    float32, a slot's bias against ITS row's keys, added to every head's
    scores beside the causal mask: 0 on the keys the slot attends,
    ``NEG_INF`` on the others), then ``out_ref, buf, sem, m_ref, l_ref,
    acc_ref``.

    q_ref/out_ref: [SB, Hq, Dh]; rows_ref [2, n_blocks]: the first row
    with a slot in the block and one past the last; qstart/qlen/lens [R]:
    a row's first slot, its slots, its context including them.
    buf: [2, 2, Hkv, chunk*page_size, Dh] double-buffered kv slabs;
    m/l [Hkv, G*SB, 1], acc [Hkv, G*SB, Dh]: the running softmax state
    of the block's slots.

    A row without slots in the block (a pad row, ``q_len`` 0) runs its
    chunk loop zero times, so no page DMA is armed and no matmul runs.
    Mosaic cannot lower the layout transposes inside a ``pl.when`` branch,
    so that skip is expressed through the loop bounds."""
    bias_ref = rest[0] if biased else None
    out_ref, buf, sem, m_ref, l_ref, acc_ref = rest[biased:]
    i = pl.program_id(0)
    layer = layer_ref[0]
    win = window_ref[0]
    SB = q_block
    Hq, Dh = q_ref.shape[1], q_ref.shape[2]
    G = Hq // n_kv
    span = chunk * page_size
    P = table_ref.shape[1]
    t0 = i * SB

    q = q_ref[...].reshape(SB, n_kv, G, Dh).transpose(1, 2, 0, 3) \
        .reshape(n_kv, G * SB, Dh)
    # the packed slot of each query row of the block
    slot_t = t0 + jax.lax.broadcasted_iota(jnp.int32, (1, G, SB, 1), 2)
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
        # kv the row's slots of this block can see: causal bound, inside
        # the live context by construction (hi <= q_start + q_len)
        visible = pos0 + hi
        if block > 1:
            # block-wise visibility: the last slot sees to its block's end
            visible = jnp.where(
                active, jax.lax.div(visible - 1, block) * block + block,
                visible)
        num_chunks = jnp.maximum(jax.lax.div(visible + span - 1, span), 1)
        first_pos = jnp.where(win > 0,
                              jnp.maximum(pos0 + lo - win + 1, 0), 0)
        c0 = jnp.minimum(jax.lax.div(first_pos, span), num_chunks - 1)
        n_end = jnp.where(active, num_chunks, c0)

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
            start_chunk(jax.lax.rem(c0, 2), c0)

        qpos = pos0 + slot_t                               # [1, G, SB, 1]
        in_row = (slot_t >= q_start) & (slot_t < q_start + q_len)

        def body(c, _):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_end)
            def _():
                start_chunk(jax.lax.rem(c + 1, 2), c + 1)

            wait_chunk(slot, c)
            k = buf[slot, 0]                               # [Hkv, span, Dh]
            v = buf[slot, 1]

            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)        # [Hkv, G*SB, span]
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s4 = s.reshape(n_kv, G, SB, span)
            if biased:
                s4 = s4 + bias_ref[c][None, None]
            t_pos = c * span + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, 1, span), 3)
            mask = in_row & (t_pos <= _horizon(qpos, block))  # [1,G,SB,span]
            mask &= (win <= 0) | (t_pos > qpos - win)
            s4 = jnp.where(mask, s4, NEG_INF)
            s = s4.reshape(n_kv, G * SB, span)

            # slots of other rows see nothing here: their max stays, their
            # p is 0 (or masked below while they have seen nothing at all)
            m = m_ref[...]                                 # [Hkv, G*SB, 1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
            scale = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
            l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)        # [Hkv, G*SB, Dh]
            acc_ref[...] = acc_ref[...] * scale + pv
            m_ref[...] = m_new
            return 0

        jax.lax.fori_loop(c0, n_end, body, 0)
        return 0

    jax.lax.fori_loop(rows_ref[0, i], rows_ref[1, i], one_row, 0)
    # slots of no row kept acc == 0, l == 0: zeros, a deterministic output
    # for the parity oracle
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
    out = out.reshape(n_kv, G, SB, Dh).transpose(2, 0, 1, 3) \
        .reshape(SB, Hq, Dh)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "softcap", "interpret",
                                    "block", "name"))
def _ragged_mixed(q, kv_pages, layer_idx, window, page_table, q_starts,
                  q_lens, kv_lens, sm_scale: float, softcap: float = 0.0,
                  interpret: bool = False, block: int = 1, n_rows=None,
                  bias=None, name: str = "ragged_mixed"):
    """``n_rows`` (optional traced scalar): only the first ``n_rows`` rows
    have slots for this kernel; a block's row bounds end there. ``bias [T,
    P * ps]`` float32 (optional): the masked form, under the caller's
    ``name`` in a device trace; the rows may then lie apart on the flat
    axis (a ``[B, S]`` step's, ``S`` apart)."""
    T, Hq, Dh = q.shape
    _L, _N, _two, Hkv, page_size, _ = kv_pages.shape
    P = page_table.shape[1]
    biased = bias is not None
    chunk = min(BIASED_PAGES_PER_CHUNK if biased else PAGES_PER_CHUNK, P)
    span = chunk * page_size
    slab_bytes = 2 * 2 * Hkv * span * Dh * kv_pages.dtype.itemsize
    SB = (max(1, min(T, BIASED_Q_BLOCK)) if biased
          else _fit_query_block(T, Hq, Dh, span, slab_bytes))
    n_blocks = -(-T // SB)
    # sm_scale rides the packed q (the kernel's matmuls see it once)
    qs = (q * sm_scale).astype(q.dtype)
    if n_blocks * SB != T:
        qs = jnp.pad(qs, ((0, n_blocks * SB - T), (0, 0), (0, 0)))
    # the rows with slots in each block: rows are packed in order, so
    # those whose end lies past the block's start and whose start lies
    # before its end
    t0 = jnp.arange(n_blocks, dtype=jnp.int32)[:, None] * SB
    rows = jnp.stack([
        jnp.sum(((q_starts + q_lens)[None, :] <= t0), axis=1),
        jnp.sum((q_starts[None, :] < t0 + SB), axis=1)]).astype(jnp.int32)
    if n_rows is not None:
        rows = jnp.minimum(rows, n_rows)

    kernel = functools.partial(_ragged_kernel, page_size=page_size,
                               n_kv=Hkv, chunk=chunk, q_block=SB,
                               softcap=softcap, block=block, biased=biased)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    G = Hq // Hkv
    extra, extra_specs, params = (), [], {}
    if biased:
        # [T, S] -> [chunks, T, span], a chunk's block one leading index:
        # keys past the table's and slots past the step's read NEG_INF
        n_chunks = -(-P // chunk)
        b = jnp.pad(bias.astype(jnp.float32),
                    ((0, n_blocks * SB - T),
                     (0, n_chunks * span - P * page_size)),
                    constant_values=NEG_INF)
        extra = (b.reshape(n_blocks * SB, n_chunks, span)
                 .transpose(1, 0, 2),)
        extra_specs = [pl.BlockSpec((n_chunks, SB, span),
                                    lambda i: (0, i, 0))]
        params = {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=BIASED_VMEM_LIMIT)}
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((SB, Hq, Dh), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            smem, smem, smem, smem, smem, smem, smem,
        ] + extra_specs,
        out_specs=pl.BlockSpec((SB, Hq, Dh), lambda i: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 2, Hkv, chunk * page_size, Dh), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk)),
            pltpu.VMEM((Hkv, G * SB, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * SB, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * SB, Dh), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((n_blocks * SB, Hq, Dh), q.dtype),
        interpret=interpret,
        name=name,
        **params,
    )(qs, kv_pages, layer_idx, window, page_table, rows, q_starts, q_lens,
      kv_lens, *extra)
    return out[:T]


def selected_attention_rows(q: jnp.ndarray, pages: jnp.ndarray, layer_idx,
                            page_table: jnp.ndarray, q_starts: jnp.ndarray,
                            new_lens: jnp.ndarray, kv_lens: jnp.ndarray,
                            one, bias, sm_scale: float,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Grouped-query attention of a step's rows over a LEARNED SELECTION of
    their contexts, in the masked form: the bias of
    ``ops/indexer.select_split`` added to the scores while a row's whole
    context streams through the kernel that serves its kind.

    q:        [N, Hq, Dh] the step's queries on the flat axis (packed back
              to back, or a ``[B, S]`` step's rows ``S`` apart)
    q_starts: [R] a row's first slot; new_lens [R] its new tokens; kv_lens
              [R] its context, those included
    one:      ``(bias [R, S], to [R])`` the rows of ONE token, or None: the
              decode kernel with a bias (``selected_rows`` in a device
              trace), a grid program a row, results laid at slots ``to``
    bias:     ``[N, S]`` the rows of several tokens, or None: the ragged
              kernel with a bias (``selected_chunks``)

    Returns ``[N, Hq, Dh]``, zero in the slots of no row."""
    N = q.shape[0]
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    win = jnp.zeros((1,), jnp.int32)
    table = page_table.astype(jnp.int32)
    q_starts = q_starts.astype(jnp.int32)
    new_lens = new_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    interpret = _resolve_interpret(interpret)
    out = None
    if bias is not None:
        several = new_lens > (1 if one is not None else 0)
        out = _ragged_mixed(
            q, pages, layer, win, table, q_starts,
            jnp.where(several, new_lens, 0), kv_lens, sm_scale,
            interpret=interpret, bias=bias, name="selected_chunks")
    if one is not None:
        rows_bias, to = one
        # a row that brings another number of tokens streams nothing
        # there and its result is laid nowhere
        res = _paged_decode(
            q[jnp.clip(q_starts, 0, N - 1)], pages, layer, win, table,
            jnp.where(new_lens == 1, kv_lens, 0), sm_scale,
            interpret=interpret, bias=rows_bias, name="selected_rows")
        if out is None:
            out = jnp.zeros_like(q)
        out = out.at[to].set(res.astype(out.dtype), mode="drop")
    return out


def takes_decode_kernel(block: int = 1) -> bool:
    """Whether a packed step's one-token rows go through ``paged_decode``
    (causal visibility) or stay with the ragged kernel (a visibility
    ``block``: the decode kernel knows none)."""
    return block <= 1


def _decode_rows(q_starts, q_lens):
    """The rows of a packed step that the decode kernel attends: the
    one-token rows behind the last row of several tokens whose slots
    follow one another, row ``r`` at slot ``first + r`` — by
    ``_prefill_arrays``' layout every decode row of the step and a
    one-token chunk that happens to be the last chunk row; a one-token row
    off that line (there is none in the engine's steps) stays with the
    ragged kernel. Returns (mask [R], first)."""
    R = q_lens.shape[0]
    r = jnp.arange(R, dtype=jnp.int32)
    n_chunk = jnp.max(jnp.where(q_lens > 1, r + 1, 0))
    first = q_starts[jnp.minimum(n_chunk, R - 1)] - n_chunk
    return (r >= n_chunk) & (q_lens == 1) & (q_starts - r == first), first


def ragged_mixed_attention_packed(q: jnp.ndarray, pages: jnp.ndarray,
                                  layer_idx, page_table: jnp.ndarray,
                                  q_starts: jnp.ndarray,
                                  q_lens: jnp.ndarray,
                                  kv_lens: jnp.ndarray, sm_scale: float,
                                  window=None, softcap=None,
                                  interpret: bool | None = None,
                                  block: int = 1) -> jnp.ndarray:
    """Drop-in for ``ops.attention.ragged_paged_attention`` on a
    token-packed step: the trailing run of one-token rows through the
    decode kernel, every other row through the ragged kernel (module
    docstring).

    q:          [T, Hq, Dh] every row's query tokens back to back; slots of
                no row are pad (their output is zero)
    pages:      [L, N, 2, Hkv, page_size, Dh]
    layer_idx:  scalar int (python int or traced scan index)
    page_table: [R, P]
    q_starts:   [R] a row's first slot (ascending, packed: the exclusive
                cumulative sum of ``q_lens``)
    q_lens:     [R] real query tokens per row (a decode row is 1, a pad
                row 0)
    kv_lens:    [R] context per row including its new tokens
    window:     optional scalar (python int or traced, 0 = unlimited)
    softcap:    optional STATIC float (gemma logit soft-capping)
    block:      STATIC visibility block (``ops.attention.horizon``; 1 =
                causal)
    """
    layer = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    win = (jnp.zeros((1,), jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))
    page_table = page_table.astype(jnp.int32)
    q_starts = q_starts.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    kw = dict(softcap=float(softcap or 0.0),
              interpret=_resolve_interpret(interpret))
    T, R = q.shape[0], page_table.shape[0]
    if not takes_decode_kernel(int(block)) or R > T:
        # (R > T: no window of R slots to cut the decode rows' queries
        # from; a step of so few tokens has little to save)
        return _ragged_mixed(q, pages, layer, win, page_table, q_starts,
                             q_lens, kv_lens, sm_scale, block=int(block),
                             **kw)

    decode, first = _decode_rows(q_starts, q_lens)
    r = jnp.arange(R, dtype=jnp.int32)
    ragged_lens = jnp.where(decode, 0, q_lens)
    out = _ragged_mixed(
        q, pages, layer, win, page_table, q_starts, ragged_lens, kv_lens,
        sm_scale, n_rows=jnp.max(jnp.where(ragged_lens > 0, r + 1, 0)),
        **kw)
    # their queries are the R slots from ``first`` on, moved inside the
    # packed axis where that window overhangs it; the row arrays turn with
    # it, so program j of the decode kernel serves row j - turn. A row of
    # length 0 (every row that is no decode row) streams no page there,
    # and its output is not selected.
    at = jnp.clip(first, 0, T - R)
    turn = first - at
    lens = jnp.roll(jnp.where(decode, kv_lens, 0), turn)
    attended = _paged_decode(
        jax.lax.dynamic_slice_in_dim(q, at, R), pages, layer, win,
        jnp.roll(page_table, turn, axis=0), lens, sm_scale, **kw)
    merged = jnp.where((lens > 0)[:, None, None], attended,
                       jax.lax.dynamic_slice_in_dim(out, at, R))
    return jax.lax.dynamic_update_slice_in_dim(out, merged, at, 0)


# the family forwards consult these markers before handing an impl their
# per-layer window/softcap kwargs (see ops/pallas/prefill.py)
ragged_mixed_attention_packed.supports_window_softcap = True
ragged_mixed_attention_packed.pallas_paged_kernel = True


__all__ = ["ragged_mixed_attention_packed", "selected_attention_rows",
           "supports", "takes_decode_kernel"]
