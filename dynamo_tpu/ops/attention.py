"""Paged attention over a block-paged KV cache — unified prefill/decode path.

Capability parity with the reference's engine-internal paged attention (the
reference delegates this to vLLM/SGLang CUDA kernels; here it is native).
Design is TPU-first:

- Cache layout is PAGE-MAJOR: per layer ``[N, 2, Hkv, page_size, Dh]``
  (page, k/v, kv-head), stacked to ``[L, N, 2, Hkv, page_size, Dh]`` for
  the ``lax.scan`` forward. One page is one contiguous slab holding BOTH
  K and V for every kv head — so the Pallas decode kernel
  (``ops/pallas/decode.py``) fetches a page's entire contribution with ONE
  DMA descriptor, and device-to-device block transfers (disagg prefill →
  decode) move whole pages with unit-stride copies. (A head-major layout
  fragments every page into per-head 4 KB strips — measured ~10× worse on
  both the DMA and the XLA-gather paths.)
- New K/V are written a PAGE at a time (``_write_pages``): the pages a
  row's new tokens fall in are gathered, the tokens laid over them, and
  the pages scattered back. A whole page is the window that is contiguous
  in this layout, so the scatter asks for the row-major pool the Pallas
  kernels pin (``memory_space=pl.ANY``) and updates the donated pool in
  place. A window per token (``[2, Hkv, Dh]``: the slot axis lies between
  ``Hkv`` and ``Dh``) makes XLA's TPU scatter ask for a token-major pool,
  and layout assignment copies the whole pool there and back every layer
  (8.3 ms each for 2.7 GB on a v5e); a window per ``[Dh]`` row is in place
  but costs ~70 ns an index, 18 ms a layer for a [32, 512] step (PERF.md
  section 6, PR 25).
- Page 0 is a reserved garbage page: a slab that takes no real token
  (a padded row, the pages past a short row's end) addresses it, which
  makes every scatter shape-static and mask-free.
- One code path serves prefill (S = chunk length) and decode (S = 1): new K/V
  is scattered into the cache first, then the full context is gathered from the
  page table and attended with a causal mask on absolute positions. Chunked
  prefill with a prefix-cache hit falls out for free — queries attend to
  whatever the page table already holds.

The XLA gather path materializes ``[B, T, Hkv, Dh]`` per layer; the Pallas
decode kernel fuses that gather away on TPU. This XLA path is the portable
reference implementation and the CPU-test path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# new tokens a row up to which ``_write_pages`` lays them over their slots
# by selects instead of a per-row shift
SELECT_SHIFT_MAX = 8


def _write_pages(pool: jnp.ndarray, layer_idx, k_new: jnp.ndarray,
                 v_new: jnp.ndarray, page_table: jnp.ndarray,
                 positions: jnp.ndarray, new_lens: jnp.ndarray) -> jnp.ndarray:
    """The cache write behind ``write_kv`` (``layer_idx`` a scalar, pool
    ``[L, N, 2, Hkv, ps, Dh]``): ``write_slabs`` of keys and values."""
    return write_slabs(pool, layer_idx, jnp.stack([k_new, v_new], axis=2),
                       page_table, positions, new_lens)


def write_slabs(pool: jnp.ndarray, layer_idx, new: jnp.ndarray,
                page_table: jnp.ndarray, positions: jnp.ndarray,
                new_lens: jnp.ndarray) -> jnp.ndarray:
    """``new [B, S, A, Hkv, Dh]`` into a pool ``[L, N, A, Hkv, ps, Dh]``
    (``A`` = 2, keys and values, for the attention caches; 1 for a pool of
    one array a token), a page at a time: gather the pages each
    row's new tokens fall in, lay the tokens over them, scatter the pages
    back.

    A row's real tokens are CONSECUTIVE positions from ``positions[b, 0]``
    (what a prefill chunk, a decode step and a verify window are; the ring
    path reads the prefix length off ``positions[:, 0]`` too), so they fall
    in at most ``J`` pages in table order. Why pages and no smaller
    window: the module docstring.
    """
    A, Hkv, page_size, Dh = _page_geometry(pool, new)
    B, S = positions.shape
    J = (S + page_size - 2) // page_size + 1
    start = positions[:, 0]
    first, off = start // page_size, start % page_size    # [B]
    # pages that take a real token; every other slab is the garbage page 0
    # written back to itself (pads land nowhere)
    n_live = jnp.where(new_lens > 0,
                       (off + new_lens + page_size - 1) // page_size, 0)
    j = jnp.arange(J, dtype=start.dtype)[None, :]
    logical = jnp.minimum(first[:, None] + j, page_table.shape[1] - 1)
    phys = jnp.where(j < n_live[:, None],
                     jnp.take_along_axis(page_table, logical, axis=1), 0)
    at = pool.at[layer_idx, phys]
    # the new tokens shifted to their slots, token-major, then page-major
    new = new.astype(pool.dtype)
    if S == 1:
        # one token: whichever slot the mask below picks holds it (XLA runs
        # the shift as a loop over rows, 57 us a decode layer on a v5e)
        laid = jnp.broadcast_to(new, (B, page_size, A, Hkv, Dh))
    elif S <= SELECT_SHIFT_MAX:
        # a few tokens (a pass over a block of diffusion generation, a
        # verify window): each laid over its slot by a select. The shift
        # below runs as a loop over rows, a dozen small operations a row a
        # layer: at [32, 4] they were 74,000 of a million device
        # operations in 6 s (PERF.md section 6, PR 37)
        tok_at = (jnp.arange(J * page_size, dtype=start.dtype)[None, :]
                  - off[:, None])                  # the token at a slot
        laid = jnp.zeros((B, J * page_size, A, Hkv, Dh), pool.dtype)
        for s in range(S):
            laid = jnp.where((tok_at == s)[:, :, None, None, None],
                             new[:, s][:, None], laid)
    else:
        laid = jax.vmap(
            lambda buf, row, o: jax.lax.dynamic_update_slice_in_dim(
                buf, row, o, axis=0))(
            jnp.zeros((B, J * page_size, A, Hkv, Dh), pool.dtype), new, off)
    t = jnp.arange(J * page_size, dtype=start.dtype)[None, :]
    real = (t >= off[:, None]) & (t < (off + new_lens)[:, None])
    return _commit_pages(at, laid.reshape(B, J, page_size, A, Hkv, Dh),
                         real.reshape(B, J, page_size))


def _page_geometry(pool: jnp.ndarray, new: jnp.ndarray) -> tuple:
    """``(A, Hkv, page_size, Dh)`` of a pool ``[L, N, A, Hkv, ps, Dh]``, or
    of a FLAT pool ``[L, N, ps * A * Hkv * Dh]`` whose page is one row (a
    pool of narrow arrays: a minor axis under the chip's 128 lanes is
    padded to them, and the compiler re-lays such a pool around every
    dispatch), read off the new tokens ``[..., A, Hkv, Dh]``."""
    if pool.ndim != 3:
        return pool.shape[-4:]
    A, Hkv, Dh = new.shape[-3:]
    return A, Hkv, pool.shape[-1] // (A * Hkv * Dh), Dh


def _commit_pages(at, laid: jnp.ndarray, real: jnp.ndarray) -> jnp.ndarray:
    """The page gather / select / scatter both forms of the write end in.
    ``at`` indexes the pool by the physical page of every slab ``[..., ]``,
    ``laid [..., ps, 2, Hkv, Dh]`` holds the new tokens at their slots,
    token-major, and ``real [..., ps]`` says which slots take one; every
    other slot keeps what the page held. A flat pool's pages (``[...,
    ps * A * Hkv * Dh]``, ``_page_geometry``) are seen as slabs for the
    select and scattered back as rows."""
    old = at.get(mode="clip")                        # [..., 2, Hkv, ps, Dh]
    laid = jnp.moveaxis(laid, -4, -2)
    flat = old.shape
    new = jnp.where(real[..., None, None, :, None], laid,
                    old.reshape(laid.shape))
    return at.set(new.reshape(flat), mode="drop")


def write_kv_packed(pool: jnp.ndarray, layer_idx, k_new: jnp.ndarray,
                    v_new: jnp.ndarray, page_table: jnp.ndarray,
                    starts: jnp.ndarray, new_lens: jnp.ndarray,
                    total_lens: jnp.ndarray) -> jnp.ndarray:
    """``_write_pages`` for a TOKEN-PACKED step into the stacked pool
    (``write_slabs_packed`` of keys and values)."""
    return write_slabs_packed(pool, layer_idx,
                              jnp.stack([k_new, v_new], axis=1), page_table,
                              starts, new_lens, total_lens)


def write_slabs_packed(pool: jnp.ndarray, layer_idx, new: jnp.ndarray,
                       page_table: jnp.ndarray, starts: jnp.ndarray,
                       new_lens: jnp.ndarray,
                       total_lens: jnp.ndarray) -> jnp.ndarray:
    """``write_slabs`` for a TOKEN-PACKED step: ``new [T, A, Hkv, Dh]``
    holds every row's new tokens back to back, row ``r``
    at slots ``starts[r] .. starts[r] + new_lens[r]`` and at positions
    ``total_lens[r] - new_lens[r] ..``. Same pages, same select, same
    scatter; what differs is how the slabs are found. The rows' live pages
    are numbered back to back too (at most ``T // ps + 2 R`` of them: a row
    of ``n`` tokens at any offset spans at most ``(n - 1) // ps + 2``), and
    slab ``m`` takes the ``ps`` packed tokens from ``starts[r] + j * ps -
    off[r]`` on: one slice a page, no per-row padded buffer."""
    page_size = _page_geometry(pool, new)[2]
    T = new.shape[0]
    R = page_table.shape[0]
    M = T // page_size + 2 * R
    begin = total_lens - new_lens                   # first new position [R]
    first, off = begin // page_size, begin % page_size
    n_live = jnp.where(new_lens > 0,
                       (off + new_lens + page_size - 1) // page_size, 0)
    ends = jnp.cumsum(n_live)
    m = jnp.arange(M, dtype=begin.dtype)
    # slab m -> (row, page of the row); slabs past the last live page are
    # the garbage page 0 written back to itself
    row = jnp.minimum(jnp.sum(m[:, None] >= ends[None, :], axis=1), R - 1)
    j = m - (ends - n_live)[row]
    logical = jnp.minimum(first[row] + j, page_table.shape[1] - 1)
    phys = jnp.where(m < ends[-1], page_table[row, logical], 0)
    at = pool.at[layer_idx, phys]
    # the slab's tokens: slot s of slab m is token j*ps + s - off of the row
    tok0 = j * page_size - off[row]                               # [M]
    new = new.astype(pool.dtype)
    new = jnp.pad(new, ((page_size, page_size), (0, 0), (0, 0), (0, 0)))
    laid = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
        new, s, page_size, axis=0))(page_size + starts[row] + tok0)
    tok = tok0[:, None] + jnp.arange(page_size, dtype=begin.dtype)[None, :]
    real = ((m < ends[-1])[:, None] & (tok >= 0)
            & (tok < new_lens[row][:, None]))
    return _commit_pages(at, laid, real)


def write_kv(pages: jnp.ndarray, layer_idx, k_new: jnp.ndarray,
             v_new: jnp.ndarray, page_table: jnp.ndarray,
             positions: jnp.ndarray, new_lens: jnp.ndarray) -> jnp.ndarray:
    """Scatter new K/V into layer ``layer_idx`` of the stacked cache.

    pages:      [L, N, 2, Hkv, page_size, Dh]
    k_new/v_new:[B, S, Hkv, Dh]
    page_table: [B, P] logical-page -> physical-page map (int32)
    positions:  [B, S] absolute token positions of the new tokens
    new_lens:   [B] number of real (non-pad) new tokens per sequence
    """
    return _write_pages(pages, layer_idx, k_new, v_new, page_table, positions,
                       new_lens)


def _softcap(scores: jnp.ndarray, cap) -> jnp.ndarray:
    """gemma-style logit soft-capping: cap * tanh(scores / cap). Callers
    pass ``cap=None`` when disabled (never a zero scalar), so the enabled
    path is a bare tanh — no masking over the score tensor."""
    if cap is None:
        return scores
    return jnp.tanh(scores / cap) * cap


def horizon(positions, block: int = 1):
    """The last key position each query sees. ``block`` 1 is the causal
    mask (a query sees itself and what lies before: the array comes back
    as it is, so a causal program is the program it was); ``block`` B > 1
    is the visibility of generation by diffusion over blocks: positions
    are cut into blocks of B from 0, and query ``i`` sees key ``j`` iff
    ``j // B <= i // B`` - every key of its own and of earlier blocks."""
    if block <= 1:
        return positions
    return positions // block * block + (block - 1)


def _attend(qg: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            positions: jnp.ndarray, total_lens: jnp.ndarray,
            sm_scale: float, window=None, softcap=None,
            block: int = 1) -> jnp.ndarray:
    """qg [B,S,Hkv,G,Dh]; k/v [B,Hkv,T,Dh] -> [B,S,Hkv*G,Dh].

    ``window`` (traced int32, 0 = unlimited) restricts each query to the
    last ``window`` kv positions — gemma-2 alternating sliding-window
    layers; ``softcap`` applies attention-logit soft-capping."""
    B, S, Hkv, G, Dh = qg.shape
    T = k.shape[2]
    scores = jnp.einsum("bsngd,bntd->bnsgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale  # [B,Hkv,S,G,T]
    scores = _softcap(scores, softcap)
    t_pos = jnp.arange(T)[None, None, :]                   # [1, 1, T]
    causal = t_pos <= horizon(positions, block)[:, :, None]  # [B, S, T]
    valid = t_pos < total_lens[:, None, None]              # [B, 1, T]
    if window is not None:
        in_win = (window <= 0) | (t_pos > positions[:, :, None] - window)
        causal = causal & in_win
    mask = (causal & valid)[:, None, :, None, :]           # [B, 1, S, 1, T]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnsgt,bntd->bsngd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, Hkv * G, Dh)


# pages per streamed chunk on the blockwise path; 8 pages x 16-token pages
# = 128 kv positions per chunk — one chunk's matmul fills the MXU's lanes
PAGES_PER_CHUNK = 8


def _attend_blockwise(qg: jnp.ndarray, gather_chunk, num_table_pages: int,
                      page_size: int, chunk_pages: int,
                      positions: jnp.ndarray, total_lens: jnp.ndarray,
                      sm_scale: float, window=None, softcap=None,
                      return_partials: bool = False,
                      block: int = 1) -> jnp.ndarray:
    """Flash-style chunked attention over the paged context.

    The full-gather path above materializes ``[B,Hkv,S,G,T]`` scores — at
    serving shapes (B=8, S=512, T=704, 3B model) that is ~250 MB of f32 per
    layer, which is what made round 2's real-config prefill bench blow its
    budget. Here the kv context is consumed in chunks of ``chunk_pages``
    pages with the same online-softmax (running max + rescaled accumulators)
    the ring/Pallas paths use, so peak intermediate size is
    ``[B,Hkv,S,G,chunk_span]`` regardless of context length, and the
    ``fori_loop`` bound is dynamic — chunks beyond the longest live context
    are never touched, even though the page table is padded to
    ``max_context``.

    qg: [B, S, Hkv, G, Dh] queries (grouped);
    gather_chunk(c) -> (k, v) each [B, Hkv, span, Dh] for pages
    ``[c*chunk_pages, (c+1)*chunk_pages)`` of the (padded) page table.
    Matmuls run in the cache dtype with f32 accumulation (MXU-friendly;
    same numerics as the Pallas decode kernel).
    """
    B, S, Hkv, G, Dh = qg.shape
    span = chunk_pages * page_size
    n_static = -(-num_table_pages // chunk_pages)
    max_t = jnp.max(total_lens)
    n_chunks = jnp.minimum((max_t + span - 1) // span, n_static)

    def body(c, carry):
        num, den, mx = carry
        k, v = gather_chunk(c)
        s = jnp.einsum("bsngd,bntd->bnsgt", qg, k,
                       preferred_element_type=jnp.float32) * sm_scale
        s = _softcap(s, softcap)
        t_pos = c * span + jnp.arange(span)
        causal = (t_pos[None, None, :]
                  <= horizon(positions, block)[:, :, None])      # [B,S,span]
        if window is not None:
            in_win = ((window <= 0)
                      | (t_pos[None, None, :] > positions[:, :, None]
                         - window))
            causal = causal & in_win
        valid = t_pos[None, None, :] < total_lens[:, None, None]
        mask = (causal & valid)[:, None, :, None, :]
        s = jnp.where(mask, s, NEG_INF)
        mx_new = jnp.maximum(mx, jnp.max(s, axis=-1))            # [B,Hkv,S,G]
        p = jnp.exp(s - mx_new[..., None])
        # rows with no visible kv yet (mx_new still -inf): exp(-inf - -inf)
        # is exp(0)=1 in floats — zero those rows explicitly
        p = jnp.where((mx_new > NEG_INF / 2)[..., None], p, 0.0)
        scale = jnp.where(mx > NEG_INF / 2, jnp.exp(mx - mx_new), 0.0)
        pv = jnp.einsum("bnsgt,bntd->bnsgd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        num = num * scale[..., None] + pv
        den = den * scale + jnp.sum(p, axis=-1)
        return num, den, mx_new

    num0 = jnp.zeros((B, Hkv, S, G, Dh), jnp.float32)
    den0 = jnp.zeros((B, Hkv, S, G), jnp.float32)
    mx0 = jnp.full((B, Hkv, S, G), NEG_INF, jnp.float32)
    num, den, mx = jax.lax.fori_loop(0, n_chunks, body, (num0, den0, mx0))
    if return_partials:
        # [B,Hq,S,...] layout (grouped heads folded), matching the ring
        # path's partials so the two contexts merge elementwise
        Hq = Hkv * G
        num_p = num.transpose(0, 1, 3, 2, 4).reshape(B, Hq, S, Dh)
        den_p = den.transpose(0, 1, 3, 2).reshape(B, Hq, S)
        mx_p = mx.transpose(0, 1, 3, 2).reshape(B, Hq, S)
        return num_p, den_p, mx_p
    out = num / jnp.maximum(den, 1e-20)[..., None]               # [B,Hkv,S,G,Dh]
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, Hkv * G, Dh)


def merge_softmax_partials(a, b):
    """Combine two un-normalized online-softmax states over DISJOINT kv
    contexts (e.g. ring self-attention over new tokens + blockwise
    attention over cached pages). Each is (num [..., D], den [...],
    mx [...]); dead states (mx == -inf: that context had no visible kv)
    contribute zero. Returns the same triple."""
    num_a, den_a, mx_a = a
    num_b, den_b, mx_b = b
    mx = jnp.maximum(mx_a, mx_b)
    sa = jnp.where(mx_a > NEG_INF / 2, jnp.exp(mx_a - mx), 0.0)
    sb = jnp.where(mx_b > NEG_INF / 2, jnp.exp(mx_b - mx), 0.0)
    num = num_a * sa[..., None] + num_b * sb[..., None]
    den = den_a * sa + den_b * sb
    return num, den, mx


def normalize_softmax_partials(num, den):
    """(num, den) -> attention output; all-dead rows produce zeros."""
    return num / jnp.maximum(den, 1e-20)[..., None]


def _pad_table(page_table: jnp.ndarray, chunk_pages: int) -> jnp.ndarray:
    """Pad the page-table width to a multiple of ``chunk_pages`` with page 0
    (the reserved garbage page) so chunk slices are always full-width."""
    P = page_table.shape[1]
    rem = P % chunk_pages
    if rem:
        page_table = jnp.pad(page_table, ((0, 0), (0, chunk_pages - rem)))
    return page_table


def _gathered_to_bhtd(g: jnp.ndarray) -> jnp.ndarray:
    """[B, P, Hkv, ps, Dh] gathered pages -> [B, Hkv, T, Dh]."""
    B, P, Hkv, ps, Dh = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * ps, Dh)


def packed_token_rows(T: int, page_table: jnp.ndarray,
                      q_starts: jnp.ndarray, q_lens: jnp.ndarray,
                      kv_lens: jnp.ndarray):
    """A token-packed step's rows, told per token: ``(valid [T], pos [T],
    table [T, P], total [T])``. Token ``t`` belongs to the first row whose
    end exceeds ``t`` and sits at ``kv_lens - q_lens + (t - q_starts)`` of
    it; a pad slot (of no row) gets the garbage page with a 1-token
    context: finite work, its result masked by ``valid``."""
    t_idx = jnp.arange(T)
    ends = q_starts + q_lens
    row = jnp.sum(t_idx[:, None] >= ends[None, :], axis=1)
    row = jnp.minimum(row, page_table.shape[0] - 1)
    valid = (t_idx >= q_starts[row]) & (t_idx < ends[row])
    pos = kv_lens[row] - q_lens[row] + (t_idx - q_starts[row])
    return (valid, jnp.where(valid, pos, 0),
            jnp.where(valid[:, None], page_table[row], 0),
            jnp.where(valid, kv_lens[row], 1))


def ragged_paged_attention(q: jnp.ndarray, pages: jnp.ndarray, layer_idx,
                           page_table: jnp.ndarray, q_starts: jnp.ndarray,
                           q_lens: jnp.ndarray, kv_lens: jnp.ndarray,
                           sm_scale: float, window=None,
                           softcap=None, block: int = 1) -> jnp.ndarray:
    """Ragged paged attention over a FLATTENED mixed batch — the reference
    lowering of the kernel shape continuous batching needs (Ragged Paged
    Attention, PAPERS.md): one dispatch where each row contributes an
    arbitrary number of query tokens (a prefill chunk, or a single decode
    token) against its own paged KV context.

    q:          [T, Hq, Dh] — every row's query tokens packed back to back
                (row i occupies ``q_starts[i] .. q_starts[i]+q_lens[i]``);
                slots past the last row's end are pad.
    pages:      [L, N, 2, Hkv, page_size, Dh] stacked cache
    page_table: [B, P] per-ROW page table
    q_starts:   [B] row offsets into the flat axis (ascending, packed)
    q_lens:     [B] real query tokens per row (a decode row is 1)
    kv_lens:    [B] total context per row INCLUDING its new tokens — row
                i's token j sits at absolute position
                ``kv_lens[i] - q_lens[i] + j``
    returns     [T, Hq, Dh]; pad slots are zeroed.

    Built on the same blockwise online-softmax machinery as the chunked
    paths (``_attend_blockwise``): each flat token attends to its row's
    pages as a [T, 1]-query batch, so peak intermediates stay bounded by
    the chunk span regardless of context length. The Pallas kernel
    (``ops/pallas/ragged.py``) fuses the per-token gather away on TPU;
    this is the portable reference and the CPU-test oracle.
    """
    T, Hq, Dh = q.shape
    P = page_table.shape[1]
    Hkv = pages.shape[3]
    ps = pages.shape[4]
    valid, pos, tok_table, tok_total = packed_token_rows(
        T, page_table, q_starts, q_lens, kv_lens)
    qg = q.reshape(T, 1, Hkv, Hq // Hkv, Dh)
    chunk_pages = min(PAGES_PER_CHUNK, P)
    table = _pad_table(tok_table, chunk_pages)

    def gather_chunk(c):
        tbl = jax.lax.dynamic_slice(
            table, (0, c * chunk_pages), (T, chunk_pages))
        g = pages[layer_idx, tbl]          # [T, C, 2, Hkv, ps, Dh]
        return _gathered_to_bhtd(g[:, :, 0]), _gathered_to_bhtd(g[:, :, 1])

    out = _attend_blockwise(qg, gather_chunk, P, ps, chunk_pages,
                            pos[:, None], tok_total, sm_scale,
                            window=window, softcap=softcap, block=block)
    out = out.reshape(T, Hq, Dh)
    return jnp.where(valid[:, None, None], out, 0.0).astype(q.dtype)


def selected_attention(q: jnp.ndarray, pages: jnp.ndarray, layer_idx,
                       tables: jnp.ndarray, sel: jnp.ndarray,
                       live: jnp.ndarray, sm_scale: float) -> jnp.ndarray:
    """Grouped-query attention of ``T`` queries, each over a SELECTION of
    its own context, in the GATHERED form: the selected tokens' keys and
    values are fetched by ``(page, offset)`` and the softmax runs over
    them alone - the path without kernels (the CPU) and the oracle of the
    masked kernels (``ops/pallas/ragged.selected_attention_rows``), which
    stream a row's whole context under a bias instead: a token is ``2
    Hkv`` rows of ``Dh`` in the page layout, and a fetch of rows costs
    the TPU more than the stream it saves (PERF.md section 6, PR 56).

    q:      [T, Hq, Dh]
    pages:  [L, N, 2, Hkv, page_size, Dh]
    tables: [T, P] each query's own page-table row
    sel:    [T, K] int32 positions in the query's context; ``live [T, K]``
            which of them count (``ops/indexer.select``)
    returns [T, Hq, Dh] float32, zero where a query has no live key.

    A block of queries at a time: the gathered rows ``[block, K, 2, Hkv,
    Dh]`` are the large temporary."""
    T, Hq, Dh = q.shape
    L, N, _two, Hkv, ps, _ = pages.shape
    K = sel.shape[1]
    G = Hq // Hkv
    tb = T
    while tb > 8 and tb * K * 2 * Hkv * Dh > (1 << 27):
        tb = -(-tb // 2)
    nb = -(-T // tb)
    pad = nb * tb - T

    def cut(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, tb) + x.shape[1:])

    # every layer's pages on one axis: no slice of the layer is made
    flat = pages.reshape((L * N, 2, Hkv, ps, Dh))
    base = layer_idx * N

    def block(xs):
        qb, table, s, lv = xs
        page = base + jnp.take_along_axis(table, s // ps, axis=1)
        kv = flat[page, :, :, s % ps]                  # [tb, K, 2, Hkv, Dh]
        qg = qb.reshape(tb, Hkv, G, Dh).astype(pages.dtype)
        a = jnp.einsum("tngd,tknd->tngk", qg, kv[:, :, 0],
                       preferred_element_type=jnp.float32) * sm_scale
        a = jnp.where(lv[:, None, None, :], a, NEG_INF)
        m = jnp.max(a, axis=-1, keepdims=True)
        p = jnp.where(lv[:, None, None, :], jnp.exp(a - m), 0.0)
        den = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("tngk,tknd->tngd", p.astype(pages.dtype),
                         kv[:, :, 1], preferred_element_type=jnp.float32)
        return (out / jnp.maximum(den, 1e-20)).reshape(tb, Hq, Dh)

    out = jax.lax.map(block, (cut(q), cut(tables), cut(sel), cut(live)))
    return out.reshape(nb * tb, Hq, Dh)[:T]


def paged_attention(q: jnp.ndarray, pages: jnp.ndarray, layer_idx,
                    page_table: jnp.ndarray, positions: jnp.ndarray,
                    total_lens: jnp.ndarray, sm_scale: float,
                    window=None, softcap=None,
                    block: int = 1) -> jnp.ndarray:
    """Attend queries to the stacked paged context (scan path).
    ``block``: the visibility block (``horizon``; 1 = causal).

    q:          [B, S, Hq, Dh]
    pages:      [L, N, 2, Hkv, page_size, Dh]
    page_table: [B, P]
    positions:  [B, S] absolute positions of the queries
    total_lens: [B] total context length (cached + new)
    returns     [B, S, Hq, Dh]
    """
    B, S, Hq, Dh = q.shape
    Hkv = pages.shape[3]
    ps = pages.shape[4]
    P = page_table.shape[1]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, Dh)
    if S > 1 and P > PAGES_PER_CHUNK:
        table = _pad_table(page_table, PAGES_PER_CHUNK)

        def gather_chunk(c):
            tbl = jax.lax.dynamic_slice(
                table, (0, c * PAGES_PER_CHUNK), (B, PAGES_PER_CHUNK))
            # traced layer_idx rides the advanced index (see below)
            g = pages[layer_idx, tbl]      # [B, C, 2, Hkv, ps, Dh]
            return _gathered_to_bhtd(g[:, :, 0]), _gathered_to_bhtd(g[:, :, 1])

        return _attend_blockwise(qg, gather_chunk, P, ps, PAGES_PER_CHUNK,
                                 positions, total_lens, sm_scale,
                                 window=window, softcap=softcap,
                                 block=block).astype(q.dtype)

    # Single fused gather: the traced layer_idx participates as an advanced
    # index so XLA reads only the gathered pages (slicing pages[layer_idx]
    # first would dynamic-slice-copy the whole layer's cache).
    gathered = pages[layer_idx, page_table]  # [B, P, 2, Hkv, ps, Dh]
    k = _gathered_to_bhtd(gathered[:, :, 0])
    v = _gathered_to_bhtd(gathered[:, :, 1])
    return _attend(qg, k, v, positions, total_lens, sm_scale,
                   window=window, softcap=softcap,
                   block=block).astype(q.dtype)


__all__ = ["write_kv", "write_kv_packed", "paged_attention", "horizon",
           "ragged_paged_attention", "selected_attention",
           "packed_token_rows",
           "merge_softmax_partials", "normalize_softmax_partials",
           "NEG_INF"]
