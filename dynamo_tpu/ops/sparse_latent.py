"""Latent attention over a LEARNED SELECTION of the context, and over a
WINDOW kept in a ring: the two cache-reading mechanisms of
``models/dots3.py``: the selection, the ring's arithmetic, and both
attentions in plain XLA (the GATHERED forms: the path without kernels and
the tests' oracle; on the chip every row of either kind is attended by a
latent kernel with a bias that this module makes). The equations, then how
a step's rows are walked.

**The indexer** (a full-attention layer). Every token caches one index key
``k_s`` (``D`` wide) in index pages ``[L, N, ps, D]`` addressed by the
latent pages' own page table. A query token ``t`` with ``J`` index heads
``q_{t,j}`` and head weights ``w_{t,j}`` scores every token it can see::

    I[t, s] = sum_j  w[t, j] * relu(q[t, j] . k[s])          s <= t

and keeps the ``min(topk, t + 1)`` largest, EXACTLY (``topk_mask``: an
approximate selection is another model). One selection a token, shared by
every attention head.

**Attention over the selection.** The latent pages ``[L, N, 2, 1, ps,
dkv]`` are the MLA family's (slot 0 the latent, slot 1 the rotary key,
padded to the latent's width) and the absorbed form's softmax runs over the
selected tokens alone::

    a[t, h, s] = (q_lat[t, h] . c[s] + q_pe[t, h] . k_pe[s]) * scale
    out[t, h]  = sum_{s in S_t} softmax_{s in S_t}(a[t, h, s]) c[s]

in one of two forms. **Gathered** (``sparse_attend``): the selected rows
are fetched by ``(page, offset)`` from the selection as a sorted list
(``select``) - the path without kernels (the CPU, the oracle). **Masked**:
the row's whole context streams through a latent kernel and a bias, 0 on
the selection and ``NEG_INF`` off it, keeps the softmax to the selection -
what every row runs on the chip (``select_split``): the rows of SEVERAL
tokens through ``ops/pallas/mla_ragged.py`` (``bias [T, S]``), where
fetching 2,048 rows of 1 KB for each of 512 queries takes 38 ms a layer
(XLA's gather, 29 ns a row) and streaming 16 k rows once for all of them a
third of that; the rows of ONE token through
``ops/pallas/mla_decode_masked.py`` (``bias [R, S]``), where a row's 2,048
gathered rows cost what streaming 25 k tokens does and the list they are
gathered by costs a sort of the table's width besides (3.7 ms a layer for
24 rows of 25,600). The two are the same sum.

**The window.** A window layer keeps, a sequence, a ring of ``R``
positions in pages of the latent layout ``[L, slots * R / ps, 2, 1, ps,
dkv]`` (slot ``s`` owns pages ``s * R / ps ..``): token ``p`` lives at ``p
mod R`` and is overwritten by token ``p + R``. A query at ``p`` attends
``{s : p - window < s <= p}``; ring entry ``j`` holds token ``last -
((last - j) mod R)`` (``last`` the newest position written), which is
masked by its TRUE position (``ring_seen``: as a bias it is what the
masked kernels take with the ring's pages as a page table of its own,
``ring_table`` - ``mla_window`` for a row of several tokens,
``mla_window_rows`` for the rows of one; ``window_attend`` is the same sum
read out of the ring in XLA). A step writes a row's new
tokens before it attends, so ``R >= window - 1 + (the most tokens a row
brings in one step)`` keeps every key a query of the same step still needs
(``ring_size``). The ring's pages are written by the page-granular write
of every cache (``ops/attention.write_slabs``) through a table that names
the ring's pages twice over, so that a chunk that wraps runs on
(``ring_table``).

**Rows.** A step's tokens lie on one flat axis (``ops/gdn.token_rows``:
packed back to back, or ``[B, S]`` rows ``S`` apart). The indexer and the
gathered forms need a row's keys once for all its queries, so they walk
rows: the rows of ONE token together (``one_token_rows``: a decode step;
the trailing rows of a packed step), the rows of several one after another
under a ``cond`` that skips every other row (``several_token_rows``).
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.ops.gdn import Rows

NEG_INF = -1e30
# elements of the largest temporary one call may make (f32 scores of a
# block): what the block sizes below are cut to
BLOCK_ELEMS = 1 << 25


def ring_size(window: int, max_chunk: int, page_size: int = 1) -> int:
    """Positions of a window ring that lets a row bring ``max_chunk``
    tokens in one step: whole chunks of eight pages (what the latent
    kernels stream at a time), and never less than 128."""
    need = window - 1 + max(1, max_chunk)
    step = max(128, 8 * page_size)
    return -(-need // step) * step


def token_positions(rows: Rows, total_lens: jnp.ndarray) -> jnp.ndarray:
    """``[N]`` the position of every slot's token in its row's context (0
    for a slot without one)."""
    pos = (total_lens - rows.new)[rows.row] + rows.off
    return jnp.where(rows.valid, pos, 0).astype(jnp.int32)


# ------------------------------------------------------------------ tables

def ring_table(slots: jnp.ndarray, ring_pages: int,
               twice: bool = False) -> jnp.ndarray:
    """``[R, ring_pages]`` the pages of each row's ring (``slots [R]``),
    in ring order; ``twice``: named twice over, for a write that wraps."""
    n = ring_pages * (2 if twice else 1)
    return (slots[:, None] * ring_pages
            + jnp.arange(n, dtype=jnp.int32)[None, :] % ring_pages
            ).astype(jnp.int32)


def ring_seen(rows: Rows, pos: jnp.ndarray, total_lens: jnp.ndarray,
              ring: int, window: int) -> jnp.ndarray:
    """``[N, ring]`` bool: the ring entries each slot's query attends -
    those whose TRUE position lies in its window."""
    j = jnp.arange(ring, dtype=jnp.int32)[None, :]
    last = (total_lens - 1)[rows.row][:, None]
    held = last - jnp.mod(last - j, ring)        # the token entry j holds
    p = pos[:, None]
    return ((held >= 0) & (held <= p) & (held > p - window)
            & rows.valid[:, None])


# -------------------------------------------------------------- row walkers

def one_token_rows(fn: Callable, rows: Rows, total_lens: jnp.ndarray,
                   flat: Tuple[jnp.ndarray, ...]):
    """``fn(r, qpos [1], *blocks [1, ...]) -> tree of [1, ...]`` on every
    row's FIRST slot, all rows together (a ``vmap``): ``(tree of [R,
    ...], to [R])``, ``to`` the slot each result belongs at, ``N``
    (nowhere) for a row that does not bring exactly one token (its
    ``qpos`` is -1: it sees nothing)."""
    N = flat[0].shape[0]
    R = rows.start.shape[0]
    one = rows.new == 1
    at = jnp.clip(rows.start, 0, N - 1)
    res = jax.vmap(
        lambda r, p, *xs: fn(r, p[None], *(x[None] for x in xs)))(
        jnp.arange(R), jnp.where(one, total_lens - 1, -1),
        *(x[at] for x in flat))
    res = jax.tree_util.tree_map(lambda v: v[:, 0], res)
    return res, jnp.where(one, rows.start, N)


def lay(out, res, to):
    """``res [R, ...]`` laid over ``out [N, ...]`` at slots ``to`` (``N``:
    dropped), leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda o, v: o.at[to].set(v.astype(o.dtype), mode="drop"), out, res)


def several_token_rows(fn: Callable, rows: Rows, total_lens: jnp.ndarray,
                       flat: Tuple[jnp.ndarray, ...], out, width: int,
                       least: int):
    """``fn(r, qpos [C], *blocks [C, ...]) -> tree of [C, ...]`` on every
    row of more than ``least`` tokens, one after another (the others cost
    a skipped ``cond``), laid over ``out`` (a tree of ``[N, ...]``) at the
    tokens' slots. ``qpos`` is each query's position, -1 where the
    block's slot is not the row's; ``C = width``, the most slots a row
    spans (``S`` of a ``[B, S]`` step, the whole axis of a packed one)."""
    N = flat[0].shape[0]
    R = rows.start.shape[0]
    C = width
    first = total_lens - rows.new                     # a row's first query
    tmap = jax.tree_util.tree_map
    c = jnp.arange(C, dtype=jnp.int32)
    padded = tuple(jnp.pad(x, ((0, C),) + ((0, 0),) * (x.ndim - 1))
                   for x in flat)
    out = tmap(lambda o: jnp.pad(o, ((0, C),) + ((0, 0),) * (o.ndim - 1)),
               out)

    def several(r, out):
        mine = c < rows.new[r]
        s0 = rows.start[r]
        res = fn(r, jnp.where(mine, first[r] + c, -1),
                 *(jax.lax.dynamic_slice_in_dim(x, s0, C) for x in padded))

        def over(o, v):
            old = jax.lax.dynamic_slice_in_dim(o, s0, C)
            keep = mine.reshape((C,) + (1,) * (v.ndim - 1))
            return jax.lax.dynamic_update_slice_in_dim(
                o, jnp.where(keep, v.astype(o.dtype), old), s0, axis=0)
        return tmap(over, out, res)

    out = jax.lax.fori_loop(
        0, R, lambda r, o: jax.lax.cond(rows.new[r] > least, several,
                                        lambda _r, o: o, r, o), out)
    return tmap(lambda o: o[:N], out)


def _by_rows(fn: Callable, rows: Rows, total_lens: jnp.ndarray,
             flat: Tuple[jnp.ndarray, ...], out, width: int,
             packed: bool):
    """``fn`` on every row's tokens, laid over ``out``: the rows of one
    token together where the step can hold them (``width == 1`` or
    ``packed``), the rows of more one after another."""
    least = 0
    if width == 1 or packed:
        out = lay(out, *one_token_rows(fn, rows, total_lens, flat))
        least = 1
    if width == 1:
        return out
    return several_token_rows(fn, rows, total_lens, flat, out, width, least)


# ----------------------------------------------------------------- indexer

def index_scores(q: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray,
                 n_keys=None) -> jnp.ndarray:
    """``I [C, S]`` float32 of ``C`` queries (``q [C, J, D]``, ``w [C,
    J]``) against one row's keys ``[S, D]``, computed a block of keys at a
    time so that the ``[C, J, block]`` products stay small; ``n_keys``
    (traced) stops after the blocks that hold a visible key, the rest
    reading ``NEG_INF``."""
    C, J, _D = q.shape
    S = keys.shape[0]
    wf = w.astype(jnp.float32)

    def block(kb):
        s = jnp.einsum("cjd,sd->cjs", q, kb,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * wf[:, :, None], axis=1)

    blk = 128
    while blk * 2 * C * J <= BLOCK_ELEMS:
        blk *= 2
    if blk >= S:
        return block(keys)
    pad = -S % blk
    keys = jnp.pad(keys, ((0, pad), (0, 0)))
    nb = (S + pad) // blk
    todo = nb if n_keys is None else jnp.minimum(-(-n_keys // blk), nb)

    def body(b, out):
        kb = jax.lax.dynamic_slice_in_dim(keys, b * blk, blk)
        return jax.lax.dynamic_update_slice_in_dim(out, block(kb), b * blk,
                                                   axis=1)
    out = jax.lax.fori_loop(
        0, todo, body, jnp.full((C, S + pad), NEG_INF, jnp.float32))
    return out[:, :S]


def topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """``[C, S]`` bool: the ``k`` largest of each row, exactly, the lower
    index first among equals - ``lax.top_k``'s selection (the tests'
    oracle) as a mask, less the entries at ``NEG_INF``, without a sort. The ``k``-th largest value of
    a row is found a bit at a time on the floats' ordered bit patterns (32
    counts over the row); what is larger is in, and of what is EQUAL the
    lowest indices fill the rest, as ``lax.top_k`` orders them (a running
    count, taken only where a tie straddles the ``k``-th place). On a v5e
    ``[640, 25600]`` takes 1.2 ms where the sort behind ``lax.top_k``
    takes 18.7."""
    seen = scores > NEG_INF / 2
    u = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = jnp.where(u < 0, u ^ 0x7FFFFFFF, u)          # ordered as floats
    ukey = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(
        0x80000000)                                    # ... and unsigned

    def bit(i, th):
        cand = th | jax.lax.shift_left(jnp.uint32(1),
                                       (31 - i).astype(jnp.uint32))
        enough = jnp.sum(ukey >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, th)

    th = jax.lax.fori_loop(0, 32, bit,
                           jnp.zeros(scores.shape[:1], jnp.uint32))
    more = ukey > th[:, None]
    equal = (ukey == th[:, None]) & seen
    room = k - jnp.sum(more, axis=1)
    picked = jax.lax.cond(
        jnp.any(jnp.sum(equal, axis=1) > room),
        lambda: equal & (jnp.cumsum(equal, axis=1) <= room[:, None]),
        lambda: equal)
    return (more | picked) & seen


def _row_keys(pool: jnp.ndarray, layer, page_table: jnp.ndarray):
    """``r -> [S, D]``: a row's index keys, ONE gather out of the pool's
    pages of every layer on one axis (no slice of the layer is made)."""
    flat = pool.reshape((-1,) + pool.shape[2:])
    base = layer * pool.shape[1]
    S = page_table.shape[1] * pool.shape[2]
    return lambda r: flat[base + page_table[r]].reshape(S, -1)


def select(q: jnp.ndarray, w: jnp.ndarray, pool: jnp.ndarray, layer,
           page_table: jnp.ndarray, rows: Rows, total_lens: jnp.ndarray,
           topk: int, *, width: int, packed: bool
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Every token's selection as a list: ``(sel [N, K] int32, live [N, K]
    bool)``, ``K = min(topk, the table's tokens)``. ``sel`` holds
    positions of the token's own row, its ``min(K, pos + 1)`` best-scored
    visible tokens where ``live``; a context no longer than ``topk``
    selects itself whole and scores nothing. ``q [N, J, D]``, ``w [N,
    J]``; ``pool [L, N, ps, D]`` the index pages, this step's keys already
    written. Traced under the stages ``index/score`` and ``index/topk``
    (``engine/stages.py``)."""
    N = q.shape[0]
    S = page_table.shape[1] * pool.shape[2]
    K = min(topk, S)
    if S <= topk:
        pos = token_positions(rows, total_lens)
        sel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (N, S))
        return sel, (sel <= pos[:, None]) & rows.valid[:, None]
    return _by_rows(
        _select_row(pool, layer, page_table, K, False), rows,
        total_lens, (q, w), (jnp.zeros((N, K), jnp.int32),
                             jnp.zeros((N, K), jnp.bool_)), width, packed)


def _select_row(pool, layer, page_table, K, as_bias: bool):
    """The indexer on one row's queries: ``(sel, live)`` lists, or the
    bias ``[C, S]`` of the masked form."""
    keys_of = _row_keys(pool, layer, page_table)
    S = page_table.shape[1] * pool.shape[2]

    def row(r, qpos, qb, wb):
        with stage("index/score"):
            scores = index_scores(qb, wb, keys_of(r), jnp.max(qpos) + 1)
            seen = jnp.arange(S, dtype=jnp.int32)[None, :] <= qpos[:, None]
            scores = jnp.where(seen, scores, NEG_INF)
        with stage("index/topk"):
            mask = topk_mask(scores, K)
            if as_bias:
                return jnp.where(mask, 0.0, NEG_INF)
            # the list: the selection's positions first in a sort of ONE
            # operand (``lax.top_k`` sorts pairs, 5.5 ms a [32, 25600] on
            # the chip in the step program where this takes 3.7)
            at = jnp.where(mask, jnp.arange(S, dtype=jnp.int32)[None, :], S)
            idx = jnp.sort(at, axis=1)[:, :K]
            return jnp.minimum(idx, S - 1), idx < S
    return row


def select_split(q: jnp.ndarray, w: jnp.ndarray, pool: jnp.ndarray, layer,
                 page_table: jnp.ndarray, rows: Rows,
                 total_lens: jnp.ndarray, topk: int, *, width: int,
                 packed: bool):
    """The selection in the form the chip runs (module docstring), a bias
    of 0 on a query's selection and ``NEG_INF`` off it: ``(one, bias)``.
    ``one = (bias [R, S], to [R])`` the rows of ONE token, on the rows'
    axis with the slot each belongs at (``one_token_rows``; None where the
    step holds none: a ``[B, S > 1]`` step); ``bias [N, S]`` float32 the
    rows of several, ``NEG_INF`` everywhere else (None for ``[B, 1]``).
    Both are ``topk_mask`` as it stands: no list, so no sort of the
    table's width."""
    N = q.shape[0]
    S = page_table.shape[1] * pool.shape[2]
    K = min(topk, S)
    one = bias = None
    whole = S <= topk          # every visible key is selected: no scores
    row = _select_row(pool, layer, page_table, K, True)
    s = jnp.arange(S, dtype=jnp.int32)[None, :]
    if width == 1 or packed:
        if whole:
            single = rows.new == 1
            one = (jnp.where((s < total_lens[:, None]) & single[:, None],
                             0.0, NEG_INF),
                   jnp.where(single, rows.start, N))
        else:
            one = one_token_rows(row, rows, total_lens, (q, w))
    if width > 1:
        least = 1 if packed else 0
        if whole:
            pos = token_positions(rows, total_lens)
            seen = ((s <= pos[:, None])
                    & (rows.valid & (rows.new[rows.row] > least))[:, None])
            bias = jnp.where(seen, 0.0, NEG_INF)
        else:
            bias = several_token_rows(
                row, rows, total_lens, (q, w),
                jnp.full((N, S), NEG_INF, jnp.float32), width, least)
    return one, bias


# ------------------------------------------------- attention, the two forms

def _softmax_latent(q_lat, q_pe, c, kr, seen, scale):
    """The absorbed form: ``q_lat [T, nh, dkv]``, ``q_pe [T, nh, dr]``
    against each query's own keys (``c [T, K, dkv]``, ``kr [T, K, dr]``)
    or keys all queries share (``[K, dkv]``, ``[K, dr]``), ``seen [T,
    K]`` -> ``[T, nh, dkv]`` float32 (zeros where a query sees nothing)."""
    f32 = jnp.float32
    keys = "tsk" if c.ndim == 3 else "sk"
    s = (jnp.einsum(f"thk,{keys}->ths", q_lat, c, preferred_element_type=f32)
         + jnp.einsum(f"thk,{keys}->ths", q_pe, kr,
                      preferred_element_type=f32)) * scale
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen[:, None, :], jnp.exp(s - m), 0.0)
    den = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(f"ths,{keys}->thk", p.astype(c.dtype), c,
                     preferred_element_type=f32)
    return out / jnp.maximum(den, 1e-20)


def sparse_attend(q_lat: jnp.ndarray, q_pe: jnp.ndarray, pool: jnp.ndarray,
                  layer, tables: jnp.ndarray, sel: jnp.ndarray,
                  live: jnp.ndarray, scale: float) -> jnp.ndarray:
    """The gathered form: latent attention of ``T`` queries (``q_lat [T,
    nh, dkv]``, ``q_pe [T, nh, dr]``), each over the rows ``sel [T, K]``
    (where ``live``) of its own page-table row ``tables [T, P]`` in the
    latent pages ``[L, N, 2, 1, ps, dkv]``. Returns ``[T, nh, dkv]``
    float32, zero where a query has no live key. A block of queries at a
    time: the gathered rows ``[block, K, dkv]`` are the large temporary.
    (Both slots are fetched as whole rows of ``dkv``: a gather of 64-wide
    slices runs a row at a time on the chip, 3 us each.)"""
    T, nh, dkv = q_lat.shape
    dr = q_pe.shape[-1]
    K = sel.shape[1]
    ps = pool.shape[-2]
    dt = pool.dtype
    tb = T
    while tb > 8 and tb * K * max(dkv, nh) > BLOCK_ELEMS * 4:
        tb = -(-tb // 2)
    tb = -(-tb // 8) * 8 if T >= 8 else T
    nb = -(-T // tb)
    pad = nb * tb - T

    def cut(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, tb) + x.shape[1:])

    # [L * N, 2, ps, dkv]: every layer's pages on one axis (no slice of
    # the layer is made before the gather)
    flat = pool.reshape((-1, 2, ps, dkv))
    base = layer * pool.shape[1]

    def block(xs):
        ql, qp, table, s, lv = xs
        page = base + jnp.take_along_axis(table, s // ps, axis=1)
        off = s % ps
        c = flat[page, 0, off]                              # [tb, K, dkv]
        kr = flat[page, 1, off][..., :dr]
        return _softmax_latent(ql.astype(dt), qp.astype(dt), c, kr, lv,
                               scale)

    out = jax.lax.map(block, (cut(q_lat), cut(q_pe), cut(tables), cut(sel),
                              cut(live)))
    return out.reshape(nb * tb, nh, dkv)[:T]


def window_attend(q_lat: jnp.ndarray, q_pe: jnp.ndarray, ring: jnp.ndarray,
                  layer, rows: Rows, total_lens: jnp.ndarray, window: int,
                  scale: float, *, width: int, packed: bool) -> jnp.ndarray:
    """The gathered form of the window: latent attention of every token
    over the last ``window`` tokens of its row (its own among them), read
    from the row's ring pages ``[L, slots, R / ps, 2, 1, ps, dkv]``, this
    step's tokens already written. Returns ``[N, nh, dkv]`` float32."""
    N, nh, dkv = q_lat.shape
    dr = q_pe.shape[-1]
    _L, n_slots, Rp, _two, _one, ps, _d = ring.shape
    Rg = Rp * ps
    dt = ring.dtype
    flat = ring.reshape((-1, 2, ps, dkv))

    def row(r, qpos, ql, qp):
        first = (layer * n_slots + rows.slot[r]) * Rp
        pages = jax.lax.dynamic_slice_in_dim(flat, first, Rp)
        c = pages[:, 0].reshape(Rg, dkv)
        kr = pages[:, 1].reshape(Rg, dkv)[:, :dr]
        j = jnp.arange(Rg, dtype=jnp.int32)
        last = total_lens[r] - 1
        held = last - jnp.mod(last - j, Rg)       # the token entry j holds
        seen = ((held[None, :] >= 0) & (held[None, :] <= qpos[:, None])
                & (held[None, :] > qpos[:, None] - window))
        return _softmax_latent(ql.astype(dt), qp.astype(dt), c, kr, seen,
                               scale)

    return _by_rows(row, rows, total_lens, (q_lat, q_pe),
                    jnp.zeros((N, nh, dkv), jnp.float32), width, packed)


__all__ = ["ring_size", "ring_table", "ring_seen", "token_positions",
           "index_scores", "topk_mask", "select",
           "select_split", "sparse_attend", "window_attend",
           "one_token_rows", "several_token_rows", "lay"]
