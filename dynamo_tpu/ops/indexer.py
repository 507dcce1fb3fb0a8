"""The indexer of a LEARNED SELECTION of the context: what a layer that
attends the best ``topk`` of its visible tokens computes before its
attention, whatever the attention is - latent pages (``models/dots3.py``,
``ops/sparse_latent.py``) or grouped-query pages (``models/moe.py`` with
``cfg.index_topk``, ``ops/attention.selected_attention``). The equations,
then how a step's rows are walked.

**The indexer.** Every token caches one index key ``k_s`` (``D`` wide) in
index pages ``[L, N, ps, D]`` addressed by the page table of the layer's
keys and values: one block chain holds both (``write_index_keys``). A query
token ``t`` with ``J`` index heads ``q_{t,j}`` and head weights ``w_{t,j}``
scores every token it can see::

    I[t, s] = sum_j  w[t, j] * relu(q[t, j] . k[s])          s <= t

and keeps the ``min(topk, t + 1)`` largest, EXACTLY (``topk_mask``: an
approximate selection is another model). One selection a token, shared by
every attention head.

**Two forms of a selection.** As a sorted list of positions (``select``:
what a GATHERED attention fetches rows by - the path without kernels, the
CPU, the tests' oracle) or as a bias, 0 on the selection and ``NEG_INF``
off it (``select_split``: what a MASKED kernel adds to its scores while
the row's whole context streams through it - what every row runs on the
chip, the rows of several tokens with ``bias [T, S]``, the rows of one
with ``bias [R, S]``). Both are ``topk_mask``; the bias needs no sort.

**Rows.** A step's tokens lie on one flat axis (``ops/gdn.token_rows``:
packed back to back, or ``[B, S]`` rows ``S`` apart). The indexer needs a
row's keys once for all its queries, so it walks rows: the rows of ONE
token together (``one_token_rows``: a decode step; the trailing rows of a
packed step), the rows of several one after another under a ``cond`` that
skips every other row (``several_token_rows``).
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage
from dynamo_tpu.ops.attention import write_slabs, write_slabs_packed
from dynamo_tpu.ops.gdn import Rows

NEG_INF = -1e30
# elements of the largest temporary one call may make (f32 scores of a
# block): what the block sizes below are cut to
BLOCK_ELEMS = 1 << 25
LAYER_NORM_EPS = 1e-6


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The index key's LayerNorm (weight and bias, eps 1e-6), in float32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS)).astype(x.dtype) \
        * w + b


def write_index_keys(index: jnp.ndarray, layer, k_i: jnp.ndarray,
                     page_table: jnp.ndarray, positions: jnp.ndarray,
                     total_lens: jnp.ndarray, new_lens: jnp.ndarray,
                     starts) -> jnp.ndarray:
    """A step's index keys ``k_i [B, S, D]`` into layer ``layer`` of the
    index pages through the page table of the keys and values, by the
    page-granular write every cache uses (``ops/attention.write_slabs``:
    the pool as one of one array a token); ``starts``:
    ``llama.packed_rows``. The pages are ``[L, N, ps, D]``, or FLAT ``[L,
    N, ps * D]`` where ``D`` is under the chip's 128 lanes (a page is then
    one dense row; ``index_pages``)."""
    B, S, D = k_i.shape
    pool = (index if index.ndim == 3
            else index.reshape(index.shape[:2] + (1, 1) + index.shape[2:]))
    k_i = k_i.reshape(B, S, 1, 1, D)
    if starts is not None:
        pool = write_slabs_packed(pool, layer, k_i[0], page_table, starts,
                                  new_lens, total_lens)
    else:
        pool = write_slabs(pool, layer, k_i, page_table, positions,
                           new_lens)
    return pool.reshape(index.shape)


def index_pages(layers: int, num_pages: int, page_size: int, dim: int,
                dtype) -> jnp.ndarray:
    """Zeroed index pages of ``dim``-wide keys: ``[L, N, ps, D]`` where a
    key fills the chip's 128 lanes, else flat ``[L, N, ps * D]`` - a
    64-wide minor axis is padded to 128 lanes on the chip (twice the
    bytes), and the TPU compiler gives such a pool a layout of its own and
    re-lays all of it around every dispatch (PERF.md section 6, PR 56)."""
    shape = ((layers, num_pages, page_size, dim) if dim % 128 == 0
             else (layers, num_pages, page_size * dim))
    return jnp.zeros(shape, dtype)


def pool_tokens(pool: jnp.ndarray, page_table: jnp.ndarray,
                dim: int) -> int:
    """Tokens a page table addresses in index pages of ``dim``-wide keys,
    of either shape (``index_pages``)."""
    per_page = pool.shape[2] if pool.ndim == 4 else pool.shape[2] // dim
    return page_table.shape[1] * per_page


def token_positions(rows: Rows, total_lens: jnp.ndarray) -> jnp.ndarray:
    """``[N]`` the position of every slot's token in its row's context (0
    for a slot without one)."""
    pos = (total_lens - rows.new)[rows.row] + rows.off
    return jnp.where(rows.valid, pos, 0).astype(jnp.int32)



# -------------------------------------------------------------- row walkers

def one_token_rows(fn: Callable, rows: Rows, total_lens: jnp.ndarray,
                   flat: Tuple[jnp.ndarray, ...]):
    """``fn(r, qpos [1], *blocks [1, ...]) -> tree of [1, ...]`` on every
    row's FIRST slot, all rows together (a ``vmap``): ``(tree of [R,
    ...], to [R])``, ``to`` the slot each result belongs at, ``N``
    (nowhere) for a row that does not bring exactly one token (its
    ``qpos`` is -1: it sees nothing).

    A ``fn`` that works in stages says so with ``fn.phases``, ``((stage,
    first(r, qpos, *blocks)), (stage, next(result)), ...)``: each phase is
    a ``vmap`` of its own under its stage, opened OUTSIDE the ``vmap`` - a
    scope opened inside one reaches a device trace as ``vmap(<name>)``,
    which no reader of stages takes for the stage (the one-token rows'
    indexer read as plain ``layer.attn`` until PR 56)."""
    N = flat[0].shape[0]
    R = rows.start.shape[0]
    one = rows.new == 1
    at = jnp.clip(rows.start, 0, N - 1)
    args = (jnp.arange(R), jnp.where(one, total_lens - 1, -1),
            *(x[at] for x in flat))

    def on_rows(f):
        return jax.vmap(
            lambda r, p, *xs: f(r, p[None], *(x[None] for x in xs)))(*args)

    phases = getattr(fn, "phases", None)
    if phases is None:
        res = on_rows(fn)
    else:
        (name, first), *rest = phases
        with stage(name):
            res = on_rows(first)
        for name, phase in rest:
            with stage(name):
                res = jax.vmap(phase)(res)
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


def _row_keys(pool: jnp.ndarray, layer, page_table: jnp.ndarray, S: int):
    """``r -> [S, D]``: a row's index keys, ONE gather out of the pool's
    pages of every layer on one axis (no slice of the layer is made)."""
    flat = pool.reshape((-1,) + pool.shape[2:])
    base = layer * pool.shape[1]
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
    S = pool_tokens(pool, page_table, q.shape[-1])
    K = min(topk, S)
    if S <= topk:
        pos = token_positions(rows, total_lens)
        sel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (N, S))
        return sel, (sel <= pos[:, None]) & rows.valid[:, None]
    return _by_rows(
        _select_row(pool, layer, page_table, S, K, False), rows,
        total_lens, (q, w), (jnp.zeros((N, K), jnp.int32),
                             jnp.zeros((N, K), jnp.bool_)), width, packed)


def _select_row(pool, layer, page_table, S, K, as_bias: bool):
    """The indexer on one row's queries: ``(sel, live)`` lists, or the
    bias ``[C, S]`` of the masked form. In two phases, each under its
    stage (``row.phases``: what ``one_token_rows`` runs a ``vmap``
    each)."""
    keys_of = _row_keys(pool, layer, page_table, S)

    def score(r, qpos, qb, wb):
        scores = index_scores(qb, wb, keys_of(r), jnp.max(qpos) + 1)
        seen = jnp.arange(S, dtype=jnp.int32)[None, :] <= qpos[:, None]
        return jnp.where(seen, scores, NEG_INF)

    def pick(scores):
        mask = topk_mask(scores, K)
        if as_bias:
            return jnp.where(mask, 0.0, NEG_INF)
        # the list: the selection's positions first in a sort of ONE
        # operand (``lax.top_k`` sorts pairs, 5.5 ms a [32, 25600] on
        # the chip in the step program where this takes 3.7)
        at = jnp.where(mask, jnp.arange(S, dtype=jnp.int32)[None, :], S)
        idx = jnp.sort(at, axis=1)[:, :K]
        return jnp.minimum(idx, S - 1), idx < S

    def row(r, qpos, qb, wb):
        with stage("index/score"):
            scores = score(r, qpos, qb, wb)
        with stage("index/topk"):
            return pick(scores)

    row.phases = (("index/score", score), ("index/topk", pick))
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
    S = pool_tokens(pool, page_table, q.shape[-1])
    K = min(topk, S)
    one = bias = None
    whole = S <= topk          # every visible key is selected: no scores
    row = _select_row(pool, layer, page_table, S, K, True)
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


__all__ = ["NEG_INF", "BLOCK_ELEMS", "layer_norm", "write_index_keys",
           "index_pages", "pool_tokens",
           "token_positions", "index_scores", "topk_mask", "select",
           "select_split", "one_token_rows", "several_token_rows", "lay"]
