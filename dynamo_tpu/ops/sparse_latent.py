"""Latent attention over a LEARNED SELECTION of the context, and over a
WINDOW kept in a ring: the two cache-reading mechanisms of
``models/dots3.py`` as far as they are LATENT - the ring's arithmetic, and
both attentions in plain XLA (the GATHERED forms: the path without kernels
and the tests' oracle; on the chip every row of either kind is attended by
a latent kernel with a bias). The selection itself - the indexer's scores,
the exact top-k, its two forms and the walk over a step's rows - is
``ops/indexer.py``'s, shared with the grouped-query family that selects
(``models/moe.py``).

**Attention over the selection.** The latent pages ``[L, N, 2, 1, ps,
dkv]`` are the MLA family's (slot 0 the latent, slot 1 the rotary key,
padded to the latent's width) and the absorbed form's softmax runs over the
selected tokens alone::

    a[t, h, s] = (q_lat[t, h] . c[s] + q_pe[t, h] . k_pe[s]) * scale
    out[t, h]  = sum_{s in S_t} softmax_{s in S_t}(a[t, h, s]) c[s]

in one of two forms. **Gathered** (``sparse_attend``): the selected rows
are fetched by ``(page, offset)`` from the selection as a sorted list
(``indexer.select``) - the path without kernels (the CPU, the oracle).
**Masked**: the row's whole context streams through a latent kernel and a
bias, 0 on the selection and ``NEG_INF`` off it, keeps the softmax to the
selection - what every row runs on the chip (``indexer.select_split``):
the rows of SEVERAL tokens through ``ops/pallas/mla_ragged.py`` (``bias
[T, S]``), where fetching 2,048 rows of 1 KB for each of 512 queries takes
38 ms a layer (XLA's gather, 29 ns a row) and streaming 16 k rows once for
all of them a third of that; the rows of ONE token through
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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.gdn import Rows
from dynamo_tpu.ops.indexer import BLOCK_ELEMS, NEG_INF, _by_rows


def ring_size(window: int, max_chunk: int, page_size: int = 1) -> int:
    """Positions of a window ring that lets a row bring ``max_chunk``
    tokens in one step: whole chunks of eight pages (what the latent
    kernels stream at a time), and never less than 128."""
    need = window - 1 + max(1, max_chunk)
    step = max(128, 8 * page_size)
    return -(-need // step) * step


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


__all__ = ["ring_size", "ring_table", "ring_seen", "sparse_attend",
           "window_attend"]
