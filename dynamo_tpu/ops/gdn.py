"""The gated delta rule and its causal convolution, over either step form.

A Gated DeltaNet layer (``models/qwen3_next.py``) keeps, for each request
and value head, one state ``S [Dk, Dv]`` in float32, and for each token
``t`` with key ``k``, query ``q`` (both L2-normalised, ``q`` scaled by
``Dk ** -0.5``), value ``v``, log-decay ``g <= 0`` and write strength
``beta`` in (0, 1), or in (0, 2) where the model allows the transition
``I - beta k k^T`` a negative eigenvalue::

    S <- exp(g_t) S
    u  = beta_t (v_t - S^T k_t)
    S <- S + k_t u^T
    o_t = S^T q_t

A step carries rows of new tokens on one flat axis of ``N`` slots - row
``r`` owns slots ``row_start[r] .. row_start[r] + new_lens[r]`` (a padded
``[B, S]`` step flattened: ``row_start = r * S``; a token-packed step: the
exclusive cumulative sum of ``new_lens``) - and every row names the slot of
the state pool that holds its state (slot 0 belongs to no request: rows
that carry no token read and write it, and it stays what it was). The rule
runs in two forms, chosen by what a row carries:

- a row of ONE token (a decode row, in a fused block or beside prompt
  chunks): ``gdn_step``, the five lines above once;
- a row of several tokens (a prompt chunk): ``gdn_chunk``, chunk-parallel.
  The row's tokens are cut into chunks of ``CHUNK`` from its first token;
  inside a chunk, with ``G`` the running sum of ``g``, ``A[t, s] = beta_t
  exp(G_t - G_s) k_t.k_s`` for ``s < t`` and ``T = (I + A)^-1``::

      U  = T (beta V - (beta exp(G) K) S0)
      O  = (exp(G) Q) S0 + (exp(G_t - G_s) q_t.k_s)[s <= t] U
      S1 = exp(G_C) S0 + (exp(G_C - G) K)^T U

  and ``S1`` is the next chunk's ``S0``. Slots of a chunk past the row's
  end carry ``g = 0``, ``beta = 0`` and zero vectors: they change nothing.

A row whose first new token sits at position 0 starts from zeros whatever
its slot holds (``fresh``), so the host never clears a slot. Both forms
exist twice: in plain ``jax.numpy`` here (the CPU's, and what the kernels
are tested against) and as the Mosaic kernels ``gdn_chunk`` / ``gdn_step``
(``ops/pallas/gdn.py``).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.stages import stage

CHUNK = 64


class Rows(NamedTuple):
    """A step's rows told per slot of the flat axis (``token_rows``)."""
    row: jnp.ndarray        # [N] the row a slot belongs to
    off: jnp.ndarray        # [N] its offset in the row's new tokens
    valid: jnp.ndarray      # [N] whether it holds a token
    start: jnp.ndarray      # [R] each row's first slot
    new: jnp.ndarray        # [R] each row's new tokens
    fresh: jnp.ndarray      # [R] the row starts at position 0
    slot: jnp.ndarray       # [R] the row's slot of the state pool


def token_rows(n_slots: int, row_start: jnp.ndarray, new_lens: jnp.ndarray,
               total_lens: jnp.ndarray, slots: jnp.ndarray) -> Rows:
    """``Rows`` of a step whose rows follow one another on the flat axis
    (with or without gaps). A row without a new token (a pad row, a dead
    row of a fused block) is sent to slot 0."""
    t = jnp.arange(n_slots, dtype=jnp.int32)
    ends = row_start + new_lens
    row = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1),
                      row_start.shape[0] - 1).astype(jnp.int32)
    off = t - row_start[row]
    valid = (off >= 0) & (t < ends[row])
    return Rows(row, off, valid, row_start.astype(jnp.int32),
                new_lens.astype(jnp.int32), total_lens <= new_lens,
                jnp.where(new_lens > 0, slots, 0).astype(jnp.int32))


# ------------------------------------------------------------ convolution

def causal_conv(x: jnp.ndarray, w: jnp.ndarray, pool: jnp.ndarray, layer,
                rows: Rows) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal depthwise convolution of width ``K`` over each row's tokens,
    continued from the row's last ``K - 1`` inputs of earlier steps.

    ``x [N, Ch]`` the step's inputs on the flat axis; ``w [K, Ch]`` (tap
    ``K - 1`` weighs the token itself); ``pool [L, slots, K - 1, Ch]`` the
    carried inputs, layer ``layer``. Returns ``(y [N, Ch] float32, pool)``
    with each row's slot holding the last ``K - 1`` inputs it has seen (a
    row without new tokens keeps what it had)."""
    N, Ch = x.shape
    K = w.shape[0]
    f32 = jnp.float32
    wf = w.astype(f32)
    carry = jnp.where(rows.fresh[:, None, None], 0,
                      pool[layer, rows.slot])              # [R, K-1, Ch]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    y = x.astype(f32) * wf[K - 1]
    for j in range(1, K):
        # the input j tokens back, where it is of this row and this step
        back = jax.lax.dynamic_slice_in_dim(xp, K - 1 - j, N, axis=0)
        y = y + jnp.where((rows.off >= j)[:, None], back, 0).astype(f32) \
            * wf[K - 1 - j]
    # a row's first K - 1 tokens reach into the carried inputs: token s
    # (s < K - 1) takes carried input K - 1 + s - j for every j > s
    # (built as one value: as K (K - 1) / 2 updates of a zero array each
    # rewrote the whole [R, K - 1, Ch] - six passes of 6.6 MB a layer at 48
    # rows of 11,520 channels, a fifth of a decode step: PERF.md, PR 51)
    cf = carry.astype(f32)
    head = jnp.stack(
        [sum(cf[:, K - 1 + s - j] * wf[K - 1 - j] for j in range(s + 1, K))
         for s in range(K - 1)], axis=1)
    s_idx = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    at = jnp.where(s_idx < rows.new[:, None],
                   rows.start[:, None] + s_idx, N)          # N: dropped
    y = y.at[at.reshape(-1)].add(head.reshape(-1, Ch), mode="drop")
    # the carried inputs after this step: entries n .. n + K - 2 of
    # (carried ++ the row's new inputs); a cache write, and named as one
    # (``engine/stages.py``)
    with stage("conv_write"):
        e = rows.new[:, None] + s_idx                        # [R, K-1]
        taken = x[jnp.clip(rows.start[:, None] + e - (K - 1), 0, N - 1)]
        old = jnp.take_along_axis(carry, jnp.minimum(e, K - 2)[..., None],
                                  axis=1)
        carry = jnp.where((e < K - 1)[..., None], old,
                          taken.astype(pool.dtype))
        pool = pool.at[layer, rows.slot].set(carry)
    return y, pool


# ------------------------------------------------------------- chunk plan

class Chunks(NamedTuple):
    """The rows of several tokens cut into chunks of ``CHUNK``."""
    src: jnp.ndarray        # [NC, C] the flat slot of each chunk slot
    valid: jnp.ndarray      # [NC, C] whether it holds a token
    slot: jnp.ndarray       # [NC] the pool slot of the chunk's row (0: dead)
    first: jnp.ndarray      # [NC] the row's first chunk
    fresh: jnp.ndarray      # [NC] ... of a row that starts from zeros
    last: jnp.ndarray       # [NC] the row's last chunk
    live: jnp.ndarray       # [] chunks that hold a token (they come first)
    back: jnp.ndarray       # [N] chunk slot (flattened) of each flat slot


def chunk_plan(rows: Rows, chunk: int = CHUNK) -> Chunks:
    """Where the chunk form finds its tokens: rows of ONE token take no
    chunk (``gdn_step`` computes them). At most ``N // chunk + R``
    chunks: a row of ``n`` tokens takes ``ceil(n / chunk)``."""
    N, R = rows.row.shape[0], rows.start.shape[0]
    NC = N // chunk + R
    i32 = jnp.int32
    n_ck = jnp.where(rows.new > 1, -(-rows.new // chunk), 0)     # [R]
    end = jnp.cumsum(n_ck)
    j = jnp.arange(NC, dtype=i32)
    crow = jnp.minimum(jnp.sum(j[:, None] >= end[None, :], axis=1),
                       R - 1).astype(i32)
    live = j < end[-1]
    coff = (j - (end - n_ck)[crow]) * chunk           # offset in the row
    c = jnp.arange(chunk, dtype=i32)[None, :]
    valid = live[:, None] & (coff[:, None] + c < rows.new[crow][:, None])
    src = jnp.where(valid, rows.start[crow][:, None] + coff[:, None] + c, 0)
    first = live & (coff == 0)
    # each flat slot's place among the chunk slots (rows of one token and
    # empty slots point at chunk slot 0 and are masked by the caller)
    back = ((end - n_ck)[rows.row] + rows.off // chunk) * chunk \
        + rows.off % chunk
    back = jnp.where(rows.valid & (rows.new[rows.row] > 1), back, 0)
    return Chunks(src, valid, jnp.where(live, rows.slot[crow], 0), first,
                  first & rows.fresh[crow],
                  live & (coff + chunk >= rows.new[crow]),
                  end[-1].astype(i32), back.astype(i32))


# ------------------------------------------------------------ the XLA rule

def _solve_unit_lower(a: jnp.ndarray, rhs: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1 rhs`` for strictly lower-triangular ``a [..., C, C]``."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(a + eye, rhs, lower=True,
                                             unit_diagonal=True)


def chunk_math(q, k, v, g, beta, s0, solve=_solve_unit_lower):
    """One chunk of one head batch: ``q``/``k [..., C, Dk]``, ``v [..., C,
    Dv]``, ``g``/``beta [..., C]``, ``s0 [..., Dk, Dv]``, all float32.
    Returns ``(o [..., C, Dv], s1)``."""
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-1)
    diff = G[..., :, None] - G[..., None, :]               # G_t - G_s
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.exp(jnp.where(t >= s, diff, -jnp.inf))    # 0 above diag
    kk = jnp.einsum("...td,...sd->...ts", k, k)
    a = jnp.where(t > s, beta[..., :, None] * decay * kk, 0.0)
    eg = jnp.exp(G)[..., None]
    rhs = beta[..., None] * (v - jnp.einsum("...td,...dv->...tv",
                                            eg * k, s0))
    u = solve(a, rhs)
    qk = jnp.einsum("...td,...sd->...ts", q, k) * decay
    o = jnp.einsum("...td,...dv->...tv", eg * q, s0) \
        + jnp.einsum("...ts,...sv->...tv", qk, u)
    g_end = G[..., -1]
    s1 = jnp.exp(g_end)[..., None, None] * s0 + jnp.einsum(
        "...td,...tv->...dv", jnp.exp(g_end[..., None] - G)[..., None] * k, u)
    return o, s1


def gdn_chunk_xla(q, k, v, g, beta, pool, layer, ck: Chunks):
    """The chunk form in ``jax.numpy``: a scan over the chunks with the
    row's state carried. ``q``/``k [NC, C, Hk, Dk]`` (normalised, ``q``
    scaled), ``v [NC, C, Hv, Dv]``, ``g``/``beta [NC, C, Hv]`` float32
    (zero where ``ck.valid`` is not), ``pool [L, slots, Hv, Dk, Dv]``
    float32. Returns ``(o [NC, C, Hv, Dv] float32, pool)``."""
    f32 = jnp.float32
    rep = v.shape[2] // q.shape[2]

    def heads_first(a):                       # [C, H, D] -> [H, C, D]
        return jnp.swapaxes(a.astype(f32), 0, 1)

    def body(carry, xs):
        s_cur, pool = carry
        qc, kc, vc, gc, bc, slot, first, fresh, last = xs
        s0 = jnp.where(first, jnp.where(fresh, 0.0, pool[layer, slot]),
                       s_cur)
        o, s1 = chunk_math(jnp.repeat(heads_first(qc), rep, axis=0),
                           jnp.repeat(heads_first(kc), rep, axis=0),
                           heads_first(vc), gc.T, bc.T, s0)
        dst = jnp.where(last, slot, 0)
        pool = pool.at[layer, dst].set(
            jnp.where(last, s1, pool[layer, dst]))
        return (s1, pool), jnp.swapaxes(o, 0, 1)

    (_, pool), o = jax.lax.scan(
        body, (jnp.zeros(pool.shape[2:], f32), pool),
        (q, k, v, g, beta, ck.slot, ck.first, ck.fresh, ck.last))
    return o, pool


def gdn_step_xla(q, k, v, g, beta, pool, layer, slot, fresh):
    """One token a row in ``jax.numpy``: ``q``/``k [R, Hk, Dk]``, ``v [R,
    Hv, Dv]``, ``g``/``beta [R, Hv]`` float32 (``g = 0``, ``beta = 0`` for
    a row that takes no step here), ``slot``/``fresh [R]``. Returns ``(o
    [R, Hv, Dv] float32, pool)``."""
    f32 = jnp.float32
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(a.astype(f32), rep, axis=1) for a in (q, k))
    s = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, slot])
    s = jnp.exp(g)[..., None, None] * s
    u = beta[..., None] * (v.astype(f32) - jnp.einsum("rhd,rhdv->rhv", k, s))
    s = s + k[..., :, None] * u[..., None, :]
    return (jnp.einsum("rhd,rhdv->rhv", q, s),
            pool.at[layer, slot].set(s))


def gated_delta_rule(q, k, v, g, beta, pool, layer, rows: Rows,
                     use_pallas: bool = False, several: bool = True):
    """The rule over one step's flat axis: rows of one token through the
    step form, rows of several through the chunk form. ``q``/``k [N, Hk,
    Dk]`` (normalised, ``q`` scaled), ``v [N, Hv, Dv]``, ``g``/``beta [N,
    Hv]`` float32; ``several`` (static) is off where the step's form gives
    every row one slot (a decode step: the chunk form is not in the
    program); ``use_pallas`` asks for the Mosaic kernels, which serve where
    they lower at this geometry (``ops/pallas/gdn.supports``). Returns ``(o
    [N, Hv, Dv] float32, pool)``; slots that hold no token come back
    zero."""
    N = q.shape[0]
    one = rows.new == 1                                      # [R]
    if use_pallas:
        from dynamo_tpu.ops.pallas.gdn import gdn_chunk, gdn_step, supports
        # a geometry the kernels cannot lower serves on the plain forms
        # (the worker's ``startup.engine`` span says which, and why)
        use_pallas = supports(q.shape[1], v.shape[1], q.shape[2],
                              v.shape[2])
    if not use_pallas:
        gdn_chunk, gdn_step = gdn_chunk_xla, gdn_step_xla
    # the step form, over every row: its one token, or nothing
    at = jnp.minimum(rows.start, N - 1)
    mask = one[:, None].astype(jnp.float32)
    o_step, pool = gdn_step(
        q[at], k[at], v[at], g[at] * mask, beta[at] * mask, pool, layer,
        jnp.where(one, rows.slot, 0), rows.fresh | ~one)
    of_step = rows.valid & one[rows.row]
    o = jnp.where(of_step[:, None, None], o_step[rows.row], 0.0)
    if not several:
        return o, pool
    ck = chunk_plan(rows)
    live = ck.valid[..., None]
    o_ck, pool = gdn_chunk(
        jnp.where(live[..., None], q[ck.src], 0),
        jnp.where(live[..., None], k[ck.src], 0),
        jnp.where(live[..., None], v[ck.src], 0),
        jnp.where(live, g[ck.src], 0.0), jnp.where(live, beta[ck.src], 0.0),
        pool, layer, ck)
    o_ck = o_ck.reshape((-1,) + o_ck.shape[2:])[ck.back]
    several = rows.valid & ~one[rows.row]
    return jnp.where(several[:, None, None], o_ck, o), pool


__all__ = ["CHUNK", "Rows", "Chunks", "token_rows", "causal_conv",
           "chunk_plan", "chunk_math", "gdn_chunk_xla", "gdn_step_xla",
           "gated_delta_rule"]
