"""Vectorized on-device token sampling.

One jittable ``sample_tokens`` handles a whole decode batch with *per-request*
temperature / top-k / top-p (the reference forwards these to vLLM's sampler;
here they run natively on TPU).

Strategy: take the static ``TOPK_MAX`` highest logits of each row once
(``top_candidates``), then apply per-request top-k and top-p masks inside that
candidate set and draw via Gumbel-max. Greedy requests (temperature == 0) take
candidate 0. Restricting sampling to the top ``TOPK_MAX=64`` candidates is
exact for any top_k <= 64 and an excellent approximation otherwise (tail mass
beyond the top 64 is noise for served models); a request's ``top_k`` above
``TOPK_MAX`` is clamped to it.

``top_candidates`` returns what ``jax.lax.top_k`` returns, bit for bit, but
never orders an axis as long as the vocabulary: at a real vocabulary XLA's
TPU ``top_k`` is a key+index sort of all ``V`` columns (6.2 ms a step at
``[32, 151936]``, the largest device operation of a decode step; PERF.md
section 6, PR 30). It takes the maximum of each group of ``g`` contiguous
columns, the ``k`` best groups, and the ``k`` best of those groups' columns.
``g`` follows from the static ``(V, k)`` (``_group_width``); a small
vocabulary takes ``lax.top_k`` itself. Every selection over the vocabulary in
a step program goes through it: the sampler's candidates, the speculative
verifier's, and the engine's top-logprob alternatives (the first columns of
the same candidates), so a step holds one selection and no full-vocabulary
sort (``engine/program_check.vocab_sorts`` holds that line).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.stages import stage

TOPK_MAX = 64


@dataclass
class SamplingParamsBatch:
    """Host-side batch of per-request sampling parameters (device-ready)."""

    temperature: np.ndarray  # [B] f32, 0 => greedy
    top_k: np.ndarray        # [B] i32, 0 => disabled
    top_p: np.ndarray        # [B] f32, 1.0 => disabled

    @classmethod
    def build(cls, temps: List[float], top_ks: List[Optional[int]],
              top_ps: List[Optional[float]]) -> "SamplingParamsBatch":
        return cls(
            temperature=np.asarray(temps, dtype=np.float32),
            top_k=np.asarray([k if k and k > 0 else 0 for k in top_ks],
                             dtype=np.int32),
            top_p=np.asarray([p if p is not None else 1.0 for p in top_ps],
                             dtype=np.float32),
        )

    @classmethod
    def greedy(cls, batch: int) -> "SamplingParamsBatch":
        return cls(temperature=np.zeros(batch, np.float32),
                   top_k=np.zeros(batch, np.int32),
                   top_p=np.ones(batch, np.float32))


def apply_penalties(logits: jnp.ndarray, pen_ids: jnp.ndarray,
                    pen_counts: jnp.ndarray, pen_in_ctx: jnp.ndarray,
                    freq_pen: jnp.ndarray, pres_pen: jnp.ndarray,
                    rep_pen: jnp.ndarray,
                    pen_bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Frequency / presence / repetition penalties on device.

    The host ships each row's penalized token ids as a SPARSE window
    (ids unique per row, zero-padded with count 0 / in_ctx 0 so pad
    entries contribute a zero delta — scatter-ADD makes duplicate pad
    writes safe):

    pen_ids:    [B, W] i32 token ids
    pen_counts: [B, W] f32 occurrences among GENERATED tokens
                (frequency/presence semantics, vLLM/OpenAI)
    pen_in_ctx: [B, W] f32 1.0 if the token appears in prompt+generated
                (repetition-penalty semantics, HF: divide positive /
                multiply negative logits)
    freq_pen/pres_pen: [B] f32 (0 = off); rep_pen: [B] f32 (1 = off)
    pen_bias:   optional [B, W] f32 OpenAI logit_bias, added
                unconditionally per entry (0 on pads)
    """
    if pen_ids.shape[1] == 0:
        return logits
    logits = logits.astype(jnp.float32)
    sel = jnp.take_along_axis(logits, pen_ids, axis=1)     # [B, W]
    rp = jnp.where(rep_pen[:, None] <= 0, 1.0, rep_pen[:, None])
    adj = jnp.where(pen_in_ctx > 0,
                    jnp.where(sel > 0, sel / rp, sel * rp), sel)
    adj = adj - freq_pen[:, None] * pen_counts
    adj = adj - pres_pen[:, None] * (pen_counts > 0)
    if pen_bias is not None:
        adj = adj + pen_bias
    delta = adj - sel                                      # 0 on pads
    rows = jnp.arange(logits.shape[0])[:, None]
    return logits.at[rows, pen_ids].add(delta)


def update_penalty_window(pen_ids: jnp.ndarray, pen_counts: jnp.ndarray,
                          pen_in_ctx: jnp.ndarray, pen_n: jnp.ndarray,
                          tokens: jnp.ndarray, active: jnp.ndarray):
    """One fused-decode step of the device-resident penalty window.

    The fused multistep block keeps each row's penalty entries as a
    fixed-capacity window riding the scan carry; after a token is
    sampled this folds it in without leaving the device:

      - a token already in the row's window (first ``pen_n`` slots) gets
        its count bumped and is marked in-context;
      - a new token is appended at slot ``pen_n`` (count 1, in-context)
        when capacity remains — the scheduler's width gate guarantees a
        fused block never sees the window fill mid-block, so the
        saturation branch is unreachable on planned traffic.

    Inserts never touch the bias column: new slots keep the zero pad,
    and all logit-bias entries are preloaded before the block starts, so
    an insert can never collide with a biased slot.

    pen_ids/pen_counts/pen_in_ctx: [B, W] as ``apply_penalties``
    pen_n:  [B] i32 occupied slots per row
    tokens: [B] i32 tokens just sampled
    active: [B] bool rows whose window should absorb the token
            (alive AND carrying penalties/bias)
    Returns the four updated window arrays.
    """
    W = pen_ids.shape[1]
    if W == 0:
        return pen_ids, pen_counts, pen_in_ctx, pen_n
    occ = jnp.arange(W)[None, :] < pen_n[:, None]            # [B, W]
    match = (pen_ids == tokens[:, None]) & occ
    bump = match & active[:, None]
    pen_counts = pen_counts + bump.astype(pen_counts.dtype)
    pen_in_ctx = jnp.maximum(pen_in_ctx, bump.astype(pen_in_ctx.dtype))
    can_ins = active & ~jnp.any(match, axis=1) & (pen_n < W)
    slot = (jnp.arange(W)[None, :] == pen_n[:, None]) & can_ins[:, None]
    pen_ids = jnp.where(slot, tokens[:, None], pen_ids)
    pen_counts = jnp.where(slot, jnp.ones_like(pen_counts), pen_counts)
    pen_in_ctx = jnp.where(slot, jnp.ones_like(pen_in_ctx), pen_in_ctx)
    pen_n = pen_n + can_ins.astype(pen_n.dtype)
    return pen_ids, pen_counts, pen_in_ctx, pen_n


def penalty_window_entries(prompt_ids: jnp.ndarray, prompt_valid: jnp.ndarray,
                           pen_ids: jnp.ndarray,
                           pen_n: jnp.ndarray) -> jnp.ndarray:
    """Which static prompt entries the fused penalty step should include.

    The per-step host builder backfills a penalized row's window with
    distinct prompt tokens (repetition-penalty context) after the
    generated/bias entries, up to capacity ``W``. On device the prompt
    side is a STATIC list shipped once per batch composition
    (``prompt_ids``/``prompt_valid``, deduped reverse-prompt order, 2W
    entries — enough that at least W survive any overlap with the
    dynamic window); each step this recomputes which of them the host
    would have kept: not already in the dynamic window's first ``pen_n``
    slots, and within the ``W - pen_n`` remaining capacity, first come
    first served.

    Returns an [B, S] bool include mask; included entries are applied
    with count 0 / in-context 1 / bias 0, excluded ones pad to a zero
    delta under ``apply_penalties``.
    """
    W = pen_ids.shape[1]
    occ = jnp.arange(W)[None, None, :] < pen_n[:, None, None]
    in_dyn = jnp.any(
        (prompt_ids[:, :, None] == pen_ids[:, None, :]) & occ, axis=2)
    eligible = prompt_valid & ~in_dyn                        # [B, S]
    rank = jnp.cumsum(eligible.astype(jnp.int32), axis=1) \
        - eligible.astype(jnp.int32)                         # exclusive
    return eligible & (pen_n[:, None] + rank < W)


# columns per group of the two-stage selection: one lane tile. Timed on a
# v5e in the sampling tail at k = 64 (PERF.md section 6, PR 30): at
# [32, 151936] widths 32 / 64 / 128 / 256 / 512 / 1024 take 0.50 / 0.33 /
# 0.31 / 0.48 / 0.91 / 1.81 ms where lax.top_k's sort takes 6.09; 64 and
# 128 stay within 0.07 ms of each other down to V = 32,000 and up to 128
# rows, so one width serves every (V, k)
GROUP_WIDTH = 128


def _group_width(V: int, k: int) -> int:
    """Columns per group for ``top_candidates`` at a vocabulary of ``V``
    and ``k`` wanted, or 0 where the selection is ``lax.top_k`` itself:
    the second stage orders ``k * g`` candidates, so grouping pays only
    while that is at most half of ``V`` (0.24 against 0.86 ms at
    V = 32,000; the tests' toy vocabularies stay direct)."""
    g = GROUP_WIDTH
    return g if 2 * k * g <= V else 0


def candidate_form(V: int, k: int = TOPK_MAX) -> str:
    """Which form ``top_candidates`` takes at ``(V, k)`` — by default the
    sampler's own selection at a vocabulary of ``V`` — for a start-up
    span: ``grouped[G=1187,g=128]`` or ``direct``."""
    g = _group_width(V, min(k, V))
    return f"grouped[G={-(-V // g)},g={g}]" if g else "direct"


def top_candidates(logits: jnp.ndarray, k: int):
    """``jax.lax.top_k(logits, k)`` over the last axis — same values, same
    indices, same order, ties to the lower index — without ordering an
    axis of the vocabulary's length.

    Exact, not approximate: under the total order (value descending,
    index ascending) each of the ``k`` best columns lies in a group whose
    best column is itself among the ``k`` best, so at most ``k`` groups
    matter; contiguous groups order by their best column exactly as
    ``top_k`` orders their maxima (a tie between maxima falls to the
    lower group, which holds the lower indices). The chosen groups are
    gathered in ascending order, so a tie between candidates again falls
    to the lower vocabulary index. Pad columns (``-inf``, where ``g`` does
    not divide ``V``) sit behind every real column of the last group and
    are never returned.
    """
    *lead, V = logits.shape
    g = _group_width(V, k)
    if not g:
        return jax.lax.top_k(logits, k)
    with stage("top_candidates"):
        G = -(-V // g)
        x = logits.reshape(-1, V)
        if G * g != V:
            x = jnp.pad(x, ((0, 0), (0, G * g - V)),
                        constant_values=-jnp.inf)
        x = x.reshape(-1, G, g)
        _, groups = jax.lax.top_k(jnp.max(x, axis=-1), k)     # [R, k]
        groups = jnp.sort(groups, axis=-1)
        cand = jnp.take_along_axis(x, groups[:, :, None], axis=1)
        vals, pos = jax.lax.top_k(cand.reshape(-1, k * g), k)
        idx = jnp.take_along_axis(groups, pos // g, axis=1) * g + pos % g
        return vals.reshape(*lead, k), idx.reshape(*lead, k)


def _masked_candidates(logits: jnp.ndarray, temperature: jnp.ndarray,
                       top_k: jnp.ndarray, top_p: jnp.ndarray,
                       min_p: Optional[jnp.ndarray] = None):
    """Shared candidate filter of every sampling path.

    logits: [R, V] f32; per-row temperature/top_k/top_p ([R]).
    Returns (scaled [R, k], top_idx [R, k]) where ``scaled`` is the
    temperature-scaled logits over the top ``k`` candidates with the
    per-row top-k / top-p / min-p rejects set to -inf — ``softmax(scaled)``
    is the exact distribution sampling draws from, and Gumbel-argmax over
    ``scaled`` draws from it without materializing the softmax.
    """
    R, V = logits.shape
    k = min(TOPK_MAX, V)
    top_vals, top_idx = top_candidates(logits, k)         # [R, k]

    ranks = jnp.arange(k)[None, :]                        # [1, k]
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k), k)  # [R]
    keep = ranks < eff_k[:, None]

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = top_vals / temp
    scaled = jnp.where(keep, scaled, -jnp.inf)
    probs = jax.nn.softmax(scaled, axis=-1)
    # top-p: keep the smallest prefix of candidates whose cumulative
    # probability reaches top_p (always keep the first).
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (cum - probs) < top_p[:, None]
    if min_p is not None:
        # min_p (vLLM semantics): drop candidates whose post-temperature
        # probability falls below min_p x the best candidate's (0 = off;
        # candidate 0 always survives: probs[...,0] is the max)
        keep_p &= probs >= min_p[:, None] * probs[:, :1]
    return jnp.where(keep_p, scaled, -jnp.inf), top_idx


def apply_vocab_mask(logits: jnp.ndarray,
                     mask_words: jnp.ndarray) -> jnp.ndarray:
    """Guided-decoding allow-mask, unpacked on device.

    The host ships each row's allowed-token set as a uint32 bitfield
    ``[B, ceil(V/32)]`` (~4 KB/row at 32k vocab — vs 128 KB for a f32
    mask); the bits are expanded with a gather + shift/and here, inside
    the jitted step. An all-ones row (0xFFFFFFFF words) is the compiled-in
    no-op for unconstrained rows sharing a batch with constrained ones.
    """
    B, V = logits.shape
    idx = jnp.arange(V, dtype=jnp.int32)
    words = mask_words[:, idx // 32]                      # [B, V] u32
    bits = (words >> (idx % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(bits.astype(bool), logits.astype(jnp.float32),
                     -jnp.inf)


def sample_tokens(logits: jnp.ndarray, rng: jax.Array,
                  temperature: jnp.ndarray, top_k: jnp.ndarray,
                  top_p: jnp.ndarray, seeds: Optional[jnp.ndarray] = None,
                  seed_rng: Optional[jax.Array] = None,
                  seed_pos: Optional[jnp.ndarray] = None,
                  min_p: Optional[jnp.ndarray] = None):
    """Sample next tokens.

    logits: [B, V] (any float dtype; promoted to f32)
    seeds:  optional [B] i32 per-request seeds (0 = unseeded). A seeded
            row's randomness depends only on (base engine rng, seed, the
            row's TOKEN POSITION ``seed_pos``) — not on its batch position,
            the global step counter, or what it was batched with — so a
            seeded request replays deterministically under any concurrency.
    seed_rng: the engine's BASE key (pre step-fold); required with seeds.
    seed_pos: [B] i32 position of the token being sampled per row.
    returns (tokens [B] i32, logprobs [B] f32 — logprob of the chosen
    token under the GIVEN logits before temperature/top-k/top-p (matching
    OpenAI logprobs semantics; when the engine applies penalties upstream,
    the reported logprobs reflect that penalized distribution — the one
    actually sampled from).
    """
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    k = min(TOPK_MAX, V)
    scaled, top_idx = _masked_candidates(logits, temperature, top_k, top_p,
                                         min_p)

    if seeds is None:
        gumbel = jax.random.gumbel(rng, (B, k), dtype=jnp.float32)
    else:
        # per-row keys: unseeded rows fold their batch position (rows stay
        # independent), seeded rows fold ONLY the seed (batch-invariant)
        def draw(key):
            return jax.random.gumbel(key, (k,), dtype=jnp.float32)

        g_row = jax.vmap(lambda r: draw(
            jax.random.fold_in(jax.random.fold_in(rng, 7), r)))(
            jnp.arange(B))
        base = rng if seed_rng is None else seed_rng
        pos = (jnp.zeros(B, jnp.uint32) if seed_pos is None
               else seed_pos.astype(jnp.uint32))
        g_seed = jax.vmap(lambda s, p: draw(jax.random.fold_in(
            jax.random.fold_in(base, s), p)))(
            seeds.astype(jnp.uint32), pos)
        gumbel = jnp.where((seeds != 0)[:, None], g_seed, g_row)
    choice = jnp.argmax(scaled + gumbel, axis=-1)          # [B]
    greedy = temperature <= 0.0
    choice = jnp.where(greedy, 0, choice)
    tokens = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]

    logz = jax.nn.logsumexp(logits, axis=-1)
    chosen_logit = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return tokens.astype(jnp.int32), chosen_logit - logz


def reveal(conf: jnp.ndarray, masked: jnp.ndarray, pass_idx: jnp.ndarray,
           steps: jnp.ndarray, threshold: jnp.ndarray) -> jnp.ndarray:
    """Which masked positions of each row's block one pass of generation
    by diffusion over blocks reveals - the one reveal rule of the program.

    conf:      [R, B] f32 confidence of each position's sampled token (its
               probability); only masked positions count
    masked:    [R, B] bool positions not revealed yet
    pass_idx:  [R] i32 the pass's index within its block, from 0
    steps:     [R] i32 denoising steps a block (clipped to 1..B)
    threshold: [R] f32 confidence threshold (>= 1 never fires: the static
               schedule)
    returns    [R, B] bool, a subset of ``masked``.

    The pass's quota is ``B // steps``, one more in the first ``B % steps``
    passes, never more than are masked. Every masked position whose
    confidence exceeds the threshold is revealed if those are at least
    the quota; else the quota's worth of the most confident (ties to the
    lower position). At least one masked position is revealed while any
    is left, so a block of B masks commits after at most B + 1 passes."""
    B = conf.shape[1]
    steps = jnp.clip(steps, 1, B)
    quota = B // steps + (pass_idx < B % steps).astype(jnp.int32)
    quota = jnp.minimum(quota, jnp.sum(masked, axis=1))[:, None]   # [R, 1]
    c = jnp.where(masked, conf, -jnp.inf)
    high = masked & (c > threshold[:, None])
    idx = jnp.arange(B)
    # beats[r, i, j]: position j comes before position i in the order
    beats = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (idx[None, None, :]
                                            < idx[None, :, None]))
    rank = jnp.sum(beats, axis=2)
    top = masked & (rank < quota)
    return jnp.where(jnp.sum(high, axis=1, keepdims=True) >= quota,
                     high, top)


def spec_verify(logits: jnp.ndarray, tokens: jnp.ndarray, rng: jax.Array,
                temperature: jnp.ndarray, top_k: jnp.ndarray,
                top_p: jnp.ndarray,
                mask_words: Optional[jnp.ndarray] = None):
    """Exact rejection-sampling verification of drafted tokens, one pass.

    The speculative-decode acceptance rule (Leviathan et al.) with a
    DETERMINISTIC proposal (the n-gram draft is a point mass): draft ``d``
    at a position with target distribution ``p`` is accepted with
    probability ``p(d)``, and on rejection the replacement is drawn from
    ``p`` with ``d`` excluded, renormalized — together these sample exactly
    from ``p``. Greedy rows (temperature 0) degenerate to "accept while the
    draft equals the argmax", so greedy output is bit-identical with
    speculation on or off. ``p`` here is the FILTERED distribution
    (temperature/top-k/top-p via ``_masked_candidates``) — the same one
    ``sample_tokens`` draws from.

    logits: [B, S, V] — logits[:, j] is the next-token distribution after
            consuming chunk slot j (predicts the token at slot j+1)
    tokens: [B, S] the fed tokens; tokens[:, 0] is the last accepted
            context token, tokens[:, j] (j >= 1) is draft j
    mask_words: optional [B, S, ceil(V/32)] uint32 guided-decoding
            allow-masks, one PER CHUNK SLOT (the host walks the grammar
            automaton along the draft path, so slot j's mask reflects the
            state after drafts 1..j). Applied to the logits before
            filtering, exactly like the plain path — a mask-illegal draft
            gets probability 0 and is rejected, and the replacement /
            bonus draw is masked by its own slot's state. Reported
            logprobs are then under the MASKED distribution (the one
            actually sampled from), matching the plain guided path.
    returns (n_acc [B] i32 accepted drafts in [0, K],
             final_tok [B] i32 — the rejection replacement, or the bonus
             token sampled after all K drafts accepted,
             final_lp [B] f32 logprob of final_tok under its UNfiltered
             row logits (OpenAI logprob semantics, as sample_tokens),
             draft_lps [B, K] f32 logprobs of each draft at its position)
    """
    lf = logits.astype(jnp.float32)
    B, S, V = lf.shape
    K = S - 1
    if mask_words is not None:
        lf = apply_vocab_mask(
            lf.reshape(B * S, V),
            mask_words.reshape(B * S, -1)).reshape(B, S, V)
    k = min(TOPK_MAX, V)
    rep = lambda a: jnp.repeat(a, S, axis=0)  # noqa: E731  [B] -> [B*S]
    scaled, top_idx = _masked_candidates(
        lf.reshape(B * S, V), rep(temperature), rep(top_k), rep(top_p))
    scaled = scaled.reshape(B, S, k)
    top_idx = top_idx.reshape(B, S, k)
    q = jax.nn.softmax(scaled, axis=-1)                   # filtered probs

    drafts = tokens[:, 1:]                                # [B, K]
    in_cand = top_idx[:, :K] == drafts[..., None]         # [B, K, k]
    p_draft = jnp.sum(jnp.where(in_cand, q[:, :K], 0.0), axis=-1)

    k_u, k_g = jax.random.split(jax.random.fold_in(rng, 0x5bec))
    u = jax.random.uniform(k_u, (B, K), dtype=jnp.float32)
    greedy = (temperature <= 0.0)[:, None]
    acc = jnp.where(greedy, drafts == top_idx[:, :K, 0], u < p_draft)
    n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                    axis=1).astype(jnp.int32)             # [B] in [0, K]

    # final token from chunk slot n_acc: the rejection position, or slot K
    # (the bonus draw) when everything was accepted
    sel = n_acc[:, None, None]
    scaled_a = jnp.take_along_axis(scaled, sel, axis=1)[:, 0]   # [B, k]
    idx_a = jnp.take_along_axis(top_idx, sel, axis=1)[:, 0]     # [B, k]
    d_rej = jnp.take_along_axis(drafts, jnp.minimum(n_acc, K - 1)[:, None],
                                axis=1)[:, 0] if K > 0 else None
    if d_rej is not None:
        # residual of a rejection excludes the draft; a bonus draw does not
        excl = (idx_a == d_rej[:, None]) & (n_acc < K)[:, None]
        scaled_a = jnp.where(excl, -jnp.inf, scaled_a)
    gumbel = jax.random.gumbel(k_g, (B, k), dtype=jnp.float32)
    choice = jnp.argmax(scaled_a + gumbel, axis=-1)
    # greedy: candidate 0 is correct for both cases — a greedy rejection
    # means the draft was NOT candidate 0, so the exclusion never hides it
    choice = jnp.where(temperature <= 0.0, 0, choice)
    final_tok = jnp.take_along_axis(idx_a, choice[:, None], axis=1)[:, 0]

    logz = jax.nn.logsumexp(lf, axis=-1)                  # [B, S]
    if K > 0:
        d_logit = jnp.take_along_axis(lf[:, :K], drafts[..., None],
                                      axis=2)[..., 0]     # [B, K]
        draft_lps = d_logit - logz[:, :K]
    else:
        draft_lps = jnp.zeros((B, 0), jnp.float32)
    lf_a = jnp.take_along_axis(lf, sel, axis=1)[:, 0]     # [B, V]
    logz_a = jnp.take_along_axis(logz, n_acc[:, None], axis=1)[:, 0]
    f_logit = jnp.take_along_axis(lf_a, final_tok[:, None], axis=1)[:, 0]
    return (n_acc, final_tok.astype(jnp.int32), f_logit - logz_a, draft_lps)


__all__ = ["SamplingParamsBatch", "sample_tokens", "apply_penalties",
           "apply_vocab_mask", "update_penalty_window",
           "penalty_window_entries", "spec_verify", "reveal",
           "top_candidates",
           "candidate_form", "TOPK_MAX"]
