"""Plain reference of SDAR-MoE (``model_type`` ``sdar_moe``, e.g.
SDAR-30B-A3B-Chat): the Qwen3-MoE block under the block-wise visibility of
generation by diffusion over blocks, the generation loop itself, and the
rule by which a served token is scored - in float32 ``jax.numpy``, one whole
sequence at a time, with no cache, kernel or batching.

Written from the model's published ``config.json``
(huggingface.co/JetLM/SDAR-30B-A3B-Chat) and, for what it does not state,
from the released ``generate.py`` (``block_diffusion_generate``) as
remembered; each size the config lacks is the configuration file's
``assumed``. With block length ``B = hf["block_size"]``:

- A layer on ``h [T, H]``: ``x = RMSNorm(h)``; ``q = x W_q`` (heads x dh),
  ``k = x W_k``, ``v = x W_v`` (kv heads x dh); RMSNorm over each head of q
  and k (Qwen3's q/k norm), then rotate-half RoPE by absolute position;
  grouped-query attention in which query position ``i`` sees key position
  ``j`` iff ``j // B <= i // B`` - every key of its own and of earlier
  blocks (``B = 1`` is the causal mask); ``h += attn W_o``; ``x =
  RMSNorm(h)``; ``p = softmax(x W_r)`` over the experts, the
  ``num_experts_per_tok`` largest, renormalised (``norm_topk_prob``); ``h +=
  sum_e p_e W_down,e (silu(x W_gate,e) * (x W_up,e))``. No shared expert,
  every layer sparse.
- Logits ``RMSNorm(h_i) W_head`` at position ``i`` score the token AT
  position ``i`` (no shift). A position not revealed yet holds the embedding
  of ``hf["mask_token_id"]``.
- Generation: positions are cut into blocks of ``B`` from 0. Block by block
  (the first is the one the prompt ends in, its prompt positions known):
  while the block holds a masked position that the budget pays for, one
  forward pass over everything before the block (final) and the block as it
  stands; at each masked position the sampler's token (greedy here) and its
  probability, the position's confidence; the pass reveals every masked
  position whose confidence exceeds ``threshold`` if those are at least the
  pass's quota, else the quota's worth of the most confident (ties to the
  lower position). Quota: ``B // steps``, one more in the first ``B % steps``
  passes, never more than are masked.

Departures from the release, each stated:

1. ``max_tokens`` inside a block: the release denoises whole blocks and cuts
   the text afterwards. Here the positions past the budget are never
   revealed (they stay masks, in every pass and in what later blocks would
   read), as the program serves it: what a served token was conditioned on
   is then a function of the served tokens alone, which is what lets
   ``score`` replay it.
2. Confidence is the sampled token's probability under the plain softmax of
   the logits (temperature 1, no top-k/top-p filter); the release takes it
   from the filtered, temperature-scaled distribution. Greedy decoding, the
   only kind the benchmark sends, reads the same either way.
3. The release's other remasking strategies (``sequential``,
   ``low_confidence_static``, ``entropy_bounded``) are not built: a
   threshold of 1 or more is the static schedule.
4. The release runs the finished block once more to write its keys and
   values into the cache. With no cache there is nothing to write: what
   later blocks read of a block is computed from its final tokens, which is
   what that pass leaves behind.

It shares no code with ``dynamo_tpu/models``. Weights are data: the arrays
the worker serves, cast to float32 a piece at a time (an expert layer of 128
x 3 x 2048 x 768 is 2.4 GB in float32 beside the 10 GB the child holds, so
experts are upcast ``EXPERT_BLOCK`` at a time, each run on every row and
weighted by the gate's column, zero where it was not chosen).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 32
EXPERTS = ("w_gate", "w_up", "w_down")
f32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, pos, theta):
    """x [T, heads, dh] at absolute positions pos [T]: position t turns the
    planes (i, i + dh/2) by t * theta^(-2i/dh)."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=f32) / dh)
    ang = pos.astype(f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(hf, w, h, pos):
    """Queries, keys and values of rows ``h [T, H]`` at positions ``pos``."""
    T = h.shape[0]
    nq, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    dh = hf.get("head_dim") or hf["hidden_size"] // nq
    eps = hf["rms_norm_eps"]
    x = rms_norm(h, w["attn_norm"], eps)
    q = rms_norm((x @ w["wq"]).reshape(T, nq, dh), w["q_norm"], eps)
    k = rms_norm((x @ w["wk"]).reshape(T, nkv, dh), w["k_norm"], eps)
    v = (x @ w["wv"]).reshape(T, nkv, dh)
    theta = float(hf["rope_theta"])
    return rope(q, pos, theta), rope(k, pos, theta), v


def attend(q, k, v, sees):
    """q [T, nq, dh] over k/v [S, nkv, dh]; ``sees [T, S]`` says which keys
    a query reads. Query head j reads key/value head j // (nq / nkv)."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("tnd,snd->nts", q, k) / jnp.sqrt(f32(q.shape[-1]))
    scores = jnp.where(sees[None], scores, -jnp.inf)
    out = jnp.einsum("nts,snd->tnd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(q.shape[0], -1)


def gate(hf, w, x):
    """[T, E] weight of expert e for row t, zero where e was not chosen."""
    p = jax.nn.softmax(x @ w["w_router"], axis=-1)
    top_w, top_i = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)


def expert_block(x, weight, acc, w_gate, w_up, w_down):
    """acc + the experts of one block ``[n, ...]``, each on every row, times
    its column of the gate ``weight [T, n]``, in a plain loop."""
    def one(acc, ew):
        g, u, d, col = ew
        return acc + col[:, None] * ((jax.nn.silu(x @ g) * (x @ u)) @ d), None

    return jax.lax.scan(one, acc, (w_gate, w_up, w_down, weight.T))[0]


def as_f32(a):
    return a.astype(f32)


def experts(hf, w, h, upcast=as_f32):
    """The expert layer's residual on rows ``h``; ``w["w_gate"]`` etc. are
    the layer's experts ``[E, ...]`` as served, made float32 (``upcast``) a
    block at a time."""
    x = rms_norm(h, w["mlp_norm"], hf["rms_norm_eps"])
    weight = gate(hf, w, x)
    acc = jnp.zeros_like(x)
    E = w["w_gate"].shape[0]
    for lo in range(0, E, EXPERT_BLOCK):
        hi = min(lo + EXPERT_BLOCK, E)
        acc = _expert_block(x, weight[:, lo:hi], acc,
                            *(upcast(w[k][lo:hi]) for k in EXPERTS))
    return h + acc


_expert_block = jax.jit(expert_block)


def layer(hf, w, h):
    """One block on the whole sequence ``h [T, H]``, under the block-wise
    visibility of ``hf["block_size"]``."""
    T, B = h.shape[0], int(hf.get("block_size", 1))
    pos = jnp.arange(T)
    q, k, v = qkv(hf, w, h, pos)
    sees = (pos[None, :] // B) <= (pos[:, None] // B)
    h = h + attend(q, k, v, sees) @ w["wo"]
    return experts(hf, w, h)


def layers(params):
    """(kind, stacked layer weights, count) in model order."""
    return [("block", params["layers"], params["layers"]["wq"].shape[0])]


LAYER_FNS = {"block": layer}


def head(hf, params, h, upcast=as_f32):
    """Final norm and vocabulary projection: logits [T, V]."""
    h = rms_norm(h, params["final_norm"].astype(f32), hf["rms_norm_eps"])
    return h @ upcast(params["lm_head"])


def _layer_weights(stack, i, upcast=as_f32):
    """Layer ``i``: everything but the experts in float32, the experts as
    served (``experts`` upcasts them a block at a time)."""
    return {k: (v[i] if k in EXPERTS else upcast(v[i]))
            for k, v in stack.items()}


def forward(hf, params, tokens):
    """Logits ``[T, V]`` of one whole sequence (position ``i`` scores the
    token at ``i``), a layer at a time."""
    h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(f32)
    for _kind, stack, n in layers(params):
        for i in range(n):
            h = layer(hf, _layer_weights(stack, i), h)
    return head(hf, params, h)


def quota(B, steps, pass_idx, n_masked):
    steps = min(max(int(steps), 1), B)
    return min(B // steps + (pass_idx < B % steps), n_masked)


def reveal(conf, masked, pass_idx, steps, threshold):
    """The positions (indices into the block) one pass reveals: ``conf``
    the confidence at each, ``masked`` those still masked."""
    B = len(conf)
    cand = [b for b in range(B) if masked[b]]
    q = quota(B, steps, pass_idx, len(cand))
    high = [b for b in cand if conf[b] > threshold]
    if len(high) >= q:
        return high
    return sorted(cand, key=lambda b: (-conf[b], b))[:q]


def generate(hf, params, prompt, max_new, steps, threshold,
             full_logp=False):
    """Free-running greedy generation, no cache. Returns ``(tokens,
    reveal_pass, logp)``: the ``max_new`` generated tokens in position
    order, for each the pass of its block that revealed it, and its
    log-probability in that pass (``full_logp``: the whole log-probability
    vector instead)."""
    B, mask = int(hf["block_size"]), int(hf["mask_token_id"])
    P, end = len(prompt), len(prompt) + max_new
    seq = list(prompt)
    passes, logps = [], []
    while len(seq) < end:
        start = len(seq) // B * B
        block = seq[start:] + [mask] * (B - (len(seq) - start))
        known = [start + b < len(seq) for b in range(B)]
        rpass, lp = [0] * B, [None] * B
        p = 0
        while True:
            masked = [not known[b] and start + b < end for b in range(B)]
            if not any(masked):
                break
            logp = jax.nn.log_softmax(
                forward(hf, params, seq[:start] + block)[start:], axis=-1)
            best = np.asarray(jnp.argmax(logp, axis=-1))
            conf = np.exp(np.asarray(jnp.max(logp, axis=-1)))
            for b in reveal(conf, masked, p, steps, threshold):
                block[b], known[b], rpass[b] = int(best[b]), True, p
                lp[b] = logp[b] if full_logp else float(logp[b, best[b]])
            p += 1
        for b in range(len(seq) - start, B):
            if start + b < end:
                passes.append(rpass[b])
                logps.append(lp[b])
        seq = seq[:start] + [t for b, t in enumerate(block)
                             if start + b < end]
    return seq[P:], passes, logps


def _replays(hf, prompt, continuation, reveal_pass):
    """What ``score`` has to run: the clean sequence (prompt + served
    tokens, masks at the positions past the budget of the last block) and,
    for each block that holds a served token and each pass that revealed
    one, the block as that pass saw it: the prompt's tail, the tokens
    revealed in earlier passes, masks elsewhere. Returns ``(clean tokens,
    [(block start, pass, block tokens)], per served token its (replay
    index, position in the block))``."""
    B, mask = int(hf["block_size"]), int(hf["mask_token_id"])
    P, N = len(prompt), len(prompt) + len(continuation)
    clean = list(prompt) + list(continuation)
    clean += [mask] * (-N % B)
    replays, where, index = [], [], {}
    for g in range(len(continuation)):
        start, p = (P + g) // B * B, int(reveal_pass[g])
        if (start, p) not in index:
            block = []
            for b in range(B):
                at = start + b
                shown = at < P or (at < N and reveal_pass[at - P] < p)
                block.append(clean[at] if shown else mask)
            index[(start, p)] = len(replays)
            replays.append((start, p, block))
        where.append((index[(start, p)], P + g - start))
    return clean, replays, where


@functools.partial(jax.jit, static_argnums=(0,))
def _clean_attention(hf_items, w, h):
    """The clean sequence's attention residual, and its keys and values."""
    hf = dict(hf_items)
    T, B = h.shape[0], int(hf["block_size"])
    pos = jnp.arange(T)
    q, k, v = qkv(hf, w, h, pos)
    sees = (pos[None, :] // B) <= (pos[:, None] // B)
    return h + attend(q, k, v, sees) @ w["wo"], k, v


@functools.partial(jax.jit, static_argnums=(0,))
def _replay_attention(hf_items, w, h, starts, k_clean, v_clean):
    """Attention of the replayed blocks ``h [R, B, H]`` at ``starts [R]``:
    each reads the clean keys and values of the positions BEFORE its block
    (final there: by the visibility rule they depend on nothing at or past
    the block) and its own block's, computed here."""
    hf = dict(hf_items)
    B = h.shape[1]
    S = k_clean.shape[0]

    def one(hb, start):
        q, k, v = qkv(hf, w, hb, start + jnp.arange(B))
        sees = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(S)[None, :] < start, (B, S)),
             jnp.ones((B, B), bool)], axis=1)
        return hb + attend(q, jnp.concatenate([k_clean, k]),
                           jnp.concatenate([v_clean, v]), sees) @ w["wo"]

    return jax.vmap(one)(h, starts)


def score(hf, params, layer_fns, prompt, continuation, carried,
          reuse=True, upcast=as_f32):
    """The log-probability vector ``[len(continuation), V]`` each served
    token is held to: that of its position in the pass that revealed it
    (``carried["reveal_pass"]``), replayed from the served tokens.

    The clean sequence runs once, a layer at a time; each replayed block
    runs beside it, reading per layer the clean keys and values of the
    positions before it (``reuse``; off, every replay recomputes its whole
    prefix - the same numbers, which a test at toy size holds). ``upcast``
    makes a served weight matrix float32: the control of ``correct``
    (``reference/control_sdar.py``) rounds it to a lower precision on the
    way."""
    clean, replays, where = _replays(hf, prompt, continuation,
                                     carried["reveal_pass"])
    B = int(hf["block_size"])
    if not reuse:
        rows = []
        for start, _p, block in replays:
            rows.append(jax.nn.log_softmax(forward(
                hf, params, clean[:start] + block)[start:], axis=-1))
        return jnp.stack([rows[r][b] for r, b in where])
    hf_items = tuple(sorted((k, v) for k, v in hf.items()
                            if isinstance(v, (int, float, str, bool))))
    embed = upcast(params["embed"])
    h = embed[jnp.asarray(clean, jnp.int32)]                        # [T, H]
    hr = embed[jnp.asarray([b for _s, _p, b in replays], jnp.int32)]
    del embed
    starts = jnp.asarray([s for s, _p, _b in replays], jnp.int32)
    T, R = h.shape[0], hr.shape[0]
    for _kind, stack, n in layers(params):
        for i in range(n):
            w = _layer_weights(stack, i, upcast)
            attn = {k: v for k, v in w.items() if k not in EXPERTS}
            h, k, v = _clean_attention(hf_items, attn, h)
            hr = _replay_attention(hf_items, attn, hr, starts, k, v)
            # one walk over the layer's experts for every row of both
            both = experts(hf, w, jnp.concatenate(
                [h, hr.reshape(R * B, -1)]), upcast)
            h, hr = both[:T], both[T:].reshape(R, B, -1)
    logp = jax.nn.log_softmax(
        head(hf, params, hr.reshape(R * B, -1), upcast), axis=-1)
    return logp[jnp.asarray([r * B + b for r, b in where], jnp.int32)]
