"""What a step of dots3-note-prev's family has to read and compute on ONE
RANK of its expert-parallel deployment, from the family's own keys
(``layer_types``, the ``swa_*`` and ``index_*`` keys; ``moe_cost.py``,
``longcat_cost.py``, ``gdn_cost.py`` and ``peaks.py`` read other families'
names) and from the program's counts. The per-layer readers of the
``dots3-note-prev`` cells divide these by measured time (``peaks.py`` has
the chip's peaks).

The three cache-reading mechanisms are counted FROM THE MATHEMATICS and not
from how the program runs them, so a later kernel is read on the same
yardstick and no share can pass 100 %:

- the indexer scores a query against every key it can SEE (the ring's
  ``score_pairs``): ``index_n_heads`` dot products of ``index_head_dim``
  and one weighted sum a pair; it reads each visible key once a ROW (a
  chunk's queries share them) - counted once a query-row below, from the
  dispatch's rows - and writes one score a pair;
- the latent attention of a full layer reads the SELECTED rows only (the
  ring's ``selected_keys``): a latent of ``kv_lora_rank`` and a rotary key
  of ``qk_rope_head_dim`` a key, and multiplies every head's query with
  them and the probabilities with the latent;
- the window attention multiplies ``min(sliding_window_size, p + 1)`` keys
  a query at ``p``, a wider latent and a rotary key each; a one-token row
  reads its window, a chunk's queries SHARE theirs (the chunk and the
  window before it: ``chunk_window_keys``), so a chunk is bound by its
  FLOPs.

In the configuration's file ``n_routed_experts`` counts the experts HELD
here (``ep_rank`` of ``ep_size``). A held expert that a step touched is
read whole, once; only picks computed here (the ring's
``moe_held_assignments``) are multiplied with an expert.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kinds(hf: dict) -> tuple:
    """(full-attention layers, window layers)."""
    types = hf["layer_types"][:hf["num_hidden_layers"]]
    return (sum(k == "full_attention" for k in types),
            sum(k == "sliding_attention" for k in types))


def attention_params(hf: dict, window: bool) -> int:
    """One latent-attention block's matrices at a kind's geometry: the
    compressed query, the latent, the out-projection, the gate, and a full
    layer's indexer."""
    p = "swa_" if window else ""
    H, n = hf["hidden_size"], hf[p + "num_attention_heads"]
    dn, dr = hf[p + "qk_nope_head_dim"], hf[p + "qk_rope_head_dim"]
    dv, dc, rq = hf[p + "v_head_dim"], hf[p + "kv_lora_rank"], \
        hf[p + "q_lora_rank"]
    params = (H * rq + rq * n * (dn + dr) + H * (dc + dr)
              + dc * n * (dn + dv) + n * dv * H + H * n)
    if not window:
        J, D = hf["index_n_heads"], hf["index_head_dim"]
        params += rq * J * D + H * D + H * J
    return params


def expert_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def experts_held(hf: dict) -> int:
    return hf["n_routed_experts"]


def router_width(hf: dict) -> int:
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf["n_routed_experts"] * ep


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def expert_slots(hf: dict) -> int:
    """Held experts of every sparse layer: what one forward pass could
    touch."""
    return expert_layers(hf) * experts_held(hf)


def fixed_params(hf: dict) -> int:
    """Every matrix outside the held experts and the head: each layer's
    attention, the dense FFNs, each sparse layer's router and shared
    expert. Each is read once a step, and every token is multiplied with
    each."""
    H = hf["hidden_size"]
    full, win = kinds(hf)
    K = hf.get("first_k_dense_replace", 0)
    return (full * attention_params(hf, False)
            + win * attention_params(hf, True)
            + K * 3 * H * hf["intermediate_size"]
            + expert_layers(hf) * (H * router_width(hf) + expert_params(hf)
                                   * hf.get("n_shared_experts", 0)))


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def total_params(hf: dict) -> int:
    """Parameters this rank holds: the matrices, the held experts, the
    embedding and the head (norm vectors and the router's bias left
    out)."""
    return (fixed_params(hf) + expert_slots(hf) * expert_params(hf)
            + 2 * head_params(hf))


# ------------------------------------------------------ the three mechanisms

def index_cost(hf: dict, dtype: str, pairs: float, row_keys: float) -> tuple:
    """(FLOPs, bytes) of ONE full layer's indexer over ``pairs`` (query,
    visible key) pairs whose rows hold ``row_keys`` keys in all: a dot
    product a head and the weighted sum; every row's keys in once, a
    float32 score a pair out."""
    J, D = hf["index_n_heads"], hf["index_head_dim"]
    return (pairs * J * (2.0 * D + 2.0),
            row_keys * D * _ITEMSIZE[dtype] + pairs * 4.0)


def sparse_attn_cost(hf: dict, dtype: str, selected: float) -> tuple:
    """(FLOPs, bytes) of ONE full layer's latent attention over
    ``selected`` (query, selected key) pairs in the absorbed form: every
    head's score against latent and rotary key, every head's probability
    times the latent; each selected row read once."""
    n = hf["num_attention_heads"]
    dc, dr = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    return (selected * n * 2.0 * (2 * dc + dr),
            selected * (dc + dr) * _ITEMSIZE[dtype])


def window_pairs(start: float, n: float, window: int) -> float:
    """(query, key) pairs of queries at ``start .. start + n`` that see
    ``min(window, p + 1)`` keys each."""
    whole = max(0.0, min(start + n, window) - start)
    return whole * (2 * start + whole + 1) / 2 + (n - whole) * window


def window_attn_cost(hf: dict, dtype: str, pairs: float,
                     keys: float = None) -> tuple:
    """(FLOPs, bytes) of ONE window layer's latent attention over
    ``pairs`` (query, key in the window) pairs that read ``keys`` cached
    keys (default ``pairs``: every query its own window, a one-token row's
    count; a chunk's queries share theirs - the window before the chunk
    and the chunk itself, ``chunk_window_keys``)."""
    n = hf["swa_num_attention_heads"]
    dc, dr = hf["swa_kv_lora_rank"], hf["swa_qk_rope_head_dim"]
    return (pairs * n * 2.0 * (2 * dc + dr),
            (pairs if keys is None else keys) * (dc + dr) * _ITEMSIZE[dtype])


def chunk_window_keys(hf: dict, tokens: float) -> float:
    """The cached keys a chunk of ``tokens`` consecutive queries has to
    read in one window layer: its own and the window before its first."""
    return tokens + hf["sliding_window_size"] - 1 if tokens > 0 else 0.0


def record_window_pairs(hf: dict, r: dict) -> float:
    """Window pairs of ONE window layer for a ring record: from the
    record's visible pairs and tokens alone the contexts are not known, so
    a query is credited a whole window unless it can see fewer keys than
    that on average (``score_pairs / tokens_real``)."""
    tokens = float(r.get("tokens_real", 0))
    if tokens <= 0:
        return 0.0
    seen = r.get("score_pairs", 0) / tokens
    return tokens * min(float(hf["sliding_window_size"]), seen)


# ----------------------------------------------------------- the whole step

def step_flops(hf: dict, tokens: float, held_assignments: float,
               sampled: float, score_pairs: float, selected: float,
               win_pairs: float) -> float:
    """Matrix-multiplication FLOPs of steps that ran ``tokens`` real
    tokens through the layers, computed ``held_assignments`` token-expert
    pairs here, scored ``score_pairs`` pairs in each full layer's indexer,
    attended ``selected`` selected keys in each full layer and
    ``win_pairs`` keys in each window layer, and took logits for
    ``sampled`` tokens."""
    full, win = kinds(hf)
    return (2.0 * (tokens * fixed_params(hf)
                   + held_assignments * expert_params(hf)
                   + sampled * head_params(hf))
            + full * (index_cost(hf, "bfloat16", score_pairs, 0)[0]
                      + sparse_attn_cost(hf, "bfloat16", selected)[0])
            + win * window_attn_cost(hf, "bfloat16", win_pairs)[0])


def page_bytes_per_token(hf: dict, dtype: str) -> int:
    """Page-pool bytes a token of context holds: in every full layer the
    latent and the rotary key, each padded to the latent width, and one
    index key."""
    full, _win = kinds(hf)
    return full * (2 * hf["kv_lora_rank"] + hf["index_head_dim"]) \
        * _ITEMSIZE[dtype]


def ring_positions(hf: dict, max_chunk: int) -> int:
    """Positions of a window ring: the window less one and the most tokens
    a row brings in one step, in steps of 128."""
    need = hf["sliding_window_size"] - 1 + max(1, max_chunk)
    return -(-need // 128) * 128


def window_bytes_per_sequence(hf: dict, dtype: str, max_chunk: int) -> int:
    """Bytes a sequence holds for its window layers, whatever its
    context, in the layout the program stores: a ring a layer of latents
    and of rotary keys padded to the latent's width."""
    _full, win = kinds(hf)
    return (win * ring_positions(hf, max_chunk)
            * 2 * hf["swa_kv_lora_rank"] * _ITEMSIZE[dtype])


def decode_step_bytes(hf: dict, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """Bytes one decode step of ``rows`` rows has to read beside the
    experts it touches: every matrix outside them once, the head, every
    row's index keys of its whole context (``context_tokens`` summed over
    rows) and its ``min(index_topk, context)`` selected latent rows in
    each full layer, its window in each window layer."""
    size = _ITEMSIZE[dtype]
    full, win = kinds(hf)
    ctx = context_tokens / max(1.0, rows)
    picked = rows * min(float(hf["index_topk"]), ctx)
    seen = rows * min(float(hf["sliding_window_size"]), ctx)
    return ((fixed_params(hf) + head_params(hf)) * size
            + full * (context_tokens * hf["index_head_dim"] * size
                      + sparse_attn_cost(hf, dtype, picked)[1])
            + win * window_attn_cost(hf, dtype, seen)[1])


def grouped_cost(hf: dict, dtype: str, touched: float,
                 held_assignments: float) -> tuple:
    """(FLOPs, bytes) of grouped-matmul calls that touched ``touched`` held
    experts (summed over calls) for ``held_assignments`` token-expert
    pairs."""
    flops = 2.0 * held_assignments * expert_params(hf)
    nbytes = (touched * expert_bytes(hf, dtype) + held_assignments
              * hf["hidden_size"] * (_ITEMSIZE[dtype] + 4))
    return flops, nbytes
