"""What a step of Keye-VL-2.0's language model has to read and compute, from
the family's own keys (``sa_config``, the Qwen3-MoE block's sizes) and from
the program's counts. The per-layer readers of the ``keye-vl-2.0-30b-a3b``
cells divide these by measured time (``peaks.py`` has the chip's peaks; its
``active_params`` reads ``intermediate_size`` as a dense feed-forward width,
which this model does not have, and knows no indexer).

The two cache-reading mechanisms are counted FROM THE MATHEMATICS and not
from how the program runs them, so a masked kernel reads its true, low
share, a later kernel that fetches the selected rows is read on the same
yardstick, and no share can pass 100 %:

- the indexer scores a query against every key it can SEE (the ring's
  ``score_pairs``): ``indexer_num_heads`` dot products of
  ``indexer_head_dim`` and one weighted sum a pair; it reads each visible
  index key once a ROW (a chunk's queries share them) and writes one score
  a pair;
- the grouped-query attention reads the SELECTED tokens only (the ring's
  ``selected_keys``): a key and a value of ``head_dim`` for each of the
  ``num_key_value_heads`` a selected token a query, and multiplies every
  query head with them.

A touched expert is read whole, once: gate, up and down matrices; which
experts are touched is the router's choice, not the kernel's.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def experts(hf: dict) -> int:
    return hf.get("num_local_experts") or hf["num_experts"]


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def expert_slots(hf: dict) -> int:
    """Experts of every layer: what one forward pass could touch."""
    return hf["num_hidden_layers"] * experts(hf)


def attention_params(hf: dict) -> int:
    """One layer's query, key, value and output projections."""
    H, dh = hf["hidden_size"], _head_dim(hf)
    return (2 * H * hf["num_attention_heads"] * dh
            + 2 * H * hf["num_key_value_heads"] * dh)


def indexer_params(hf: dict) -> int:
    """One layer's indexer: the index queries, the one index key, the head
    weights (the key's LayerNorm vectors left out)."""
    sa, H = hf["sa_config"], hf["hidden_size"]
    J, D = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return H * J * D + H * D + H * J


def fixed_params(hf: dict) -> int:
    """Every matrix outside the routed experts and the head: attention,
    indexer and router of every layer. Each is read once a step, and every
    token is multiplied with each."""
    return hf["num_hidden_layers"] * (
        attention_params(hf) + indexer_params(hf)
        + hf["hidden_size"] * experts(hf))


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def total_params(hf: dict) -> int:
    """Parameters the chip holds: the matrices, every expert, the embedding
    and the head (norm vectors left out)."""
    return (fixed_params(hf) + expert_slots(hf) * expert_params(hf)
            + 2 * head_params(hf))


def active_params(hf: dict) -> tuple:
    """(parameters one token is multiplied with on its way through the
    layers - attention, indexer, router, the ``num_experts_per_tok``
    experts it is sent to - and parameters of the vocabulary projection).
    Two FLOPs each a token; the indexer's and the attention's scores
    against the context are not in it."""
    return (fixed_params(hf) + hf["num_hidden_layers"]
            * hf["num_experts_per_tok"] * expert_params(hf),
            head_params(hf))


def cache_bytes_per_token(hf: dict, dtype: str) -> int:
    """Page-pool bytes a token of context holds: in every layer a key and
    a value a key/value head, and one index key."""
    return hf["num_hidden_layers"] * (
        2 * hf["num_key_value_heads"] * _head_dim(hf)
        + hf["sa_config"]["indexer_head_dim"]) * _ITEMSIZE[dtype]


# ------------------------------------------------------- the two mechanisms

def index_cost(hf: dict, dtype: str, pairs: float, row_keys: float) -> tuple:
    """(FLOPs, bytes) of ONE layer's indexer over ``pairs`` (query, visible
    key) pairs whose rows hold ``row_keys`` keys in all: a dot product a
    head and the weighted sum; every row's keys in once, a float32 score a
    pair out."""
    sa = hf["sa_config"]
    J, D = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (pairs * J * (2.0 * D + 2.0),
            row_keys * D * _ITEMSIZE[dtype] + pairs * 4.0)


def sparse_attn_cost(hf: dict, dtype: str, selected: float) -> tuple:
    """(FLOPs, bytes) of ONE layer's grouped-query attention over
    ``selected`` (query, selected token) pairs: every query head's score
    against its key and its probability times its value; each selected
    token's keys and values (every key/value head) read once a query."""
    dh = _head_dim(hf)
    return (selected * hf["num_attention_heads"] * 4.0 * dh,
            selected * 2 * hf["num_key_value_heads"] * dh * _ITEMSIZE[dtype])


def record_row_keys(r: dict) -> float:
    """Index keys the rows of a ring record hold between them, from its
    counts: a decode step's (a fused block's, a step a time) rows each
    bring one query that sees its whole context, so the keys ARE the pairs;
    a prefill-carrying step's chunk queries share their row's keys - the
    mean pairs a token, once a row."""
    pairs = float(r.get("score_pairs", 0))
    if r["kind"] in ("prefill", "mixed"):
        tokens = float(r.get("tokens_real", 0))
        return pairs * r.get("rows", 0) / tokens if tokens else 0.0
    return pairs


def grouped_cost(hf: dict, dtype: str, touched: float,
                 assignments: float) -> tuple:
    """(FLOPs, bytes) of grouped-matmul calls that touched ``touched``
    experts (summed over calls) for ``assignments`` token-expert pairs."""
    flops = 2.0 * assignments * expert_params(hf)
    nbytes = (touched * expert_bytes(hf, dtype)
              + assignments * hf["hidden_size"] * (_ITEMSIZE[dtype] + 4))
    return flops, nbytes


def decode_step_bytes(hf: dict, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """Bytes one decode step of ``rows`` rows has to read beside the
    experts it touches: every matrix outside them once, the head, and in
    every layer each row's index keys of its whole context
    (``context_tokens`` summed over rows) and the keys and values of its
    ``min(topk, context)`` selected tokens."""
    size = _ITEMSIZE[dtype]
    L, sa = hf["num_hidden_layers"], hf["sa_config"]
    ctx = context_tokens / max(1.0, rows)
    picked = rows * min(float(sa["topk"]), ctx)
    return ((fixed_params(hf) + head_params(hf)) * size
            + L * (context_tokens * sa["indexer_head_dim"] * size
                   + sparse_attn_cost(hf, dtype, picked)[1]))
