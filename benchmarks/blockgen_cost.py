"""What a forward pass of a softmax-routed grouped-query model that
generates by diffusion over blocks (``sdar_moe``) has to read and compute,
from shapes and from the number of experts the pass touched. The per-layer
readers of the ``sdar-30b-a3b-chat`` cells divide these by measured time
(``peaks.py`` has the chip's peaks; its ``active_params`` reads this
family's ``intermediate_size`` as a dense feed-forward width, which the
model does not have, so the counts live here).

A pass runs every live row's block of ``block_size`` positions through
every layer and takes logits at every one of them. A touched expert is
read whole, once: gate, up and down matrices; which experts are touched is
the router's choice, not the kernel's.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def expert_slots(hf: dict) -> int:
    """Experts of every layer: what one forward pass could touch."""
    return hf["num_hidden_layers"] * hf["num_experts"]


def attention_params(hf: dict) -> int:
    """One layer's query, key, value and output projections."""
    H, n = hf["hidden_size"], hf["num_attention_heads"]
    dh = hf.get("head_dim") or H // n
    return 2 * H * n * dh + 2 * H * hf["num_key_value_heads"] * dh


def fixed_params(hf: dict) -> int:
    """Every matrix outside the routed experts and the head: attention and
    router of every layer."""
    return hf["num_hidden_layers"] * (
        attention_params(hf) + hf["hidden_size"] * hf["num_experts"])


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def active_params(hf: dict) -> tuple:
    """(parameters one position is multiplied with on its way through the
    layers - attention, router, the ``num_experts_per_tok`` experts it is
    sent to - and parameters of the vocabulary projection). Two FLOPs each
    a position; attention's scores against the context are not in it."""
    return (fixed_params(hf) + hf["num_hidden_layers"]
            * hf["num_experts_per_tok"] * expert_params(hf),
            head_params(hf))


def grouped_rows(hf: dict, assignments: int) -> int:
    """Rows of the ``moe_grouped`` call that computes ``assignments``
    position-expert pairs (``models/moe.py``: the pairs' tiles and one more
    for every expert that can own a group; a tile is 16 rows up to 2,048
    pairs and 128 beyond). 32 rows x 4 positions x 8 experts of 128: 192
    tiles, 3,072 rows."""
    tile = 16 if assignments <= 2048 else 128
    return (-(-assignments // tile)
            + min(hf["num_experts"], assignments)) * tile


def grouped_cost(hf: dict, dtype: str, touched: float,
                 assignments: float) -> tuple:
    """(FLOPs, bytes) of grouped-matmul calls that touched ``touched``
    experts (summed over calls) for ``assignments`` pairs: 2 FLOPs per
    multiply-add of each pair through the three matrices; every touched
    expert's weights once, each pair's row in (``dtype``) and out
    (float32)."""
    flops = 2.0 * assignments * expert_params(hf)
    nbytes = (touched * expert_bytes(hf, dtype)
              + assignments * hf["hidden_size"] * (_ITEMSIZE[dtype] + 4))
    return flops, nbytes


def kv_bytes_per_token(hf: dict, dtype: str) -> int:
    """Cache bytes a pass reads per token of context: K and V of every kv
    head of every layer."""
    dh = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"] * dh
            * _ITEMSIZE[dtype])


def pass_bytes(hf: dict, dtype: str, context_tokens: float) -> float:
    """Bytes one pass has to read beside the experts it touches: every
    matrix outside them once, the output head, and the live cache of
    ``context_tokens`` tokens of context (summed over rows)."""
    return ((fixed_params(hf) + head_params(hf)) * _ITEMSIZE[dtype]
            + context_tokens * kv_bytes_per_token(hf, dtype))
