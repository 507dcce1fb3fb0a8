"""What the grouped expert layer and a decode step of a sigmoid- or
softmax-routed MLA model have to read and compute, from shapes and from the
number of experts the step touched. The per-layer readers of the
``joyai-llm-flash`` cells divide these by measured time (``peaks.py`` has
the chip's peaks; its ``weight_bytes`` counts every expert as read and
knows no compressed query, so it is not used here).

A touched expert is read whole, once: gate, up and down matrices. That is
the least the layer can read: which experts are touched is the router's
choice, not the kernel's.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def expert_slots(hf: dict) -> int:
    """Experts of every expert layer: what one forward pass could touch."""
    return expert_layers(hf) * hf["n_routed_experts"]


def grouped_rows(hf: dict, assignments: int) -> int:
    """Rows of the ``moe_grouped`` call that computes ``assignments``
    token-expert pairs (a step's token slots times the experts per token):
    ``models/moe.py`` sorts the pairs by expert and starts each expert's
    group on a tile boundary, so the call has the pairs' tiles and one more
    for every expert that can own a group; a tile is 16 rows up to 2,048
    pairs and 128 beyond. 16 rows x 8 experts of 256: 136 tiles, 2,176
    rows."""
    tile = 16 if assignments <= 2048 else 128
    return (-(-assignments // tile)
            + min(hf["n_routed_experts"], assignments)) * tile


def grouped_cost(hf: dict, dtype: str, touched: float,
                 assignments: float) -> tuple:
    """(FLOPs, bytes) of grouped-matmul calls that touched ``touched``
    experts (summed over calls) for ``assignments`` token-expert pairs:
    2 FLOPs per multiply-add of each pair through the three matrices; every
    touched expert's weights once, each pair's row in (``dtype``) and out
    (float32)."""
    flops = 2.0 * assignments * expert_params(hf)
    nbytes = (touched * expert_bytes(hf, dtype)
              + assignments * hf["hidden_size"] * (_ITEMSIZE[dtype] + 4))
    return flops, nbytes


def attention_params(hf: dict) -> int:
    """One layer's MLA matrices, with the compressed query where the
    configuration has one."""
    H, n = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, dc = hf["v_head_dim"], hf["kv_lora_rank"]
    rq = hf.get("q_lora_rank")
    q = H * rq + rq * n * (dn + dr) if rq else H * n * (dn + dr)
    return q + H * (dc + dr) + dc * n * (dn + dv) + n * dv * H


def decode_step_bytes(hf: dict, dtype: str, touched: float,
                      context_tokens: float) -> float:
    """Bytes one decode step has to read: every matrix outside the routed
    experts once (attention, the dense layers, router, shared experts, the
    output head), ``touched`` routed experts (summed over the expert
    layers), and the latent cache of ``context_tokens`` tokens of context
    (summed over rows) in the layout the program stores (latent and rotary
    key, each padded to the latent width, every layer)."""
    H, L = hf["hidden_size"], hf["num_hidden_layers"]
    k = hf.get("first_k_dense_replace", 0)
    Im = hf["moe_intermediate_size"]
    fixed = (L * attention_params(hf) + k * 3 * H * hf["intermediate_size"]
             + (L - k) * (H * hf["n_routed_experts"]
                          + 3 * H * Im * hf.get("n_shared_experts", 0))
             + hf["vocab_size"] * H)
    cache = context_tokens * L * 2 * hf["kv_lora_rank"]
    return (fixed + touched * expert_params(hf) + cache) * _ITEMSIZE[dtype]
