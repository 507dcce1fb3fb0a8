"""What a step of the LongCat-Flash family has to read and compute on ONE
RANK of its expert-parallel deployment, from this family's own keys
(``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``; ``moe_cost.py`` and ``peaks.py`` read
DeepSeek's names) and from the program's counts. The per-layer readers of
the ``longcat-flash-omni`` cells divide these by measured time (``peaks.py``
has the chip's peaks).

A layer is two latent-attention blocks, two dense SwiGLU FFNs and one
router outside the experts. In the configuration's file ``n_routed_experts``
counts the experts HELD here (``ep_rank`` of ``ep_size``). A held expert
that a step touched is read whole, once: gate, up and down matrices. A pick
of an expert held elsewhere and a pick of a zero-compute expert cost no
FLOP and no weight byte here; only picks computed here (the ring's
``moe_held_assignments``) are multiplied with an expert.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["expert_ffn_hidden_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def experts_held(hf: dict) -> int:
    return hf["n_routed_experts"]


def router_width(hf: dict) -> int:
    """The router's outputs: every computing expert of the model, held
    here or not, and the zero-compute ones."""
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf["n_routed_experts"] * ep + hf.get("zero_expert_num", 0)


def expert_slots(hf: dict) -> int:
    """Held experts of every layer: what one forward pass could touch."""
    return hf["num_layers"] * experts_held(hf)


def attention_params(hf: dict) -> int:
    """One latent-attention block's matrices, compressed query included."""
    H, n = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dv, dc, rq = hf["v_head_dim"], hf["kv_lora_rank"], hf["q_lora_rank"]
    return (H * rq + rq * n * (dn + dr) + H * (dc + dr)
            + dc * n * (dn + dv) + n * dv * H)


def fixed_params(hf: dict) -> int:
    """Every matrix outside the experts and the head: of each layer two
    attention blocks, two dense FFNs and the router. Each is read once a
    step, and every token is multiplied with each."""
    H = hf["hidden_size"]
    return hf["num_layers"] * (2 * attention_params(hf)
                               + 2 * 3 * H * hf["ffn_hidden_size"]
                               + H * router_width(hf))


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def cache_layers(hf: dict) -> int:
    """Two attention blocks a layer, a cache layer each."""
    return 2 * hf["num_layers"]


def kv_bytes_per_token(hf: dict, dtype: str) -> int:
    """Cache bytes a decode step reads per token of context, in the layout
    the program stores: the latent and the rotary key, each padded to the
    latent width, every cache layer."""
    return cache_layers(hf) * 2 * hf["kv_lora_rank"] * _ITEMSIZE[dtype]


def step_flops(hf: dict, tokens: float, held_assignments: float,
               sampled: float) -> float:
    """Matrix-multiplication FLOPs of steps that ran ``tokens`` real tokens
    through the layers, computed ``held_assignments`` token-expert pairs
    here and took logits for ``sampled`` tokens: 2 FLOPs for every
    parameter met. Identity picks and picks held elsewhere are zero;
    attention's scores against the context are left out (counted low)."""
    return 2.0 * (tokens * fixed_params(hf)
                  + held_assignments * expert_params(hf)
                  + sampled * head_params(hf))


def decode_step_bytes(hf: dict, dtype: str, context_tokens: float) -> float:
    """Bytes one decode step has to read beside the experts it touches:
    every matrix outside them once, the output head, and the latent cache
    of ``context_tokens`` tokens of context (summed over rows)."""
    return ((fixed_params(hf) + head_params(hf)) * _ITEMSIZE[dtype]
            + context_tokens * kv_bytes_per_token(hf, dtype))


def grouped_rows(hf: dict, tokens: int) -> int:
    """Rows of the ``moe_grouped`` call of a step with ``tokens`` token
    slots (``models/moe.py``): a token's ``moe_topk`` distinct picks can
    send at most ``min(moe_topk, held)`` of them here, which bounds the
    rows; each held expert's group starts on a tile boundary, so one more
    tile for every expert that can own a group; a tile is 16 rows up to
    2,048 picks in all and 128 beyond. 128 rows x 12 of 16 held: 112 tiles
    of 16, 1,792 rows; 512 slots: 64 tiles of 128, 8,192 rows."""
    k, held = hf["moe_topk"], experts_held(hf)
    tile = 16 if tokens * k <= 2048 else 128
    bound = tokens * min(k, held)
    return (-(-bound // tile) + min(held, bound)) * tile


def grouped_cost(hf: dict, dtype: str, touched: float,
                 held_assignments: float) -> tuple:
    """(FLOPs, bytes) of grouped-matmul calls that touched ``touched`` held
    experts (summed over calls) for ``held_assignments`` token-expert
    pairs: 2 FLOPs per multiply-add of each pair through the three
    matrices; every touched expert's weights once, each pair's row in
    (``dtype``) and out (float32)."""
    flops = 2.0 * held_assignments * expert_params(hf)
    nbytes = (touched * expert_bytes(hf, dtype) + held_assignments
              * hf["hidden_size"] * (_ITEMSIZE[dtype] + 4))
    return flops, nbytes
