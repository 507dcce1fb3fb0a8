"""What a step of the Qwen3-Next family has to read and compute on ONE RANK
of its expert-parallel deployment, from the family's own keys
(``full_attention_interval``, ``linear_*``, ``num_experts``,
``shared_expert_intermediate_size``; ``moe_cost.py``, ``longcat_cost.py``
and ``peaks.py`` read other families' names) and from the program's counts.
The per-layer readers of the ``qwen3-next-80b-a3b-instruct`` cells divide
these by measured time (``peaks.py`` has the chip's peaks).

The gated delta rule is counted FROM THE RULE and not from a kernel, so a
later kernel with another chunking is read on the same yardstick. For one
token and one value head with a state ``S [Dk, Dv]``::

    S <- exp(g) S          Dk Dv multiplications
    u  = beta (v - S^T k)  Dk Dv multiply-adds
    S <- S + k u^T         Dk Dv multiply-adds
    o  = S^T q             Dk Dv multiply-adds

``7 Dk Dv`` FLOPs (2 a multiply-add). A call moves each token's ``q``,
``k`` (a key head each), ``v`` in the served dtype, ``g`` and ``beta`` in
float32 in, ``o`` in float32 out, and each row's state once in and once
out, float32.

In the configuration's file ``num_experts`` counts the experts HELD here
(``ep_rank`` of ``ep_size``). A held expert that a step touched is read
whole, once; only picks computed here (the ring's
``moe_held_assignments``) are multiplied with an expert.
"""

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def periods(hf: dict) -> int:
    return hf["num_hidden_layers"] // hf["full_attention_interval"]


def linear_layers(hf: dict) -> int:
    """Gated DeltaNet layers: all but one a period."""
    return periods(hf) * (hf["full_attention_interval"] - 1)


def full_layers(hf: dict) -> int:
    return periods(hf)


def conv_channels(hf: dict) -> int:
    """q | k | v: what the linear layers' convolution runs over."""
    return (2 * hf["linear_num_key_heads"] * hf["linear_key_head_dim"]
            + hf["linear_num_value_heads"] * hf["linear_value_head_dim"])


def gdn_mixer_params(hf: dict) -> int:
    """One Gated DeltaNet mixer's matrices and vectors."""
    H, Hv = hf["hidden_size"], hf["linear_num_value_heads"]
    value = Hv * hf["linear_value_head_dim"]
    return (H * (conv_channels(hf) + value) + H * 2 * Hv
            + hf["linear_conv_kernel_dim"] * conv_channels(hf) + 2 * Hv
            + hf["linear_value_head_dim"] + value * H)


def full_mixer_params(hf: dict) -> int:
    """One gated full-attention mixer: the query projection is twice as
    wide (query + output gate), and the two per-head norms."""
    H, n, d = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    nkv = hf["num_key_value_heads"]
    return H * 2 * n * d + 2 * H * nkv * d + n * d * H + 2 * d


def expert_params(hf: dict) -> int:
    """Parameters of one routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict, dtype: str) -> int:
    return expert_params(hf) * _ITEMSIZE[dtype]


def experts_held(hf: dict) -> int:
    return hf["num_experts"]


def router_width(hf: dict) -> int:
    ep = hf.get("ep_size", 1) if "ep_rank" in hf else 1
    return hf["num_experts"] * ep


def ffn_fixed_params(hf: dict) -> int:
    """A layer's two stream norms, router, shared expert and its gate."""
    H = hf["hidden_size"]
    return (2 * H + H * router_width(hf)
            + 3 * H * hf["shared_expert_intermediate_size"] + H)


def layer_params(hf: dict, kind: str) -> int:
    """One whole layer as this rank holds it (``kind``: ``gdn``/``full``)."""
    mixer = gdn_mixer_params(hf) if kind == "gdn" else full_mixer_params(hf)
    return mixer + ffn_fixed_params(hf) + experts_held(hf) * expert_params(hf)


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def total_params(hf: dict) -> int:
    """Everything this rank holds: the layers, embedding, head (untied)
    and the final norm."""
    tied = 1 if hf.get("tie_word_embeddings") else 2
    return (linear_layers(hf) * layer_params(hf, "gdn")
            + full_layers(hf) * layer_params(hf, "full")
            + tied * head_params(hf) + hf["hidden_size"])


def fixed_params(hf: dict) -> int:
    """Every parameter outside the experts, the embedding and the head:
    read once a step, and every token is multiplied with each."""
    return (linear_layers(hf) * gdn_mixer_params(hf)
            + full_layers(hf) * full_mixer_params(hf)
            + hf["num_hidden_layers"] * ffn_fixed_params(hf))


def expert_slots(hf: dict) -> int:
    """Held experts of every layer: what one forward pass could touch."""
    return hf["num_hidden_layers"] * experts_held(hf)


# ------------------------------------------------------------ the rule

def rule_flops_per_token(hf: dict) -> int:
    """The recurrence's own FLOPs of one token through ONE linear layer."""
    return (7 * hf["linear_key_head_dim"] * hf["linear_value_head_dim"]
            * hf["linear_num_value_heads"])


def state_bytes(hf: dict) -> int:
    """One row's recurrent state of one linear layer, float32."""
    return (hf["linear_num_value_heads"] * hf["linear_key_head_dim"]
            * hf["linear_value_head_dim"] * 4)


def conv_state_bytes(hf: dict, dtype: str) -> int:
    """One row's carried convolution inputs of one linear layer."""
    return ((hf["linear_conv_kernel_dim"] - 1) * conv_channels(hf)
            * _ITEMSIZE[dtype])


def rule_token_bytes(hf: dict, dtype: str) -> int:
    """What one token moves through the rule of one layer: q, k, v in,
    g and beta (float32) in, o (float32) out."""
    Hv = hf["linear_num_value_heads"]
    return (conv_channels(hf) * _ITEMSIZE[dtype] + 2 * Hv * 4
            + Hv * hf["linear_value_head_dim"] * 4)


def rule_cost(hf: dict, dtype: str, tokens: float, rows: float) -> tuple:
    """(FLOPs, bytes) of the rule's calls that took ``tokens`` tokens of
    ``rows`` rows through ONE layer each (sum over calls and layers):
    each row's state once in and once out a call."""
    return (float(tokens) * rule_flops_per_token(hf),
            float(tokens) * rule_token_bytes(hf, dtype)
            + float(rows) * 2 * state_bytes(hf))


# -------------------------------------------------------- the whole step

def kv_bytes_per_token(hf: dict, dtype: str) -> int:
    """Paged-cache bytes a decode step reads per token of context: K and V
    of the full-attention layers alone."""
    return (full_layers(hf) * 2 * hf["num_key_value_heads"]
            * hf["head_dim"] * _ITEMSIZE[dtype])


def score_flops(hf: dict, pairs: float) -> float:
    """FLOPs of the full layers' causal attention for ``pairs`` (query,
    key) pairs of one layer (the ring's ``score_pairs``): the score and the
    weighted sum, every query head."""
    return (4.0 * pairs * hf["num_attention_heads"] * hf["head_dim"]
            * full_layers(hf))


def step_flops(hf: dict, tokens: float, held_assignments: float,
               sampled: float, score_pairs: float) -> float:
    """FLOPs of steps that ran ``tokens`` real tokens through the layers,
    computed ``held_assignments`` token-expert pairs here, scored
    ``score_pairs`` query-key pairs a full layer and took logits for
    ``sampled`` tokens: 2 for every parameter met outside the experts and
    for every pick through its expert, the rule's own, the attention
    scores, the head."""
    return (2.0 * (tokens * fixed_params(hf)
                   + held_assignments * expert_params(hf)
                   + sampled * head_params(hf))
            + tokens * linear_layers(hf) * rule_flops_per_token(hf)
            + score_flops(hf, score_pairs))


def decode_step_bytes(hf: dict, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """Bytes one decode step of ``rows`` rows has to move beside the
    experts it touches: every matrix outside them once, the head, the
    paged cache of ``context_tokens`` tokens of context (summed over
    rows), and every row's state and carried convolution inputs of every
    linear layer, read and written."""
    return ((fixed_params(hf) + head_params(hf)) * _ITEMSIZE[dtype]
            + context_tokens * kv_bytes_per_token(hf, dtype)
            + rows * linear_layers(hf) * 2 * (
                state_bytes(hf) + conv_state_bytes(hf, dtype)))


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
