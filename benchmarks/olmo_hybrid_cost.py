"""What a step of the dense hybrid family (Olmo-Hybrid) has to read and
compute, from the family's own keys (``layer_types``, ``linear_*``,
``intermediate_size``; ``gdn_cost.py`` reads the sparse family's
``full_attention_interval`` and ``num_experts``, which this family's file
does not have) and from the program's counts. The per-layer readers of the
``olmo-hybrid-7b`` cells divide these by measured time (``peaks.py`` has the
chip's peaks).

The gated delta rule is counted FROM THE RULE, by ``gdn_cost``'s own count
(``7 Dk Dv`` FLOPs a token a head; a token's q, k, v, g, beta in and o out,
each row's state once in and once out a call), at this family's geometry
and never from a kernel's padded tiles: 96 x 192 is 18,432 elements a
state, not the 128 x 256 a VMEM tile holds.
"""

import gdn_cost

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def linear_layers(hf: dict) -> int:
    return sum(k == "linear_attention" for k in hf["layer_types"])


def full_layers(hf: dict) -> int:
    return sum(k == "full_attention" for k in hf["layer_types"])


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def gdn_mixer_params(hf: dict) -> int:
    """One Gated DeltaNet mixer: the projections q, k, v, z (gate), a, b,
    the convolution's taps, ``A_log``, ``dt_bias``, the output norm and the
    output projection."""
    H, Hv = hf["hidden_size"], hf["linear_num_value_heads"]
    value = Hv * hf["linear_value_head_dim"]
    conv = gdn_cost.conv_channels(hf)
    return (H * (conv + value) + H * 2 * Hv
            + hf["linear_conv_kernel_dim"] * conv + 2 * Hv
            + hf["linear_value_head_dim"] + value * H)


def full_mixer_params(hf: dict) -> int:
    """One full-attention mixer: q, k, v, o and the two whole-width q/k
    norms."""
    H, d = hf["hidden_size"], head_dim(hf)
    q, kv = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    return H * q + 2 * H * kv + q * H + q + kv


def ffn_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def layer_params(hf: dict, kind: str) -> int:
    """One whole layer (``kind``: ``gdn``/``full``): mixer, FFN and the two
    branch norms."""
    mixer = gdn_mixer_params(hf) if kind == "gdn" else full_mixer_params(hf)
    return mixer + ffn_params(hf) + 2 * hf["hidden_size"]


def head_params(hf: dict) -> int:
    return hf["vocab_size"] * hf["hidden_size"]


def fixed_params(hf: dict) -> int:
    """Every parameter outside the embedding and the head: read once a
    step, and every token is multiplied with each."""
    return (linear_layers(hf) * layer_params(hf, "gdn")
            + full_layers(hf) * layer_params(hf, "full"))


def total_params(hf: dict) -> int:
    tied = 1 if hf.get("tie_word_embeddings") else 2
    return fixed_params(hf) + tied * head_params(hf) + hf["hidden_size"]


def sequence_state_bytes(hf: dict) -> int:
    """What one sequence keeps of recurrent state: a float32 ``[heads, Dk,
    Dv]`` a linear layer."""
    return linear_layers(hf) * gdn_cost.state_bytes(hf)


def kv_bytes_per_token(hf: dict, dtype: str) -> int:
    """Paged-cache bytes of one token: K and V of the full layers alone."""
    return (full_layers(hf) * 2 * hf["num_key_value_heads"] * head_dim(hf)
            * _ITEMSIZE[dtype])


def rule_cost(hf: dict, dtype: str, tokens: float, rows: float) -> tuple:
    """``gdn_cost.rule_cost`` at this geometry: (FLOPs, bytes) of the
    rule's calls that took ``tokens`` tokens of ``rows`` rows through ONE
    layer each (summed over calls and layers)."""
    return gdn_cost.rule_cost(hf, dtype, tokens, rows)


def score_flops(hf: dict, pairs: float) -> float:
    """FLOPs of the full layers' causal attention for ``pairs`` (query,
    key) pairs of one layer (the ring's ``score_pairs``): the score and the
    weighted sum, every query head."""
    return (4.0 * pairs * hf["num_attention_heads"] * head_dim(hf)
            * full_layers(hf))


def attn_pair_bytes(hf: dict, dtype: str) -> int:
    """What ``paged_decode`` has to read for one (query, key) pair of one
    full layer: the key and the value of every key/value head."""
    return 2 * hf["num_key_value_heads"] * head_dim(hf) * _ITEMSIZE[dtype]


def step_flops(hf: dict, tokens: float, sampled: float,
               score_pairs: float) -> float:
    """FLOPs of steps that ran ``tokens`` real tokens through the layers,
    scored ``score_pairs`` query-key pairs a full layer and took logits for
    ``sampled`` tokens: 2 for every parameter met, the rule's own in the
    linear layers, the attention scores, the head."""
    return (2.0 * (tokens * fixed_params(hf) + sampled * head_params(hf))
            + tokens * linear_layers(hf) * gdn_cost.rule_flops_per_token(hf)
            + score_flops(hf, score_pairs))


def decode_step_bytes(hf: dict, dtype: str, state_bytes: float,
                      context_tokens: float) -> float:
    """Bytes one decode step has to move: every matrix outside the
    embedding once (the layers and the head), the recurrent state it read
    and wrote (the ring's ``state_bytes`` of that step) and the paged cache
    of ``context_tokens`` tokens of context (summed over rows)."""
    return ((fixed_params(hf) + head_params(hf)) * _ITEMSIZE[dtype]
            + state_bytes + context_tokens * kv_bytes_per_token(hf, dtype))
