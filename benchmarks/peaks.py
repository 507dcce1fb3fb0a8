"""Published peaks of the chips the benchmark runs on, keyed by jax's
``device_kind``, and the bytes a decode step has to read, from shapes.

A device that is not in the table is an error, never a default.
"""

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def weight_bytes(hf: dict, dtype: str) -> int:
    """Bytes of every weight matrix a decode step reads once: all layers,
    the final projection, and one embedding row per sequence (ignored).
    A mixture layer computed densely (the program's default) reads every
    expert."""
    H, L, V = hf["hidden_size"], hf["num_hidden_layers"], hf["vocab_size"]
    n = hf["num_attention_heads"]
    if hf.get("kv_lora_rank"):
        dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        dv, dc = hf["v_head_dim"], hf["kv_lora_rank"]
        attn = (H * n * (dn + dr) + H * (dc + dr) + dc * n * (dn + dv)
                + n * dv * H)
        dense = 3 * H * hf["intermediate_size"]
        Im = hf["moe_intermediate_size"]
        moe = (H * hf["n_routed_experts"] + 3 * H * Im
               * (hf["n_routed_experts"] + hf.get("n_shared_experts", 0)))
        k = hf.get("first_k_dense_replace", 0)
        params = L * attn + k * dense + (L - k) * moe
    else:
        params = L * _dense_layer_params(hf)
    params += V * H            # the vocabulary projection (tied or not)
    return params * _ITEMSIZE[dtype]


def _dense_layer_params(hf: dict) -> int:
    """One llama-family layer: query, key, value and output projections
    and the three feed-forward matrices."""
    H, n = hf["hidden_size"], hf["num_attention_heads"]
    dh = hf.get("head_dim") or H // n
    nkv = hf.get("num_key_value_heads", n)
    return (2 * H * n * dh + 2 * H * nkv * dh
            + 3 * H * hf["intermediate_size"])


def active_params(hf: dict) -> tuple:
    """(parameters one token is multiplied with on its way through the
    layers, parameters of the vocabulary projection): every matrix of a
    dense layer; of an expert layer the attention, the router, the shared
    experts and the ``num_experts_per_tok`` routed experts a token is sent
    to. Two FLOPs each a token; attention's scores against the context are
    not in it."""
    H, L = hf["hidden_size"], hf["num_hidden_layers"]
    if hf.get("kv_lora_rank"):
        import moe_cost
        k = hf.get("first_k_dense_replace", 0)
        moe = (H * hf["n_routed_experts"] + moe_cost.expert_params(hf)
               * (hf["num_experts_per_tok"] + hf.get("n_shared_experts", 0)))
        layers = (L * moe_cost.attention_params(hf)
                  + k * 3 * H * hf["intermediate_size"] + (L - k) * moe)
    else:
        layers = L * _dense_layer_params(hf)
    return layers, hf["vocab_size"] * H


def kv_bytes_per_token(hf: dict, dtype: str) -> int:
    """Cache bytes one decode step reads per token of context, in the
    layout the program stores: K and V per kv head, or for MLA the latent
    and the rotary key each padded to the latent width."""
    L = hf["num_hidden_layers"]
    if hf.get("kv_lora_rank"):
        per = 2 * hf["kv_lora_rank"]
    else:
        n = hf["num_attention_heads"]
        dh = hf.get("head_dim") or hf["hidden_size"] // n
        per = 2 * hf.get("num_key_value_heads", n) * dh
    return L * per * _ITEMSIZE[dtype]
