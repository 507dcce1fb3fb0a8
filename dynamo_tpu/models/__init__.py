"""Model family implementations (pure-functional jax).

Each family exposes ``init_params(cfg, rng)``, ``make_pages`` (the stacked
paged KV cache ``[L, N, 2, Hkv, page_size, Dh]``) and ONE ``forward``: a
``lax.scan`` over the layers against that cache, whose attention op is an
argument (``attn_impl``). ``get_family(cfg)`` maps a config to its
implementation: configs with linear-attention layers
(``full_attention_interval > 0``) use ``models.qwen3_next`` (with experts:
Qwen3-Next) or ``models.olmo_hybrid`` (a dense FFN: Olmo-Hybrid), whose
``make_pages`` returns the paged pool AND the recurrent-state pools;
configs with window layers beside latent attention (``layer_types`` holding
``sliding_attention``: dots3-note) use ``models.dots3``, whose
``make_pages`` returns latent pages, index pages and window rings;
double-layer configs (``attn_blocks_per_layer == 2``:
LongCat-Flash) use ``models.longcat``, other MLA configs (``kv_lora_rank >
0``) ``models.deepseek``,
other MoE configs (``num_experts > 0``: mixtral / qwen3_moe routing; with
``index_topk`` - ``sa_config``, Keye-VL-2.0 - behind a learned selection,
their cache a tree of key/value and index pages on one block chain)
``models.moe``, gemma-2 ``models.gemma``; everything else in the Llama tree
(llama 2/3, mistral, qwen2/qwen3) uses ``models.llama``.
"""

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import forward, init_params, make_pages


def get_family(cfg: ModelConfig):
    """Return the module implementing this config's model family."""
    if cfg.full_attention_interval:
        # Gated DeltaNet linear-attention layers with a recurrent state
        # beside the paged cache and full attention every
        # ``full_attention_interval``-th layer: the sparse family
        # (Qwen3-Next: gated attention, an expert layer) or the dense one
        # (Olmo-Hybrid: branches normed on their way out, attention
        # without positions, a dense FFN)
        from dynamo_tpu.models import olmo_hybrid, qwen3_next
        return qwen3_next if cfg.num_experts else olmo_hybrid
    if cfg.window_layers:
        # latent attention of two geometries: full layers that attend a
        # learned selection through an indexer, window layers over a ring
        from dynamo_tpu.models import dots3
        return dots3
    if cfg.attn_blocks_per_layer == 2:
        # LongCat-Flash: double layers of latent attention around a
        # shortcut-connected expert branch with zero-compute experts
        from dynamo_tpu.models import longcat
        return longcat
    if cfg.kv_lora_rank:
        # MLA (deepseek v2/v3): latent paged cache, absorbed attention
        from dynamo_tpu.models import deepseek
        return deepseek
    if cfg.num_experts:
        from dynamo_tpu.models import moe
        return moe
    if cfg.model_type == "gemma2":
        # only gemma-2 is implemented; gemma-1/gemma-3 differ (norm
        # layout, qk-norm, dual rope thetas) and must not silently load
        from dynamo_tpu.models import gemma
        return gemma
    if cfg.model_type.startswith("gemma"):
        raise NotImplementedError(
            f"model_type {cfg.model_type!r}: only gemma2 is implemented")
    from dynamo_tpu.models import llama
    return llama


__all__ = ["ModelConfig", "forward", "init_params", "make_pages",
           "get_family"]
