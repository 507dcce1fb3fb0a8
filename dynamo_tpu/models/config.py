"""Model architecture config, constructed from HF ``config.json``.

Capability parity: reference ``lib/llm/src/model_card/model.rs:87-230`` reads
HF config for context length / arch metadata; here the config additionally
drives the native jax model (the reference never builds the model itself).

Covers the Llama family tree: llama/llama-3, mistral, qwen2/qwen3 (qwen3 adds
per-head q/k RMS norm), the MoE variants (mixtral/qwen3_moe/deepseek-style
``num_experts``/``top_k`` routing) handled by ``models/moe.py``, and the
gemma-2 family (GeGLU, sandwich norms, logit softcaps, alternating
sliding-window layers) handled by ``models/gemma.py``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


SA_CONFIG_KEYS = ("indexer_head_dim", "indexer_num_heads",
                  "indexer_num_kv_heads", "topk")


def _selection(hf: Dict[str, Any], mla: bool, num_experts: int
               ) -> Dict[str, Any]:
    """``sa_config`` of a file of the llama tree as config fields: an
    indexer of ``indexer_num_heads`` heads of ``indexer_head_dim`` over ONE
    index key a token that keeps the best ``topk`` visible tokens, in every
    layer, in front of the grouped-query cache (``models/moe.py``).
    ``q_chunk_size`` / ``kv_chunk_size`` are the tiling in which the
    published code evaluates the scores and change no selection: read and
    dropped. Anything else the loader cannot honour is an error that names
    the key."""
    def no(key, why):
        raise NotImplementedError(
            f"sa_config {key} {(hf.get('sa_config') or {}).get(key)!r}: "
            f"{why} (models/moe.py)")
    sa = hf["sa_config"]
    if not isinstance(sa, dict):
        raise NotImplementedError(
            f"sa_config {sa!r}: a mapping with "
            + ", ".join(SA_CONFIG_KEYS) + " is implemented (models/moe.py)")
    for key in SA_CONFIG_KEYS:
        if not sa.get(key):
            no(key, "the selection needs every one of "
                    + ", ".join(SA_CONFIG_KEYS))
    for key in sorted(sa):
        if key not in SA_CONFIG_KEYS + ("q_chunk_size", "kv_chunk_size"):
            no(key, "a key of the selection this loader does not implement")
    if int(sa["indexer_num_kv_heads"]) != 1:
        no("indexer_num_kv_heads", "one index key a token, shared by "
                                   "every index head, is implemented")
    if int(sa["indexer_head_dim"]) % 2:
        no("indexer_head_dim", "rotary turns the index heads in halves")
    if mla or not num_experts:
        raise NotImplementedError(
            "sa_config: the learned selection is implemented in front of "
            "the grouped-query cache of the expert family (models/moe.py), "
            "not beside " + ("kv_lora_rank (latent attention selects "
                             "through the index_* keys, models/dots3.py)"
                             if mla else "a dense FFN"))
    if hf.get("use_sliding_window") or hf.get("sliding_window"):
        raise NotImplementedError(
            "sa_config beside a sliding window: every layer attends its "
            "selection of the whole context (models/moe.py)")
    return dict(index_n_heads=int(sa["indexer_num_heads"]),
                index_head_dim=int(sa["indexer_head_dim"]),
                index_topk=int(sa["topk"]))


def _plain_rotary(hf: Dict[str, Any]) -> None:
    """Raise where a file of the llama tree asks for rotary positions this
    loader would silently not give it. Implemented: no ``rope_scaling``,
    or type ``default`` - with or without ``mrope_section``, the split of a
    head's rotary pairs over three position streams (time, height, width)
    of a multimodal model: text alone feeds the three the SAME position,
    and the rotation is then plain rotary whatever the split
    (``tests/test_keye.py`` holds it)."""
    rs = hf.get("rope_scaling")
    if not rs:
        return
    rtype = rs.get("rope_type", rs.get("type"))
    if rtype not in (None, "default"):
        raise NotImplementedError(
            f"rope_scaling type {rtype!r}: outside the latent-attention "
            "family (yarn) only plain rotary positions are implemented "
            "(type 'default' or none)")
    for key in sorted(rs):
        if key not in ("rope_type", "type", "mrope_section",
                       "mrope_interleaved"):
            raise NotImplementedError(
                f"rope_scaling {key} {rs[key]!r}: plain rotary positions "
                "take no parameter")


def _topk_method(hf: Dict[str, Any], model_type: str) -> str:
    """The MLA family's gate, from the config's own keys first:
    ``topk_method``, else ``scoring_func`` (sigmoid scores are the
    aux-loss-free ``noaux_tc`` gate's). Only a config that says neither
    falls back on its name: HF's DeepseekV3Config serialises no
    ``topk_method``. A pair the gates do not implement is an error, not a
    silent softmax."""
    scoring = hf.get("scoring_func")
    method = hf.get("topk_method") or (
        "noaux_tc" if scoring == "sigmoid" or (
            scoring is None and model_type == "deepseek_v3") else "greedy")
    want = "sigmoid" if method == "noaux_tc" else "softmax"
    if scoring not in (None, want):
        raise NotImplementedError(
            f"topk_method {method!r} with scoring_func {scoring!r} (the "
            f"{method} gate scores with {want})")
    return method


# the per-layer kinds ``layer_types`` may hold, and where each is built
IMPLEMENTED_LAYER_KINDS = ("linear_attention", "full_attention",
                           "sliding_attention")


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # 0.0: the family applies no rotary embedding at all (the dense hybrid
    # of ``models/olmo_hybrid.py``, whose linear layers carry the order)
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    qk_norm: bool = False          # qwen3-style per-head q/k RMSNorm
    attention_bias: bool = False   # qwen2-style qkv bias
    model_type: str = "llama"
    dtype: str = "bfloat16"
    # MoE (0 experts => dense MLP)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # expert compute: "grouped" sorts the assignments by expert and runs
    # one grouped matmul over the groups that exist — exact, no capacity,
    # no drop (models/moe.py grouped_experts); "dispatch" gathers each
    # expert's routed tokens into a fixed-capacity buffer pinned to the ep
    # axis and drops past capacity (the wide-EP path)
    moe_backend: str = "grouped"
    # dispatch capacity per expert = ceil(T * k / E * this); tokens routed
    # past capacity are dropped (their combine weight is zero) — the
    # standard GShard/Switch overflow semantics
    moe_capacity_factor: float = 2.0
    # DeepSeek V2/V3 MLA + MoE shape (models/deepseek.py). kv_lora_rank
    # > 0 selects the MLA family: the KV cache stores the compressed
    # latent (+ the shared rope key) instead of per-head K/V.
    q_lora_rank: int = 0               # 0 = direct q projection
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0     # leading dense (non-MoE) layers
    routed_scaling_factor: float = 1.0
    topk_method: str = "greedy"        # greedy | group_limited_greedy
    n_group: int = 1
    topk_group: int = 1
    # YaRN rope scaling (real DeepSeek checkpoints ship
    # rope_scaling={type: yarn, ...}); factor 0 = disabled
    rope_scaling_factor: float = 0.0
    rope_orig_max_position: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0
    rope_mscale_all_dim: float = 0.0
    rope_attention_factor: float = 0.0  # 0 = infer from factor/mscale
    # deepseek rope convention: True = complex-pair interleaved (the HF
    # default for this family), False = llama-style rotate-half halves
    rope_interleave: bool = True
    # gemma-2 family (models/gemma.py)
    sliding_window: int = 0            # 0 = all layers global attention
    attn_logit_softcap: float = 0.0    # 0 = disabled
    final_logit_softcap: float = 0.0
    query_pre_attn_scalar: float = 0.0  # 0 = use head_dim
    # how the model generates. "causal": one next token a row a step.
    # "block_diffusion" (sdar, sdar_moe): positions are cut into blocks of
    # ``gen_block`` from 0, a query sees every key of its own and earlier
    # blocks, the logits at a position score the token AT that position,
    # and a block is denoised in place - passes over ``[rows, gen_block]``
    # reveal masked positions (``mask_token_id`` stands in for them) by
    # confidence, then one pass commits the finished block's keys and
    # values (``engine/scheduler.py`` ``GenPassBatch``)
    generation: str = "causal"
    gen_block: int = 1
    mask_token_id: int = -1
    # LongCat-Flash family (models/longcat.py): a layer holds TWO latent
    # attention blocks and two dense FFNs around one expert branch, so the
    # paged cache has ``attn_blocks_per_layer`` cache layers a layer
    # (``num_cache_layers``: what the cache is sized by, everywhere)
    attn_blocks_per_layer: int = 1
    # the router's last ``zero_expert_num`` outputs compute nothing: a
    # pick of one adds the token itself times its weight ("identity", the
    # one kind implemented); ``num_experts`` counts the computing ones
    zero_expert_num: int = 0
    zero_expert_type: str = "identity"
    # which experts live here: rank ``ep_rank`` of ``ep_size`` holds the
    # contiguous ``experts_held`` from ``expert_offset`` on; the router
    # keeps its whole width, picks of experts held elsewhere add nothing
    # here (1 holds all)
    ep_size: int = 1
    ep_rank: int = 0
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: the compressed query's
    # projection and the normed kv latent are multiplied by
    # sqrt(hidden / rank) (1.0 = the DeepSeek form)
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    # Families with linear-attention layers (sparse: models/qwen3_next.py;
    # dense: models/olmo_hybrid.py, whose loader derives the interval from
    # ``layer_types``): layer ``i`` is full
    # attention where ``(i + 1) % full_attention_interval == 0`` and a Gated
    # DeltaNet linear-attention layer otherwise (0 = every layer attends:
    # every other family). A linear layer keeps no pages: a request carries
    # one recurrent state ``[linear_num_value_heads, linear_key_head_dim,
    # linear_value_head_dim]`` float32 and the convolution's last
    # ``linear_conv_kernel_dim - 1`` inputs a layer, in a slot of the state
    # pool beside the paged cache (``state_layers`` of them)
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    # ``linear_allow_neg_eigval``: the rule's write strength is ``beta = 2
    # sigmoid(b)`` in (0, 2) - the transition ``I - beta k k^T`` then has
    # the eigenvalue ``1 - beta`` in (-1, 1) and may reflect - where it is
    # ``sigmoid(b)`` in (0, 1) otherwise
    linear_allow_neg_eigval: bool = False
    # rotary position embedding turns the first ``partial_rotary_factor``
    # of a head's dimensions only
    partial_rotary_factor: float = 1.0
    # one always-on expert beside the routed ones, its output gated by a
    # sigmoid of the token (0 = none)
    shared_expert_intermediate_size: int = 0
    # latent attention of two geometries in one model (models/dots3.py):
    # ``layer_types[i]`` is ``"full_attention"`` (the geometry of the
    # fields above; with ``index_topk`` an indexer of ``index_n_heads``
    # heads of ``index_head_dim`` scores every visible token and the
    # latent attention reads the best ``index_topk`` only, out of index
    # pages beside the latent pages) or ``"sliding_attention"`` (the
    # ``swa_*`` geometry over the last ``swa_window`` tokens, the query's
    # own among them, kept in a ring a sequence whose size does not grow
    # with its context). () = every layer the same: every other family
    # Without window layers or a latent (``sa_config`` of a file of the
    # llama tree, ``_selection``): EVERY layer of an expert model selects,
    # in front of its grouped-query pages (``models/moe.py``); the index
    # pages share the block chain of the keys and values, there is no
    # slot, and the prefix cache stays on
    layer_types: tuple = ()
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    swa_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # ``attention_gate_type`` / ``swa_attention_gate_type`` "headwise": a
    # head's attention output is multiplied by a sigmoid of the token
    attn_gate: bool = False
    swa_attn_gate: bool = False

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def num_periods(self) -> int:
        """Periods of ``full_attention_interval`` layers (linear layers,
        then one full-attention layer); 0 for a family without."""
        return (self.num_layers // self.full_attention_interval
                if self.full_attention_interval else 0)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state and no pages."""
        return self.num_periods * max(self.full_attention_interval - 1, 0)

    @property
    def window_layers(self) -> int:
        """Layers that keep a ring of ``swa_window`` tokens a sequence
        and no pages."""
        return sum(k == "sliding_attention" for k in self.layer_types)

    @property
    def slot_kind(self) -> str:
        """What a sequence keeps in a slot of fixed size beside its page
        chain: ``"recurrent_state"`` (linear-attention layers),
        ``"window_cache"`` (window layers), ``""`` for a family whose
        cache is its pages."""
        if self.state_layers:
            return "recurrent_state"
        return "window_cache" if self.window_layers else ""

    @property
    def num_cache_layers(self) -> int:
        """Layers of the paged pool: the attention blocks that keep keys
        and values (the full-attention layers alone where the others are
        linear or see a window)."""
        return ((self.num_layers - self.state_layers - self.window_layers)
                * self.attn_blocks_per_layer)

    @property
    def layer_kinds(self) -> tuple:
        """``"linear_attention"`` / ``"sliding_attention"`` /
        ``"full_attention"`` a layer."""
        if self.layer_types:
            return self.layer_types
        n = self.full_attention_interval
        return tuple("linear_attention" if n and (i + 1) % n
                     else "full_attention" for i in range(self.num_layers))

    def paged_only(self, what: str) -> None:
        """Raise, by the family's name, where ``what`` moves block chains
        of the paged cache only: a request of a family with linear or
        window layers is its pages AND its slot (``slot_kind``), a block
        of a family that selects is its keys and values AND its index
        keys, and a chain without the matching slot or index keys is a
        wrong answer, not a slow one."""
        if self.state_layers:
            raise NotImplementedError(
                f"model_type {self.model_type!r} keeps a recurrent state "
                f"beside the paged cache ({self.state_layers} "
                f"linear-attention layers): {what} moves block chains only "
                "and cannot move a state yet")
        if self.index_topk and not self.window_layers:
            raise NotImplementedError(
                f"model_type {self.model_type!r} keeps index pages beside "
                "the pages of its keys and values (a learned selection of "
                f"{self.index_topk} tokens, one block chain for both "
                f"pools): {what} moves the pages of one pool only, and a "
                "chain without its index keys selects by zeros")
        if self.window_layers:
            raise NotImplementedError(
                f"model_type {self.model_type!r} keeps a window cache "
                f"beside the paged cache ({self.window_layers} "
                f"sliding-attention layers, a ring of {self.swa_window} "
                f"tokens a sequence) and index pages: {what} moves block "
                "chains of one pool only and cannot move either yet")

    def window_cfg(self) -> "ModelConfig":
        """The window layers' latent attention as a config of its own:
        the ``swa_*`` geometry in the fields ``models/deepseek.py``'s
        shared functions read (heads, ranks, head sizes, theta and the two
        rescales)."""
        import dataclasses
        scaled = self.mla_q_scale != 1.0 or self.mla_kv_scale != 1.0
        H = self.hidden_size
        return dataclasses.replace(
            self, num_heads=self.swa_num_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            head_dim=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim,
            rope_theta=self.swa_rope_theta,
            mla_q_scale=((H / self.swa_q_lora_rank) ** 0.5
                         if scaled else 1.0),
            mla_kv_scale=((H / self.swa_kv_lora_rank) ** 0.5
                          if scaled else 1.0),
            attn_gate=self.swa_attn_gate)

    @property
    def linear_conv_dim(self) -> int:
        """Channels the linear layers' convolution runs over: q | k | v."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.ep_size

    @property
    def expert_offset(self) -> int:
        return self.ep_rank * self.experts_held

    @property
    def num_expert_layers(self) -> int:
        return ((self.num_layers - self.first_k_dense_replace)
                if self.num_experts else 0)

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def __post_init__(self):
        if self.moe_backend not in ("grouped", "dispatch"):
            raise ValueError(
                f"moe_backend {self.moe_backend!r}: 'grouped' (the exact "
                "expert layer) or 'dispatch' (capacity-factor, wide-EP)")
        if self.generation not in ("causal", "block_diffusion"):
            raise ValueError(f"generation {self.generation!r}: 'causal' or "
                             "'block_diffusion'")
        if (self.generation == "block_diffusion") != (self.gen_block > 1):
            raise ValueError(
                f"generation {self.generation!r} with gen_block "
                f"{self.gen_block}: block diffusion needs a block of at "
                "least 2 positions and a causal model has none")
        if self.gen_block > 1 and not (
                0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                f"mask_token_id {self.mask_token_id} outside the "
                f"vocabulary of {self.vocab_size}")
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise NotImplementedError(
                f"zero_expert_type {self.zero_expert_type!r}: only "
                "'identity' zero-compute experts are implemented")
        if self.ep_size < 1 or self.num_experts % self.ep_size:
            raise ValueError(
                f"ep_size {self.ep_size} does not divide the "
                f"{self.num_experts} routed experts")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_rank {self.ep_rank} outside ep_size {self.ep_size}")
        if self.layer_types:
            self.layer_pattern()
        n = self.full_attention_interval
        if n and (n < 2 or self.num_layers % n):
            raise ValueError(
                f"full_attention_interval {n} does not cut "
                f"{self.num_layers} layers into whole periods of at least "
                "one linear and one full-attention layer")

    def layer_pattern(self) -> tuple:
        """``(G, P, tail)`` of a model with window layers: after the
        ``first_k_dense_replace`` leading layers (full attention, a dense
        FFN) come ``P`` periods of one full-attention layer and ``G``
        window layers, then ``tail`` (0 or 1) full-attention layers -
        what ``models/dots3.py`` scans over. Any other ``layer_types`` is
        an error that names it."""
        kinds, K = tuple(self.layer_types), self.first_k_dense_replace
        bad = set(kinds) - set(IMPLEMENTED_LAYER_KINDS[1:])
        rest = kinds[K:]
        G = 0
        while 1 + G < len(rest) and rest[1 + G] == "sliding_attention":
            G += 1
        period = ("full_attention",) + ("sliding_attention",) * G
        P = len(rest) // len(period)
        tail = len(rest) - P * len(period)
        if (bad or len(kinds) != self.num_layers or not G or not P
                or tail > 1 or any(k != "full_attention" for k in kinds[:K])
                or rest != period * P + period[:tail]):
            raise NotImplementedError(
                f"layer_types {list(kinds)}: after first_k_dense_replace "
                f"({K}) full-attention layers, whole periods of one "
                "full_attention and some sliding_attention layers (and at "
                "most one more full_attention layer) are implemented "
                "(models/dots3.py)")
        return G, P, tail

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], dtype: str = "bfloat16") -> "ModelConfig":
        if "expert_ffn_hidden_size" in hf and "ffn_hidden_size" in hf:
            return cls._from_longcat(hf, dtype)
        kinds = set(hf.get("layer_types") or ())
        if ("full_attention_interval" in hf
                or "linear_attention" in kinds
                or any(k.startswith("linear_") for k in hf)):
            # two families keep linear-attention layers: the sparse one
            # states an interval (and experts), the dense one lists its
            # layers and has no expert key at all
            dense = ("full_attention_interval" not in hf and not any(
                k in hf for k in ("num_experts", "num_local_experts",
                                  "n_routed_experts")))
            return (cls._from_olmo_hybrid if dense
                    else cls._from_qwen3_next)(hf, dtype)
        if ("sliding_attention" in kinds and hf.get("kv_lora_rank")) or any(
                k.startswith(("swa_", "index_")) for k in hf):
            return cls._from_dots3(hf, dtype)
        if kinds - {"full_attention"} and not str(
                hf.get("model_type", "")).startswith("gemma"):
            # a per-layer kind this loader would silently drop: the file
            # describes another model than the one it would build
            raise NotImplementedError(
                f"layer_types holds {sorted(kinds - {'full_attention'})}: "
                "the layer kinds implemented are "
                f"{list(IMPLEMENTED_LAYER_KINDS)} (linear_attention in the "
                "pattern full_attention_interval gives, models/"
                "qwen3_next.py; sliding_attention as latent attention "
                "beside kv_lora_rank and the swa_* keys, models/dots3.py)")
        heads = hf["num_attention_heads"]
        mt = hf.get("model_type", "llama")
        num_experts = hf.get("num_local_experts", hf.get("num_experts", 0)) or 0
        extra: Dict[str, Any] = {}
        # the MLA family is read off the keys that make it one, never off
        # a model_type string: a public model with these keys under
        # another name (joyai_llm_flash, ...) is the same architecture
        if hf.get("kv_lora_rank"):
            num_experts = hf.get("n_routed_experts", 0) or 0
            extra = dict(
                q_lora_rank=int(hf.get("q_lora_rank") or 0),
                kv_lora_rank=int(hf.get("kv_lora_rank") or 0),
                qk_rope_head_dim=int(hf.get("qk_rope_head_dim") or 0),
                qk_nope_head_dim=int(hf.get("qk_nope_head_dim") or 0),
                v_head_dim=int(hf.get("v_head_dim") or 0),
                n_shared_experts=int(hf.get("n_shared_experts") or 0),
                first_k_dense_replace=int(
                    hf.get("first_k_dense_replace") or 0),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor") or 1.0),
                topk_method=_topk_method(hf, mt),
                n_group=int(hf.get("n_group") or 1),
                topk_group=int(hf.get("topk_group") or 1),
            )
            rs = hf.get("rope_scaling") or {}
            rtype = rs.get("rope_type", rs.get("type"))
            if rtype == "yarn":
                extra.update(
                    rope_scaling_factor=float(rs.get("factor") or 1.0),
                    rope_orig_max_position=int(
                        rs.get("original_max_position_embeddings") or 0),
                    rope_beta_fast=float(rs.get("beta_fast") or 32.0),
                    rope_beta_slow=float(rs.get("beta_slow") or 1.0),
                    rope_mscale=float(rs.get("mscale") or 0.0),
                    rope_mscale_all_dim=float(
                        rs.get("mscale_all_dim") or 0.0),
                    rope_attention_factor=float(
                        rs.get("attention_factor") or 0.0),
                )
            elif rtype is not None:
                raise NotImplementedError(
                    f"MLA rope_scaling type {rtype!r} (only yarn is "
                    "implemented)")
            extra["rope_interleave"] = bool(
                hf.get("rope_interleave", True))
        mla = bool(extra.get("kv_lora_rank"))
        if not mla:
            _plain_rotary(hf)
        # a learned selection in front of the grouped-query cache, read off
        # the key that makes it one (``sa_config``), never off a model_type
        selects = "sa_config" in hf
        if selects:
            extra.update(_selection(hf, mla, num_experts))
        if mt in ("sdar", "sdar_moe"):
            # generation by diffusion over blocks (the Qwen3 / Qwen3-MoE
            # block under a block-wise visibility). The published config
            # carries neither size: ``block_size`` and ``mask_token_id``
            # are read from the file's keys where it states them
            extra.update(
                generation="block_diffusion",
                gen_block=int(hf.get("block_size")
                              or hf.get("block_length") or 4),
                mask_token_id=int(hf.get("mask_token_id", 151669)))
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            # MLA: the paged cache stores ONE shared latent per token —
            # [N, 2, 1, ps, kv_lora_rank], slot 0 = compressed kv latent,
            # slot 1 = the (padded) shared rope key — so the generic cache
            # machinery sizes from Hkv=1 x head_dim=kv_lora_rank
            num_kv_heads=1 if mla else hf.get("num_key_value_heads", heads),
            head_dim=(extra["kv_lora_rank"] if mla
                      else hf.get("head_dim") or hf["hidden_size"] // heads),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            # transformers omits fields equal to its per-arch defaults:
            # gemma ties embeddings by default and serializes nothing
            tie_word_embeddings=bool(hf.get("tie_word_embeddings",
                                            mt.startswith("gemma"))),
            # per-head q/k norm: the Qwen3 block's, by the names that are
            # it - and by what the file carries where it carries
            # ``sa_config`` (a Qwen3-MoE block under another name, whose
            # ``qk_norm`` key, where present, has the last word)
            qk_norm=(bool(hf.get("qk_norm", True)) if selects else
                     mt in ("qwen3", "qwen3_moe", "sdar", "sdar_moe")),
            attention_bias=bool(hf.get("attention_bias", mt == "qwen2")),
            model_type=mt,
            dtype=dtype,
            num_experts=num_experts,
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            moe_intermediate_size=hf.get("moe_intermediate_size",
                                         hf.get("intermediate_size", 0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            sliding_window=int(hf.get("sliding_window") or 0)
            if mt.startswith("gemma") else 0,
            attn_logit_softcap=float(hf.get("attn_logit_softcapping") or 0.0),
            final_logit_softcap=float(
                hf.get("final_logit_softcapping") or 0.0),
            query_pre_attn_scalar=float(
                hf.get("query_pre_attn_scalar") or 0.0),
            **extra,
        )

    @classmethod
    def _from_longcat(cls, hf: Dict[str, Any], dtype: str) -> "ModelConfig":
        """The LongCat-Flash family, read off its OWN key names
        (``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
        ``moe_topk``, ``zero_expert_num``, ...), never off a model_type.

        Which experts a model directory holds: a published file holds all
        ``n_routed_experts``. A directory written for one rank of an
        expert-parallel deployment says so with ``ep_rank`` (which no
        published file carries) beside ``ep_size``; ``n_routed_experts``
        then counts the experts HELD, and the router's width is rebuilt as
        ``n_routed_experts * ep_size + zero_expert_num``."""
        if (hf.get("attention_method") or "MLA") != "MLA":
            raise NotImplementedError(
                f"attention_method {hf['attention_method']!r} (MLA only)")
        H = int(hf["hidden_size"])
        ep_size = int(hf.get("ep_size") or 1) if "ep_rank" in hf else 1
        q_rank, kv_rank = int(hf["q_lora_rank"]), int(hf["kv_lora_rank"])
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=H,
            intermediate_size=int(hf["ffn_hidden_size"]),
            num_layers=int(hf["num_layers"]),
            num_heads=int(hf["num_attention_heads"]),
            num_kv_heads=1,             # the latent page layout, as above
            head_dim=kv_rank,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            attention_bias=bool(hf.get("attention_bias", False)),
            model_type=hf.get("model_type", "longcat_flash"),
            dtype=dtype,
            num_experts=int(hf["n_routed_experts"]) * ep_size,
            num_experts_per_tok=int(hf["moe_topk"]),
            moe_intermediate_size=int(hf["expert_ffn_hidden_size"]),
            norm_topk_prob=False,       # the picked scores stay as they are
            q_lora_rank=q_rank,
            kv_lora_rank=kv_rank,
            qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]),
            routed_scaling_factor=float(
                hf.get("routed_scaling_factor") or 1.0),
            rope_interleave=bool(hf.get("rope_interleave", True)),
            attn_blocks_per_layer=2,
            zero_expert_num=int(hf.get("zero_expert_num") or 0),
            zero_expert_type=hf.get("zero_expert_type") or "identity",
            ep_size=ep_size,
            ep_rank=int(hf.get("ep_rank") or 0),
            mla_q_scale=((H / q_rank) ** 0.5
                         if hf.get("mla_scale_q_lora") else 1.0),
            mla_kv_scale=((H / kv_rank) ** 0.5
                          if hf.get("mla_scale_kv_lora") else 1.0),
        )

    @classmethod
    def _from_qwen3_next(cls, hf: Dict[str, Any], dtype: str) -> "ModelConfig":
        """The Qwen3-Next family, read off its own keys
        (``full_attention_interval``, ``linear_*``, ``partial_rotary_factor``,
        ``shared_expert_intermediate_size``), never off a model_type. What
        of the published keys the family does not implement is an error
        that names the key, not a plain attention model built from the
        keys this loader knows.

        As for LongCat, a directory written for one rank of an
        expert-parallel deployment says so with ``ep_rank`` beside
        ``ep_size``: ``num_experts`` then counts the experts HELD and the
        router's width is ``num_experts * ep_size``."""
        def no(key, why):
            raise NotImplementedError(
                f"{key} {hf.get(key)!r}: {why} (models/qwen3_next.py)")
        wanted = ("full_attention_interval", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel_dim")
        for key in wanted:
            if not hf.get(key):
                no(key, "the linear-attention family needs every one of "
                        + ", ".join(wanted))
        n, L = int(hf["full_attention_interval"]), int(hf["num_hidden_layers"])
        kinds = hf.get("layer_types")
        if kinds is not None and list(kinds) != [
                "linear_attention" if (i + 1) % n else "full_attention"
                for i in range(L)]:
            no("layer_types", "only the pattern full_attention_interval "
                              "gives is implemented")
        if int(hf.get("decoder_sparse_step") or 1) != 1:
            no("decoder_sparse_step", "every layer's FFN is the sparse "
                                      "block")
        if hf.get("mlp_only_layers"):
            no("mlp_only_layers", "every layer's FFN is the sparse block")
        if hf.get("use_sliding_window"):
            no("use_sliding_window", "the full-attention layers see their "
                                     "whole context")
        if hf.get("rope_scaling"):
            no("rope_scaling", "plain rotary positions only")
        if (hf.get("hidden_act") or "silu") != "silu":
            no("hidden_act", "SwiGLU experts")
        if int(hf["linear_num_value_heads"]) % int(
                hf["linear_num_key_heads"]):
            no("linear_num_value_heads", "a key head serves a whole number "
                                         "of value heads")
        if not hf.get("num_experts"):
            no("num_experts", "the family's FFN is the sparse block")
        ep_size = int(hf.get("ep_size") or 1) if "ep_rank" in hf else 1
        heads = int(hf["num_attention_heads"])
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=int(hf["hidden_size"]),
            intermediate_size=int(hf.get("intermediate_size") or 0),
            num_layers=L,
            num_heads=heads,
            num_kv_heads=int(hf.get("num_key_value_heads", heads)),
            head_dim=int(hf.get("head_dim")
                         or hf["hidden_size"] // heads),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            qk_norm=True,
            attention_bias=bool(hf.get("attention_bias", False)),
            model_type=hf.get("model_type", "qwen3_next"),
            dtype=dtype,
            num_experts=int(hf["num_experts"]) * ep_size,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            ep_size=ep_size,
            ep_rank=int(hf.get("ep_rank") or 0),
            full_attention_interval=n,
            linear_num_key_heads=int(hf["linear_num_key_heads"]),
            linear_num_value_heads=int(hf["linear_num_value_heads"]),
            linear_key_head_dim=int(hf["linear_key_head_dim"]),
            linear_value_head_dim=int(hf["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(hf["linear_conv_kernel_dim"]),
            partial_rotary_factor=float(
                hf.get("partial_rotary_factor") or 1.0),
            shared_expert_intermediate_size=int(
                hf.get("shared_expert_intermediate_size") or 0),
            linear_allow_neg_eigval=bool(
                hf.get("linear_allow_neg_eigval", False)),
        )

    @classmethod
    def _from_olmo_hybrid(cls, hf: Dict[str, Any], dtype: str) -> "ModelConfig":
        """The dense hybrid family (Olmo-Hybrid: Gated DeltaNet layers and
        full attention without positions, a dense FFN in every layer),
        read off its own keys - ``layer_types`` holding ``linear_attention``,
        the ``linear_*`` sizes, ``linear_allow_neg_eigval``, no
        ``full_attention_interval`` and no expert key - never off a
        model_type. ``layer_types`` has to be whole periods of some linear
        layers and one full-attention layer: the period's length is kept as
        ``full_attention_interval``, which every property of the layer
        pattern reads. A key the family cannot place is an error that
        names it."""
        def no(key, why):
            raise NotImplementedError(
                f"{key} {hf.get(key)!r}: {why} (models/olmo_hybrid.py)")
        wanted = ("layer_types", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel_dim",
                  "intermediate_size")
        for key in wanted:
            if not hf.get(key):
                no(key, "the dense linear-attention family needs every one "
                        "of " + ", ".join(wanted))
        for key in sorted(hf):
            if key.startswith("linear_") and key not in wanted + (
                    "linear_allow_neg_eigval",):
                no(key, "a linear-attention key this family does not "
                        "implement")
        kinds, L = list(hf["layer_types"]), int(hf["num_hidden_layers"])
        n = kinds.index("full_attention") + 1 if "full_attention" in kinds \
            else 0
        if n < 2 or len(kinds) != L or L % n or kinds != (
                ["linear_attention"] * (n - 1) + ["full_attention"]) * (L // n):
            no("layer_types", f"{L} layers in whole periods of some "
                              "linear_attention layers and one "
                              "full_attention layer are implemented")
        theta = (hf.get("rope_parameters") or {}).get("rope_theta",
                                                      hf.get("rope_theta"))
        if theta is not None:
            no("rope_theta", "the family's full attention has no positions "
                             "(rope_theta null): a base to rotate by "
                             "describes another model")
        if (hf.get("rope_parameters") or {}).get("rope_type") not in (
                None, "default") or hf.get("rope_scaling"):
            no("rope_scaling" if hf.get("rope_scaling")
               else "rope_parameters", "no rotary embedding, so none to "
                                       "scale")
        if hf.get("attention_bias"):
            no("attention_bias", "projections without bias")
        if (hf.get("hidden_act") or "silu") != "silu":
            no("hidden_act", "a SwiGLU FFN")
        if hf.get("sliding_window") or hf.get("use_sliding_window"):
            no("sliding_window", "the full-attention layers see their "
                                 "whole context")
        if int(hf["linear_num_value_heads"]) % int(
                hf["linear_num_key_heads"]):
            no("linear_num_value_heads", "a key head serves a whole number "
                                         "of value heads")
        heads = int(hf["num_attention_heads"])
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=int(hf["hidden_size"]),
            intermediate_size=int(hf["intermediate_size"]),
            num_layers=L,
            num_heads=heads,
            num_kv_heads=int(hf.get("num_key_value_heads") or heads),
            head_dim=int(hf.get("head_dim")
                         or hf["hidden_size"] // heads),
            rope_theta=0.0,
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            qk_norm=True,               # over the whole width, not a head
            model_type=hf.get("model_type", "olmo_hybrid"),
            dtype=dtype,
            full_attention_interval=n,
            linear_num_key_heads=int(hf["linear_num_key_heads"]),
            linear_num_value_heads=int(hf["linear_num_value_heads"]),
            linear_key_head_dim=int(hf["linear_key_head_dim"]),
            linear_value_head_dim=int(hf["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(hf["linear_conv_kernel_dim"]),
            linear_allow_neg_eigval=bool(
                hf.get("linear_allow_neg_eigval", False)),
        )

    @classmethod
    def _from_dots3(cls, hf: Dict[str, Any], dtype: str) -> "ModelConfig":
        """Latent attention of two geometries in one model, read off its
        own keys (``layer_types`` holding ``sliding_attention`` beside
        ``kv_lora_rank``, the ``swa_*`` and ``index_*`` keys), never off a
        model_type. A file that has some of those keys and lacks one the
        family needs, or asks for what the family does not implement, is
        an error that names the key, never a model built without it.

        As for LongCat, a directory written for one rank of an
        expert-parallel deployment says so with ``ep_rank`` beside
        ``ep_size``: ``n_routed_experts`` then counts the experts HELD
        and the router's width is ``n_routed_experts * ep_size``."""
        def no(key, why):
            raise NotImplementedError(
                f"{key} {hf.get(key)!r}: {why} (models/dots3.py)")
        wanted = ("layer_types", "kv_lora_rank", "q_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "index_n_heads", "index_head_dim", "index_topk",
                  "sliding_window_size", "swa_num_attention_heads",
                  "swa_q_lora_rank", "swa_kv_lora_rank",
                  "swa_qk_nope_head_dim", "swa_qk_rope_head_dim",
                  "swa_v_head_dim", "swa_rope_theta", "n_routed_experts",
                  "moe_intermediate_size", "num_experts_per_tok")
        for key in wanted:
            if not hf.get(key):
                no(key, "the family of full layers with an indexer and "
                        "window layers of their own geometry needs every "
                        "one of " + ", ".join(wanted))
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if hf.get(key) not in (None, "none", "headwise"):
                no(key, "only the headwise output gate is implemented")
        if hf.get("rope_scaling"):
            no("rope_scaling", "plain rotary positions only")
        if (hf.get("hidden_act") or "silu") != "silu":
            no("hidden_act", "SwiGLU experts")
        if int(hf.get("moe_layer_freq") or 1) != 1:
            no("moe_layer_freq", "every layer after the dense ones is "
                                 "sparse")
        if int(hf["index_head_dim"]) < int(hf["qk_rope_head_dim"]):
            no("index_head_dim", "an index head holds the rotary "
                                 "dimensions and more")
        mt = hf.get("model_type", "dots3_note")
        H = int(hf["hidden_size"])
        ep_size = int(hf.get("ep_size") or 1) if "ep_rank" in hf else 1
        q_rank, kv_rank = int(hf["q_lora_rank"]), int(hf["kv_lora_rank"])
        rescale = bool(hf.get("apply_mla_qkv_lora_rescale"))
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=H,
            intermediate_size=int(hf["intermediate_size"]),
            num_layers=int(hf["num_hidden_layers"]),
            num_heads=int(hf["num_attention_heads"]),
            num_kv_heads=1,             # the latent page layout, as above
            head_dim=kv_rank,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            attention_bias=bool(hf.get("attention_bias", False)),
            model_type=mt,
            dtype=dtype,
            num_experts=int(hf["n_routed_experts"]) * ep_size,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            q_lora_rank=q_rank,
            kv_lora_rank=kv_rank,
            qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            first_k_dense_replace=int(hf.get("first_k_dense_replace") or 0),
            routed_scaling_factor=float(
                hf.get("routed_scaling_factor") or 1.0),
            topk_method=_topk_method(hf, mt),
            n_group=int(hf.get("n_group") or 1),
            topk_group=int(hf.get("topk_group") or 1),
            rope_interleave=bool(hf.get("rope_interleave", True)),
            ep_size=ep_size,
            ep_rank=int(hf.get("ep_rank") or 0),
            mla_q_scale=(H / q_rank) ** 0.5 if rescale else 1.0,
            mla_kv_scale=(H / kv_rank) ** 0.5 if rescale else 1.0,
            layer_types=tuple(hf["layer_types"]),
            index_n_heads=int(hf["index_n_heads"]),
            index_head_dim=int(hf["index_head_dim"]),
            index_topk=int(hf["index_topk"]),
            swa_window=int(hf["sliding_window_size"]),
            swa_num_heads=int(hf["swa_num_attention_heads"]),
            swa_q_lora_rank=int(hf["swa_q_lora_rank"]),
            swa_kv_lora_rank=int(hf["swa_kv_lora_rank"]),
            swa_qk_nope_head_dim=int(hf["swa_qk_nope_head_dim"]),
            swa_qk_rope_head_dim=int(hf["swa_qk_rope_head_dim"]),
            swa_v_head_dim=int(hf["swa_v_head_dim"]),
            swa_rope_theta=float(hf["swa_rope_theta"]),
            attn_gate=hf.get("attention_gate_type") == "headwise",
            swa_attn_gate=hf.get("swa_attention_gate_type") == "headwise",
        )

    @classmethod
    def from_pretrained(cls, path: str, dtype: str = "bfloat16") -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf(json.load(f), dtype=dtype)

    @classmethod
    def llama32_3b(cls, **kw) -> "ModelConfig":
        """Llama-3.2-3B geometry — the single-chip flagship/bench config
        (bf16 params + KV fit a v5e chip; head_dim=128 rides the Pallas
        decode kernel). Shared by bench.py and __graft_entry__.py."""
        defaults = dict(
            vocab_size=128256, hidden_size=3072, intermediate_size=8192,
            num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
            rope_theta=500000.0, max_position_embeddings=8192,
            tie_word_embeddings=True, dtype="bfloat16")
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "ModelConfig":
        """A toy config for tests (runs in ms on CPU)."""
        defaults = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                        rope_theta=10000.0, max_position_embeddings=512,
                        dtype="float32")
        defaults.update(kw)
        return cls(**defaults)


__all__ = ["ModelConfig"]
