"""GSPMD shardings for the Llama-family pytree and the paged KV cache.

Tensor parallelism the XLA way (reference: ``--tensor-parallel-size`` handed
to vLLM's NCCL Megatron kernels, SURVEY §2.7): annotate the weight shardings,
keep activations replicated-per-``dp``-shard, and let the partitioner insert
the two all-reduces per layer (after attention out-proj and after mlp
down-proj) on ICI.

Layout (params carry a leading ``L`` layer axis from the ``lax.scan`` stack):

- ``wq/wk/wv`` ``[L, H, out]``  — shard ``out`` (head) dim over ``tp``
- ``wo``       ``[L, q, H]``    — shard ``q`` (head) dim over ``tp``
- ``w_gate/w_up`` ``[L, H, I]`` — shard ``I`` over ``tp``
- ``w_down``   ``[L, I, H]``    — shard ``I`` over ``tp``
- ``embed``    ``[V, H]``       — replicated (all-gather-free lookup)
- ``lm_head``  ``[H, V]``       — shard ``V`` over ``tp`` (logits sharded,
  top-k/sampling runs fine on sharded logits)
- KV pages ``[L, N, 2, Hkv, page, Dh]`` (stacked) or per-layer
  ``[N, 2, Hkv, page, Dh]`` — shard ``Hkv`` over ``tp``; each chip holds its
  own heads' slice of every page, so paged writes/gathers (and the Pallas
  decode kernel's page DMAs) are chip-local.

``num_kv_heads`` must be divisible by ``tp`` (e.g. Llama-3-8B: 8 KV heads →
tp ∈ {1,2,4,8}); for tp > Hkv one would replicate KV heads — rejected for
now with a clear error.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh


class ModelSharding:
    """Sharding specs bound to a mesh for one model configuration."""

    def __init__(self, cfg: ModelConfig, mesh: Mesh):
        self.cfg = cfg
        self.mesh = mesh
        if cfg.attn_blocks_per_layer != 1:
            # models/longcat.py: its pytree (attn0/attn1/ffn0/ffn1 and a
            # held range of experts) has no specs here, and the MLA specs
            # below would place another family's leaves
            raise NotImplementedError(
                f"no sharding specs for {cfg.model_type!r} (two attention "
                "blocks a layer); it is served on one chip as one rank of "
                "its expert-parallel deployment (ep_rank of ep_size)")
        tp = mesh.shape.get("tp", 1)
        ep = mesh.shape.get("ep", 1)
        if tp > 1:
            if cfg.kv_lora_rank:
                # MLA: tp splits the QUERY heads (the latent cache is
                # shared/replicated), so num_heads is the constraint
                if cfg.num_heads % tp:
                    raise ValueError(
                        f"num_heads={cfg.num_heads} not divisible by "
                        f"tp={tp}")
            elif cfg.num_kv_heads % tp:
                raise ValueError(
                    f"num_kv_heads={cfg.num_kv_heads} not divisible by tp={tp}")
            if cfg.intermediate_size % tp:
                raise ValueError(
                    f"intermediate_size={cfg.intermediate_size} not divisible "
                    f"by tp={tp}")
            if cfg.num_experts:
                # both MoE spec families shard the expert FFN width over tp
                moe_i = cfg.moe_intermediate_size or cfg.intermediate_size
                if moe_i % tp:
                    raise ValueError(
                        f"moe_intermediate_size={moe_i} not divisible "
                        f"by tp={tp}")
        if ep > 1 and cfg.num_experts % ep:
            raise ValueError(
                f"num_experts={cfg.num_experts} not divisible by ep={ep}")

    # -- specs -------------------------------------------------------------

    def param_specs(self) -> Dict[str, Any]:
        if self.cfg.kv_lora_rank:
            return self._deepseek_specs()
        layers = {
            "attn_norm": P(),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        }
        if self.cfg.num_experts:
            # MoE: experts over ep, expert-FFN width over tp; the dense
            # routed-compute einsums then run expert-local per chip with one
            # combine all-reduce inserted by the partitioner
            layers.update(
                w_router=P(),
                w_gate=P(None, "ep", None, "tp"),
                w_up=P(None, "ep", None, "tp"),
                w_down=P(None, "ep", "tp", None),
            )
        if self.cfg.attention_bias:
            layers.update(bq=P(None, "tp"), bk=P(None, "tp"), bv=P(None, "tp"))
        if self.cfg.qk_norm:
            layers.update(q_norm=P(), k_norm=P())
        specs: Dict[str, Any] = {
            "embed": P(),
            "layers": layers,
            "final_norm": P(),
        }
        if not self.cfg.tie_word_embeddings:
            # logits shard cleanly for real vocabs (128256, 32000, ...);
            # replicate as a fallback for odd-sized vocabs (toy models)
            tp = self.mesh.shape.get("tp", 1)
            specs["lm_head"] = (P(None, "tp")
                                if self.cfg.vocab_size % tp == 0 else P())
        return self._add_quant_specs(specs)

    def _add_quant_specs(self, specs: Dict[str, Any]) -> Dict[str, Any]:
        """Specs for int8-quantized trees (``ops/quant.quantize_params``).

        The int8 tensor shards exactly like the bf16 original; the
        per-out-channel scale keeps the layer and out dims and drops the
        contraction axis (axis 1 of a stacked ``[L, K, N]``, axis 0 of
        ``lm_head``). Correctness under a SHARDED contraction (wo/w_down:
        ``P(None, "tp", None)``): the scale multiply distributes over the
        sum, so GSPMD may psum the int32 partials before or after the
        rescale — both orders are exact. Extra spec keys are inert for
        unquantized trees (``shard_params`` walks the tree's keys).
        """
        from dynamo_tpu.ops.quant import LAYER_WEIGHTS
        layers = specs["layers"]
        for name in LAYER_WEIGHTS:
            spec = layers.get(name)
            if spec is None or len(spec) != 3:
                continue  # MoE 4-d expert stacks don't quantize yet
            layers[name + "_q"] = spec
            layers[name + "_scale"] = P(spec[0], spec[2])
        lm = specs.get("lm_head")
        if lm is not None:
            specs["lm_head_q"] = lm
            specs["lm_head_scale"] = P(lm[1]) if len(lm) == 2 else P()
        return specs

    def _deepseek_specs(self) -> Dict[str, Any]:
        """MLA (deepseek) pytree: HEAD-carrying projections shard their
        head-packed dim over tp (wq/wq_b/wkv_b outputs, wo input) — under
        GSPMD the whole latent attention then runs head-local per chip
        with one psum after wo; the latent path (wkv_a/kv_a_norm) and the
        shared-per-token cache replicate over tp. Routed experts shard
        over ep, shared experts' ffn width over tp."""
        attn = {
            "attn_norm": P(),
            "wkv_a": P(),
            "kv_a_norm": P(),
            "wkv_b": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(),
            "wq": P(None, None, "tp"),
            "wq_a": P(),
            "q_a_norm": P(),
            "wq_b": P(None, None, "tp"),
        }
        dense = dict(attn)
        dense.update(w_gate=P(None, None, "tp"), w_up=P(None, None, "tp"),
                     w_down=P(None, "tp", None))
        moe = dict(attn)
        moe.update(
            w_router=P(),
            router_bias=P(),
            w_gate=P(None, "ep", None, "tp"),
            w_up=P(None, "ep", None, "tp"),
            w_down=P(None, "ep", "tp", None),
            ws_gate=P(None, None, "tp"),
            ws_up=P(None, None, "tp"),
            ws_down=P(None, "tp", None),
        )
        specs: Dict[str, Any] = {
            "embed": P(),
            "final_norm": P(),
            "dense_layers": dense,
            "moe_layers": moe,
        }
        if not self.cfg.tie_word_embeddings:
            tp = self.mesh.shape.get("tp", 1)
            specs["lm_head"] = (P(None, "tp")
                                if self.cfg.vocab_size % tp == 0 else P())
        return specs

    def pages_spec(self) -> P:
        """Stacked cache [L, N, 2, Hkv, page, Dh]: Hkv over tp (MLA: the
        latent is shared across heads — replicated)."""
        if self.cfg.kv_lora_rank:
            return P()
        return P(None, None, None, "tp", None, None)

    # -- application -------------------------------------------------------

    def _named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def shard_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        specs = self.param_specs()

        def place(path, leaf):
            node = specs
            for k in path:
                node = node[k.key]
            return jax.device_put(leaf, self._named(node))

        return jax.tree_util.tree_map_with_path(place, params)

    def shard_pages(self, pages):
        return jax.device_put(pages, self._named(self.pages_spec()))

    def replicate(self, x):
        return jax.device_put(x, self._named(P()))


def tp_sharding(cfg: ModelConfig, tp_size: int,
                devices: Optional[list] = None) -> ModelSharding:
    """Pure tensor-parallel sharding over the first ``tp_size`` devices."""
    devs = list(devices if devices is not None else jax.devices())[:tp_size]
    mesh = make_mesh(MeshSpec(tp=tp_size), devices=devs)
    return ModelSharding(cfg, mesh)


# -- transport-array sharding helpers ---------------------------------------
# The KV transfer paths move blocks as a STACKED rank-6 array
# [L, n, 2, Hkv, ps, Dh], the cache's own rank; these helpers are the one
# place the cache placement -> transport placement mapping lives
# (engine/transfer.py and the engine's sharded gather both use them).


def transport_sharding(pages):
    """Sharding of the stacked ``[L, n, ...]`` transport array matching the
    cache's placement: the cache's own (block indexing runs along the
    unsharded page axis)."""
    return pages.sharding


def shard_layout(sharding) -> tuple:
    """``(shard_count, axis)`` a sharding partitions its array over:
    ``(1, -1)`` for unpartitioned/single-device placements, ``(0, -1)``
    when more than one axis is partitioned (the per-shard KV wire carries
    exactly one sharded axis — multi-axis caches fall back to merged
    frames)."""
    if not isinstance(sharding, NamedSharding):
        return (1, -1)
    mesh_shape = dict(sharding.mesh.shape)
    parted = []
    for i, entry in enumerate(sharding.spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for nm in names:
            n *= int(mesh_shape.get(nm, 1))
        if n > 1:
            parted.append((n, i))
    if not parted:
        return (1, -1)
    if len(parted) > 1:
        return (0, -1)
    return parted[0]


__all__ = ["ModelSharding", "tp_sharding", "transport_sharding",
           "shard_layout"]
