"""Pipeline parallelism: layer stages over the ``pp`` mesh axis.

The reference never implements PP itself — it only forwards engine flags
(SURVEY §2.7; ``launch/dynamo-run/src/main.rs:28``); the engines' PP is
NCCL send/recv between layer shards. Here PP is built the XLA way
(SURVEY §7 stage 8, "GSPMD stage partitioning"): ONE ``shard_map`` program
in which

- the layer-stacked parameter pytree and the stacked paged KV cache shard
  their LAYER axis over ``pp`` — stage ``s`` holds layers
  ``[s*L/pp, (s+1)*L/pp)`` and exactly those layers' KV pages, so paged
  reads/writes stay stage-local with no cross-stage traffic;
- the batch is split into microbatches that flow through the stages on a
  ``lax.ppermute`` ring (the classic pipeline schedule: at tick ``t``
  stage ``s`` works microbatch ``t - s``); with ``M`` microbatches the
  pipeline runs ``M + pp - 1`` ticks and each stage idles only during
  fill/drain ticks;
- inactive ticks compute on garbage but their page writes are masked to
  the reserved garbage page (``new_lens = 0``) and their outputs dropped,
  keeping every tick shape-identical — the XLA-friendly alternative to
  data-dependent control flow;
- last-stage logits are collected per microbatch and ``psum``-broadcast
  at the end, so every rank returns the full ``[B, vocab]`` (multi-host
  leaders read results locally, like every other step family).

PP composes with TP (``pp x tp`` mesh): the ``shard_map`` stays fully
manual (partial-manual shard_map is not supported by this jax), so the
stage body does tensor parallelism explicitly — weights placed with
``P("pp", ..., "tp")`` (``pp_sharding_fns`` with a model config), each
device computing its head/ffn shard and the standard two per-layer
``lax.psum`` all-reduces over ``tp`` (after the attention out-projection
and the mlp down-projection) completing the activations. KV pages shard
``Hkv`` over tp inside each stage, so paged reads/writes stay chip-local
exactly as in the plain tp path.

PP also composes with DP (``pp x dp`` mesh): the batch splits over ``dp``
OUTSIDE the pipeline ring — each dp replica pipelines its own
microbatches — while the page pool stays REPLICATED across dp. The
invariant that keeps the replicas' caches identical: before every cache
write, the per-layer K/V (and the tick's table/position/new-length rows)
``all_gather`` over dp, so every replica applies the identical GLOBAL
write while attending only its local rows. The gathered K/V rows are KBs
at decode (vs psum-merging whole page-stack deltas, which would move the
entire cache per step).

The stage body takes the engine's Pallas ``attn_impl`` (the stacked
decode/prefill kernels run fine on a shard_map-local cache slab — same
call signature as ``paged_attention``), so pp serving no longer forces
the XLA scan path.

The schedule is family-agnostic over STAGE ADAPTERS (``_STAGE_ADAPTERS``):
llama-tree dense, gemma-2 (norm sandwich, GeGLU, per-layer windows,
softcaps), and MoE (routed experts, FFN width tp-sharded with one psum
after the linear combine). DeepSeek MLA is refused — its heterogeneous
dense/MoE two-stack layout doesn't fit a uniform stage slab; that family
serves via tp/dp/sp.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    _finish_layer,
    _project_qkv,
    _rms_norm,
)
from dynamo_tpu.ops.attention import paged_attention, write_kv


# tp tail (dims after the leading L axis) per layer-stacked leaf — the
# same placement ``parallel/sharding.py`` uses for the plain tp path:
# qkv/ffn-up shard their OUTPUT dim, out/down projections their INPUT dim
# (so the partial products line up for the per-layer psum). Families with
# differently-shaped leaves override via their stage adapter's TP_TAILS.
_TP_TAILS: Dict[str, Tuple] = {
    "attn_norm": (), "mlp_norm": (), "q_norm": (), "k_norm": (),
    "wq": (None, "tp"), "wk": (None, "tp"), "wv": (None, "tp"),
    "wo": ("tp", None),
    "w_gate": (None, "tp"), "w_up": (None, "tp"), "w_down": ("tp", None),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",),
}

# MoE expert leaves carry a leading E dim: [L, E, H, I] / [L, E, I, H]
_TP_TAILS_MOE: Dict[str, Tuple] = {
    **_TP_TAILS,
    "w_router": (),
    "w_gate": (None, None, "tp"), "w_up": (None, None, "tp"),
    "w_down": (None, "tp", None),
}


def _layer_spec(name: str, pp_axis: str, tp: int,
                tails: Dict[str, Tuple] = _TP_TAILS) -> P:
    if tp == 1:
        return P(pp_axis)
    return P(pp_axis, *tails.get(name, ()))


# ------------------------------------------------------------- stage bodies
# One adapter per supported family: the pieces of a layer that differ
# (embedding, qkv projection, per-layer attention kwargs, the post-attention
# tail with its tp psum points, the final vocab projection). The pipeline
# schedule, KV writes, dp gathers, and microbatch ring are family-agnostic.


class _LlamaStage:
    TP_TAILS = _TP_TAILS

    def __init__(self, cfg: ModelConfig, cfg_local: ModelConfig):
        self.cfg, self.cfg_local = cfg, cfg_local
        self.sm_scale = cfg.head_dim ** -0.5

    def embed(self, params, tok):
        return params["embed"][tok]

    def qkv(self, lp, h, pos):
        return _project_qkv(self.cfg_local, lp, h, pos)

    def attend_kwargs(self, global_lidx):
        return {}

    def finish(self, lp, h, attn, psum):
        cfg = self.cfg
        if psum is None:
            return _finish_layer(cfg, lp, h, attn)
        # manual tensor parallelism: each device holds its head slice of
        # wo / ffn slice of w_down, so the projections produce PARTIAL
        # sums — the standard two all-reduces per layer complete them
        # (parallel/sharding.py places the plain-tp path identically;
        # GSPMD inserts the same psums there automatically)
        Bm_, S_ = h.shape[0], h.shape[1]
        h = h + psum(attn.reshape(Bm_, S_, -1) @ lp["wo"])
        x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"])
               * (x @ lp["w_up"])) @ lp["w_down"]
        return h + psum(mlp)

    def tail(self, params, hidden):
        hn = _rms_norm(hidden, params["final_norm"], self.cfg.rms_norm_eps)
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        # model-dtype operands + f32 accumulation, matching llama._logits
        # (f32-cast operands would run the vocab matmul at f32 MXU rate)
        return jnp.dot(hn, lm_head, preferred_element_type=jnp.float32)


class _MoeStage(_LlamaStage):
    """Mixtral/Qwen3-MoE: llama attention + routed experts. Under manual
    tp the expert FFN width shards (``_TP_TAILS_MOE``); the token-combine
    is LINEAR in the expert outputs, so ONE psum after the routed result
    completes the partial down-products — same two all-reduce points per
    layer as the dense family. The dispatch backend works too (its
    scatter/combine is also linear); the expert layer's counts are
    discarded here (the pipeline returns the llama 2-tuple contract)."""

    TP_TAILS = _TP_TAILS_MOE

    def finish(self, lp, h, attn, psum):
        from dynamo_tpu.models import moe as _moe

        cfg = self.cfg
        if psum is None:
            h, _aux = _moe._moe_layer_tail(cfg, lp, h, attn)
            return h
        Bm_, S_ = h.shape[0], h.shape[1]
        h = h + psum(attn.reshape(Bm_, S_, -1) @ lp["wo"])
        x = _rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        if cfg.moe_backend == "dispatch":
            routed, _dropped = _moe.moe_mlp_dispatch(cfg, lp, x)
        else:
            routed = _moe.moe_mlp(cfg, lp, x)
        return h + psum(routed)


class _GemmaStage:
    """gemma-2: (1+w) RMSNorm sandwich around attention AND the GeGLU mlp,
    sqrt(H)-scaled embedding, alternating per-layer sliding windows, logit
    softcaps on attention and the final projection."""

    TP_TAILS = _TP_TAILS

    def __init__(self, cfg: ModelConfig, cfg_local: ModelConfig):
        from dynamo_tpu.models import gemma as _g

        self._g = _g
        self.cfg, self.cfg_local = cfg, cfg_local
        self.sm_scale = _g._sm_scale(cfg)

    def embed(self, params, tok):
        return self._g._embed(self.cfg, params, tok)

    def qkv(self, lp, h, pos):
        return self._g._project_qkv(self.cfg_local, lp, h, pos)

    def attend_kwargs(self, global_lidx):
        cfg = self.cfg
        win = 0
        if cfg.sliding_window:
            # even GLOBAL layers slide, odd are global (models/gemma.py
            # layer_windows) — closed form on the traced stage-local index
            win = jnp.where(global_lidx % 2 == 0, cfg.sliding_window, 0)
        return {"window": win,
                "softcap": cfg.attn_logit_softcap or None}

    def finish(self, lp, h, attn, psum):
        cfg, g = self.cfg, self._g
        if psum is None:
            return g._finish_layer(cfg, lp, h, attn)
        eps = cfg.rms_norm_eps
        Bm_, S_ = h.shape[0], h.shape[1]
        attn_out = psum(attn.reshape(Bm_, S_, -1) @ lp["wo"])
        h = h + g._rms_norm(attn_out, lp["post_attn_norm"], eps)
        x = g._rms_norm(h, lp["pre_ffw_norm"], eps)
        mlp = psum((jax.nn.gelu(x @ lp["w_gate"], approximate=True)
                    * (x @ lp["w_up"])) @ lp["w_down"])
        return h + g._rms_norm(mlp, lp["post_ffw_norm"], eps)

    def tail(self, params, hidden):
        cfg, g = self.cfg, self._g
        hn = g._rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        # model-dtype operands + f32 accumulation (gemma._logits)
        logits = jnp.dot(hn, lm_head, preferred_element_type=jnp.float32)
        cap = cfg.final_logit_softcap
        if cap:
            logits = jnp.tanh(logits / cap) * cap
        return logits


_STAGE_ADAPTERS = {
    "dynamo_tpu.models.llama": _LlamaStage,
    "dynamo_tpu.models.gemma": _GemmaStage,
    "dynamo_tpu.models.moe": _MoeStage,
}


def stage_adapter_for(cfg: ModelConfig):
    """The pipeline stage adapter CLASS for this config's family, or None
    when the family cannot stage (DeepSeek MLA). The worker flag guard and
    both sharding/forward paths resolve through this one lookup."""
    from dynamo_tpu.models import get_family

    return _STAGE_ADAPTERS.get(getattr(get_family(cfg), "__name__", ""))


def _ffn_width(cfg: ModelConfig) -> int:
    """The per-layer FFN width the tp axis shards (expert width on MoE)."""
    if cfg.num_experts:
        return cfg.moe_intermediate_size or cfg.intermediate_size
    return cfg.intermediate_size


def _param_specs(params: Dict[str, Any], pp_axis: str, tp: int,
                 tails: Dict[str, Tuple] = _TP_TAILS) -> Dict[str, Any]:
    """Layer-stacked leaves shard axis 0 over pp (+ tp tails); the rest
    replicate (incl. lm_head: the vocab projection runs once on the full
    hidden state after the pipeline, replicated per device)."""
    layer_spec = {k: _layer_spec(k, pp_axis, tp, tails)
                  for k in params["layers"]}
    specs: Dict[str, Any] = {k: P() for k in params if k != "layers"}
    specs["layers"] = layer_spec
    return specs


def pipeline_forward(params: Dict[str, Any], cfg: ModelConfig,
                     tokens: jnp.ndarray, positions: jnp.ndarray,
                     pages: jnp.ndarray, page_table: jnp.ndarray,
                     total_lens: jnp.ndarray, new_lens: jnp.ndarray,
                     mesh: Mesh, pp_axis: str = "pp", tp_axis: str = "tp",
                     dp_axis: str = "dp",
                     n_microbatches: int | None = None,
                     attn_impl=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``llama.forward`` running the layers as a pp pipeline.

    Requires ``cfg.num_layers %% pp == 0``. ``n_microbatches`` must divide
    the PER-REPLICA batch; the default picks the LARGEST divisor of B/dp
    that is <= pp — M == pp keeps every stage busy in steady state,
    smaller batches run with pipeline bubbles rather than failing.
    ``pages`` is the stacked cache ``[L, N, 2, Hkv, ps, Dh]``. A ``tp``
    mesh axis > 1 additionally head/ffn-shards each stage (weights placed
    by ``pp_sharding_fns``); a ``dp`` axis > 1 splits the batch across
    replicas (module docstring: K/V writes all_gather over dp so the
    replicated page pool stays consistent). ``attn_impl`` optionally
    replaces the XLA paged attention inside the stage body — the stacked
    Pallas kernels match the call signature.

    Families (one stage adapter each, ``_STAGE_ADAPTERS``): the llama
    tree (llama/mistral/qwen dense), gemma-2 (4-norm sandwich, GeGLU,
    embed scaling, alternating per-layer windows + both softcaps), and
    MoE (routed experts; dispatch-backend drop counts are NOT surfaced
    under pp — the worker warns at startup). DeepSeek MLA is refused:
    its layers differ from any staged body and would serve silently
    wrong outputs.
    """
    from dynamo_tpu.models import get_family
    n_stages = mesh.shape[pp_axis]
    tp = dict(mesh.shape).get(tp_axis, 1)
    dp = dict(mesh.shape).get(dp_axis, 1)
    if n_stages == 1:
        # no stage body runs: every family's own forward serves
        out = get_family(cfg).forward(params, cfg, tokens, positions,
                                      pages, page_table, total_lens,
                                      new_lens)
        return out[0], out[1]
    adapter_factory = stage_adapter_for(cfg)
    if adapter_factory is None:
        raise ValueError(
            f"pipeline_forward has no stage adapter for "
            f"{cfg.model_type!r} — running it through another family's "
            f"layers would serve silently wrong outputs; use tp/dp/sp "
            f"for this family (worker/main.py guards the flag)")
    if cfg.num_layers % n_stages:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by "
                         f"pp={n_stages}")
    if tp > 1 and (cfg.num_kv_heads % tp or _ffn_width(cfg) % tp):
        raise ValueError(f"num_kv_heads={cfg.num_kv_heads}/"
                         f"ffn_width={_ffn_width(cfg)} not divisible by "
                         f"tp={tp}")
    B = tokens.shape[0]
    if B % dp:
        raise ValueError(f"batch {B} not divisible by dp={dp} (the engine "
                         f"aligns its batch buckets to dp when cfg.mesh "
                         f"is set)")
    B_local = B // dp
    # default: the largest microbatch count <= pp that divides the
    # per-replica batch (a small serving batch pipelines with bubbles
    # rather than failing)
    M = n_microbatches or max(m for m in range(1, n_stages + 1)
                              if B_local % m == 0)
    if B_local % M:
        raise ValueError(f"per-replica batch {B_local} not divisible by "
                         f"n_microbatches={M}")
    Bm = B_local // M
    layers_per_stage = cfg.num_layers // n_stages
    # per-device view of the head/ffn dims under manual tp: _project_qkv
    # reshapes by head COUNTS, which are local inside the shard_map body
    cfg_local = cfg
    if tp > 1:
        import dataclasses
        cfg_local = dataclasses.replace(
            cfg, num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp)
    stage_body = adapter_factory(cfg, cfg_local)
    sm_scale = stage_body.sm_scale
    # a passed attn_impl must carry the family's per-layer kwargs (the
    # stacked Pallas kernels advertise window/softcap support); otherwise
    # the XLA path serves — never silently drop a gemma window
    attend = attn_impl or paged_attention
    if (isinstance(stage_body, _GemmaStage) and attn_impl is not None
            and not getattr(attn_impl, "supports_window_softcap", False)):
        attend = paged_attention

    def shard_fn(params, tokens, positions, page_table, total_lens,
                 new_lens, pages_local):
        stage = lax.axis_index(pp_axis)
        last = n_stages - 1
        # microbatch stacks [M, Bm, ...] (per-dp-replica local rows)
        tok_mb = tokens.reshape(M, Bm, -1)
        pos_mb = positions.reshape(M, Bm, -1)
        tbl_mb = page_table.reshape(M, Bm, -1)
        tot_mb = total_lens.reshape(M, Bm)
        new_mb = new_lens.reshape(M, Bm)
        S = tok_mb.shape[2]
        H = cfg.hidden_size

        def gather_dp(x):
            """Global batch rows for the cache write: every dp replica at
            (stage, tick) processes the same microbatch index, so tiled
            all_gathers line up and all replicas apply identical writes."""
            if dp == 1:
                return x
            return lax.all_gather(x, dp_axis, axis=0, tiled=True)

        # local layer ids are GLOBAL indices into the pp-sharded page
        # stack's local slab (axis 0 of pages_local is layers_per_stage)
        local_layer_ids = jnp.arange(layers_per_stage)

        def run_stage(h, pages_local, pos, tbl, tot, new):
            pos_g, tbl_g, new_g = gather_dp(pos), gather_dp(tbl), \
                gather_dp(new)

            def body(carry, xs):
                h, pages_local = carry
                lp, lidx = xs
                q, k, v = stage_body.qkv(lp, h, pos)
                pages_local = write_kv(pages_local, lidx, gather_dp(k),
                                       gather_dp(v), tbl_g, pos_g, new_g)
                attn = attend(q, pages_local, lidx, tbl, pos, tot,
                              sm_scale,
                              **stage_body.attend_kwargs(
                                  stage * layers_per_stage + lidx))
                psum = ((lambda x: lax.psum(x, tp_axis)) if tp > 1
                        else None)
                h = stage_body.finish(lp, h, attn, psum)
                return (h, pages_local), None

            (h, pages_local), _ = lax.scan(
                body, (h, pages_local), (params["layers"], local_layer_ids))
            return h, pages_local

        def tick(t, carry):
            pages_local, h_in, out = carry
            m = t - stage                      # this stage's microbatch
            active = jnp.logical_and(m >= 0, m < M)
            mc = jnp.clip(m, 0, M - 1)
            tok = lax.dynamic_index_in_dim(tok_mb, mc, keepdims=False)
            pos = lax.dynamic_index_in_dim(pos_mb, mc, keepdims=False)
            tbl = lax.dynamic_index_in_dim(tbl_mb, mc, keepdims=False)
            tot = lax.dynamic_index_in_dim(tot_mb, mc, keepdims=False)
            new = lax.dynamic_index_in_dim(new_mb, mc, keepdims=False)
            # inactive ticks: mask page writes to the garbage page and let
            # the compute produce don't-care values
            new = jnp.where(active, new, 0)
            h0 = stage_body.embed(params, tok)  # [Bm, S, H]
            h = jnp.where(stage == 0, h0, h_in)
            h, pages_local = run_stage(h, pages_local, pos, tbl, tot, new)
            # last stage: record this microbatch's LAST-TOKEN hidden state
            # (the vocab projection — the dominant small-batch matmul —
            # runs ONCE after the loop, not per tick per stage)
            last_idx = jnp.maximum(new, 1) - 1                 # [Bm]
            h_last = jnp.take_along_axis(
                h, last_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            keep = jnp.logical_and(active, stage == last)
            prev = lax.dynamic_index_in_dim(out, mc, keepdims=False)
            out = lax.dynamic_update_index_in_dim(
                out, jnp.where(keep, h_last, prev), mc, 0)
            # hand the activation to the next stage (stage 0 re-embeds, so
            # the value it receives is ignored)
            h_next = lax.ppermute(
                h, pp_axis, [(i, i + 1) for i in range(n_stages - 1)])
            return pages_local, h_next, out

        out0 = jnp.zeros((M, Bm, H), params["embed"].dtype)
        h0 = jnp.zeros((Bm, S, H), params["embed"].dtype)
        pages_local, _h, out = lax.fori_loop(
            0, M + n_stages - 1, tick, (pages_local, h0, out0))
        # only the last stage holds real hidden states; broadcast them,
        # then project to the vocab once (per-replica local rows)
        out = lax.psum(
            jnp.where(stage == last, out, jnp.zeros_like(out)), pp_axis)
        logits = stage_body.tail(params, out.reshape(B_local, H))
        return logits, pages_local

    pages_spec = (P(pp_axis) if tp == 1
                  else P(pp_axis, None, None, tp_axis))
    batch = P(dp_axis)                 # rows split across dp replicas
    specs_in = (
        _param_specs(params, pp_axis, tp, stage_body.TP_TAILS),
        batch, batch, batch, batch, batch,  # tokens/pos/table/total/new
        pages_spec,                    # pages: layers staged, Hkv over tp,
                                       # REPLICATED over dp (gathered writes)
    )
    specs_out = (batch, pages_spec)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=specs_in,
                       out_specs=specs_out, check_vma=False)
    logits, pages = fn(params, tokens, positions, page_table, total_lens,
                       new_lens, pages)
    return logits, pages


def pp_sharding_fns(mesh: Mesh, cfg: ModelConfig | None = None,
                    pp_axis: str = "pp", tp_axis: str = "tp"):
    """(shard_params_fn, shard_pages_fn) placing the layer-stacked leaves
    and the stacked page cache on the pp axis — what a worker plugs into
    ``JaxEngineConfig`` to serve with ``pipeline_forward``.

    With a ``tp`` axis > 1 on the mesh, each layer leaf composes the stage
    placement with the tensor-parallel tail (wq ``P("pp", None, "tp")``,
    pages ``P("pp", None, None, "tp", ...)``); non-layer leaves replicate
    (the vocab projection runs replicated after the pipeline). ``cfg`` is
    required then, for the divisibility checks."""
    from jax.sharding import NamedSharding

    tp = dict(mesh.shape).get(tp_axis, 1)
    tails = _TP_TAILS
    if cfg is not None:
        adapter = stage_adapter_for(cfg)
        if adapter is not None:
            tails = adapter.TP_TAILS
    if tp > 1:
        if cfg is None:
            raise ValueError("pp x tp sharding needs the model config")
        if cfg.num_kv_heads % tp or _ffn_width(cfg) % tp:
            raise ValueError(
                f"num_kv_heads={cfg.num_kv_heads}/ffn_width="
                f"{_ffn_width(cfg)} not divisible by tp={tp}")
    pages_spec = (P(pp_axis) if tp == 1
                  else P(pp_axis, None, None, tp_axis))

    def shard_params(params):
        out = dict(params)
        out["layers"] = {
            k: jax.device_put(
                v, NamedSharding(mesh, _layer_spec(k, pp_axis, tp, tails)))
            for k, v in params["layers"].items()}
        for k, v in params.items():
            if k != "layers":
                out[k] = jax.device_put(v, NamedSharding(mesh, P()))
        return out

    def shard_pages(pages):
        return jax.device_put(pages, NamedSharding(mesh, pages_spec))

    return shard_params, shard_pages


__all__ = ["pipeline_forward", "pp_sharding_fns", "stage_adapter_for"]
