"""The TPU serving engine: jit-compiled model steps + continuous batching.

This is the component the reference never builds natively — its workers shell
out to vLLM/SGLang CUDA engines (SURVEY §2.5); here the model loop is owned by
the framework and designed for XLA:

- TWO compiled step families, prefill (``[B, S]`` chunk batch — multiple
  sequences share one step under a token budget) and decode (``[B, 1]``
  batch), with power-of-two bucketing on S and B so the set of compiled
  programs is small and fixed. The page-table width is static
  (``max_context / page_size``), so no shape depends on sequence length.
- The paged KV cache is ONE device array, donated through every step
  (``donate_argnums``), and every step program updates it in place: the
  cache write scatters whole pages (``ops/attention._write_pages``), the
  window that the pool's row-major layout — the one the kernels'
  ``pl.ANY`` operand pins — holds contiguously. A write the compiler has
  to re-lay the pool for costs a copy of all of it per layer and shows
  nowhere in the source; ``engine/program_check.py`` reads the compiled
  programs for one (tests, ``chip_smoke.py``).
- Sampling runs on device in the same program as the forward pass
  (``ops/sampling.sample_tokens``): one host round-trip per step (the sampled
  token ids), nothing else.
- The asyncio step loop (``engine/loop.py``) runs jitted calls in a worker
  thread so request intake / streaming stays responsive while the device is
  busy; host-side bookkeeping overlaps the next dispatch.

Capability parity: the role of vLLM's ``AsyncLLM`` behind the reference's
worker handlers (``components/backends/vllm/src/dynamo/vllm/handlers.py``),
including prefix caching, chunked prefill, preemption, KV events, and
load-metric publication.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.loop import BlockState, ScheduledEngineBase
from dynamo_tpu.engine.scheduler import PrefillBatch, StepPlan
from dynamo_tpu.engine import stages
from dynamo_tpu.engine.steptrace import MOE_COUNTS, stage
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.ops.sampling import (TOPK_MAX, reveal, sample_tokens,
                                     top_candidates)

logger = logging.getLogger(__name__)

# what JaxEngineConfig.attn_impl (and the worker's --attn-impl) may say
ATTN_IMPLS = ("auto", "pallas", "scan")


@dataclass
class JaxEngineConfig:
    """Engine sizing knobs (the analog of vLLM's EngineArgs for this engine)."""

    num_pages: int = 512          # physical KV pages (page 0 reserved)
    page_size: int = 16           # tokens per page == router block size
    max_num_seqs: int = 8         # max concurrent sequences
    max_prefill_chunk: int = 512  # prompt-token budget per prefill step
    max_prefill_seqs: int = 8     # sequences sharing one prefill step
    max_context: int = 2048       # max prompt+generation length
    min_prefill_bucket: int = 16
    # floor for the padded decode batch: raising it to max_num_seqs gives ONE
    # compiled decode shape (fewer compiles, steadier step time); leaving it
    # at 1 compiles each power-of-two batch as load ramps
    min_decode_bucket: int = 1
    # same knob for the prefill batch dimension: raising it pins B to fewer
    # compiled (B, S) combinations at the cost of padded rows
    min_prefill_seqs_bucket: int = 1
    # alternatives returned per sampled token (OpenAI top_logprobs; the
    # on-device top-k over [B, V] logits is noise next to the forward pass).
    # 0 disables the extra [B, K] outputs entirely.
    num_top_logprobs: int = 8
    # sparse window of penalized token ids shipped per row per step
    # (frequency/presence count generated tokens, repetition marks
    # prompt+generated presence — ops/sampling.apply_penalties). Rows
    # beyond W distinct penalizable ids keep the most frequent W.
    # 0 disables the penalty inputs entirely.
    penalty_window: int = 32
    # guided decoding on the FUSED multistep path: a grammar whose dense
    # token-level transition table (engine/guided.build_guided_table)
    # fits under this byte cap runs inside the fused block; larger (or
    # unbounded — {"mode": "json"} nests forever) grammars fall back
    # per-row to per-step decode with fallback reason "guided_table".
    guided_table_bytes: int = 8 << 20
    seed: int = 0
    # attention implementation (one forward per family — lax.scan over
    # layers, one compiled layer body — over one stacked page pool; what
    # varies is the attention op the scan body calls):
    #   "scan"     — XLA attention (portable; the CPU's path and the
    #                reference the kernel tests compare with)
    #   "pallas"   — the layer-indexed Pallas kernels of each step form
    #                (decode, padded prefill chunks, token-packed) with
    #                their page-streaming DMAs (TPU default)
    #   "auto"     — pallas on TPU where the kernels can run, scan elsewhere
    attn_impl: str = "auto"
    # weight quantization applied at load time: "" (serve the checkpoint
    # dtype) or "int8" (W8A8-dynamic, ops/quant.py — halves the per-step
    # parameter stream and runs the matmuls on the MXU's double-rate int8
    # path; llama-family dense models only)
    quantize: str = ""
    # pipelined decode: step N+1 consumes step N's sampled tokens directly
    # on device; the host fetches step N's results while N+1 runs, so the
    # device->host readback overlaps the next step (cost on the chip: not
    # measured). Disable for strict step-at-a-time debugging.
    pipeline_decode: bool = True
    # fused decode: max decode steps run inside ONE jitted dispatch
    # (lax.scan over the step body with on-device sampling and stop
    # checks — engine/scheduler.py narrows the width per batch). None
    # resolves DYN_DECODE_MULTISTEP / RuntimeConfig.decode_multistep
    # (default 8); 1 disables the fused path (per-step/chained decode
    # still applies under pipeline_decode).
    decode_multistep: Optional[int] = None
    # mixed prefill+decode dispatch: pack decode rows into prefill steps
    # as length-1 ragged chunks (ONE [B, S] dispatch instead of the strict
    # prefill-XOR-decode alternation) and lift the fused-multistep
    # "no waiters/prefills" gate so blocks keep running while arrivals
    # onboard. None resolves RuntimeConfig.mixed_batch then the
    # DYN_MIXED_BATCH env; False restores the legacy alternation.
    mixed_batch: Optional[bool] = None
    # decode-progress guarantee on the legacy alternation path: at most
    # K-1 consecutive prefill-only steps while decode rows exist. None
    # resolves RuntimeConfig.decode_progress_every / DYN_DECODE_PROGRESS.
    decode_progress_every: Optional[int] = None
    # speculative decoding (engine/spec.py): n-gram prompt-lookup drafts
    # verified K at a time in one [B, K+1] step (0 = off), yielding up to
    # K+1 tokens per step. Composes with pipelined decode: verify steps
    # can't chain (drafts need the sampled tokens host-side), but plain
    # decode steps between them still hide the readback, with the chain
    # broken every spec_chain_break steps to let fresh context draft.
    # Every built-in family serves speculated (their forwards carry
    # logits_window); custom forward_fns (pp stages) do not.
    spec_tokens: int = 0
    spec_ngram_max: int = 4
    spec_ngram_min: int = 2
    spec_chain_break: int = 8
    # generation by diffusion over blocks (a model whose config says so,
    # ``ModelConfig.generation``): the worker's defaults for the one
    # reveal rule (``ops/sampling.reveal``). ``denoising_steps`` is the
    # number of revealing passes a block of masks takes at most (0 = the
    # block length: one token a pass), ``confidence_threshold`` the
    # confidence above which a pass reveals beyond its quota (>= 1: the
    # static schedule). A request overrides both under ``nvext``. The
    # passes a fused dispatch runs are ``decode_multistep`` (1 = pass by
    # pass).
    denoising_steps: int = 0
    confidence_threshold: float = 0.9
    # prompt-scoring (completions echo + logprobs) length cap; 0 = use
    # max_context. Scoring runs the PAGED chunked-prefill forward — linear
    # memory — but against a FRESH scratch cache allocated next to the
    # live serving pool, so the default stays bounded: a ~max_context
    # scoring request on a long-context deployment would otherwise
    # double-allocate HBM mid-serve. Raise deliberately.
    score_max_tokens: int = 4096
    # mesh/sharding hooks (filled by dynamo_tpu.parallel when multi-chip)
    shard_params_fn: Optional[Callable] = None
    shard_pages_fn: Optional[Callable] = None
    # sequence-parallel long-prompt prefill: when ``mesh`` has an ``sp``
    # axis > 1, prompts longer than ``ring_threshold`` (default: the chunk
    # budget) prefill in ONE ring-attention step over the sp ring instead of
    # serial chunks (``parallel/ring_prefill.py``)
    mesh: Optional[object] = None
    sp_axis: str = "sp"
    ring_threshold: Optional[int] = None
    # slots of the pool a family keeps beside its pages
    # (``ModelConfig.slot_kind``: the recurrent state of linear-attention
    # layers, the rings of window layers): a request owns one while it
    # is admitted, so fewer than ``max_num_seqs`` caps the rows. None =
    # ``max_num_seqs``; a family without such layers has no pool
    state_slots: Optional[int] = None


# prompt-scoring LM-head chunk: the ONE constant both the host padding
# (_score_batch) and the traced reshape (family score()) must share
_SCORE_CHUNK = 256

# default fused-decode width (decode steps per jitted dispatch)
DECODE_MULTISTEP = 8

# defaults for the mixed-dispatch knobs (see JaxEngineConfig)
MIXED_BATCH = True
DECODE_PROGRESS_EVERY = 2


def _runtime_default(attr: str, fallback):
    """RuntimeConfig field (dataclass -> TOML -> ``DYN_RUNTIME_*`` env)
    with the shared error discipline: a bad TOML/env must not break an
    engine build. Resolved at engine build, not at import, so
    monkeypatched env changes take effect."""
    try:
        from dynamo_tpu.utils.config import RuntimeConfig
        return getattr(RuntimeConfig.load(), attr)
    except Exception:  # noqa: BLE001
        logger.warning("bad runtime config; %s falls back to %r",
                       attr, fallback, exc_info=True)
        return fallback


def _env_int_default(env: str, val: int) -> int:
    """Short-form env override for an int knob; malformed values keep
    the resolved default instead of breaking the engine build."""
    raw = os.environ.get(env)
    try:
        return int(raw) if raw is not None else val
    except (TypeError, ValueError):
        logger.warning("malformed %s %r; using %d", env, raw, val)
        return val


def mixed_batch_default() -> bool:
    """Defaults layer for the mixed-dispatch enable flag:
    ``RuntimeConfig.mixed_batch``, then the short-form ``DYN_MIXED_BATCH``
    env wins."""
    val = bool(_runtime_default("mixed_batch", MIXED_BATCH))
    raw = os.environ.get("DYN_MIXED_BATCH")
    if raw is not None:
        val = raw.strip().lower() not in ("0", "false", "no", "off", "")
    return val


def decode_progress_default() -> int:
    """Defaults layer for the decode-progress guarantee K
    (``RuntimeConfig.decode_progress_every``, then the short-form
    ``DYN_DECODE_PROGRESS`` env wins)."""
    val = _runtime_default("decode_progress_every", DECODE_PROGRESS_EVERY)
    return max(0, _env_int_default("DYN_DECODE_PROGRESS", int(val)))


def decode_multistep_default() -> int:
    """Defaults layer for the fused-decode width
    (``RuntimeConfig.decode_multistep``, then the short-form
    ``DYN_DECODE_MULTISTEP`` env wins)."""
    val = _runtime_default("decode_multistep", DECODE_MULTISTEP)
    return max(1, _env_int_default("DYN_DECODE_MULTISTEP", int(val)))


def _bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(b, hi)


def _token_bucket(n: int, lo: int, cap: int = 0) -> int:
    """The packed step's token axis ``T``: powers of two from ``lo`` up to
    512, then ONE rung, the step's cap (``cap``: what the prefill budget
    beside the decode rows rounds to in steps of 128 — 1,152 for the
    worker's default 1,024 tokens beside up to 64 rows). A ladder over the
    step's TOKENS, so a step of three chunks and twenty decode rows pays
    for 1,152 slots and not for ``rows x longest chunk``. One rung above
    512 because each rung is a program, first called (seconds) where a run
    first meets it: steps of 128 gave a server that admits one or two
    prompts beside its rows five rungs that a few per cent of its steps
    land on, and the pad costs little where it lands (PERF.md section 6,
    PR 38). A floor above 512 pins the axis instead: one packed program,
    steps of 128 above it."""
    if n <= 512 and lo <= 512:
        return _bucket(n, lo, 512)
    return max(-(-n // 128) * 128, lo, cap if lo <= 512 else 0)


def qkv_form(model_cfg: ModelConfig, params, config: "JaxEngineConfig",
             forward_fn: Optional[Callable] = None) -> str:
    """``fused`` where an engine of this configuration serves the layers'
    ``wq``, ``wk``, ``wv`` as one stored ``wqkv`` and one product
    (``llama.fuse_qkv``), else ``split:<reason>``: a custom ``forward_fn``
    (the pipeline stages place and read the three by name), a mesh
    (``parallel/sharding`` shards the three's columns by ``tp``; a fused
    stack's would have to be grouped by shard, and ring prefill reads that
    tree), a family forward that does not read one (``reads_wqkv``: the
    families through ``llama.qkv_products``), or a tree that holds neither
    the three nor the one. Read off what the engine is handed - no flag,
    no model name."""
    from dynamo_tpu.models import get_family
    from dynamo_tpu.ops.quant import holds
    if forward_fn is not None:
        return "split:forward"
    if config.mesh is not None or config.shard_params_fn is not None:
        return "split:mesh"
    if not getattr(get_family(model_cfg).forward, "reads_wqkv", False):
        return "split:family"
    layers = params["layers"]
    if not (holds(layers, "wqkv")
            or all(name in layers for name in llama.QKV)):
        return "split:tree"
    return "fused"


def serving_weights(model_cfg: ModelConfig, params,
                    config: "JaxEngineConfig",
                    forward_fn: Optional[Callable] = None):
    """The tree as an engine of this configuration holds it: ``wq | wk |
    wv`` side by side where ``qkv_form`` says ``fused``, else the tree as
    it is. The engine applies it to whatever tree it is handed; a caller
    that OWNS its tree (the worker) applies it first and keeps the result
    alone, so that the three are gone before the engine makes its pools -
    an argument lives as long as the call it was passed to."""
    if qkv_form(model_cfg, params, config, forward_fn) != "fused":
        return params
    # what made the tree may still be running: an initialiser's float32
    # temporaries are held until the device has used them, and a fused
    # stack allocated meanwhile stands on top of them (one run of three
    # peaked 0.56 GB over ``init_params``' own 14.45 GB at Qwen3-4B)
    jax.block_until_ready(params)
    return {**params, "layers": llama.fuse_qkv(params["layers"])}


class JaxEngine(ScheduledEngineBase):
    """Continuous-batching paged-KV engine over a jax Llama-family model."""

    # jax dispatch is asynchronous: dispatch_decode / dispatch_multistep
    # return once the program is enqueued (engine/loop.py)
    dispatch_head_start_s = 0.02

    def __init__(self, model_cfg: ModelConfig, params,
                 config: Optional[JaxEngineConfig] = None,
                 forward_fn: Optional[Callable] = None):
        self.model_cfg = model_cfg
        self.cfg = config or JaxEngineConfig()
        self._sp = 1
        self._dp = 1
        if self.cfg.mesh is not None:
            self._sp = dict(self.cfg.mesh.shape).get(self.cfg.sp_axis, 1)
            self._dp = dict(self.cfg.mesh.shape).get("dp", 1)
        if self._dp > 1:
            # batch-dim sharding needs every padded batch divisible by dp:
            # raise the bucket floors so even a 1-sequence step pads to dp,
            # and reject a cap that cannot divide — buckets double from the
            # floor then CLAMP at max_num_seqs, so an indivisible cap would
            # silently run the heaviest (full-load) batches replicated
            if self.cfg.max_num_seqs % self._dp:
                raise ValueError(
                    f"max_num_seqs={self.cfg.max_num_seqs} not divisible "
                    f"by dp={self._dp}: the saturated decode batch could "
                    "not shard over the dp axis")
            self.cfg.min_decode_bucket = max(self.cfg.min_decode_bucket,
                                             self._dp)
            self.cfg.min_prefill_seqs_bucket = max(
                self.cfg.min_prefill_seqs_bucket, self._dp)
        ring_threshold = None
        if self._sp > 1:
            ring_threshold = (self.cfg.ring_threshold
                              if self.cfg.ring_threshold is not None
                              else self.cfg.max_prefill_chunk)
        self.multistep = (max(1, int(self.cfg.decode_multistep))
                          if self.cfg.decode_multistep is not None
                          else decode_multistep_default())
        self.mixed_batch = (bool(self.cfg.mixed_batch)
                            if self.cfg.mixed_batch is not None
                            else mixed_batch_default())
        # a family that keeps a slot a sequence beside its pages: what
        # only moves block chains is refused by name here, at start-up
        self.state_slots = 0
        # ... and a family that keeps index pages beside its keys and
        # values (one block chain, no slot): what places or moves the
        # pages of one pool is refused the same way
        selects = bool(model_cfg.index_topk)
        if model_cfg.slot_kind or selects:
            for what, on in (
                    ("a device mesh (--tensor-parallel-size, "
                     "--data-parallel-size, --sequence-parallel-size)",
                     self.cfg.mesh is not None
                     or self.cfg.shard_pages_fn is not None),
                    ("a custom forward_fn (pipeline stages)",
                     forward_fn is not None),
                    ("--quantize", bool(self.cfg.quantize)),
                    ("--speculative-num-tokens (a rejected draft would "
                     "have to roll the slot back)",
                     bool(self.cfg.spec_tokens)
                     and bool(model_cfg.slot_kind))):
                if on:
                    model_cfg.paged_only(what)
            if self.cfg.spec_tokens and not model_cfg.slot_kind:
                raise NotImplementedError(
                    "--speculative-num-tokens: a verify window over a "
                    "learned selection (sa_config, models/moe.py) is not "
                    "implemented")
        if model_cfg.slot_kind:
            self.state_slots = int(self.cfg.state_slots
                                   or self.cfg.max_num_seqs)
            if self.state_slots < 1:
                raise ValueError("state_slots must be at least 1")
        super().__init__(
            num_pages=self.cfg.num_pages, page_size=self.cfg.page_size,
            max_num_seqs=self.cfg.max_num_seqs,
            max_prefill_chunk=self.cfg.max_prefill_chunk,
            max_context=self.cfg.max_context,
            max_prefill_seqs=self.cfg.max_prefill_seqs,
            ring_threshold=ring_threshold,
            spec_tokens=int(self.cfg.spec_tokens or 0),
            spec_ngram_max=self.cfg.spec_ngram_max,
            spec_ngram_min=self.cfg.spec_ngram_min,
            spec_chain_break=self.cfg.spec_chain_break,
            decode_multistep=self.multistep,
            mixed_batch=self.mixed_batch,
            decode_progress_every=(
                int(self.cfg.decode_progress_every)
                if self.cfg.decode_progress_every is not None
                else decode_progress_default()),
            state_slots=self.state_slots, slot_kind=model_cfg.slot_kind)
        # fused-path gates for penalized/guided rows: the scheduler
        # narrows block widths by the penalty window's remaining capacity
        # and asks the engine whether a row's grammar lowered to a device
        # table (engine-specific knowledge the raw Scheduler lacks)
        self.scheduler.cfg.penalty_window = self.cfg.penalty_window
        self.scheduler.cfg.guided_fuse_check = self._guided_fuse_check
        from dynamo_tpu.models import get_family
        family = get_family(model_cfg)
        # q, k and v from ONE stored matrix where this engine's forward
        # reads one: laid side by side here, once, ahead of the int8
        # transform (a tree that ``serving_weights`` already laid out is
        # taken as it is)
        self.qkv = qkv_form(model_cfg, params, self.cfg, forward_fn)
        self.params = serving_weights(model_cfg, params, self.cfg,
                                      forward_fn)
        if self.cfg.quantize:
            if self.cfg.quantize != "int8":
                raise ValueError(
                    f"quantize={self.cfg.quantize!r}: only 'int8' "
                    "(W8A8 dynamic) is implemented")
            from dynamo_tpu.models import gemma
            if family is not llama and family is not gemma:
                # the MoE/MLA families' expert/latent matmul sites do not
                # dispatch through quant.mm yet
                raise ValueError(
                    f"quantize='int8' currently covers the llama family "
                    f"tree (llama/mistral/qwen dense) and gemma-2; "
                    f"model_type {model_cfg.model_type!r} is served bf16")
            if forward_fn is not None:
                # custom forwards (the pp stage bodies) are not
                # quant-aware: _LlamaStage.tail would silently fall back
                # to embed.T when quantize_params pops "lm_head"
                raise ValueError(
                    "quantize='int8' does not compose with a custom "
                    "forward_fn (pipeline parallelism) yet")
            from dynamo_tpu.ops.quant import quantize_params
            self.params = quantize_params(self.params)
        self._forward = forward_fn or family.forward
        if (forward_fn is None and self.cfg.mesh is not None
                and self.cfg.mesh.shape.get("ep", 1) > 1):
            # EP active: hand the MoE families the mesh so their dispatch
            # buffers pin to P("ep") — each chip holds [E_local, C]
            import functools
            import inspect
            if "ep_mesh" in inspect.signature(family.forward).parameters:
                self._forward = functools.partial(
                    family.forward, ep_mesh=self.cfg.mesh)
        impl = self.cfg.attn_impl
        if impl not in ATTN_IMPLS:
            raise ValueError(
                f"unknown attn_impl {impl!r}: one of "
                + ", ".join(repr(v) for v in ATTN_IMPLS))
        # "auto" may settle on the XLA path where the kernels cannot run;
        # an attn_impl asked for by name is honoured or is an error
        auto = impl == "auto"
        if auto:
            impl = ("pallas" if jax.devices()[0].platform == "tpu"
                    else "scan")
        if forward_fn is not None and impl == "pallas":
            # custom forwards get the attn_impl kwarg only when their
            # signature accepts it (pipeline_forward does — its stage body
            # runs the stacked kernels on the shard_map-local cache slab)
            import inspect
            try:
                takes_attn = "attn_impl" in inspect.signature(
                    forward_fn).parameters
            except (TypeError, ValueError):
                takes_attn = False
            if not takes_attn:
                if not auto:
                    raise ValueError(
                        "attn_impl='pallas' was asked for, but the custom "
                        "forward_fn takes no attn_impl")
                logger.info("custom forward_fn without attn_impl support: "
                            "using the XLA scan path")
                impl = "scan"
        if impl == "pallas":
            from dynamo_tpu.ops.pallas.decode import supports
            if not supports(model_cfg.head_dim, self.cfg.page_size):
                why = ("the Pallas kernels need head_dim%128==0 and "
                       f"page_size%8==0 (got {model_cfg.head_dim}/"
                       f"{self.cfg.page_size})")
                if not auto:
                    raise ValueError(
                        f"attn_impl={impl!r} was asked for, but {why}")
                logger.info("%s; using the XLA path", why)
                impl = "scan"
        if (forward_fn is None and self.cfg.mesh is not None
                and impl == "pallas" and model_cfg.kv_lora_rank):
            # GSPMD cannot partition a Mosaic call ("Mosaic kernels cannot
            # be automatically partitioned"): on a mesh the GQA stacked
            # kernels run per shard (_per_shard below); the MLA kernels
            # have no such wrapper
            why = ("the MLA Pallas kernels are not wrapped in shard_map, "
                   "so they cannot run on a mesh")
            if not auto:
                raise ValueError(
                    f"attn_impl={impl!r} was asked for, but {why}")
            logger.info("%s; using the XLA path", why)
            impl = "scan"
        self.attn_impl = impl
        # the attention op of each step form — S == 1, a padded prefill
        # chunk batch, a token-packed (ragged) step — or None: the
        # family's own XLA attention
        self._attn_decode = self._attn_prefill = self._attn_packed = None
        if impl == "pallas":
            from dynamo_tpu.ops.pallas.decode import (
                paged_decode_attention_stacked)
            from dynamo_tpu.ops.pallas.prefill import (
                paged_prefill_attention_stacked)
            from dynamo_tpu.ops.pallas.ragged import (
                ragged_mixed_attention_packed)
            self._attn_decode = self._per_shard(
                paged_decode_attention_stacked, forward_fn)
            self._attn_prefill = self._per_shard(
                paged_prefill_attention_stacked, forward_fn)
            self._attn_packed = self._per_shard(
                ragged_mixed_attention_packed, forward_fn)
        if self.state_slots:
            # several kinds of cache in one donated value: the paged pool
            # of the full-attention layers and the pools whose slots the
            # other layers' rows own (a window ring holds the most tokens
            # a row brings in one step beside the window)
            self.pages = family.make_pages(
                model_cfg, self.cfg.num_pages, self.cfg.page_size,
                state_slots=self.state_slots,
                max_chunk=self.cfg.max_prefill_chunk)
            if "state" in self.pages:
                # what one row's step moves of the recurrent state: its
                # slot of every linear layer, once in and once out
                pool = self.pages["state"]
                self.state_row_bytes = (
                    pool.shape[0] * 2 * int(np.prod(pool.shape[2:]))
                    * pool.dtype.itemsize)
        else:
            self.pages = llama.make_pages(model_cfg, self.cfg.num_pages,
                                          self.cfg.page_size)
        if self.cfg.shard_params_fn is not None:
            self.params = self.cfg.shard_params_fn(self.params)
        if self.cfg.shard_pages_fn is not None:
            self.pages = self.cfg.shard_pages_fn(self.pages)
        import inspect
        try:
            # gate for the logits_window surfaces (speculative verify +
            # prompt scoring); computed once — custom forward_fns
            # (pipeline stages) and exotic families lack the kwarg
            self._fwd_has_logits_window = (
                "logits_window" in inspect.signature(
                    self._forward).parameters)
        except (TypeError, ValueError):
            self._fwd_has_logits_window = False
        self.spec_K = int(self.cfg.spec_tokens or 0)
        if self.spec_K:
            if forward_fn is not None:
                raise ValueError(
                    "spec_tokens>0 does not compose with a custom "
                    "forward_fn (pipeline parallelism); drop "
                    "--speculative-num-tokens or the pp flag")
            if not self._fwd_has_logits_window:
                raise ValueError(
                    "spec_tokens>0 needs a family forward with "
                    "logits_window support (all built-in families carry "
                    f"it); {model_cfg.model_type!r} has none — drop "
                    "--speculative-num-tokens to serve it")
        self.gen_block = int(model_cfg.gen_block)
        if self.gen_block > 1:
            self._init_block_diffusion(forward_fn)
        # a row of the page table: the row's pages and, for a family with
        # a recurrent state, its slot of the state pool in one more column
        # (``_table_row``; the family's forward cuts it off)
        self.table_width = (self.cfg.max_context // self.cfg.page_size
                            + bool(self.state_slots))
        self._rng = jax.random.PRNGKey(self.cfg.seed)
        self._step_counter = 0
        self._jit_step = jax.jit(self._step_impl, donate_argnums=(1,))
        self._jit_ring_step = jax.jit(self._ring_step_impl,
                                      donate_argnums=(1,))
        # chained decode: tokens come from the previous step's on-device
        # packed output (column 0) instead of the host. prev_packed is NOT
        # donated — the host still fetches it after this dispatch.
        self._jit_chained = jax.jit(self._chained_step_impl,
                                    donate_argnums=(1,))
        self._jit_spec = jax.jit(self._spec_step_impl, donate_argnums=(1,))
        # the prefill-carrying steps (a MixedStepBatch, a non-ring
        # PrefillBatch) run TOKEN-PACKED where the engine can tell, from
        # what it is, that they may: one [T] program for prompt chunks and
        # decode rows (_packed_step_impl). Everywhere else they are the
        # plain [B, S] step program, and the counter says why.
        self.padded_reason = self._why_padded(forward_fn, family)
        # the most slots a packed step holds: the prompt-token budget and
        # one token for every other row, in steps of 128 (_token_bucket)
        self._packed_cap = -(-(self.cfg.max_prefill_chunk
                               + self.cfg.max_num_seqs) // 128) * 128
        self._jit_packed = (jax.jit(self._packed_step_impl,
                                    donate_argnums=(1,))
                            if self.padded_reason is None else None)
        # rows of packed steps the decode kernel attended (the ring's
        # ``decode_kernel_rows``, summed:
        # dynamo_worker_packed_decode_kernel_rows_total)
        self.packed_decode_kernel_rows = 0
        # the form in which the one-token rows of a model whose full
        # layers attend a learned selection attend it: "masked" on the
        # latent kernels, "gathered" on the XLA path (None: no layer
        # selects), and the rows dispatched in each
        # (dynamo_worker_attn_one_token_rows_total{form})
        self.one_token_form: Optional[str] = None
        if model_cfg.index_topk:
            on_kernels = (
                family.on_kernels(model_cfg, self._attn_decode,
                                  self.cfg.page_size)
                if model_cfg.slot_kind
                else llama.selects_on_kernels(self._attn_decode, self.pages))
            self.one_token_form = "masked" if on_kernels else "gathered"
        self.attn_one_token_rows: Dict[str, int] = {}
        # prefill-carrying dispatches by form: "packed", "padded:<reason>"
        # (dynamo_worker_prefill_steps_total; the collector pre-seeds the
        # labels, worker/metrics.py PREFILL_FORMS)
        self.prefill_steps: Dict[str, int] = {}
        # whether a packed step's one-token rows take the decode kernel:
        # a causal GQA model's on the kernels (``ops/pallas/ragged.py``)
        self._packed_splits = False
        if self.padded_reason is None and not model_cfg.kv_lora_rank:
            from dynamo_tpu.ops.pallas.ragged import takes_decode_kernel
            self._packed_splits = takes_decode_kernel(self.gen_block)
        self._last_packed = None  # most recent packed output (device)
        self.ring_steps = 0  # diagnostics: sequence-parallel prefills run
        self.chained_decode_steps = 0  # diagnostics: decode steps chained
        # diagnostics + test tap: jitted page-scatter dispatches (KV
        # inject commits). The batched inject pipeline's regression guard
        # counts these instead of timing walls.
        self.page_scatter_dispatches = 0
        # fused decode: per-width jits (lax.scan length is static) and the
        # dispatch tap the M-tokens-cost-<=M/N+c regression guard counts
        # (dynamo_worker_decode_dispatches_total samples these at scrape)
        self._jit_ms: Dict[int, Callable] = {}
        # from a mixed step's packed output to the carry of the block
        # chained behind it (``_handover_impl``), made on first use
        self._jit_handover: Optional[Callable] = None
        # the tokens of a mixed step chained behind a mixed step, its
        # decode rows' from that step's packed output (``_fill_impl``)
        self._jit_fill: Optional[Callable] = None
        self.decode_dispatches = 0   # decode-family jitted dispatches
        self.multistep_blocks = 0    # of which fused multi-step blocks
        self.mixed_steps = 0         # mixed prefill+decode dispatches
        # device-resident decode sampling/stop arrays, rebuilt only when
        # the decode batch composition changes (not ~10 jnp.asarray
        # uploads per step): (key, arrays)
        self._samp_cache: Optional[Tuple] = None
        # padded page-table host+device arrays for decode-family batches,
        # keyed on batch composition and per-row Sequence.table_version
        # (the _samp_cache pattern): reused verbatim until a row's pages
        # change instead of rebuilding + re-uploading the padding every
        # step — (key, versions, np table, device table)
        self._table_cache: Optional[Tuple] = None
        # expert-layer accounting: every dispatch of a MoE family returns
        # its counts as device scalars through ``aux`` — experts touched
        # and assignments from the grouped layer, dropped assignments from
        # the dispatch backend (VERDICT r4 weak 5). They queue here;
        # stats() and the metrics scrape drain them into the totals, so
        # the hot loop never pays a host round trip for them.
        self._pending_moe_aux: list = []
        self.moe_totals: Dict[str, int] = {
            "moe_dropped_assignments": 0, "moe_experts_touched": 0,
            "moe_assignments": 0, "moe_held_assignments": 0,
            "moe_zero_assignments": 0}
        # appends happen on the step worker thread, drains on either that
        # thread (the >512 cap) or the event-loop thread (stats scrape)
        self._moe_aux_lock = threading.Lock()
        # what "every expert of every expert layer" counts to in one
        # forward pass: the denominator of the touched share
        # (of the experts this worker HOLDS: the touched count's range)
        self._moe_slots_per_step = int(
            model_cfg.experts_held * model_cfg.num_expert_layers)
        self.moe_expert_slots = 0
        # compile-event detection (engine/steptrace.py): the first call on
        # a fresh (jit program, B, S) bucket ALWAYS traces+compiles, so
        # its dispatch wall IS the compile cost — no threshold guessing.
        # Seen keys use id(fn) (not the kind name) so a padded mixed step
        # shares the plain step program's buckets (same trace, zero extra
        # compiles). Appends happen on the step worker thread, the loop
        # drains on the event-loop thread (the _moe_drops idiom).
        self._jit_seen: set = set()
        self._pending_compiles: list = []
        self._compile_lock = threading.Lock()
        # multi-host: called with (kind, arrays, step) right before each
        # dispatch so rank 0 can broadcast the step to follower ranks
        # (parallel/multihost.py); None on single-host workers
        self.step_tap: Optional[Callable] = None
        # guided decoding (engine/guided.py): set by enable_guided once the
        # worker knows the tokenizer's byte vocabulary
        self._guided_vocab = None
        self._guided_bytes = None
        self._guided_reqs: dict = {}
        self._grammar_cache: dict = {}
        self._grammar_lock = threading.Lock()
        # fused guided decoding: lowered device tables per grammar (None =
        # not tableable), keyed like _grammar_cache and guarded by the
        # same lock
        self._guided_tables: dict = {}
        # host-side automaton mirrors for the post-block parity
        # cross-check — owned by the EVENT-LOOP thread only (the step
        # thread owns _guided_reqs; GuidedRequest objects are never
        # shared across the two)
        self._guided_mirrors: dict = {}
        self.guided_parity_mismatches = 0
        # cancel/finish release: the event-loop thread records finished
        # request ids; the step thread drains them before assembling the
        # next device-sampling batch so a dead row's FSM/ring-buffer
        # state cannot linger in the composition-keyed caches
        self._released: set = set()
        self._released_lock = threading.Lock()
        # requests refused at admission, by reason
        # (dynamo_worker_requests_refused_total)
        self.requests_refused: Dict[str, int] = {}

    def _init_block_diffusion(self, forward_fn) -> None:
        """A model that generates by diffusion over blocks: refuse by name
        what does not compose with it yet, and hand the scheduler the
        block length."""
        B = self.gen_block
        no = None
        if self.spec_K:
            no = ("--speculative-num-tokens (n-gram drafts verify one "
                  "next token a position)")
        elif forward_fn is not None:
            no = "a custom forward_fn (pipeline stages)"
        elif self.cfg.mesh is not None:
            no = "a device mesh (the per-shard kernels take no block)"
        elif not self._fwd_has_logits_window:
            no = "a family forward without logits_window"
        elif not self.cfg.pipeline_decode:
            no = "pipeline_decode off (a pass dispatch is asynchronous)"
        elif self.cfg.page_size % B:
            no = (f"a page of {self.cfg.page_size} tokens that is not a "
                  f"multiple of the block")
        if no is not None:
            raise ValueError(
                f"{self.model_cfg.model_type!r} generates by diffusion over "
                f"blocks of {B} positions, which does not compose with {no}")
        self.scheduler.cfg.gen_block = B
        self.gen_steps = min(max(int(self.cfg.denoising_steps or B), 1), B)
        self.gen_threshold = float(self.cfg.confidence_threshold)
        # per-width pass programs, and the composition-keyed sampling
        # arrays of a pass dispatch (the ``_samp_cache`` pattern)
        self._jit_passes: Dict[int, Callable] = {}
        self._gen_samp_cache: Optional[Tuple] = None

    @property
    def generation(self) -> str:
        """How this engine generates, for the ``startup.engine`` span."""
        if self.gen_block <= 1:
            return "causal"
        return (f"block_diffusion[B={self.gen_block},steps={self.gen_steps}"
                f",tau={self.gen_threshold:g}]")

    @property
    def kv_pool(self):
        """The paged pool alone (of a family with a slot a sequence,
        ``pages`` holds the other pools too)."""
        return self.pages["kv"] if isinstance(self.pages, dict) \
            else self.pages

    @property
    def page_pools(self) -> Tuple[str, ...]:
        """The pools of ``pages`` that the page table addresses (axis 1:
        the pages), of a family that keeps several: one block chain
        holds a page of each."""
        return tuple(k for k in ("kv", "index")
                     if isinstance(self.pages, dict) and k in self.pages)

    @property
    def cache_kinds(self) -> str:
        """The kinds of cache the engine keeps, for ``startup.engine``."""
        L, _n, _two, Hkv, _ps, Dh = self.kv_pool.shape
        kinds = f"paged[L={L},Hkv={Hkv},Dh={Dh}]"
        if self.model_cfg.state_layers:
            kinds += (f"+state[L={self.model_cfg.state_layers},"
                      f"S={self.state_slots},f32]")
        if "index" in self.page_pools:
            kinds += (f"+index[L={self.pages['index'].shape[0]},"
                      f"D={self.model_cfg.index_head_dim}]")
        if self.model_cfg.window_layers:
            Lw, _s, Rp, _two, _one, ps, Dw = self.pages["win"].shape
            kinds += (f"+window[L={Lw},"
                      f"S={self.state_slots},R={Rp * ps},D={Dw}]")
        return kinds

    @property
    def cache_bytes(self) -> Dict[str, int]:
        """Device bytes of the cache by kind
        (``dynamo_worker_cache_bytes{kind}``)."""
        kind = {"kv": "paged", "index": "index", "win": "window"}
        out: Dict[str, int] = {}
        pools = (self.pages if isinstance(self.pages, dict)
                 else {"kv": self.pages})
        for name, pool in pools.items():
            k = kind.get(name, "state")
            out[k] = out.get(k, 0) + int(pool.size) * pool.dtype.itemsize
        return out

    def _table_row(self, table: np.ndarray, i: int, seq) -> None:
        """Row ``i`` of a step's page table: where ``seq``'s cache lives."""
        table[i, :len(seq.page_ids)] = seq.page_ids
        if self.state_slots:
            table[i, -1] = seq.state_slot

    @property
    def packed_attention(self) -> Optional[str]:
        """The kernels that attend a token-packed step, by row kind, for
        the ``startup.engine`` span (None: no step runs packed): latent
        attention has one kernel for every row; the GQA families hand
        their one-token rows to the decode kernel unless the model's
        visibility has a block (``ops/pallas/ragged.py``)."""
        if self.padded_reason is not None:
            return None
        if self.one_token_form is not None:
            if self.one_token_form != "masked":
                return "gathered"
            return ("chunks:mla_selected,one_token:mla_selected_rows"
                    if self.model_cfg.kv_lora_rank
                    else "chunks:selected_chunks,one_token:selected_rows")
        if self.model_cfg.kv_lora_rank:
            return "mla_ragged"
        if self._packed_splits:
            return "chunks:ragged_mixed,one_token:paged_decode"
        return "ragged_mixed"

    def _count_one_token_rows(self, n: int) -> None:
        """``n`` more one-token rows dispatched, under this engine's form
        of a learned selection (nothing where no layer selects)."""
        form = self.one_token_form
        if form is not None:
            self.attn_one_token_rows[form] = (
                self.attn_one_token_rows.get(form, 0) + n)

    def _decode_kernel_rows(self, new: np.ndarray, slots: int) -> int:
        """Rows of one packed step that the decode kernel attends: the
        one-token rows behind the last row of several tokens — the rule of
        ``ragged_mixed_attention_packed``, on the host's copy of
        ``new_lens``."""
        if not self._packed_splits or len(new) > slots:
            return 0
        several = np.flatnonzero(new > 1)
        n_chunk = int(several[-1]) + 1 if several.size else 0
        return int(np.count_nonzero(new[n_chunk:] == 1))

    def _per_shard(self, kernel: Callable, forward_fn) -> Callable:
        """On a mesh, run a GQA stacked kernel once per ``tp`` shard under
        ``shard_map``: attention is independent per kv head, so each chip
        runs the kernel on its own heads' slice of the queries and of
        every page (the cache is sharded on Hkv —
        ``parallel/sharding.py``) with no collective, and rows split over
        ``dp`` when the batch divides. Without this the TPU compiler
        refuses the program. Custom forwards (pipeline stages) already
        call the kernel inside their own ``shard_map``."""
        mesh = self.cfg.mesh
        if mesh is None or forward_fn is not None:
            return kernel
        from jax.sharding import PartitionSpec as P

        def per_shard(q, pages, layer_idx, page_table, *rest, window=None,
                      softcap=None):
            # rest: the kernel's row arrays, then sm_scale — a padded
            # kernel's (positions, total_lens), the packed kernel's
            # (q_starts, q_lens, kv_lens) over q [T, Hq, Dh], whose token
            # axis has no rows to split
            *row_arrays, sm_scale = rest
            packed = q.ndim == 3
            rows = ("dp" if not packed and self._dp > 1
                    and q.shape[0] % self._dp == 0 else None)
            heads = (P(None, "tp", None) if packed
                     else P(rows, None, "tp", None))

            def local(q, pages, layer, win, table, *row_arrays):
                return kernel(q, pages, layer, table, *row_arrays, sm_scale,
                              window=win, softcap=softcap)

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(heads, P(None, None, None, "tp", None, None),
                          P(), P(), P(rows, None),
                          *(P(rows, *([None] * (a.ndim - 1)))
                            for a in row_arrays)),
                out_specs=heads, check_vma=False)(
                q, pages, jnp.asarray(layer_idx, jnp.int32),
                jnp.asarray(0 if window is None else window, jnp.int32),
                page_table, *row_arrays)

        # the markers the gemma and deepseek forwards look for
        per_shard.supports_window_softcap = True
        per_shard.pallas_paged_kernel = True
        # ... and the one that keeps the expert layer's own Mosaic kernel
        # off a mesh (models/moe.grouped_on_chip)
        per_shard.per_shard = True
        return per_shard

    # -- guided decoding ---------------------------------------------------

    def enable_guided(self, token_bytes, eos_ids) -> None:
        """Arm response_format support: ``token_bytes[id]`` is the byte
        string token id appends to the output (None for special tokens),
        ``eos_ids`` the ids allowed once the document completes."""
        from dynamo_tpu.engine.guided import GuidedVocab
        self._guided_bytes = list(token_bytes)
        if len(self._guided_bytes) < self.model_cfg.vocab_size:
            # model vocabs are usually PADDED past the tokenizer's: the
            # mask must cover every logit column or the device-side gather
            # clamps and padded ids inherit arbitrary bits from the last
            # word (sampleable garbage that silently un-wedges the
            # constraint)
            self._guided_bytes += [None] * (
                self.model_cfg.vocab_size - len(self._guided_bytes))
        for e in eos_ids:
            # an EOS that is a regular vocab entry (toy tokenizers) must
            # never be walked as literal text — it ENDS the document
            if 0 <= e < len(self._guided_bytes):
                self._guided_bytes[e] = None
        self._guided_vocab = GuidedVocab(self._guided_bytes, list(eos_ids))

    def _refusal(self, request) -> Optional[Tuple[str, str]]:
        """(reason, message) of what a block-diffusion engine cannot
        serve yet, or None: requests whose sampling would have to act on
        one next token a row a step."""
        so = request.sampling_options
        what = None
        if so.guided:
            what = ("guided", "guided decoding (response_format, forced "
                    "tool calls)")
        elif (so.frequency_penalty or so.presence_penalty
              or (so.repetition_penalty not in (None, 0, 1.0))):
            what = ("penalties", "frequency, presence and repetition "
                    "penalties")
        elif so.logit_bias:
            what = ("logit_bias", "logit_bias")
        elif request.prefill_only:
            what = ("disagg_prefill", "disaggregated prefill (a prefill "
                    "worker samples one next token)")
        if what is None:
            return None
        return what[0], (f"{what[1]} cannot be served by a model that "
                         "generates by diffusion over blocks")

    def validate_request(self, request) -> Optional[str]:
        if self.gen_block > 1:
            refused = self._refusal(request)
            if refused is not None:
                self.requests_refused[refused[0]] = (
                    self.requests_refused.get(refused[0], 0) + 1)
                return refused[1]
        spec = request.sampling_options.guided
        if not spec:
            return None
        if self._guided_vocab is None:
            return ("guided decoding (response_format) is not available: "
                    "the worker did not register a token-byte vocabulary")
        try:
            self._grammar_for(spec)
        except Exception as e:  # noqa: BLE001 — surface compile errors
            return f"response_format rejected: {e}"
        try:
            # pre-lower the fused-path table here (event-loop thread, per
            # grammar, cached) so the step thread never pays the BFS; a
            # non-tableable grammar is NOT an error — the row just decodes
            # per-step (fallback reason "guided_table")
            self._guided_table_for(spec)
        except Exception:  # noqa: BLE001 — table lowering is best-effort
            logger.warning("guided table lowering failed; request %s "
                           "decodes per-step", request.request_id,
                           exc_info=True)
        return None

    def _grammar_for(self, spec: dict):
        """Compile-or-cache a guided grammar. Called from BOTH the
        event-loop thread (validate_request) and the step worker thread
        (_guided_masks) — the lock keeps the evict/insert pair atomic."""
        import json as _json

        from dynamo_tpu.engine.guided import compile_guided
        key = _json.dumps(spec, sort_keys=True)
        with self._grammar_lock:
            g = self._grammar_cache.get(key)
        if g is None:
            g = compile_guided(spec)
            with self._grammar_lock:
                if len(self._grammar_cache) >= 64:
                    self._grammar_cache.pop(
                        next(iter(self._grammar_cache)), None)
                g = self._grammar_cache.setdefault(key, g)
        return g

    def _guided_table_for(self, spec: dict):
        """Lowered device transition table for a grammar, or None when it
        is not tableable (state count over ``guided_table_bytes``, or a
        reachable empty-mask state). Cached beside the grammar cache under
        the same lock; normally warmed by ``validate_request`` on the
        event-loop thread so the step thread only ever reads."""
        import json as _json

        from dynamo_tpu.engine.guided import build_guided_table
        key = _json.dumps(spec, sort_keys=True)
        with self._grammar_lock:
            if key in self._guided_tables:
                return self._guided_tables[key]
        table = build_guided_table(self._grammar_for(spec),
                                   self._guided_vocab,
                                   self.cfg.guided_table_bytes)
        with self._grammar_lock:
            if len(self._guided_tables) >= 64:
                self._guided_tables.pop(
                    next(iter(self._guided_tables)), None)
            if key not in self._guided_tables:
                self._guided_tables[key] = table
            return self._guided_tables[key]

    def _guided_fuse_check(self, seq) -> bool:
        """Scheduler hook: may this guided row ride a fused multistep
        block? True iff its grammar lowered to a device table."""
        spec = seq.request.sampling_options.guided
        if not spec or self._guided_vocab is None:
            return False
        try:
            return self._guided_table_for(spec) is not None
        except Exception:  # noqa: BLE001 — a lowering bug must not
            return False   # break planning; the row decodes per-step

    def release_request(self, rid) -> None:
        """A request left the scheduler (finished or cancelled). Drop its
        event-loop-side automaton mirror now and queue the step-thread
        state (``_guided_reqs`` entry, composition-keyed sampling cache)
        for release at the next batch assembly — the two threads never
        touch each other's objects."""
        self._guided_mirrors.pop(rid, None)
        with self._released_lock:
            self._released.add(rid)

    def multistep_guided_check(self, seq) -> None:
        """Post-block guided parity cross-check (event-loop thread).

        The fused block enforces the grammar with the DEVICE table; this
        re-derives the automaton on the host from the committed tokens and
        verifies each one is byte-walk legal (EOS: ``eos_ok``). The mirror
        set here is separate from the step thread's ``_guided_reqs`` and
        legality runs on the pure ``step``/``eos_ok`` walkers, never
        ``GuidedVocab.mask`` (its cache eviction is not thread-safe). A
        mismatch means device/host state divergence: counted on
        ``guided_parity_mismatches`` and logged, and the mirror wedges so
        one divergence is reported once."""
        spec = seq.request.sampling_options.guided
        if not spec or self._guided_vocab is None:
            return
        from dynamo_tpu.engine.guided import GuidedRequest, eos_ok
        rid = seq.request.request_id
        gen = seq.generated
        gr = self._guided_mirrors.get(rid)
        if gr is None or gr.n_seen > len(gen):
            try:
                gr = GuidedRequest(self._grammar_for(spec),
                                   self._guided_vocab, self._guided_bytes)
            except Exception:  # noqa: BLE001 — mirror is best-effort
                return
            self._guided_mirrors[rid] = gr
        new = gen[gr.n_seen:]
        gr.n_seen = len(gen)
        ok = True
        for t in new:
            if gr.wedged:
                return
            t = int(t)
            if t in self._guided_vocab.eos_ids:
                if not eos_ok(gr.grammar, gr.state):
                    ok = False
                    break
                continue          # host advance no-ops EOS
            gr.advance(t)
            if gr.wedged:
                ok = False
                break
        if not ok:
            self.guided_parity_mismatches += 1
            gr.wedged = True
            logger.warning(
                "fused guided block committed a grammar-illegal token for "
                "%s: device table and host automaton diverged", rid)
        if len(self._guided_mirrors) > 4 * self.cfg.max_num_seqs:
            stale = sorted(self._guided_mirrors)
            for k in stale[:len(stale) // 2]:
                self._guided_mirrors.pop(k, None)

    def _guided_req_for(self, seq, spec: dict):
        """Get-or-(re)build the per-request automaton and sync it to the
        sequence's generated tokens — shared by the plain per-step masks
        and the verify step's per-slot masks. ``n_seen`` beyond
        ``generated`` means a preemption rewound the sequence; rebuild
        and re-walk from scratch."""
        from dynamo_tpu.engine.guided import GuidedRequest
        rid = seq.request.request_id
        gr = self._guided_reqs.get(rid)
        if gr is None or gr.n_seen > len(seq.generated):
            gr = GuidedRequest(self._grammar_for(spec), self._guided_vocab,
                               self._guided_bytes)
            self._guided_reqs[rid] = gr
        gr.catch_up(seq.generated)
        gr.last_step = self._step_counter
        return gr

    def _guided_masks(self, rows, B: int) -> Optional[np.ndarray]:
        """Per-row packed allow-masks for this step, or None when no row
        is constrained. Unconstrained rows are all-ones (the device no-op).
        Automata catch up lazily from ``seq.generated`` — no token hook in
        the loop, and replays/preemption revives re-walk deterministically."""
        gv = self._guided_vocab
        if gv is None:
            return None
        masks = None
        for i, seq in enumerate(rows):
            spec = seq.request.sampling_options.guided
            if not spec:
                continue
            gr = self._guided_req_for(seq, spec)
            m = gr.mask()
            if m is not None:
                if masks is None:
                    masks = np.full((B, gv.words), 0xFFFFFFFF, np.uint32)
                masks[i] = m
        if len(self._guided_reqs) > 4 * self.cfg.max_num_seqs:
            # size-capped eviction by last touch (finished requests are
            # never unregistered explicitly — the step worker thread must
            # not race the event-loop thread over scheduler state)
            stale = sorted(self._guided_reqs.items(),
                           key=lambda kv: getattr(kv[1], "last_step", 0))
            for rid, _ in stale[:len(stale) // 2]:
                del self._guided_reqs[rid]
        return masks

    # -- compiled step -----------------------------------------------------

    def _shard_batch(self, tokens, positions, page_table, total_lens,
                     new_lens, temperature, top_k, top_p):
        """Constrain the batch dim over the mesh's ``dp`` axis (cross-host
        data parallelism): GSPMD partitions the whole forward along batch,
        and ``_sample_tail`` re-replicates the packed output (a tiny
        [B, 2+2K] all-gather) so rank 0 reads every row locally — the
        missing piece that kept multi-host at tp/sp-only (VERDICT r3 §5)."""
        if self._dp <= 1 or tokens.shape[0] % self._dp:
            # indivisible batch (e.g. the B=1 ring prefill): replicated
            return (tokens, positions, page_table, total_lens, new_lens,
                    temperature, top_k, top_p)
        from jax.sharding import NamedSharding, PartitionSpec
        row = NamedSharding(self.cfg.mesh, PartitionSpec("dp"))
        mat = NamedSharding(self.cfg.mesh, PartitionSpec("dp", None))
        c = jax.lax.with_sharding_constraint
        with stages.stage("step.inputs"):
            return (c(tokens, mat), c(positions, mat), c(page_table, mat),
                    c(total_lens, row), c(new_lens, row),
                    c(temperature, row), c(top_k, row), c(top_p, row))

    def _run_forward(self, attn, params, tokens, positions, pages,
                     page_table, total_lens, new_lens, **kw):
        """The family forward with the step form's attention op ``attn``
        (``_attn_decode`` / ``_attn_prefill`` / ``_attn_packed``), or with
        none where the XLA path serves: a custom ``forward_fn``
        (``pipeline_forward``) may implement the base signature only.
        Returns (logits, pages, aux); MoE families return the aux dict
        (dispatch drop counts), dense ones the plain pair."""
        if attn is not None:
            kw["attn_impl"] = attn
        out = self._forward(params, self.model_cfg, tokens, positions,
                            pages, page_table, total_lens, new_lens, **kw)
        return out[0], out[1], (out[2] if len(out) > 2 else {})

    def _step_impl(self, params, pages, tokens, positions, page_table,
                   total_lens, new_lens, rng, step, temperature, top_k,
                   top_p, pen=None):
        (tokens, positions, page_table, total_lens, new_lens, temperature,
         top_k, top_p) = self._shard_batch(
            tokens, positions, page_table, total_lens, new_lens, temperature,
            top_k, top_p)
        attn = (self._attn_decode if tokens.shape[1] == 1
                else self._attn_prefill)
        logits, pages, aux = self._run_forward(
            attn, params, tokens, positions, pages, page_table, total_lens,
            new_lens)
        pages, packed = self._sample_tail(logits, pages, rng, step,
                                          temperature, top_k, top_p, pen,
                                          total_lens)
        return pages, packed, aux

    def _why_padded(self, forward_fn, family) -> Optional[str]:
        """None where the prefill-carrying steps run token-packed, else
        the first reason they cannot: a custom
        ``forward_fn`` (pipeline stages), a family forward that does not
        declare the packed form (``supports_packed``; every built-in
        family declares it), a mesh
        with ``dp > 1`` (``_shard_batch`` splits rows, a packed axis has
        none), speculation (the verify window is ``[B, K+1]``), and the
        XLA ``scan`` path (the CPU's). Read off what the
        engine is — no flag, no model name."""
        if forward_fn is not None:
            return "forward"
        if not getattr(family.forward, "supports_packed", False):
            return "family"
        if self._dp > 1:
            return "dp"
        if self.spec_K:
            return "spec"
        if self.attn_impl != "pallas":
            return "attn_impl"
        return None

    def _packed_step_impl(self, params, pages, tokens, positions,
                          page_table, total_lens, new_lens, rng, step,
                          temperature, top_k, top_p, pen=None):
        """The TOKEN-PACKED step program (prompt chunks + decode rows in
        one ``[T]`` dispatch): ``tokens``/``positions`` ``[1, T]`` hold
        every row's new tokens back to back, the row arrays are ``[R]``
        as in ``_step_impl``, and row ``r`` owns slots ``cu[r] .. cu[r] +
        new_lens[r]`` with ``cu`` the exclusive cumulative sum the forward
        computes on the device (``models/llama.packed_rows``). Everything
        per token runs on ``[1, T, H]``; the cache write, the attention op
        and the last-token select take the rows as descriptors. The
        attention op (``_attn_packed``, ``ops/pallas/ragged.py``) runs the
        ragged kernel over the rows of several tokens and the decode
        kernel over the trailing one-token rows — both inside this one
        program; latent attention has ``mla_ragged`` for every row.
        Sampling sees the same ``[R]`` rows in the same order as the
        padded step."""
        logits, pages, aux = self._run_forward(
            self._attn_packed, params, tokens, positions, pages, page_table,
            total_lens, new_lens, packed=True)
        pages, packed = self._sample_tail(logits, pages, rng, step,
                                          temperature, top_k, top_p, pen,
                                          total_lens)
        return pages, packed, aux

    def _chained_step_impl(self, params, pages, prev_packed, positions,
                           page_table, total_lens, new_lens, rng, step,
                           temperature, top_k, top_p, pen=None):
        """Decode step whose input token is the previous step's on-device
        sampled token (packed column 0), row-aligned with the previous
        plan."""
        with stages.stage("step.chain"):
            tokens = prev_packed[:, :1]                    # [B, 1] int32
        return self._step_impl(params, pages, tokens, positions, page_table,
                               total_lens, new_lens, rng, step, temperature,
                               top_k, top_p, pen)

    def _decode_forward(self, params, pages, tok, pos, table, total, new):
        """One S==1 decode forward (the scan body of the fused block).
        Returns (logits [B, V], pages, aux)."""
        return self._run_forward(self._attn_decode, params, tok, pos, pages,
                                 table, total, new)

    def _multistep_impl(self, params, pages, tok, pos, table, total, alive,
                        budget, min_gate, rng, step0, temperature, top_k,
                        top_p, stop_ids, pen=None, pcarry=None, n_steps=1):
        """FUSED decode: ``n_steps`` decode steps in one jitted program —
        a ``lax.scan`` over the step body with donated ``pages`` carry,
        on-device sampling (``ops/sampling.sample_tokens``, the same
        epilogue as ``_sample_tail``), on-device position/total increment,
        and per-row stop detection. The host pays ONE dispatch and ONE
        fetch per block instead of per token.

        Carry per row: current input token, its position, total context
        length, liveness, and the remaining max-token budget / min_tokens
        gate. A row whose sampled token hits its stop set (EOS +
        stop_token_ids, ``min_tokens``-gated — ``stop_ids`` is the padded
        merge, -1 never matches) or exhausts its budget is masked to a
        no-op for the rest of the block: ``new_lens`` goes to 0 (finished
        sequences stop writing KV), position/total freeze, and its later
        sampled slots are garbage the host never reads (it re-derives the
        identical stop point from the same rules).

        Penalized/biased/guided rows ride the same block (no per-batch
        fallback): ``pcarry`` carries each row's penalty ring-buffer
        window (ids/cnt/ctx/bias/n — preloaded host-side on a fresh
        block, chained on device afterwards) and its guided automaton
        state id; ``pen`` carries the batch-static pieces (per-row knobs,
        the 2W prompt-reproduction list under ``pw``, the batched
        grammar transition table/masks under ``gt``). Per step the body
        applies penalties + bias over the window ∪ prompt entries, the
        grammar allow-mask LAST (same order as ``_sample_tail``), then
        absorbs the sampled token into the window and steps the
        automaton. The per-step path rebuilds the identical entry SET
        host-side each step, so fused vs per-step stays bit-identical.

        Returns (pages, packed [B, n_steps, 2+2K] — per-step rows in the
        exact ``_sample_tail`` column layout so the host unpack is shared
        — the carry dict for chaining block k+1, and the summed MoE drop
        aux). ``step0 + j`` feeds the rng fold so a fused run consumes the
        same per-step key sequence as ``n_steps`` per-step dispatches.
        """
        # the block's row-aligned inputs take the SAME dp partitioning as
        # the per-step dispatch it must stay bit-identical to: reuse
        # _shard_batch for the shared operands (``alive`` rides the
        # row-vector slot ``new_lens`` occupies there — the constraint
        # only cares about the [B] shape), then constrain the fused-path
        # extras under the identical divisibility gate
        (tok, pos, table, total, alive, temperature, top_k,
         top_p) = self._shard_batch(tok, pos, table, total, alive,
                                    temperature, top_k, top_p)
        if self._dp > 1 and tok.shape[0] % self._dp == 0:
            from jax.sharding import NamedSharding, PartitionSpec
            row = NamedSharding(self.cfg.mesh, PartitionSpec("dp"))
            mat = NamedSharding(self.cfg.mesh, PartitionSpec("dp", None))
            c = jax.lax.with_sharding_constraint
            with stages.stage("step.inputs"):
                stop_ids = c(stop_ids, mat)
                budget, min_gate = c(budget, row), c(min_gate, row)
                if pcarry is not None:
                    pcarry = {k: c(v, mat if v.ndim == 2 else row)
                              for k, v in pcarry.items()}
        B = tok.shape[0]
        pw = pen.get("pw") if pen is not None else None
        gt = pen.get("gt") if pen is not None else None
        if pcarry is not None:
            pids0 = pcarry["pids"]
            pcnt0, pctx0 = pcarry["pcnt"], pcarry["pctx"]
            pbias0, pn0 = pcarry["pbias"], pcarry["pn"]
            gstate0 = pcarry["gstate"]
        else:
            # unconstrained trace: zero-filled window/state so every
            # width's carry output keeps ONE fixed pytree structure (and
            # one set of out_shardings)
            W = self.cfg.penalty_window
            with stages.stage("step.inputs"):
                pids0 = jnp.zeros((B, W), jnp.int32)
                pcnt0 = jnp.zeros((B, W), jnp.float32)
                pctx0 = jnp.zeros((B, W), jnp.float32)
                pbias0 = jnp.zeros((B, W), jnp.float32)
                pn0 = jnp.zeros(B, jnp.int32)
                gstate0 = jnp.zeros(B, jnp.int32)

        def body(carry, j):
            (pages, tok, pos, total, alive,
             pids, pcnt, pctx, pbias, pn, gstate) = carry
            with stages.stage("step.inputs"):
                new = alive.astype(jnp.int32)
            logits, pages, aux = self._decode_forward(
                params, pages, tok, pos, table, total, new)
            with stages.stage("sample"):
                logits = logits.astype(jnp.float32)
                key = jax.random.fold_in(rng, step0 + j)
                if pw is not None:
                    # dynamic window ∪ prompt-reproduction entries, one
                    # scatter-add (excluded/pad entries carry a zero delta)
                    from dynamo_tpu.ops.sampling import (
                        apply_penalties, penalty_window_entries)
                    inc = penalty_window_entries(
                        pw["prompt_ids"], pw["prompt_valid"], pids, pn)
                    zs = jnp.zeros(inc.shape, jnp.float32)
                    logits = apply_penalties(
                        logits,
                        jnp.concatenate([pids, pw["prompt_ids"]], axis=1),
                        jnp.concatenate([pcnt, zs], axis=1),
                        jnp.concatenate([pctx, inc.astype(jnp.float32)],
                                        axis=1),
                        pw["fp"], pw["pp"], pw["rp"],
                        pen_bias=jnp.concatenate([pbias, zs], axis=1))
                if gt is not None:
                    # grammar allow-mask LAST: a penalty/bias can reweight
                    # inside the grammar but never resurrect an illegal token
                    from dynamo_tpu.ops.sampling import apply_vocab_mask
                    logits = apply_vocab_mask(logits, gt["masks"][gstate])
                if pen is not None:
                    sampled, logprobs = sample_tokens(
                        logits, key, temperature, top_k, top_p,
                        seeds=pen["seeds"], seed_rng=rng, seed_pos=total,
                        min_p=pen["min_p"])
                else:
                    sampled, logprobs = sample_tokens(logits, key, temperature,
                                                      top_k, top_p)
                cols = [sampled[:, None],
                        jax.lax.bitcast_convert_type(logprobs,
                                                     jnp.int32)[:, None]]
                if self.cfg.num_top_logprobs > 0:
                    # from the PENALIZED/MASKED logits — the distribution
                    # actually sampled from, as _sample_tail reports
                    ids, lp_bits = self._topk_cols(logits)
                    cols.append(ids)
                    cols.append(lp_bits)
                packed = jnp.concatenate(cols, axis=1)
            # stop checks, budgets and the rows carried to the next step
            with stages.stage("step.stop"):
                hit = jnp.any(stop_ids == sampled[:, None], axis=1)
                min_ok = (j + 1) >= min_gate
                stopped = (hit & min_ok) | ((j + 1) >= budget)
                new_alive = alive & ~stopped
                tok = jnp.where(alive[:, None], sampled[:, None], tok)
                pos = pos + new[:, None]
                total = total + new
                if pw is not None:
                    # the sampled token joins the row's penalized set for
                    # the NEXT step (the per-step path recounts generated
                    # tokens including it next dispatch)
                    from dynamo_tpu.ops.sampling import (
                        update_penalty_window)
                    pids, pcnt, pctx, pn = update_penalty_window(
                        pids, pcnt, pctx, pn, sampled,
                        alive & pw["active"])
                if gt is not None:
                    # EOS rows self-loop in the table (the host advance
                    # no-ops EOS); dead rows freeze
                    gstate = jnp.where(alive, gt["trans"][gstate, sampled],
                                       gstate)
            return ((pages, tok, pos, total, new_alive,
                     pids, pcnt, pctx, pbias, pn, gstate), (packed, aux))

        with stages.stage("step.inputs"):
            step_ids = jnp.arange(n_steps, dtype=jnp.int32)
        (pages, tok, pos, total, alive, pids, pcnt, pctx, pbias, pn,
         gstate), (steps, aux) = jax.lax.scan(
            body, (pages, tok, pos, total, alive,
                   pids0, pcnt0, pctx0, pbias0, pn0, gstate0), step_ids)
        with stages.stage("step.stop"):
            carry = {"tok": tok, "pos": pos, "total": total, "alive": alive,
                     "budget": budget - n_steps,
                     "min_gate": min_gate - n_steps,
                     "pids": pids, "pcnt": pcnt, "pctx": pctx,
                     "pbias": pbias, "pn": pn, "gstate": gstate}
            packed = jnp.moveaxis(steps, 0, 1)
        with stages.stage("step.counts"):
            aux = {k: jnp.sum(v.astype(jnp.int32)) for k, v in aux.items()}
        return pages, packed, carry, aux

    def _handover_impl(self, prev_packed, rows, stop_ids):
        """From a mixed step's packed output to the carry a fused block
        starts from, on the device: the block's first token, liveness,
        positions and budgets without the step's result ever being on the
        host. ``rows`` ``[5, B]`` int32 is the host's one upload: for each
        row of the block the row of ``prev_packed`` that holds its token,
        its position and total length at block start, its token budget
        and its outstanding ``min_tokens`` gate, the token in flight
        counted in all four (``Scheduler.plan_behind``). A row
        lives unless that token is one of its ``stop_ids`` with the gate
        passed, or spent its budget: the two rules ``_accept_token``
        applies on the host when the step's result arrives. Pad rows
        carry budget 0. Returns the keys of ``_multistep_impl``'s carry
        that a chained block reads."""
        with stages.stage("step.chain"):
            src, pos, total, budget, min_gate = rows
            tok = prev_packed[src, :1]                      # [B, 1] int32
            hit = jnp.any(stop_ids == tok, axis=1)
            alive = (budget > 0) & ~(hit & (min_gate <= 0))
            return {"tok": tok, "pos": pos[:, None], "total": total,
                    "alive": alive, "budget": budget, "min_gate": min_gate}

    def _fill_impl(self, toks, prev_packed, fill):
        """The token array of a mixed step chained behind a mixed step,
        completed on the device: the host left its decode rows' slots
        empty, and ``fill`` ``[2, B]`` int32, its one more upload, says
        for each the slot of ``toks`` (flattened; the padded form's row
        ``i`` starts at ``i * S``) and the row of ``prev_packed`` whose
        column 0 holds its token (``Scheduler.plan_behind``). Pad
        entries point past the end and are dropped. No row is masked
        here: one that this token ends rides the step with it and the
        host drops what it samples."""
        with stages.stage("step.chain"):
            slot, src = fill
            return toks.reshape(-1).at[slot].set(
                prev_packed[src, 0], mode="drop").reshape(toks.shape)

    def _get_jit_fill(self):
        fn = self._jit_fill
        if fn is None:
            # on a mesh its output is replicated, as the host's upload of
            # the same array is to the step program
            rep = self._replicated()
            kw = {} if rep is None else {"out_shardings": rep}
            fn = self._jit_fill = jax.jit(self._fill_impl, **kw)
        return fn

    def _replicated(self):
        """The mesh's fully replicated sharding where the page pool is
        sharded over one, else None."""
        if self.cfg.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            if isinstance(self.pages.sharding, NamedSharding):
                return NamedSharding(self.cfg.mesh, PartitionSpec())
        return None

    def _get_jit_handover(self):
        fn = self._jit_handover
        if fn is None:
            # on a mesh its outputs are replicated like a block's carry,
            # so the block program sees a chained block's arguments
            rep = self._replicated()
            kw = {} if rep is None else {"out_shardings": rep}
            fn = self._jit_handover = jax.jit(self._handover_impl, **kw)
        return fn

    def _get_jit_multistep(self, w: int):
        fn = self._jit_ms.get(w)
        if fn is None:
            # scan length is static: one jit per (pow2-floored) width.
            # On a mesh-sharded engine the block program takes EXPLICIT
            # out-shardings (the SNIPPETS pjit shape): the donated pages
            # carry keeps the cache's NamedSharding (donation needs
            # out == in), while the packed block, the scalar carry
            # (tok/pos/total/alive/budget/min_gate) and the MoE drop
            # count come back fully REPLICATED so the host fetch and the
            # next chained block read whole rows locally — a silent
            # resharding here would either break donation or ship a
            # sharded packed buffer the host cannot np.asarray.
            kw = {}
            rep = self._replicated()
            if rep is not None:
                kw["out_shardings"] = (self.pages.sharding, rep, rep, rep)
            fn = jax.jit(functools.partial(self._multistep_impl, n_steps=w),
                         donate_argnums=(1,), **kw)
            self._jit_ms[w] = fn
        return fn

    def _topk_cols(self, lf):
        """Top-K alternative (ids, logprob-bit) columns for the OpenAI
        logprobs surface — the ONE implementation both the plain sampling
        tail and the spec verify step pack (K clamps to the vocab; the
        host unpack mirrors the same clamp)."""
        kt = min(self.cfg.num_top_logprobs, lf.shape[-1])
        # the first kt of the sampler's own candidates: the same call on
        # the same logits, so a step program holds ONE selection over
        # the vocabulary (asked for apart, kt and TOPK_MAX columns would
        # be two)
        vals, ids = top_candidates(lf, max(kt, min(TOPK_MAX, lf.shape[-1])))
        vals, ids = vals[..., :kt], ids[..., :kt]
        lps = vals - jax.nn.logsumexp(lf, axis=-1, keepdims=True)
        return (ids.astype(jnp.int32),
                jax.lax.bitcast_convert_type(lps, jnp.int32))

    def _spec_step_impl(self, params, pages, tokens, positions, page_table,
                        total_lens, new_lens, rng, step, temperature, top_k,
                        top_p, gmask=None):
        """Speculative verify step: a [B, K+1] chunked forward whose
        sampling tail rejection-samples the K drafts on device
        (``ops/sampling.spec_verify``). tokens[:, 0] is each row's last
        context token; tokens[:, 1:] are the drafts. Packs, per row:
        ``[final_tok, final_lp_bits, n_acc, K draft_lp_bits]`` and — when
        ``num_top_logprobs`` > 0 — the per-chunk-slot top alternatives
        ``[S*kt top ids, S*kt top lp bits]`` with ``kt = min(K_top, V)``
        (``_topk_cols``; the host unpack in ``_execute_plan`` mirrors the
        same layout). Columns 0/1 line up with the normal packed layout
        so ``fetch_packed``'s token/logprob view is shared."""
        from dynamo_tpu.ops.sampling import spec_verify
        (tokens, positions, page_table, total_lens, new_lens, temperature,
         top_k, top_p) = self._shard_batch(
            tokens, positions, page_table, total_lens, new_lens, temperature,
            top_k, top_p)
        logits, pages, aux = self._run_forward(
            self._attn_prefill, params, tokens, positions, pages,
            page_table, total_lens, new_lens, logits_window=tokens.shape[1])
        with stages.stage("sample"):
            if gmask is not None:
                # mask ONCE here so the packed top alternatives below see the
                # same constrained distribution the verifier samples from —
                # the plain path masks before its top-K too
                from dynamo_tpu.ops.sampling import apply_vocab_mask
                Bm, Sm, Vm = logits.shape
                logits = apply_vocab_mask(
                    logits.astype(jnp.float32).reshape(Bm * Sm, Vm),
                    gmask.reshape(Bm * Sm, -1)).reshape(Bm, Sm, Vm)
            key = jax.random.fold_in(rng, step)
            n_acc, final_tok, final_lp, draft_lps = spec_verify(
                logits, tokens, key, temperature, top_k, top_p)
            bits = jax.lax.bitcast_convert_type
            cols = [final_tok[:, None], bits(final_lp, jnp.int32)[:, None],
                    n_acc[:, None], bits(draft_lps, jnp.int32)]
            if self.cfg.num_top_logprobs > 0:
                # per-POSITION top alternatives (the OpenAI logprobs surface;
                # the same columns the plain step packs, one set per chunk
                # slot): [B, S*kt] ids then [B, S*kt] logprob bits
                B = logits.shape[0]
                ids, lp_bits = self._topk_cols(logits.astype(jnp.float32))
                cols.append(ids.reshape(B, -1))
                cols.append(lp_bits.reshape(B, -1))
            packed = jnp.concatenate(cols, axis=1)
            if self._dp > 1:
                from jax.sharding import NamedSharding, PartitionSpec
                packed = jax.lax.with_sharding_constraint(
                    packed, NamedSharding(self.cfg.mesh, PartitionSpec()))
            return pages, packed, aux

    def _ring_step_impl(self, params, pages, tokens, positions, page_table,
                        total_lens, new_lens, rng, step, temperature, top_k,
                        top_p, pen=None):
        """Sequence-parallel whole-prompt prefill (ring attention over sp).
        No aux drop counts here: the ring path serves dense long-context
        families (MoE dispatch accounting rides the chunked steps)."""
        from dynamo_tpu.parallel.ring_prefill import ring_prefill
        logits, pages = ring_prefill(
            params, self.model_cfg, tokens, positions, pages, page_table,
            total_lens, new_lens, mesh=self.cfg.mesh,
            sp_axis=self.cfg.sp_axis)
        pages, packed = self._sample_tail(logits, pages, rng, step,
                                          temperature, top_k, top_p, pen,
                                          total_lens)
        return pages, packed, {}

    def _sample_tail(self, logits, pages, rng, step, temperature, top_k,
                     top_p, pen=None, total_lens=None):
        """Shared sampling epilogue of every step family (chunked + ring),
        traced under the ``sample`` stage.

        Everything the host needs is PACKED into one int32 buffer
        ``[B, 2 + 2K]`` (token id, logprob bits, K alternative ids, K
        alternative logprob bits): the host does exactly ONE device fetch
        per step (cost of a fetch on the chip: not measured)."""
        with stages.stage("sample"):
            key = jax.random.fold_in(rng, step)
            seeds = None
            if pen is not None:
                # penalties rewrite the logits BEFORE sampling and the top-K
                # alternatives, so reported logprobs reflect the distribution
                # actually sampled from
                from dynamo_tpu.ops.sampling import apply_penalties
                logits = apply_penalties(logits, pen["ids"], pen["cnt"],
                                         pen["ctx"], pen["fp"], pen["pp"],
                                         pen["rp"], pen_bias=pen["bias"])
                if "mask" in pen:
                    # guided allow-mask LAST: a penalty/bias can reweight
                    # inside the grammar but never resurrect an illegal token
                    from dynamo_tpu.ops.sampling import apply_vocab_mask
                    logits = apply_vocab_mask(logits, pen["mask"])
                seeds = pen["seeds"]
            sampled, logprobs = sample_tokens(
                logits, key, temperature, top_k, top_p, seeds=seeds,
                # seeded rows key on (base rng, seed, token position): replays
                # are deterministic under any batching/step interleaving
                seed_rng=rng, seed_pos=total_lens,
                min_p=pen["min_p"] if pen is not None else None)
            cols = [sampled[:, None],
                    jax.lax.bitcast_convert_type(logprobs, jnp.int32)[:, None]]
            if self.cfg.num_top_logprobs > 0:
                ids, lp_bits = self._topk_cols(logits.astype(jnp.float32))
                cols.append(ids)
                cols.append(lp_bits)
            packed = jnp.concatenate(cols, axis=1)
            if self._dp > 1:
                # gather the dp-sharded rows back to every rank (rank 0 reads
                # the whole batch locally; [B, 2+2K] int32 — a few KB)
                from jax.sharding import NamedSharding, PartitionSpec
                packed = jax.lax.with_sharding_constraint(
                    packed, NamedSharding(self.cfg.mesh, PartitionSpec()))
            return pages, packed

    # -- plan -> device arrays --------------------------------------------

    def _penalty_row(self, seq, W: int):
        """One row's penalty/bias window material — the ONE builder both
        the per-step host path and the fused block's fresh-dispatch
        preload derive from, so the two paths always hold the same entry
        set (``apply_penalties`` is entry-ORDER independent: equal sets
        give bit-identical logits).

        Returns None for rows without penalties/bias, else a dict:

        entries:   [(token, generated-count, in-context)] — logit_bias
                   tokens first (explicit client asks win the window),
                   then every distinct generated token by frequency. NOT
                   truncated to W here; per-step callers truncate after
                   prompt backfill, the fused planner's width gate
                   guarantees the block never outgrows W.
        prestatic: deduped reversed-prompt token list capped at 2W (at
                   most W of the first 2W distinct prompt tokens can
                   collide with a W-sized window, so W always survive
                   the ``have`` filter) — the repetition-penalty prompt
                   backfill source; empty unless rep_on.
        lb/fp/pp/rp/rep_on: the row's raw knobs.

        Migration replay/resume: the trailing ``resumed_tokens`` of the
        prompt were GENERATED by earlier legs of this stream —
        frequency/presence penalties must keep counting them, not
        reclassify them as prompt after the hop."""
        so = seq.request.sampling_options
        f = so.frequency_penalty or 0.0
        p = so.presence_penalty or 0.0
        r = so.repetition_penalty
        rep_on = r is not None and r > 0 and r != 1.0
        lb = so.logit_bias or {}
        if W <= 0 or not (f or p or rep_on or lb):
            return None
        from collections import Counter
        counts = Counter(seq.generated)
        n_prompt = seq.num_prompt - min(
            seq.request.resumed_tokens or 0, seq.num_prompt)
        if n_prompt < seq.num_prompt:
            counts.update(seq.tokens.tokens()[n_prompt:seq.num_prompt])
        prompt_set = (set(seq.tokens.tokens()[:n_prompt])
                      if rep_on else set())
        # entry = (token, generated-count, in-context). A token in
        # several roles gets ONE entry carrying its count, context flag,
        # and bias.
        entries = [(t, counts.get(t, 0), t in counts or t in prompt_set)
                   for t in list(lb)[:W]]
        have = {t for t, _c, _x in entries}
        for t, c in counts.most_common(W):
            if t not in have:
                entries.append((t, c, True))
                have.add(t)
        prestatic: list = []
        if rep_on:
            seen: set = set()
            for t in reversed(seq.tokens.tokens()[:seq.num_prompt]):
                if t not in seen:
                    seen.add(t)
                    prestatic.append(t)
                    if len(prestatic) >= 2 * W:
                        break
        return dict(entries=entries, prestatic=prestatic, lb=lb, fp=f,
                    pp=p, rp=(r if rep_on else 1.0), rep_on=rep_on)

    def _sampling_extras(self, rows, B: int) -> dict:
        """Per-row penalty/bias windows + seeds (numpy, merged into the
        step's host arrays). ``rows[i]`` is the Sequence for batch row i
        (fewer than B: pad rows stay all-zero = no-op). With
        ``penalty_window == 0`` seeds still ship (zero-width windows);
        penalties/bias need W > 0."""
        W = self.cfg.penalty_window
        out = {"seeds": np.zeros(B, np.int32)}
        ids = np.zeros((B, W), np.int32)
        cnt = np.zeros((B, W), np.float32)
        ctx = np.zeros((B, W), np.float32)
        bias = np.zeros((B, W), np.float32)
        fp = np.zeros(B, np.float32)
        pp = np.zeros(B, np.float32)
        rp = np.ones(B, np.float32)
        min_p = np.zeros(B, np.float32)
        any_active = False
        for i, seq in enumerate(rows):
            so = seq.request.sampling_options
            if so.seed is not None:
                # map any integer seed (0 included — valid per the OpenAI
                # API) into [1, 2^31-1]; 0 stays the unseeded sentinel
                out["seeds"][i] = (int(so.seed) % 0x7FFFFFFF) + 1
                any_active = True
            if so.min_p:
                min_p[i] = so.min_p
                any_active = True
            row = self._penalty_row(seq, W)
            if row is None:
                continue
            any_active = True
            fp[i], pp[i] = row["fp"], row["pp"]
            rp[i] = row["rp"]
            # bias + generated entries first, then — for repetition —
            # prompt backfill (most recent first) from the shared
            # prestatic list, to capacity
            entries = list(row["entries"])
            have = {t for t, _c, _x in entries}
            if row["rep_on"] and len(entries) < W:
                for t in row["prestatic"]:
                    if t not in have:
                        entries.append((t, 0, True))
                        have.add(t)
                        if len(entries) >= W:
                            break
            lb = row["lb"]
            for j, (t, c, x) in enumerate(entries[:W]):
                ids[i, j] = t
                cnt[i, j] = c
                ctx[i, j] = 1.0 if x else 0.0
                bias[i, j] = lb.get(t, 0.0)
        masks = self._guided_masks(rows, B)
        if not any_active and masks is None:
            # common case: nobody in the batch uses penalties, bias,
            # seeds, or guided masks — ship nothing and take the pen=None
            # trace (no extra host->device arrays, single batch-wide
            # gumbel draw)
            return {}
        out.update(pen_ids=ids, pen_cnt=cnt, pen_ctx=ctx, pen_bias=bias,
                   pen_fp=fp, pen_pp=pp, pen_rp=rp, pen_min_p=min_p,
                   pen_active=np.ones(1, np.int32))
        if masks is not None:
            out["mask_words"] = masks
        return out

    def _pen_arg(self, a: dict, B: int):
        """The ``pen`` pytree for one jitted step, with all-zero defaults
        for callers (cache priming, replayed broadcasts) whose arrays
        predate the penalty keys."""
        W = self.cfg.penalty_window
        if not np.any(a.get("pen_active", 0)):
            return None
        z_ids = a.get("pen_ids")
        out = {
            "ids": jnp.asarray(z_ids if z_ids is not None
                               else np.zeros((B, W), np.int32)),
            "cnt": jnp.asarray(a.get("pen_cnt",
                                     np.zeros((B, W), np.float32))),
            "ctx": jnp.asarray(a.get("pen_ctx",
                                     np.zeros((B, W), np.float32))),
            "bias": jnp.asarray(a.get("pen_bias",
                                      np.zeros((B, W), np.float32))),
            "fp": jnp.asarray(a.get("pen_fp", np.zeros(B, np.float32))),
            "pp": jnp.asarray(a.get("pen_pp", np.zeros(B, np.float32))),
            "rp": jnp.asarray(a.get("pen_rp", np.ones(B, np.float32))),
            "min_p": jnp.asarray(a.get("pen_min_p",
                                       np.zeros(B, np.float32))),
            "seeds": jnp.asarray(a.get("seeds", np.zeros(B, np.int32))),
        }
        mask = a.get("mask_words")
        if mask is not None:
            # key present only when some row is guided: the with-mask and
            # without-mask pen pytrees are two traces, both bounded
            out["mask"] = jnp.asarray(mask)
        return out

    def _execute_plan(self, plan: StepPlan):
        """Build padded arrays, run the jitted step, fetch sampled tokens."""
        from dynamo_tpu.engine.scheduler import (MixedStepBatch,
                                                 SpecDecodeBatch)
        if isinstance(plan, SpecDecodeBatch):
            with stage("assemble"):
                arrays = self._spec_arrays(plan.seqs, plan.drafts)
            plan._step_id = self._step_counter
            if self.step_tap is not None:
                self.step_tap("spec", arrays, self._step_counter)
            packed = self._invoke_step("spec", arrays, self._step_counter)
            self._step_counter += 1
            self.decode_dispatches += 1
            with stage("wait"):
                host = np.asarray(packed)
            hostf = host.view(np.float32)   # one reinterpret, no copies
            B = host.shape[0]
            K, S = self.spec_K, self.spec_K + 1
            # mirror _topk_cols' vocab clamp or the unpack misaligns on
            # toy models with vocab < num_top_logprobs
            kt = min(self.cfg.num_top_logprobs,
                     self.model_cfg.vocab_size)
            sampled = host[:, 0]
            logprobs = hostf[:, 1]
            extras = {"spec_acc": host[:, 2],
                      "spec_lps": hostf[:, 3:3 + K]}
            if kt > 0:
                base = 3 + K
                extras["spec_top_ids"] = host[
                    :, base:base + S * kt].reshape(B, S, kt)
                extras["spec_top_lps"] = hostf[
                    :, base + S * kt:base + 2 * S * kt].reshape(B, S, kt)
            return sampled, logprobs, extras
        mixed = isinstance(plan, MixedStepBatch)
        if not (mixed or isinstance(plan, PrefillBatch)):
            handle = self.dispatch_decode(plan)
            with stage("wait"):
                return self.fetch_packed(handle)
        packed, arrays = self._dispatch_prefill(plan, mixed)
        if (self.step_tap is None and not mixed
                and not any(c.is_last for c in plan.chunks)):
            # No row samples a token this step (intermediate chunks of long
            # prompts): skip the device->host readback, one fetch less per
            # chunk of TTFT (cost on the chip: not measured); _process never
            # reads non-last-chunk sampled values. Tradeoffs, both accepted:
            # a device error in this step surfaces at the NEXT fetch and is
            # attributed to that plan (the victims overlap — they are this
            # prompt's own later chunks); and on MULTI-HOST we never skip,
            # because the leader's step_outcome broadcast must reflect a
            # real sync or a symmetric failure would read as divergence.
            B = arrays["total"].shape[0]
            return np.zeros(B, np.int64), np.zeros(B, np.float32), None
        with stage("wait"):
            return self.fetch_packed(packed)

    def _dispatch_prefill(self, plan, mixed: bool, prev_packed=None):
        """Dispatch one prefill-carrying step without fetching its
        result: (the device's packed output, the host arrays it ran on).
        ``prev_packed``: the packed output of the mixed step a chained
        one (``plan.behind``) takes its decode rows' tokens from."""
        with stage("assemble"):
            kind, _chunks, arrays = self._prefill_arrays(plan, mixed)
        plan._step_id = self._step_counter
        if self.step_tap is not None:
            self.step_tap(kind, arrays, self._step_counter)
        packed = self._invoke_step(kind, arrays, self._step_counter,
                                   prev_packed=prev_packed)
        self._step_counter += 1
        return packed, arrays

    def _prefill_arrays(self, plan, mixed: bool):
        """Host arrays for one prefill-carrying step (token-packed, padded
        or ring), with the kind of program that runs them and the rows in
        the arrays' order: (kind, chunks, arrays)."""
        from dynamo_tpu.engine.scheduler import PrefillChunk
        P = self.table_width
        chunks = list(plan.chunks)
        ring = (not mixed) and plan.ring
        # a step chained behind a mixed step: its decode rows' tokens are
        # that step's, still on the device (``_fill_impl``)
        behind = mixed and bool(plan.behind)
        if mixed:
            # decode rows ARE ragged chunks of length 1: feed the
            # newest token at position len-1 (== num_computed), sample
            # its successor at the row's last-real-token slot — the
            # same array shape the prefill rows use (chained: the newest
            # token is the one in flight, at position len)
            chunks += [PrefillChunk(seq=s, start=len(s) - 1 + behind,
                                    length=1, is_last=True)
                       for s in plan.decode_seqs]
        # the form of this step: token-packed, or padded and why
        reason = "ring" if ring else self.padded_reason
        pack = reason is None
        form = "packed" if pack else f"padded:{reason}"
        self.prefill_steps[form] = self.prefill_steps.get(form, 0) + 1
        if ring:
            # whole-prompt sequence-parallel step: B=1, S may exceed the
            # chunk budget; pad S to a power of two (bounded compile
            # count) that divides evenly over the sp ring
            B = 1
            S = _bucket(chunks[0].length, self.cfg.min_prefill_bucket,
                        self.cfg.max_context)
            S = -(-S // self._sp) * self._sp
        else:
            B = _bucket(len(chunks), self.cfg.min_prefill_seqs_bucket,
                        self.cfg.max_num_seqs)
            S = (_token_bucket(sum(c.length for c in chunks),
                               self.cfg.min_prefill_bucket,
                               self._packed_cap)
                 if pack else
                 _bucket(max(c.length for c in chunks),
                         self.cfg.min_prefill_bucket,
                         self.cfg.max_prefill_chunk))
        # packed: every row's new tokens back to back on one [1, T]
        # axis, chunk rows then decode rows; the row arrays stay [B]
        toks = np.zeros((1 if pack else B, S), np.int32)
        pos = np.zeros_like(toks)
        table = np.zeros((B, P), np.int32)
        total = np.ones(B, np.int32)   # pad rows: 1 garbage-page token
        new = np.zeros(B, np.int32)    # pad rows: write nothing
        temp = np.zeros(B, np.float32)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        at = 0                         # a packed row's first slot
        # (chained) for each decode row the slot of ``toks``, flattened,
        # that its token goes to and the row of the previous step's
        # packed output that holds it; a pad entry's slot lies past the
        # end and is dropped
        fill = np.full((2, B), toks.size, np.int32) if behind else None
        first_decode = len(plan.chunks)
        for i, c in enumerate(chunks):
            seq = c.seq
            # where the row's new tokens go: its own padded row, or
            # its slots of the packed axis
            row, lo = (0, at) if pack else (i, 0)
            at += c.length
            if behind and i >= first_decode:
                j = i - first_decode
                fill[:, j] = (row * S + lo, plan.src_rows[j])
            elif c.length == 1 and c.start == len(seq) - 1:
                # decode row: skip the O(context) token-list build
                toks[row, lo] = seq.tokens.last_token()
            else:
                all_tokens = seq.tokens.tokens()
                toks[row, lo:lo + c.length] = all_tokens[
                    c.start:c.start + c.length]
            pos[row, lo:lo + c.length] = np.arange(c.start,
                                                   c.start + c.length)
            self._table_row(table, i, seq)
            total[i] = c.start + c.length
            new[i] = c.length
            so = seq.request.sampling_options
            if so.temperature is not None:
                temp[i] = so.temperature
            top_k[i] = so.top_k or 0
            if so.top_p is not None:
                top_p[i] = so.top_p
        kind = "step"
        if mixed:
            kind = "mixed"
            self.decode_dispatches += 1
            self.mixed_steps += 1
        if pack:
            # the program, not the plan: followers replay it by this name
            kind = "packed"
            self.last_decode_kernel_rows = self._decode_kernel_rows(new, S)
            self.packed_decode_kernel_rows += self.last_decode_kernel_rows
        elif ring:
            kind = "ring"
            self.ring_steps += 1
            logger.info("ring prefill: %d prompt tokens in one step over "
                        "sp=%d", plan.chunks[0].length, self._sp)
        arrays = dict(toks=toks, pos=pos, table=table, total=total, new=new,
                      temp=temp, top_k=top_k, top_p=top_p,
                      **self._sampling_extras([c.seq for c in chunks], B))
        if behind:
            arrays["fill"] = fill
        return kind, chunks, arrays

    def _decode_arrays(self, seqs, chained: bool) -> dict:
        """Padded host arrays for one decode step.

        Normal decode feeds the last appended token at position ``len-1``.
        A chained step (step N's token still on device, not yet appended
        host-side) feeds position ``len`` — the device substitutes the
        token from the previous packed output."""
        B = _bucket(len(seqs), self.cfg.min_decode_bucket,
                    self.cfg.max_num_seqs)
        # composition+version-cached padded table (also pre-warms the
        # device upload _step_table reuses for this dispatch)
        table, _ = self._table_arrays(seqs, B)
        with stage("assemble"):
            toks = np.zeros((B, 1), np.int32)
            pos = np.zeros((B, 1), np.int32)
            total = np.ones(B, np.int32)
            new = np.zeros(B, np.int32)
            temp = np.zeros(B, np.float32)
            top_k = np.zeros(B, np.int32)
            top_p = np.ones(B, np.float32)
            for i, seq in enumerate(seqs):
                if chained:
                    pos[i, 0] = len(seq)
                    total[i] = len(seq) + 1
                else:
                    toks[i, 0] = seq.tokens.last_token()
                    pos[i, 0] = len(seq) - 1
                    total[i] = len(seq)
                new[i] = 1
                so = seq.request.sampling_options
                if so.temperature is not None:
                    temp[i] = so.temperature
                top_k[i] = so.top_k or 0
                if so.top_p is not None:
                    top_p[i] = so.top_p
            return dict(toks=toks, pos=pos, table=table, total=total, new=new,
                        temp=temp, top_k=top_k, top_p=top_p,
                        **self._sampling_extras(seqs, B))

    def _spec_arrays(self, seqs, drafts: np.ndarray) -> dict:
        """Padded host arrays for one speculative verify step [B, K+1].

        Row i feeds its last appended token at position len-1 (slot 0, the
        token whose KV a plain decode step would write) followed by the K
        drafts at positions len..len+K-1. total_lens covers all fed
        positions so causal attention within the chunk sees every draft's
        prefix; pad rows write nothing (new=0)."""
        P = self.table_width
        K = self.spec_K
        B = _bucket(len(seqs), self.cfg.min_decode_bucket,
                    self.cfg.max_num_seqs)
        S = K + 1
        toks = np.zeros((B, S), np.int32)
        pos = np.zeros((B, S), np.int32)
        table = np.zeros((B, P), np.int32)
        total = np.ones(B, np.int32)
        new = np.zeros(B, np.int32)
        temp = np.zeros(B, np.float32)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        gmask = None
        for i, seq in enumerate(seqs):
            toks[i, 0] = seq.tokens.last_token()
            toks[i, 1:] = drafts[i]
            pos[i] = np.arange(len(seq) - 1, len(seq) + K)
            table[i, :len(seq.page_ids)] = seq.page_ids
            total[i] = len(seq) + K
            new[i] = S
            so = seq.request.sampling_options
            if so.temperature is not None:
                temp[i] = so.temperature
            top_k[i] = so.top_k or 0
            if so.top_p is not None:
                top_p[i] = so.top_p
            row_masks = self._guided_spec_masks(seq, drafts[i], S)
            if row_masks is not None:
                if gmask is None:
                    gmask = np.full(
                        (B, S, self._guided_vocab.words), 0xFFFFFFFF,
                        np.uint32)
                gmask[i] = row_masks
        out = dict(toks=toks, pos=pos, table=table, total=total, new=new,
                   temp=temp, top_k=top_k, top_p=top_p)
        if gmask is not None:
            out["gmask"] = gmask
        return out

    def _guided_spec_masks(self, seq, row_drafts, S: int):
        """Per-chunk-slot allow-masks for one guided row of a verify step.

        Slot j's mask is computed from the automaton state AFTER walking
        drafts 1..j — the host knows the whole draft path up front. A
        draft the grammar rejects simply stops the walk: its own slot's
        mask zeroes it (so verification rejects there), and later slots'
        masks are never consulted (acceptance cannot pass the rejection).
        Returns None for unguided/wedged rows (the device no-op)."""
        spec = seq.request.sampling_options.guided
        gv = self._guided_vocab
        if not spec or gv is None:
            return None
        from dynamo_tpu.engine.guided import step
        gr = self._guided_req_for(seq, spec)
        m0 = gr.mask()
        if m0 is None:
            return None           # wedged: serve unconstrained
        out = np.full((S, gv.words), 0xFFFFFFFF, np.uint32)
        out[0] = m0
        st = gr.state
        for j, tid in enumerate(row_drafts[:S - 1], start=1):
            if int(tid) in gv.eos_ids:
                # a drafted EOS leaves the automaton state unchanged —
                # exactly what GuidedRequest.advance does when an ignored
                # EOS is appended — so constraints continue past it
                out[j] = out[j - 1]
                continue
            bs = (self._guided_bytes[int(tid)]
                  if int(tid) < len(self._guided_bytes) else None)
            if bs is None:
                break             # special/illegal draft: walk ends
            ok = True
            for b in bs:
                st2 = step(gr.grammar, st, b)
                if st2 is None:
                    ok = False
                    break
                st = st2
            if not ok:
                break
            m = gv.mask(gr.grammar, st)
            if not m.any():
                # a continuation-free state mid-path would NaN the slot's
                # softmax; leave it unconstrained (the wedge behavior)
                break
            out[j] = m
        return out

    # -- pipelined decode (loop.py hooks) ----------------------------------

    @property
    def supports_pipelining(self) -> bool:
        # speculation and chaining COMPOSE: verify steps themselves can't
        # chain (drafts need the sampled tokens host-side), but plain
        # decode steps between them still do — the scheduler breaks a
        # chain every spec_chain_break steps so fresh context gets a
        # chance to draft (``Scheduler._in_flight``)
        return self.cfg.pipeline_decode

    def dispatch_decode(self, plan):
        """Dispatch one decode step WITHOUT fetching its results; returns
        the on-device packed output handle (jax dispatch is async)."""
        arrays = self._decode_arrays(plan.seqs, chained=False)
        plan._step_id = self._step_counter
        if self.step_tap is not None:
            self.step_tap("step", arrays, self._step_counter)
        packed = self._invoke_step("step", arrays, self._step_counter,
                                   seqs=plan.seqs)
        self._step_counter += 1
        self.decode_dispatches += 1
        return packed

    def dispatch_chained(self, plan, prev_packed):
        """Dispatch decode step N+1 consuming step N's on-device tokens."""
        arrays = self._decode_arrays(plan.seqs, chained=True)
        plan._step_id = self._step_counter
        if self.step_tap is not None:
            self.step_tap("chained", arrays, self._step_counter)
        packed = self._invoke_step("chained", arrays, self._step_counter,
                                   prev_packed=prev_packed, seqs=plan.seqs)
        self._step_counter += 1
        self.chained_decode_steps += 1
        self.decode_dispatches += 1
        return packed

    def fetch_packed(self, packed):
        """Blocking device->host fetch + unpack of one step's results —
        ONE device->host copy and ONE same-itemsize dtype reinterpret of
        the whole buffer (no per-column ``.copy().view()``)."""
        host = np.asarray(packed)
        hostf = host.view(np.float32)
        sampled = host[:, 0]
        logprobs = hostf[:, 1]
        extras = None
        if host.shape[1] > 2:
            K = (host.shape[1] - 2) // 2
            extras = {"top_ids": host[:, 2:2 + K],
                      "top_lps": hostf[:, 2 + K:]}
        return sampled, logprobs, extras

    def dispatch_step(self, plan, prev_handle=None):
        """Dispatch one mixed step WITHOUT fetching its result; returns
        the on-device packed output, for ``fetch_packed`` and for what is
        chained behind it: a fused block (``dispatch_multistep``) or the
        next mixed step, which gets this step's output as its
        ``prev_handle`` (``plan.behind == "mixed"``) and reads its decode
        rows' tokens from it on the device (``_fill_impl``)."""
        return self._dispatch_prefill(plan, True, prev_handle)[0]

    # -- fused multi-step decode (loop.py hooks) ---------------------------

    @property
    def supports_step_chain(self) -> bool:
        # wherever a fused block can follow a mixed step: both forms of
        # the step end in ``_sample_tail``'s packed rows, chunk rows then
        # decode rows, and the hand-over reads column 0 of either (a
        # block-diffusion engine plans no mixed step: it admits with
        # prefill steps)
        return self.supports_multistep

    @property
    def supports_multistep(self) -> bool:
        # fused decode COMPOSES with pipelined decode (the per-step chain
        # serves batches the planner refuses to fuse) AND with mesh
        # sharding (the block program jits with explicit out-shardings:
        # donated sharded pages carry, replicated scalar carry — see
        # _get_jit_multistep); it does not yet compose with multi-host
        # lockstep (step_tap broadcasts host arrays, but the block carry
        # is device-resident) or spec mode (its own [B, K+1] verify
        # path). pipeline_decode False means strict step-at-a-time
        # debugging — fusion off too.
        return ((self.multistep > 1 or self.gen_block > 1)
                and self.cfg.pipeline_decode
                and self.step_tap is None and not self.spec_K)

    @property
    def multistep_unsupported_reason(self) -> Optional[str]:
        """Why fusion is off on an engine whose config ASKED for it
        (feeds ``dynamo_worker_multistep_fallback_total{reason}``); None
        when fusion is supported or disabled by configuration. ``mesh``
        is no longer a reason — sharded engines run the fused block
        program with explicit shardings."""
        if self.multistep <= 1 or not self.cfg.pipeline_decode:
            return None
        if self.spec_K:
            return "spec"
        if self.step_tap is not None:
            return "multihost"
        return None

    def _device_sampling(self, seqs, B: int) -> dict:
        """Device-resident per-row sampling + stop arrays for the decode
        batch, rebuilt only when the batch COMPOSITION changes (the cache
        key) instead of re-uploaded every step: temperature/top_k/top_p,
        the padded EOS+stop_token_ids set (-1 pads never match), and —
        when any row uses them — the pen pytree: seeds/min_p, the
        batch-static penalty knobs + 2W prompt-reproduction arrays
        (``pw``), and the batched guided transition table (``gt``). The
        PER-TOKEN pieces (the dynamic window, the automaton state id)
        ride the block carry instead — fresh blocks preload them in
        ``dispatch_multistep``, chained blocks pass them straight
        through on device."""
        with stage("assemble"):
            with self._released_lock:
                released = self._released
                if released:
                    self._released = set()
            if released:
                # finished/cancelled rows: drop step-thread automata and any
                # composition cache that still references them, so a dead
                # guided/penalized row's table and window slots free up even
                # if an identical-looking batch never re-forms
                for rid in released:
                    self._guided_reqs.pop(rid, None)
                cached = self._samp_cache
                if cached is not None and any(
                        rid in released for rid, _s in cached[0][1]):
                    self._samp_cache = None
            key = (B, tuple((s.request.request_id, id(s)) for s in seqs))
            cached = self._samp_cache
            if cached is not None and cached[0] == key:
                return cached[1]
            temp = np.zeros(B, np.float32)
            top_k = np.zeros(B, np.int32)
            top_p = np.ones(B, np.float32)
            seeds = np.zeros(B, np.int32)
            min_p = np.zeros(B, np.float32)
            pen_active = False
            stop_lists = []
            W = self.cfg.penalty_window
            pfp = np.zeros(B, np.float32)
            ppp = np.zeros(B, np.float32)
            prp = np.ones(B, np.float32)
            pact = np.zeros(B, bool)
            prompt_ids = np.zeros((B, 2 * max(W, 1)), np.int32)
            prompt_valid = np.zeros((B, 2 * max(W, 1)), bool)
            pw_active = False
            guided_specs: dict = {}
            for i, seq in enumerate(seqs):
                so = seq.request.sampling_options
                if so.temperature is not None:
                    temp[i] = so.temperature
                top_k[i] = so.top_k or 0
                if so.top_p is not None:
                    top_p[i] = so.top_p
                if so.seed is not None:
                    # the _sampling_extras seed mapping: [1, 2^31-1], 0 = off
                    seeds[i] = (int(so.seed) % 0x7FFFFFFF) + 1
                    pen_active = True
                if so.min_p:
                    min_p[i] = so.min_p
                    pen_active = True
                f = so.frequency_penalty or 0.0
                p = so.presence_penalty or 0.0
                r = so.repetition_penalty
                rep_on = r is not None and r > 0 and r != 1.0
                if W > 0 and (f or p or rep_on or so.logit_bias):
                    pw_active = pen_active = True
                    pact[i] = True
                    pfp[i], ppp[i] = f, p
                    if rep_on:
                        prp[i] = r
                        row = self._penalty_row(seq, W)
                        ps = row["prestatic"]
                        prompt_ids[i, :len(ps)] = ps
                        prompt_valid[i, :len(ps)] = True
                spec = so.guided
                if spec and self._guided_vocab is not None:
                    table = self._guided_table_for(spec)
                    gr = self._guided_req_for(seq, spec)
                    if table is not None and not gr.wedged:
                        guided_specs[i] = (spec, table)
                sc = seq.request.stop_conditions
                ids = list(sc.stop_token_ids or [])
                if not sc.ignore_eos:
                    ids += list(seq.request.eos_token_ids or [])
                stop_lists.append(ids)
            E = max([len(x) for x in stop_lists] + [1])
            E = 1 << (E - 1).bit_length()   # pow2 pad: bounded trace count
            stop_ids = np.full((B, E), -1, np.int32)
            for i, ids in enumerate(stop_lists):
                stop_ids[i, :len(ids)] = ids
            pen = None
            gt_host = None
            if pen_active or guided_specs:
                pen = {"seeds": seeds, "min_p": min_p}
                if pw_active:
                    pen["pw"] = {
                        "fp": pfp, "pp": ppp, "rp": prp, "active": pact,
                        "prompt_ids": prompt_ids,
                        "prompt_valid": prompt_valid,
                    }
                if guided_specs:
                    # batch the distinct tables behind sentinel state 0
                    # (all-ones mask, self-loop): unguided/wedged rows sit at
                    # state 0 and ride the same gather as guided ones
                    gv = self._guided_vocab
                    V = self.model_cfg.vocab_size
                    by_key: dict = {}
                    offsets: dict = {}
                    S = 1
                    for i, (spec, table) in guided_specs.items():
                        import json as _json
                        k = _json.dumps(spec, sort_keys=True)
                        if k not in by_key:
                            by_key[k] = table
                            offsets[k] = S
                            S += table.num_states
                        offsets[i] = offsets[k]
                    S_pad = 1 << (S - 1).bit_length()
                    trans = np.zeros((S_pad, V), np.int32)
                    masks = np.full((S_pad, gv.words), 0xFFFFFFFF, np.uint32)
                    trans[0] = 0
                    for k, table in by_key.items():
                        o = offsets[k]
                        n = table.num_states
                        trans[o:o + n] = table.trans + o
                        masks[o:o + n] = table.masks
                    # pad states: unreachable; all-ones masks + self-loops so
                    # an off-by-one could never -inf a whole row
                    for s in range(S, S_pad):
                        trans[s] = s
                    pen["gt"] = {"trans": trans, "masks": masks}
                    gt_host = {"trans": trans, "offsets": {
                        i: offsets[i] for i in guided_specs}}
        with stage("upload"):
            # (``pen`` and its ``pw`` / ``gt`` groups: numpy until here)
            out = jax.tree_util.tree_map(jnp.asarray, {
                "temp": temp, "top_k": top_k, "top_p": top_p,
                "stop_ids": stop_ids, "pen": pen})
        out["needs_pcarry"] = pw_active or bool(guided_specs)
        out["gt_host"] = gt_host
        self._samp_cache = (key, out)
        return out

    def _fresh_pcarry(self, seqs, B: int, samp: dict) -> dict:
        """Preload the per-token block carry for a FRESH constrained
        block: each penalized/biased row's window (bias + every distinct
        generated token, from the same ``_penalty_row`` builder the
        per-step path uses — the width gate guarantees it fits W), and
        each guided row's automaton state id (the host walks the batched
        transition table over the row's generated tokens from its
        grammar's offset; wedged rows were already dropped to sentinel
        state 0 at composition time)."""
        with stage("assemble"):
            W = self.cfg.penalty_window
            pids = np.zeros((B, W), np.int32)
            pcnt = np.zeros((B, W), np.float32)
            pctx = np.zeros((B, W), np.float32)
            pbias = np.zeros((B, W), np.float32)
            pn = np.zeros(B, np.int32)
            gstate = np.zeros(B, np.int32)
            gt_host = samp.get("gt_host")
            for i, seq in enumerate(seqs):
                row = self._penalty_row(seq, W)
                if row is not None:
                    lb = row["lb"]
                    entries = row["entries"][:W]
                    for j, (t, c, x) in enumerate(entries):
                        pids[i, j] = t
                        pcnt[i, j] = c
                        pctx[i, j] = 1.0 if x else 0.0
                        pbias[i, j] = lb.get(t, 0.0)
                    pn[i] = len(entries)
                if gt_host is not None and i in gt_host["offsets"]:
                    s = gt_host["offsets"][i]
                    trans = gt_host["trans"]
                    for t in seq.generated:
                        s = int(trans[s, int(t)])
                    gstate[i] = s
        with stage("upload"):
            return {"pids": jnp.asarray(pids), "pcnt": jnp.asarray(pcnt),
                    "pctx": jnp.asarray(pctx), "pbias": jnp.asarray(pbias),
                    "pn": jnp.asarray(pn), "gstate": jnp.asarray(gstate)}

    def dispatch_multistep(self, plan, prev_handle=None):
        """Dispatch one fused block of ``plan.width`` decode steps;
        returns the opaque (packed block, device carry) handle without
        blocking. A chained block takes its first token / position /
        liveness / budgets from the device — only the (possibly grown)
        page table re-uploads: behind a block (``prev_handle`` that
        block's handle) from its carry, behind a mixed step
        (``plan.behind == "mixed"``, ``prev_handle`` the step's packed
        output, still being computed) from ``_handover_impl`` over that
        output, in front of the same block program."""
        if self.gen_block > 1:
            return self._dispatch_passes(plan, prev_handle)
        seqs = plan.seqs
        w = plan.width
        B = _bucket(len(seqs), self.cfg.min_decode_bucket,
                    self.cfg.max_num_seqs)
        _table_np, table = self._table_arrays(seqs, B)
        samp = self._device_sampling(seqs, B)
        pcarry = None
        if plan.behind == "mixed":
            c = self._handover(plan, prev_handle, B, samp["stop_ids"])
        elif prev_handle is not None:
            c = prev_handle[1]
        else:
            c = None
        if c is not None:
            tok, pos, total, alive = c["tok"], c["pos"], c["total"], c["alive"]
            budget, min_gate = c["budget"], c["min_gate"]
            if samp["needs_pcarry"]:
                # chained constrained block: window + automaton state stay
                # on device, straight from the previous block's carry
                pcarry = {"pids": c["pids"], "pcnt": c["pcnt"],
                          "pctx": c["pctx"], "pbias": c["pbias"],
                          "pn": c["pn"], "gstate": c["gstate"]}
        else:
            with stage("assemble"):
                tok = np.zeros((B, 1), np.int32)
                pos = np.zeros((B, 1), np.int32)
                total = np.ones(B, np.int32)  # pad rows: 1 garbage-page token
                alive = np.zeros(B, bool)     # pad rows: never write
                budget = np.zeros(B, np.int32)
                min_gate = np.zeros(B, np.int32)
                for i, (seq, sl) in enumerate(zip(seqs, plan.start_lens)):
                    tok[i, 0] = seq.tokens.last_token()
                    pos[i, 0] = sl - 1
                    total[i] = sl
                    alive[i] = True
                    budget[i] = plan.budgets[i]
                    min_gate[i] = plan.min_gates[i]
            if samp["needs_pcarry"]:
                pcarry = self._fresh_pcarry(seqs, B, samp)
        plan._step_id = self._step_counter
        fn = self._get_jit_multistep(w)
        _ckey = (id(fn), B, w, pcarry is not None)
        _fresh = _ckey not in self._jit_seen
        _t0 = time.perf_counter() if _fresh else 0.0
        with stage("upload"):
            # (a chained block's are on the device already)
            tok, pos, total, alive, budget, min_gate = (
                jnp.asarray(x)
                for x in (tok, pos, total, alive, budget, min_gate))
        with stage("enqueue"):
            self.pages, packed_block, carry, aux = fn(
                self.params, self.pages, tok, pos, table, total, alive,
                budget, min_gate, self._rng,
                np.int32(self._step_counter), samp["temp"], samp["top_k"],
                samp["top_p"], samp["stop_ids"], samp["pen"], pcarry)
            self._queue_moe_aux(aux, steps=w)
        # one rng-fold key per fused step: the counter advances by the
        # block width so fused and per-step runs consume the same keys
        self._step_counter += w
        self.decode_dispatches += 1
        self.multistep_blocks += 1
        self._count_one_token_rows(len(seqs) * w)
        self.last_padded = (B, w)
        self.last_program = f"multistep{w}[{B}]"
        if _fresh:
            self._mark_compile(_ckey, "multistep", B, w,
                               time.perf_counter() - _t0)
        return (packed_block, carry)

    def _fill(self, toks, prev_packed, fill):
        """A chained mixed step's tokens, its decode rows' read from the
        step in flight (``_fill_impl``): one small program enqueued
        behind that step, in front of the step program."""
        fn = self._get_jit_fill()
        _ckey = (id(fn), toks.shape, prev_packed.shape, fill.shape)
        _fresh = _ckey not in self._jit_seen
        _t0 = time.perf_counter() if _fresh else 0.0
        with stage("enqueue"):
            toks = fn(toks, prev_packed, fill)
        if _fresh:
            self._mark_compile(_ckey, "mixed", fill.shape[1], toks.shape[1],
                               time.perf_counter() - _t0)
        return toks

    def _handover(self, plan, prev_packed, B: int, stop_ids) -> dict:
        """The carry of a block chained behind a mixed step, from the
        step's packed output (``_handover_impl``): one upload, one small
        program enqueued behind the step."""
        with stage("assemble"):
            n = len(plan.seqs)
            rows = np.zeros((5, B), np.int32)
            rows[2] = 1                # pad rows: 1 garbage-page token
            start = np.asarray(plan.start_lens, np.int32)
            rows[:, :n] = (plan.src_rows, start - 1, start, plan.budgets,
                           plan.min_gates)
        fn = self._get_jit_handover()
        _ckey = (id(fn), prev_packed.shape, B, stop_ids.shape)
        _fresh = _ckey not in self._jit_seen
        _t0 = time.perf_counter() if _fresh else 0.0
        with stage("upload"):
            rows = jnp.asarray(rows)
        with stage("enqueue"):
            carry = fn(prev_packed, rows, stop_ids)
        if _fresh:
            self._mark_compile(_ckey, "multistep", B, 0,
                               time.perf_counter() - _t0)
        return carry

    # -- generation by diffusion over blocks -------------------------------

    # a seeded row's key folds ``position * PASS_KEY_STRIDE + pass``: the
    # same for a pass whatever dispatch runs it (a block of B masks takes
    # at most B + 1 passes)
    PASS_KEY_STRIDE = 64

    def _passes_impl(self, params, pages, table, state, rng, step0, samp,
                     n_passes=1):
        """FUSED passes of generation by diffusion over blocks:
        ``n_passes`` forward passes over every row's current block in one
        jitted program, a ``lax.scan`` with the donated ``pages`` and the
        rows' block state as its carry.

        ``state`` per row: ``tok``/``rev [R, B]`` the block's tokens and
        which positions are revealed (by POSITION: a revealed token may
        have any id, the mask's included), ``pidx`` the next pass's index
        within the block, ``start`` the block's first position, ``tail``
        the prompt tokens inside it, ``budget`` the tokens the row may
        still emit, ``alive``. A pass feeds the block with the mask
        token's id at unrevealed positions through the family forward at
        ``[R, B]`` with logits at every position, which writes the
        block's keys and values IN PLACE: nothing reads them but this
        row's later passes (``num_computed``, the prefix cache and the
        page hashes move only when the host sees the block commit), and
        every pass rewrites all B. A block with masks left reveals some
        (``ops/sampling.reveal``); positions past the row's budget are
        never revealed. A block with no such mask left at the pass's
        start is COMMITTED by it: the keys and values it wrote are the
        final tokens', the row moves to the next block (all masks) and
        its budget falls by the tokens the block emits; a row whose
        budget is spent is dead for the rest of the dispatch and of the
        chain (``new_lens`` 0: it writes nothing and routes to no
        expert).

        Returns (pages, packed ``[R, n_passes, 2 + B * (3 + 2K)]`` int32
        - per pass: alive at its start, committed by it, and per position
        revealed-now, the sampled token, its log-probability's bits, K
        top ids, K top log-probability bits - the state for a chained
        dispatch, and the summed MoE counts)."""
        R, B = state["tok"].shape
        rep = functools.partial(jnp.repeat, repeats=B, axis=0)
        with stages.stage("step.inputs"):
            mask_id = jnp.int32(self.model_cfg.mask_token_id)
            offs = jnp.arange(B, dtype=jnp.int32)[None, :]
            temp, top_k, top_p = (rep(samp["temp"]), rep(samp["top_k"]),
                                  rep(samp["top_p"]))
            seeds, min_p = rep(samp["seeds"]), rep(samp["min_p"])
        bits = functools.partial(jax.lax.bitcast_convert_type,
                                 new_dtype=jnp.int32)

        def body(carry, j):
            pages, tok, rev, pidx, start, tail, budget, alive = carry
            with stages.stage("step.inputs"):
                pos = start[:, None] + offs
                new = alive.astype(jnp.int32) * B
                total = jnp.where(alive, start + B, 1)
                shown = jnp.where(rev, tok, mask_id)
            logits, pages, aux = self._run_forward(
                self._attn_prefill, params, shown, pos, pages, table, total,
                new, logits_window=B)
            served = alive          # the rows this pass serves
            with stages.stage("pass/confidence"):
                # masks the budget pays for: the positions past it are
                # never revealed (the block's tail is not emitted, and what
                # a served token was conditioned on is a function of served
                # tokens)
                masked = ~rev & (offs < (tail + budget)[:, None])
                lf = logits.astype(jnp.float32).reshape(R * B, -1)
                sampled, lps = sample_tokens(
                    lf, jax.random.fold_in(rng, step0 + j), temp, top_k,
                    top_p, seeds=seeds, seed_rng=rng,
                    seed_pos=(pos * self.PASS_KEY_STRIDE
                              + pidx[:, None]).reshape(R * B),
                    min_p=min_p)
                sampled, lps = sampled.reshape(R, B), lps.reshape(R, B)
                tops = []
                if self.cfg.num_top_logprobs > 0:
                    ids, lp_bits = self._topk_cols(lf)
                    tops = [ids.reshape(R, -1), lp_bits.reshape(R, -1)]
            with stages.stage("pass/reveal"):
                now = alive[:, None] & reveal(
                    jnp.exp(lps), masked, pidx, samp["steps"], samp["tau"])
                tok = jnp.where(now, sampled, tok)
                rev = rev | now
            with stages.stage("pass/commit"):
                commit = alive & ~jnp.any(masked, axis=1)
                budget = jnp.where(commit, budget - (B - tail), budget)
                start = jnp.where(commit, start + B, start)
                tail = jnp.where(commit, 0, tail)
                rev = rev & ~commit[:, None]
                pidx = jnp.where(commit, 0, pidx + alive.astype(jnp.int32))
                alive = alive & ~(commit & (budget <= 0))
                packed = jnp.concatenate(
                    [served[:, None].astype(jnp.int32),
                     commit[:, None].astype(jnp.int32),
                     now.astype(jnp.int32), sampled, bits(lps)] + tops,
                    axis=1)
            return ((pages, tok, rev, pidx, start, tail, budget, alive),
                    (packed, aux))

        keys = ("tok", "rev", "pidx", "start", "tail", "budget", "alive")
        with stages.stage("step.inputs"):
            pass_ids = jnp.arange(n_passes, dtype=jnp.int32)
        (pages, *out), (passes, aux) = jax.lax.scan(
            body, (pages, *(state[k] for k in keys)), pass_ids)
        with stages.stage("pass/commit"):
            packed = jnp.moveaxis(passes, 0, 1)
        with stages.stage("step.counts"):
            aux = {k: jnp.sum(v.astype(jnp.int32)) for k, v in aux.items()}
        return pages, packed, dict(zip(keys, out)), aux

    def _get_jit_passes(self, w: int):
        fn = self._jit_passes.get(w)
        if fn is None:
            fn = jax.jit(functools.partial(self._passes_impl, n_passes=w),
                         donate_argnums=(1,))
            self._jit_passes[w] = fn
        return fn

    def _gen_sampling(self, seqs, R: int) -> dict:
        """Device arrays of the rows' sampling and reveal parameters,
        rebuilt when the batch's composition changes."""
        with stage("assemble"):
            key = (R, tuple((s.request.request_id, id(s)) for s in seqs))
            cached = self._gen_samp_cache
            if cached is not None and cached[0] == key:
                return cached[1]
            a = {"temp": np.zeros(R, np.float32),
                 "top_k": np.zeros(R, np.int32),
                 "top_p": np.ones(R, np.float32),
                 "seeds": np.zeros(R, np.int32),
                 "min_p": np.zeros(R, np.float32),
                 "steps": np.full(R, self.gen_steps, np.int32),
                 "tau": np.full(R, self.gen_threshold, np.float32)}
            for i, seq in enumerate(seqs):
                so = seq.request.sampling_options
                if so.temperature is not None:
                    a["temp"][i] = so.temperature
                a["top_k"][i] = so.top_k or 0
                if so.top_p is not None:
                    a["top_p"][i] = so.top_p
                if so.seed is not None:
                    # the _sampling_extras seed mapping: [1, 2^31-1], 0 = off
                    a["seeds"][i] = (int(so.seed) % 0x7FFFFFFF) + 1
                a["min_p"][i] = so.min_p or 0.0
                if so.denoising_steps:
                    a["steps"][i] = so.denoising_steps
                if so.confidence_threshold is not None:
                    a["tau"][i] = so.confidence_threshold
        with stage("upload"):
            out = {k: jnp.asarray(v) for k, v in a.items()}
        self._gen_samp_cache = (key, out)
        return out

    def _dispatch_passes(self, plan, prev_handle=None):
        """Dispatch one ``GenPassBatch``; returns the (packed passes,
        device state) handle without blocking. A fresh dispatch uploads
        each row's block state from the host's mirror
        (``Sequence.block_state``); a chained one takes the previous
        dispatch's device state - only the grown page table re-uploads."""
        seqs, w, B = plan.seqs, plan.width, self.gen_block
        R = _bucket(len(seqs), self.cfg.min_decode_bucket,
                    self.cfg.max_num_seqs)
        _table_np, table = self._table_arrays(seqs, R)
        samp = self._gen_sampling(seqs, R)
        if prev_handle is not None:
            state = prev_handle[1]
        else:
            with stage("assemble"):
                st = {"tok": np.zeros((R, B), np.int32),
                      "rev": np.zeros((R, B), bool),
                      "pidx": np.zeros(R, np.int32),
                      "start": np.zeros(R, np.int32),
                      "tail": np.zeros(R, np.int32),
                      "budget": np.zeros(R, np.int32),
                      "alive": np.zeros(R, bool)}      # pad rows: never write
                for i, seq in enumerate(seqs):
                    tail = plan.tails[i]
                    # a block in mid-denoising, or a fresh one: the prompt's
                    # tail, then masks
                    bs = seq.block_state or BlockState(
                        B, (seq.tokens.tokens()[len(seq) - tail:]
                            if tail else ()))
                    st["tok"][i], st["rev"][i] = bs.tok, bs.rev
                    st["pidx"][i] = bs.pidx
                    st["start"][i] = plan.start_lens[i]
                    st["tail"][i] = tail
                    st["budget"][i] = plan.budgets[i]
                    st["alive"][i] = plan.budgets[i] > 0
            with stage("upload"):
                state = {k: jnp.asarray(v) for k, v in st.items()}
        plan._step_id = self._step_counter
        fn = self._get_jit_passes(w)
        _ckey = (id(fn), R, w)
        _fresh = _ckey not in self._jit_seen
        _t0 = time.perf_counter() if _fresh else 0.0
        with stage("enqueue"):
            self.pages, packed, state, aux = fn(
                self.params, self.pages, table, state, self._rng,
                np.int32(self._step_counter), samp)
            self._queue_moe_aux(aux, steps=w)
        self._step_counter += w
        self.decode_dispatches += 1
        self.multistep_blocks += 1
        self.last_padded = (R, w * B)
        self.last_program = f"passes{w}[{R},{B}]"
        if _fresh:
            self._mark_compile(_ckey, "multistep", R, w,
                               time.perf_counter() - _t0)
        return (packed, state)

    def prime_multistep(self, B: int, widths=None):
        """Compile the fused block program(s) for padded batch ``B``
        outside serving (bench priming): garbage-page no-op dispatches —
        every row dead (``alive`` all False) writes nothing. Defaults to
        the pow2 ladder the scheduler narrows to (cap, cap/2, .., 2).
        Returns the last packed block for ``block_until_ready``."""
        if widths is None:
            # pow2-floor the cap first: the scheduler floors every block
            # width, so a non-pow2 cap (DYN_DECODE_MULTISTEP=6) never
            # dispatches its raw value — priming it would compile unused
            # programs and MISS the ones serving actually runs
            widths, w = [], 1 << (max(1, self.multistep).bit_length() - 1)
            while w >= 2:
                widths.append(w)
                w //= 2
        P = self.table_width
        out = None
        for w in widths:
            fn = self._get_jit_multistep(w)
            # priming IS the compile: mark the bucket seen so serving's
            # first dispatch at this (B, w) is not misreported as a
            # mid-run compile event
            self._jit_seen.add((id(fn), B, w, False))
            self.pages, out, _carry, _aux = fn(
                self.params, self.pages,
                jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 1), jnp.int32),
                jnp.zeros((B, P), jnp.int32), jnp.ones(B, jnp.int32),
                jnp.zeros(B, bool), jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), self._rng, np.int32(0),
                jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
                jnp.ones(B, jnp.float32),
                jnp.full((B, 1), -1, jnp.int32), None, None)
        return out

    def fetch_packed_block(self, handle):
        """Blocking fetch + unpack of one fused block: ONE device->host
        copy of the packed [B, w, C] buffer and ONE dtype reinterpret for
        every float column (the block-path fix for the per-fetch
        ``.copy().view(np.float32)``)."""
        host = np.asarray(handle[0])
        if self.gen_block > 1:
            # a pass dispatch: the loop unpacks it (``_process_passes``)
            return host, None, None
        hostf = host.view(np.float32)
        sampled = host[:, :, 0]
        logprobs = hostf[:, :, 1]
        extras = None
        if host.shape[2] > 2:
            K = (host.shape[2] - 2) // 2
            extras = {"top_ids": host[:, :, 2:2 + K],
                      "top_lps": hostf[:, :, 2 + K:]}
        return sampled, logprobs, extras

    def execute_arrays(self, kind: str, a: dict, step: int):
        """Run one jitted step from raw padded host arrays.

        The multi-host follower entry point: every rank calls this with
        identical arrays so the multi-controller jit executes in lockstep
        (rank 0 arrives here via ``_execute_plan``). Returns
        (sampled, logprobs, extras) where extras carries the top-K
        alternatives when ``num_top_logprobs`` > 0."""
        out = self._invoke_step(kind, a, step)
        if out is None:
            return None  # follower-side page IO (gather/scatter): no packed
        return self.fetch_packed(out)

    def _mark_compile(self, ckey, kind: str, batch: int, width: int,
                      seconds: float) -> None:
        """Record one fresh-jit-bucket first call (== a compile) for the
        step flight recorder; the loop drains these after the dispatch
        and attributes them to the step's record + live request traces."""
        self._jit_seen.add(ckey)
        with self._compile_lock:
            self._pending_compiles.append(
                {"kind": kind, "batch": batch, "width": width,
                 "seconds": seconds})
            if len(self._pending_compiles) > 256:
                # bounded: nothing is draining (no loop running — raw
                # execute_arrays callers); keep the freshest
                del self._pending_compiles[:-64]

    def drain_compile_events(self) -> list:
        with self._compile_lock:
            ev, self._pending_compiles = self._pending_compiles, []
        return ev

    def _invoke_step(self, kind: str, a: dict, step: int, prev_packed=None,
                     seqs=None):
        """Dispatch ONE jitted step of any family; returns the on-device
        packed output (jax dispatch is async — no host sync here). The
        single place the 12-argument step signature is spelled out.

        kind "chained" substitutes the previous step's on-device sampled
        tokens for ``a["toks"]``; ``prev_packed`` defaults to this rank's
        last packed output (the follower case — leaders pass it).

        ``seqs`` (decode dispatch paths only) enables the device-resident
        sampling-array cache: temperature/top_k/top_p upload once per
        batch composition instead of once per step. Multi-host followers
        and raw-array callers (``execute_arrays``) leave it None and keep
        the per-step uploads."""
        if kind == "embed":
            self._embed_batch_raw(a["toks"], a["mask"])
            return None
        if kind == "score":
            # follower side of a prompt-scoring broadcast: join the SPMD
            # jit, discard the (replicated) result
            self._score_batch_raw(a["toks"], a["mask"])
            return None
        if kind == "gather":
            # follower side of a broadcast page gather: join the SPMD op,
            # discard the (replicated) result
            self._ensure_page_io_jits()
            self._jit_gather_pages(self.pages, jnp.asarray(a["ids"]))
            return None
        if kind == "scatter":
            self._ensure_page_io_jits()
            self.pages = self._jit_scatter_pages(
                self.pages, jnp.asarray(a["ids"]), jnp.asarray(a["vals"]))
            return None
        # rows, and the width of the token arrays (a packed step's T)
        _shape = (a["toks"] if "toks" in a else a["pos"]).shape
        _B = int(a["total"].shape[0])
        _S = int(_shape[1]) if len(_shape) > 1 else 1
        # a padded mixed step IS the plain step program (same trace)
        step_fn = {"spec": self._jit_spec, "chained": self._jit_chained,
                   "ring": self._jit_ring_step,
                   "packed": self._jit_packed}.get(kind, self._jit_step)
        # the with-mask and without-mask pen pytrees are distinct traces
        # (see _pen_arg) — a bucket per variant, like the jit cache itself
        _ckey = (id(step_fn), _B, _S, a.get("mask_words") is not None)
        _fresh = _ckey not in self._jit_seen
        _t0 = time.perf_counter() if _fresh else 0.0
        # the step's arguments go up first, so that the upload and the
        # call each have their stage (``steptrace.stage``); the decode
        # paths' cached sampling arrays and table mark their own
        temp, top_k, top_p = self._step_sampling(a, kind, seqs)
        table = self._step_table(a, kind, seqs)
        with stage("upload"):
            pos, total, new = (jnp.asarray(a[k])
                               for k in ("pos", "total", "new"))
            if kind == "spec":
                gm = a.get("gmask")
                extra = jnp.asarray(gm) if gm is not None else None
            else:
                extra = self._pen_arg(a, _B)
            if kind == "chained":
                feed = (prev_packed if prev_packed is not None
                        else self._last_packed)
            else:
                feed = jnp.asarray(a["toks"])
            fill = a.get("fill")
            if fill is not None:
                fill = jnp.asarray(fill)
        if fill is not None:
            feed = self._fill(feed, prev_packed, fill)
        with stage("enqueue"):
            # one signature for every family: ``feed`` the tokens (a
            # chained step: the previous step's packed output), ``extra``
            # the ``pen`` pytree (a verify step: its guided masks). A MoE
            # family's verify step reports dispatch drops like any other
            self.pages, packed, aux = step_fn(
                self.params, self.pages, feed, pos, table, total, new,
                self._rng, np.int32(step), temp, top_k, top_p, extra)
            self._queue_moe_aux(aux)
        if self.one_token_form is not None:
            self._count_one_token_rows(int(np.count_nonzero(a["new"] == 1)))
        # the slots the device computed and the step program with its
        # bucket, as the ring names them: a packed step pays for its T
        # slots whatever its rows
        self.last_padded = (1, _S) if kind == "packed" else (_B, _S)
        self.last_program = (f"packed[{_S},{_B}]" if kind == "packed"
                             else f"{kind}[{_B},{_S}]")
        if _fresh:
            self._mark_compile(_ckey, kind, _B, _S,
                               time.perf_counter() - _t0)
        self._last_packed = packed
        return packed

    def _step_sampling(self, a: dict, kind: str, seqs):
        """temperature/top_k/top_p device arrays for one step: the
        composition-keyed cache on decode dispatch paths (``seqs`` given),
        the per-step upload everywhere else (prefill compositions change
        every chunk; followers replay raw arrays)."""
        if seqs is not None and kind in ("step", "chained"):
            samp = self._device_sampling(seqs, a["pos"].shape[0])
            return samp["temp"], samp["top_k"], samp["top_p"]
        with stage("upload"):
            return (jnp.asarray(a["temp"]), jnp.asarray(a["top_k"]),
                    jnp.asarray(a["top_p"]))

    def _table_arrays(self, seqs, B: int):
        """Padded page-table (host, device) pair for a decode-family
        batch, rebuilt per ROW only when that row's pages changed
        (``Sequence.table_version``) and re-uploaded only when any did —
        the ``_device_sampling`` pattern applied to the table instead of
        ~B*P zero-fill + one upload every step. The host array is never
        mutated after upload (stale hits copy first), so a device array
        that zero-copied it stays valid."""
        with stage("assemble"):
            P = self.table_width
            key = (B, tuple((s.request.request_id, id(s)) for s in seqs))
            cached = self._table_cache
            if cached is not None and cached[0] == key:
                _k, versions, table, dev = cached
                stale = [i for i, s in enumerate(seqs)
                         if versions[i] != s.table_version]
                if not stale:
                    return table, dev
                table = table.copy()
                for i in stale:
                    s = seqs[i]
                    table[i, :] = 0
                    self._table_row(table, i, s)
                    versions[i] = s.table_version
            else:
                table = np.zeros((B, P), np.int32)
                versions = [s.table_version for s in seqs]
                for i, s in enumerate(seqs):
                    self._table_row(table, i, s)
        with stage("upload"):
            dev = jnp.asarray(table)
        self._table_cache = (key, versions, table, dev)
        return table, dev

    def _step_table(self, a: dict, kind: str, seqs):
        """Device page table for one step: the composition+version-keyed
        cache on decode dispatch paths, the per-step upload everywhere
        else (prefill/mixed compositions change every chunk; followers
        replay raw arrays)."""
        if seqs is not None and kind in ("step", "chained"):
            return self._table_arrays(seqs, a["pos"].shape[0])[1]
        with stage("upload"):
            return jnp.asarray(a["table"])

    def _queue_moe_aux(self, aux: dict, steps: int = 1) -> None:
        """One dispatch's expert-layer counts (device scalars; nothing
        for a dense family), of ``steps`` forward passes."""
        if not aux:
            return
        if "moe_experts_touched" in aux:
            self.moe_expert_slots += steps * self._moe_slots_per_step
            # for this dispatch's ring record (loop._stamp_dispatch)
            self.last_moe_counts = tuple(aux[k] for k in MOE_COUNTS)
        with self._moe_aux_lock:
            self._pending_moe_aux.append(aux)
            overflow = len(self._pending_moe_aux) > 512
        if overflow:
            # bounded memory: drain all but the freshest few (those may
            # still be in flight; everything older has long completed)
            self._drain_moe_aux(keep_last=8)

    def _drain_moe_aux(self, keep_last: int = 0) -> None:
        # swap the list out under the lock (appends race from the step
        # worker thread, scrapes from the event loop); the device transfer
        # runs OUTSIDE it so a slow fetch never blocks the step thread
        with self._moe_aux_lock:
            if len(self._pending_moe_aux) <= keep_last:
                return
            split = len(self._pending_moe_aux) - keep_last
            done = self._pending_moe_aux[:split]
            self._pending_moe_aux = self._pending_moe_aux[split:]
        # ONE batched transfer, not a device_get per scalar
        sums: Dict[str, int] = {}
        for aux in jax.device_get(done):
            for k, v in aux.items():
                sums[k] = sums.get(k, 0) + int(v)
        with self._moe_aux_lock:
            for k, v in sums.items():
                self.moe_totals[k] = self.moe_totals.get(k, 0) + v

    def moe_counts(self) -> Dict[str, int]:
        """Cumulative expert-layer counts: assignments routed, experts
        touched and the expert slots they were touched out of (grouped
        layer), assignments dropped past capacity (dispatch backend).
        Drains every pending per-step scalar — called from the stats and
        metrics scrape paths, where blocking on at most the one in-flight
        step is acceptable."""
        self._drain_moe_aux(keep_last=0)
        with self._moe_aux_lock:
            return dict(self.moe_totals,
                        moe_expert_slots=self.moe_expert_slots)

    def stats(self):
        m = super().stats()
        m.worker_stats.moe_dropped_tokens = self.moe_counts()[
            "moe_dropped_assignments"]
        return m

    # -- page IO (KV transfer / KVBM tier moves) ---------------------------
    # On a multi-host mesh ``pages`` is a GLOBAL sharded array: every rank
    # must enter the same jitted gather/scatter. These methods broadcast
    # the op over the step stream (same ordered channel as compute steps)
    # before dispatching, and gathers produce fully-REPLICATED outputs so
    # the leader can read the whole result from its local shards. This is
    # what lifts the r2 multihost rejections on disagg + KVBM (VERDICT r2
    # item 6; reference: block_manager/distributed/{leader,worker}.rs).

    def _ensure_page_io_jits(self):
        if hasattr(self, "_jit_gather_pages"):
            return
        self.model_cfg.paged_only("KV page export / import (disaggregated "
                                  "prefill, the host and disk tiers, drain "
                                  "and migration)")
        rep = None
        if self.cfg.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.cfg.mesh, PartitionSpec())
        gather = lambda pages, ids: pages[:, ids]  # noqa: E731
        scatter = lambda pages, ids, vals: pages.at[:, ids].set(  # noqa: E731
            vals.astype(pages.dtype))
        self._jit_gather_pages = jax.jit(
            gather, out_shardings=rep) if rep is not None else jax.jit(gather)
        # sharded gather: the transport array KEEPS the cache's placement
        # (no all-gather — page indexing is along the unsharded block
        # axis, so every device reads only its own head slice). The
        # per-shard KV export path reads each addressable shard straight
        # off its device; single-device/replicated caches alias the
        # plain gather.
        self._jit_gather_pages_sharded = self._jit_gather_pages
        if rep is not None:
            from dynamo_tpu.parallel.sharding import (shard_layout,
                                                      transport_sharding)
            ts = transport_sharding(self.pages)
            if shard_layout(ts)[0] >= 2:
                self._jit_gather_pages_sharded = jax.jit(
                    gather, out_shardings=ts)
        self._jit_scatter_pages = jax.jit(scatter, donate_argnums=(0,))

    @staticmethod
    def _pad_page_ids(page_ids) -> np.ndarray:
        """Pad to the next power of two with page 0 (the garbage page) so
        the jits compile a handful of shapes, not one per transfer size."""
        n = 1
        while n < len(page_ids):
            n *= 2
        return np.asarray(list(page_ids) + [0] * (n - len(page_ids)),
                          np.int32)

    def dispatch_gather_pages(self, page_ids, replicate: bool = True):
        """Gather cache pages -> device array [L, n_pad, 2, Hkv, ps, Dh]
        (replicated on a mesh). Non-blocking; broadcast to followers.

        ``replicate=False`` keeps the gathered array on the CACHE's
        sharding instead (no all-gather; each device reads only its own
        slice) — the per-shard KV export path. Single-host only: on a
        multi-host engine (step_tap set) the broadcast gather must stay
        replicated, so the flag is ignored there."""
        self._ensure_page_io_jits()
        ids = self._pad_page_ids(page_ids)
        if self.step_tap is not None:
            # consume a step id of our own: sharing one id between a page
            # IO op and the next compute step would mispair the followers'
            # failure bookkeeping with the leader's outcome cross-check
            self.step_tap("gather", {"ids": ids}, self._step_counter)
            self._step_counter += 1
            replicate = True
        fn = (self._jit_gather_pages if replicate
              else self._jit_gather_pages_sharded)
        return fn(self.pages, jnp.asarray(ids))

    def gather_pages_host(self, page_ids) -> np.ndarray:
        """Gather + host fetch, trimmed to the real page count."""
        out = self.dispatch_gather_pages(page_ids)
        return np.asarray(jax.device_get(out))[:, :len(page_ids)]

    def scatter_pages_device(self, page_ids, vals_dev) -> None:
        """Scatter DEVICE-resident values (the same-process ICI path and
        the staged-inject commit) — no broadcast, no host bounce. vals_dev
        page axis may be narrower than the padded ids; it is padded on
        device."""
        self._ensure_page_io_jits()
        ids = self._pad_page_ids(page_ids)
        vals = jnp.asarray(vals_dev)
        if vals.shape[1] < ids.shape[0]:
            pad = [(0, 0)] * vals.ndim
            pad[1] = (0, int(ids.shape[0]) - int(vals.shape[1]))
            vals = jnp.pad(vals, pad)
        self.page_scatter_dispatches += 1
        self.pages = self._jit_scatter_pages(self.pages, jnp.asarray(ids),
                                             vals)

    def scatter_pages_host(self, page_ids, vals) -> None:
        """Scatter host values [L, n, 2, Hkv, ps, Dh] into cache pages, in
        place (donated). Broadcast with the values so every rank applies
        the identical global write."""
        self._ensure_page_io_jits()
        ids = self._pad_page_ids(page_ids)
        vals = np.asarray(vals)
        if vals.shape[1] < ids.shape[0]:
            pad = [(0, 0)] * vals.ndim
            pad[1] = (0, ids.shape[0] - vals.shape[1])
            vals = np.pad(vals, pad)
        if self.step_tap is not None:
            self.step_tap("scatter", {"ids": ids, "vals": vals},
                          self._step_counter)
            self._step_counter += 1
        self.page_scatter_dispatches += 1
        self.pages = self._jit_scatter_pages(self.pages, jnp.asarray(ids),
                                             jnp.asarray(vals))

    def scatter_pages_chunked(self, page_ids, vals,
                              max_blocks: Optional[int] = None) -> None:
        """Host-values scatter split into windows of at most ``max_blocks``
        pages (default: the DYN_KV_SCATTER_BLOCKS knob). Bounds the
        power-of-two padding blowup of one giant dispatch — scattering 65
        pages in one call would pad ids AND values to 128 — and keeps each
        jitted dispatch at the configured commit window. Callers hold the
        exclusive window across all chunks; use the staged inject pipeline
        when decode steps should interleave instead."""
        if max_blocks is None:
            # static default, NOT the configured knob: resolving that can
            # touch the config file and this method runs inside the
            # exclusive window — hot paths (the inject pipeline) resolve
            # outside and pass the value in
            from dynamo_tpu.engine.transfer import SCATTER_WINDOW_BLOCKS
            max_blocks = SCATTER_WINDOW_BLOCKS
        vals = np.asarray(vals)
        for i in range(0, len(page_ids), max_blocks):
            self.scatter_pages_host(page_ids[i:i + max_blocks],
                                    vals[:, i:i + max_blocks])

    # -- embeddings --------------------------------------------------------

    def _embed_batch(self, token_lists) -> np.ndarray:
        """Mean-pooled hidden-state embeddings (runs outside the scheduler;
        embeddings are one-shot, no KV cache involvement). On a multi-host
        mesh the batch is broadcast so every rank joins the encode jit
        (replicated output — the leader reads it locally)."""
        from dynamo_tpu.models import get_family
        family = get_family(self.model_cfg)
        encode = getattr(family, "encode", None)
        if encode is None:
            raise NotImplementedError(
                f"{self.model_cfg.model_type} has no embedding path")
        self._ensure_encode_jit(encode)
        B = len(token_lists)
        S = _bucket(max(len(t) for t in token_lists),
                    self.cfg.min_prefill_bucket, self.cfg.max_prefill_chunk)
        toks = np.zeros((B, S), np.int32)
        mask = np.zeros((B, S), bool)
        for i, ids in enumerate(token_lists):
            n = min(len(ids), S)
            toks[i, :n] = ids[:n]
            mask[i, :n] = True
        if self.step_tap is not None:
            self.step_tap("embed", {"toks": toks, "mask": mask},
                          self._step_counter)
            self._step_counter += 1
        return np.asarray(self._embed_batch_raw(toks, mask))

    def _ensure_encode_jit(self, encode=None):
        if hasattr(self, "_jit_encode"):
            return
        if encode is None:
            from dynamo_tpu.models import get_family
            encode = get_family(self.model_cfg).encode
        rep = None
        if self.cfg.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.cfg.mesh, PartitionSpec())
        self._jit_encode = jax.jit(
            lambda p, t, m: encode(p, self.model_cfg, t, m),
            **({"out_shardings": rep} if rep is not None else {}))

    def _embed_batch_raw(self, toks, mask):
        """Run the encode jit from raw padded arrays (leader AND follower
        entry — identical arrays on every rank keep the SPMD program in
        lockstep)."""
        self._ensure_encode_jit()
        return self._jit_encode(self.params, jnp.asarray(toks),
                                jnp.asarray(mask))

    async def embed(self, token_lists) -> np.ndarray:
        import asyncio
        if self.step_tap is not None:
            # multi-host: serialize with the step loop so the broadcast
            # order equals the leader's actual dispatch order — a tap from
            # a free-running thread could interleave with step taps and
            # de-lockstep the ranks' collective order
            return await self.run_exclusive(self._embed_batch, token_lists)
        return await asyncio.to_thread(self._embed_batch, token_lists)

    # -- prompt scoring (echo + logprobs / loglikelihood) ------------------

    def _score_batch(self, token_lists):
        """Per-token prompt logprobs (the OpenAI ``echo`` + lm-eval
        loglikelihood surface). Returns a list of
        (lps, top_ids [n, top_n], top_lps [n, top_n]) per input; index 0
        carries no context (lp 0).

        Runs the family's PAGED chunked-prefill forward against scratch
        pages with ``logits_window`` covering each full chunk — linear
        memory, every family, no second attention implementation. The
        dense ``llama.score`` remains as an independent test oracle."""
        if not token_lists:
            return []
        cap = (self.cfg.score_max_tokens or self.cfg.max_context)
        cap = min(cap, self.cfg.max_context)
        longest = max(len(t) for t in token_lists)
        if longest > cap:
            # name the knob(s) that actually bind: raising a non-binding
            # one cannot help. An UNSET score_max_tokens (0) follows
            # max_context automatically, so only max_context binds then;
            # when both are explicitly equal, BOTH bind.
            smt = self.cfg.score_max_tokens
            if not smt or smt > self.cfg.max_context:
                knob = "max_context"
            elif smt < self.cfg.max_context:
                knob = "score_max_tokens"
            else:
                knob = "score_max_tokens AND max_context"
            raise ValueError(
                f"prompt of {longest} tokens exceeds the scoring cap "
                f"{cap} (score_max_tokens="
                f"{self.cfg.score_max_tokens or 'max_context'}, "
                f"max_context {self.cfg.max_context}) — raise {knob} "
                "to score longer prompts")
        if not self._fwd_has_logits_window:
            raise NotImplementedError(
                f"{self.model_cfg.model_type} has no prompt-scoring "
                "path (forward lacks logits_window / custom forward_fn)")
        self._ensure_score_jit()
        B = len(token_lists)
        chunk = _SCORE_CHUNK
        S = max(chunk, -(-longest // chunk) * chunk)
        toks = np.zeros((B, S), np.int32)
        mask = np.zeros((B, S), bool)
        for i, ids in enumerate(token_lists):
            n = min(len(ids), S)
            toks[i, :n] = ids[:n]
            mask[i, :n] = True
        if self.step_tap is not None:
            self.step_tap("score", {"toks": toks, "mask": mask},
                          self._step_counter)
            self._step_counter += 1
        lps, tids, tlps = self._score_batch_raw(toks, mask)
        lps, tids, tlps = (np.asarray(lps), np.asarray(tids),
                          np.asarray(tlps))
        return [(lps[i, :len(t)], tids[i, :len(t)], tlps[i, :len(t)])
                for i, t in enumerate(token_lists)]

    def _score_impl(self, params, tokens, mask):
        """Chunked-prefill scoring as ONE jitted program: scratch pages,
        per-row disjoint page ranges, a ``lax.scan`` over full chunks of
        the family forward with ``logits_window=chunk``; each chunk's
        window logits score its own tokens' successors.

        Padding discipline that keeps it exact: S is a multiple of the
        chunk so every chunk is FULL (``new_lens`` uniform); pad
        positions write KV into the row's own pages past its real length
        and are attended by NOTHING real (pads only exist in the final
        partial region, after every real position).
        """
        cfg = self.model_cfg
        B, S = tokens.shape
        chunk = _SCORE_CHUNK
        ps = self.cfg.page_size
        per_row = -(-S // ps)   # ceil: ps need not divide the padded S
        # llama.make_pages is the universal (config-driven) page builder —
        # the engine's own cache uses it for every family, deepseek's
        # latent geometry included
        pages = llama.make_pages(cfg, B * per_row + 1, ps)
        table = (1 + jnp.arange(B * per_row, dtype=jnp.int32)
                 ).reshape(B, per_row)
        nc = S // chunk
        toks_c = tokens.reshape(B, nc, chunk).swapaxes(0, 1)  # [nc, B, c]
        # target for global position p is tokens[p+1] (the token position
        # p's logits predict) — shifted ONCE here so the last slot of a
        # chunk reaches across the chunk boundary
        tgt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        tgt_c = tgt.reshape(B, nc, chunk).swapaxes(0, 1)

        top_n = max(1, min(self.cfg.num_top_logprobs or 1,
                           cfg.vocab_size))

        def body(pages, xs):
            tc, gc, ci = xs
            pos = (ci * chunk
                   + jnp.arange(chunk, dtype=jnp.int32))[None, :]
            pos = jnp.tile(pos, (B, 1))
            total = jnp.full((B,), (ci + 1) * chunk, jnp.int32)
            new = jnp.full((B,), chunk, jnp.int32)
            # same chunked-prefill kernel the serving prefill and
            # spec-verify steps run (S > 1)
            logits, pages, _ = self._run_forward(
                self._attn_prefill, params, tc, pos, pages, table, total,
                new, logits_window=chunk)           # [B, chunk, V]
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            # gather INSIDE the scan: only [B, chunk(, top_n)] leaves each
            # step — the full [B, S, V] logits never materialize
            t_lp = jnp.take_along_axis(lsm, gc[..., None], axis=-1)[..., 0]
            top_lp, top_id = top_candidates(lsm, top_n)
            return pages, (t_lp, top_id.astype(jnp.int32), top_lp)

        _, (t_lp, top_id, top_lp) = jax.lax.scan(
            body, pages, (toks_c, tgt_c, jnp.arange(nc)))

        def unchunk(a):
            return a.swapaxes(0, 1).reshape((B, S) + a.shape[3:])

        t_lp, top_id, top_lp = (unchunk(t_lp), unchunk(top_id),
                                unchunk(top_lp))
        # position j-1 predicts token j; index 0 has no context (and the
        # wrapped final target is dropped by the same shift)
        z = jnp.zeros((B, 1), jnp.float32)
        target_lps = jnp.concatenate([z, t_lp[:, :-1]], axis=1)
        top_ids = jnp.concatenate(
            [jnp.zeros((B, 1, top_n), jnp.int32), top_id[:, :-1]], axis=1)
        top_lps = jnp.concatenate(
            [jnp.zeros((B, 1, top_n), jnp.float32), top_lp[:, :-1]],
            axis=1)
        return target_lps, top_ids, top_lps

    def _ensure_score_jit(self):
        if hasattr(self, "_jit_score"):
            return
        rep = None
        if self.cfg.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.cfg.mesh, PartitionSpec())
        self._jit_score = jax.jit(
            self._score_impl,
            **({"out_shardings": rep} if rep is not None else {}))

    def _score_batch_raw(self, toks, mask):
        """Leader AND follower entry (identical arrays keep SPMD ranks in
        lockstep, as _embed_batch_raw)."""
        self._ensure_score_jit()
        return self._jit_score(self.params, jnp.asarray(toks),
                               jnp.asarray(mask))

    async def score(self, token_lists):
        import asyncio
        if self.step_tap is not None:
            return await self.run_exclusive(self._score_batch, token_lists)
        return await asyncio.to_thread(self._score_batch, token_lists)

    @classmethod
    def random_init(cls, model_cfg: ModelConfig,
                    config: Optional[JaxEngineConfig] = None,
                    seed: int = 0) -> "JaxEngine":
        """Engine with random weights (tests / benchmarks)."""
        from dynamo_tpu.models import get_family
        params = get_family(model_cfg).init_params(
            model_cfg, jax.random.PRNGKey(seed))
        return cls(model_cfg, params, config)


__all__ = ["JaxEngine", "JaxEngineConfig", "ATTN_IMPLS",
           "qkv_form", "serving_weights",
           "decode_multistep_default",
           "mixed_batch_default", "decode_progress_default",
           "DECODE_MULTISTEP", "MIXED_BATCH", "DECODE_PROGRESS_EVERY"]
