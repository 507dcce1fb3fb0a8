"""Measurement functions for the TPU serving engine, run in ONE process.

``python bench.py [--legs a,b,...] [--tiny] [--attn-impl X]`` runs the legs
it is asked for, in this process, on the device jax finds, and prints the
result as JSON lines on stdout (the object grows leg by leg; the last line
is the whole run). Every line names the device it ran on — ``platform``,
``device_kind``, ``device_count`` as jax reports them — and a number means
what its name says only on the platform that line names: a time or a rate
from a CPU run is not a device metric.

Without a TPU the run exits non-zero: the process is held to the platform
it was started for (``dynamo_tpu.utils.platform.pin_platform``), the
full-size legs build the Llama-3.2-3B geometry only on a TPU, and the HBM
roofline needs a ``device_kind`` that ``HBM_GBPS`` lists. ``--tiny`` is the
one explicit exception: the toy model at a toy geometry, on whatever
platform ``JAX_PLATFORMS`` names — it is how the test suite drives every
leg on the CPU. A leg that fails raises, and the exit code is non-zero.

Legs: ``engine`` (closed-batch decode/prefill on one engine, the same-run
fused-vs-per-step and mixed-vs-legacy A/Bs, the KV transport planes),
``longctx``, ``mesh_sharded``, ``constrained_decode``, ``drain``,
``coord_failover``, ``fleet``, ``routing``, ``steptrace``,
``shared_prefix``, and two that build further full-size engines and run
only when named: ``quant``, ``spec``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import statistics
import sys
import time

# HBM bandwidth in GB/s, keyed by ``jax.devices()[0].device_kind``. Source:
# Google Cloud documentation, "TPU v5e" system architecture (819 GB/s per
# chip). A device that is not listed is an error, not a default.
HBM_GBPS = {
    "TPU v5 lite": 819.0,
}

# closed-batch geometry of the ``engine`` leg at full size: sequences,
# prompt tokens, generated tokens (Llama-3.2-3B, bf16, one v5e chip)
FULL_GEOMETRY = (32, 512, 128)
TINY_GEOMETRY = (4, 32, 64)


def _note(stage: str, **kw) -> None:
    """Progress line on stderr (stdout carries only result JSON)."""
    print("bench: " + json.dumps({"stage": stage, **kw}),
          file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def detect_bandwidth() -> float:
    kind = device_info()["device_kind"]
    if kind not in HBM_GBPS:
        raise SystemExit(
            f"bench: no HBM bandwidth on record for device_kind {kind!r}; "
            "add it to HBM_GBPS with its source")
    return HBM_GBPS[kind]


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _build_engine(tiny: bool, attn_impl: str, quantize: str = "",
                  spec_tokens: int = 0):
    """Build the engine: the toy model when ``tiny`` was asked for, else
    the Llama-3.2-3B geometry, which needs a TPU. The config is
    deterministic so the persistent compile-cache keys match across
    runs."""
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models.config import ModelConfig

    if tiny:
        cfg = ModelConfig.tiny(dtype="float32")
        # gen long enough that steady-state decode dominates the timed
        # window (the fused-vs-per-step A/B is measured here; a 16-token
        # tail was mostly prefill + ramp)
        seqs, prompt, gen = TINY_GEOMETRY
        page_size, max_ctx = 4, 128
    else:
        platform = device_info()["platform"]
        if platform != "tpu":
            raise SystemExit(
                f"bench: the full-size legs need a TPU, jax is on "
                f"{platform!r}; pass --tiny for the toy-model run")
        cfg = ModelConfig.llama32_3b()
        seqs, prompt, gen = FULL_GEOMETRY
        page_size, max_ctx = 16, prompt + gen + 64

    pages_needed = seqs * ((prompt + gen) // page_size + 2)
    # pin ONE compiled shape per step family ([8, prompt] prefill,
    # [seqs, 1] decode) so priming pays every compile and the timed phase
    # is pure execution
    prefill_seqs = min(8, seqs)
    ecfg = JaxEngineConfig(
        num_pages=pages_needed + 16, page_size=page_size,
        max_num_seqs=seqs, max_prefill_chunk=min(512, prompt),
        max_prefill_seqs=prefill_seqs,
        max_context=max_ctx, min_prefill_bucket=min(512, prompt),
        min_prefill_seqs_bucket=prefill_seqs,
        min_decode_bucket=seqs,
        attn_impl=attn_impl, quantize=quantize, spec_tokens=spec_tokens)
    engine = JaxEngine.random_init(cfg, ecfg)
    return engine, cfg, (seqs, prompt, gen, prefill_seqs)


def _step_arrays(P: int, B: int, S: int) -> dict:
    """Synthetic padded step arrays (garbage-page writes): the ONE
    construction priming and the step-timing legs share, so they always
    dispatch identically-shaped programs."""
    import numpy as np

    return dict(
        toks=np.zeros((B, S), np.int32),
        pos=np.tile(np.arange(S, dtype=np.int32)[None], (B, 1)),
        table=np.zeros((B, P), np.int32),
        total=np.full((B,), S, np.int32),
        new=np.zeros((B,), np.int32),  # nothing written: garbage page
        temp=np.zeros((B,), np.float32),
        top_k=np.zeros((B,), np.int32),
        top_p=np.ones((B,), np.float32))


def _prime_programs(engine, seqs: int, prompt: int, prefill_seqs: int,
                    label: str = "main") -> None:
    """Compile the three step programs one at a time (no requests). Each
    lands in THIS process's jit cache (the measurement reuses the callable
    directly) AND the persistent disk cache (a later run starts warm).
    One progress note per program, with its compile seconds."""
    import jax

    P = engine.table_width
    plans = [("prefill", "step", _step_arrays(P, prefill_seqs, prompt)),
             ("decode", "step", _step_arrays(P, seqs, 1)),
             ("chained", "chained", _step_arrays(P, seqs, 1))]
    for name, kind, a in plans:
        t0 = time.perf_counter()
        packed = engine._invoke_step(kind, a, 0)
        jax.block_until_ready(packed)
        _note("primed", program=name, label=label,
              shape=[int(a["toks"].shape[0]), int(a["toks"].shape[1])],
              s=round(time.perf_counter() - t0, 1))
    if getattr(engine, "supports_multistep", False):
        # the fused-decode scan programs: the full width plus the pow2
        # ladder the scheduler narrows budget tails to, so the timed
        # phase never pays a compile mid-block
        t0 = time.perf_counter()
        jax.block_until_ready(engine.prime_multistep(seqs))
        _note("primed", program="multistep", label=label,
              shape=[seqs, engine.multistep],
              s=round(time.perf_counter() - t0, 1))


async def _measure_engine(engine, cfg, geometry, label: str) -> dict:
    """Drive the engine through warmup + the timed run; returns the raw
    measurement numbers (no transport measurements, no JSON framing)."""
    import numpy as np

    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)

    seqs, prompt, gen, _pfs = geometry
    rng = np.random.default_rng(0)

    def make_req(rid: str, n_prompt: int, n_gen: int) -> PreprocessedRequest:
        return PreprocessedRequest(
            token_ids=rng.integers(1, cfg.vocab_size,
                                   size=n_prompt).tolist(),
            request_id=rid,
            stop_conditions=StopConditions(max_tokens=n_gen, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))

    ttfts: list = []
    arrivals: list = []  # (t, n_tokens) across all sequences

    async def drive(rid: str, n_prompt: int, n_gen: int):
        t0 = time.perf_counter()
        first = None
        count = 0
        async for out in engine.generate(make_req(rid, n_prompt, n_gen)):
            now = time.perf_counter()
            if out.token_ids and first is None:
                first = now - t0
            if out.token_ids:
                arrivals.append((now, len(out.token_ids)))
            count += len(out.token_ids)
        if first is not None:
            ttfts.append(first)
        return first, count

    # warmup: compile (or reuse from this process's jit cache, which the
    # priming stage just filled) the REAL prefill and decode shapes — a
    # full-width concurrent batch. Decode needs >2 steps so the chained
    # (pipelined) program also runs.
    t_setup = time.perf_counter()
    # label-scoped request ids: the fused-vs-per-step A/B re-measures on
    # the SAME engine, and a reused request_id on one engine wedges the
    # second generate
    await asyncio.gather(
        *[drive(f"warm{label[:2]}{i}", prompt, 8) for i in range(seqs)])
    ttfts.clear()
    warmup_s = time.perf_counter() - t_setup
    _note("warmup_done", label=label, s=round(warmup_s, 1))

    print(f"bench: {seqs} seqs x ({prompt} prompt + {gen} gen)",
          file=sys.stderr, flush=True)
    arrivals.clear()
    d0 = getattr(engine, "decode_dispatches", 0)
    t0 = time.perf_counter()
    results = await asyncio.gather(
        *[drive(f"{label[:2]}{i}", prompt, gen) for i in range(seqs)])
    wall = time.perf_counter() - t0
    decode_dispatches = getattr(engine, "decode_dispatches", 0) - d0

    total_generated = sum(c for _f, c in results)
    # the metric is DECODE throughput: measure the steady-state phase, from
    # the moment every sequence has its first token (prefill done — its own
    # cost is reported as TTFT/prefill tok/s) to the last token. A request
    # that never produced a token (error) reports first=None — exclude it
    # rather than crash the whole bench run.
    firsts = [f for f, _c in results if f is not None]
    if not firsts:
        raise RuntimeError("no request produced a first token")
    t_steady = max(firsts) + t0
    steady = [(t, n) for t, n in arrivals if t > t_steady]
    steady_tokens = sum(n for _t, n in steady)
    steady_wall = (max(t for t, _n in steady) - t_steady) if steady else 0.0
    tok_per_s = (steady_tokens / steady_wall if steady_wall > 0
                 else total_generated / wall)
    prefill_tok_s = seqs * prompt / (t_steady - t0)
    ttft_p50 = statistics.median(ttfts)
    _note("measured", label=label, tokens=total_generated,
          decode_tok_s=round(tok_per_s, 1),
          prefill_tok_s=round(prefill_tok_s, 1),
          decode_dispatches=decode_dispatches)
    return dict(tok_per_s=tok_per_s, prefill_tok_s=prefill_tok_s,
                ttft_p50=ttft_p50, warmup_s=warmup_s,
                total_generated=total_generated, wall=wall,
                decode_dispatches=decode_dispatches)


# requests / arrival rate of the continuous-arrival (mixed-batch) leg;
# the rate must SATURATE the engine (prefills arriving while decode rows
# run) or the leg measures the arrival schedule instead of the engine —
# sized for the tiny tier's ~ms step times, overridable for on-chip runs
MIXED_ARRIVAL_REQS = int(os.environ.get("BENCH_MIXED_REQS", "32"))
MIXED_ARRIVAL_RPS = float(os.environ.get("BENCH_MIXED_RPS", "120"))


async def _measure_mixed_arrivals(engine, vocab_size: int) -> dict:
    """Continuous-arrival leg: Poisson onboarding (``trace_gen``) against
    one engine, measured with the legacy prefill-XOR-decode alternation
    and with mixed dispatch ON in the same run. This is the regime the
    steady-state legs cannot see: prefill and decode contending, fused
    blocks either gated off (legacy) or running through the arrivals
    (mixed). Reports tok/s over the whole arrival window, p99 TTFT, and
    decode dispatches per generated token per leg.

    Run against BOTH the live jax engine and the mocker
    (``_leg_engine``): the jax sub-leg measures real compute on whatever
    platform the run is on — on an in-process CPU backend the dispatch
    overhead that mixed dispatch amortizes is ~free, so its A/B is
    expected ~flat there (on the chip: not measured); the mocker sub-leg
    prices each dispatch with the mocker's cost model, so the
    scheduling-policy effect shows as counts on any host (the reference
    benchmarks its schedulers on its mocker the same way)."""
    import numpy as np

    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.trace_gen import TraceConfig, generate

    sched_cfg = engine.scheduler.cfg
    # prompts span SEVERAL prefill chunks (that is the contended regime:
    # legacy gates fusion off while any row is prefilling, mixed rides
    # decode rows through those same steps), bounded by the context
    max_prompt = max(2 * sched_cfg.max_prefill_chunk,
                     min(3 * sched_cfg.max_prefill_chunk,
                         engine.max_context - 48))
    max_prompt = min(max_prompt, engine.max_context - 40)
    trace = list(generate(TraceConfig(
        num_requests=MIXED_ARRIVAL_REQS, requests_per_s=MIXED_ARRIVAL_RPS,
        block_size=max(16, engine.allocator.page_size), shared_blocks=2,
        unique_blocks_mean=4.0, output_len_mean=64.0, seed=7)))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, vocab_size,
                            size=max(2, min(r["input_length"],
                                            max_prompt))).tolist()
               for r in trace]

    async def leg(label: str, mixed: bool) -> dict:
        sched_cfg.mixed_batch = mixed
        ttfts: list = []
        counts: list = []
        d0 = getattr(engine, "decode_dispatches", 0)
        b0 = getattr(engine, "multistep_blocks", 0)
        x0 = getattr(engine, "mixed_steps", 0)
        t_start = time.perf_counter()

        async def drive(i: int, req: dict):
            # the SAME Poisson arrival schedule for both legs
            await asyncio.sleep(max(
                0.0, t_start + req["timestamp"] / 1000.0
                - time.perf_counter()))
            gen_cap = max(8, min(128, engine.max_context
                                 - len(prompts[i]) - 8))
            p = PreprocessedRequest(
                token_ids=prompts[i], request_id=f"mx{label}{i}",
                stop_conditions=StopConditions(
                    max_tokens=max(8, min(req["output_length"], gen_cap)),
                    ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            t0 = time.perf_counter()
            first = None
            n = 0
            async for out in engine.generate(p):
                if out.token_ids and first is None:
                    first = time.perf_counter() - t0
                n += len(out.token_ids)
            if first is not None:
                ttfts.append(first)
            counts.append(n)

        await asyncio.gather(*[drive(i, r) for i, r in enumerate(trace)])
        wall = time.perf_counter() - t_start
        total = sum(counts)
        dispatches = getattr(engine, "decode_dispatches", 0) - d0
        ttfts.sort()
        p99 = (ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
               if ttfts else None)
        return {
            "tok_s": round(total / wall, 1) if wall > 0 else 0.0,
            "ttft_p99_s": round(p99, 4) if p99 is not None else None,
            "decode_dispatches_per_token": round(
                dispatches / max(1, total), 4),
            "fused_blocks": getattr(engine, "multistep_blocks", 0) - b0,
            "mixed_dispatches": getattr(engine, "mixed_steps", 0) - x0,
            "total_tokens": total,
        }

    saved = sched_cfg.mixed_batch
    try:
        await leg("w", True)    # warmup: compiles any mixed-only shapes
        legacy = await leg("l", False)
        mixed = await leg("m", True)
    finally:
        sched_cfg.mixed_batch = saved
    _note("mixed_arrivals", legacy_tok_s=legacy["tok_s"],
          mixed_tok_s=mixed["tok_s"],
          legacy_dpt=legacy["decode_dispatches_per_token"],
          mixed_dpt=mixed["decode_dispatches_per_token"])
    return {"legacy": legacy, "mixed": mixed,
            "speedup": (round(mixed["tok_s"] / legacy["tok_s"], 3)
                        if legacy["tok_s"] > 0 else None)}


# sharded-tier geometry (tiny model over a tp=2 mesh; override for
# on-chip runs): sequences x (prompt + gen) per leg
MESH_SEQS = int(os.environ.get("BENCH_MESH_SEQS", "4"))
MESH_PROMPT = int(os.environ.get("BENCH_MESH_PROMPT", "32"))
MESH_GEN = int(os.environ.get("BENCH_MESH_GEN", "48"))


async def _measure_mesh_sharded() -> dict:
    """Mesh-sharded serving leg (ROADMAP item 2): the fused-multistep +
    mixed-dispatch fast path measured ON A SHARDED ENGINE — the regime
    every earlier bench tier gated off (``supports_multistep`` used to
    refuse the moment ``cfg.mesh`` was set).

    Builds a tiny-model engine tensor-parallel over 2 devices
    (``--xla_force_host_platform_device_count`` on CPU; real chips on a
    slice), runs a same-run fused-vs-per-step A/B asserting token parity,
    then a shard-aware disagg KV handoff between two sharded engines over
    the wire-v5 per-shard frame schema, recording per-shard bytes.
    Results land in the run's JSON under ``mesh_sharded``."""
    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "the mesh_sharded leg needs >=2 devices (on the CPU: XLA_FLAGS="
            "--xla_force_host_platform_device_count=2)")
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.transfer import (
        InjectPipeline, cache_shard_layout, export_frames, kv_shard_payload,
        resolve_wire, stamp_frame_crcs)
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel import tp_sharding
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)

    seqs, prompt, gen = MESH_SEQS, MESH_PROMPT, MESH_GEN
    cfg = ModelConfig.tiny(dtype="float32")
    shard = tp_sharding(cfg, 2)
    page = 4
    kw = dict(
        num_pages=seqs * ((prompt + gen) // page + 2) + 16, page_size=page,
        max_num_seqs=seqs, max_prefill_chunk=min(64, prompt),
        max_prefill_seqs=seqs, max_context=prompt + gen + 32,
        min_prefill_bucket=min(64, prompt), min_decode_bucket=seqs,
        mesh=shard.mesh, shard_params_fn=shard.shard_params,
        shard_pages_fn=shard.shard_pages)

    def build():
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return JaxEngine(cfg, params, JaxEngineConfig(**kw))

    engine = build()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=prompt).tolist()
               for _ in range(seqs)]

    async def leg(label: str) -> dict:
        tokens: dict = {}

        async def drive(i: int):
            req = PreprocessedRequest(
                token_ids=prompts[i], request_id=f"mesh{label}{i}",
                stop_conditions=StopConditions(max_tokens=gen,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            out = []
            async for f in engine.generate(req):
                out.extend(f.token_ids)
            tokens[i] = out

        d0 = engine.decode_dispatches
        b0 = engine.multistep_blocks
        x0 = engine.mixed_steps
        t0 = time.perf_counter()
        await asyncio.gather(*[drive(i) for i in range(seqs)])
        wall = time.perf_counter() - t0
        total = sum(len(t) for t in tokens.values())
        return {
            "tok_s": round(total / wall, 1),
            "decode_dispatches_per_token": round(
                (engine.decode_dispatches - d0) / max(1, total), 4),
            "fused_blocks": engine.multistep_blocks - b0,
            "mixed_dispatches": engine.mixed_steps - x0,
            "tokens": tokens,
        }

    try:
        assert engine.supports_multistep, \
            engine.multistep_unsupported_reason
        await leg("w")                    # warmup/compile
        fused = await leg("f")
        ms_saved = engine.multistep
        engine.multistep = 1              # supports_multistep -> False
        try:
            perstep = await leg("p")
        finally:
            engine.multistep = ms_saved
        parity = all(fused["tokens"][i] == perstep["tokens"][i]
                     for i in range(seqs))
        fallbacks = dict(engine.scheduler.multistep_fallbacks)

        # shard-aware KV handoff: prefill on this engine, per-shard wire
        # frames into a second sharded engine's cache (the wire-v5 path
        # disagg decode workers negotiate)
        decode_eng = build()
        try:
            hand_prompt = list(range(1, 4 * page * 6))
            req = PreprocessedRequest(
                token_ids=hand_prompt, request_id="mesh-handoff",
                stop_conditions=StopConditions(max_tokens=2,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            req.prefill_only = True
            final = None
            async for f in engine.generate(req):
                if f.finish_reason is not None:
                    final = f
            hashes = [b[0] for b in final.kv_transfer_params["blocks"]]
            layout, per, _crc, shards = resolve_wire(
                {"wire": 5, **kv_shard_payload(decode_eng)}, 1)
            t0 = time.perf_counter()
            frames = await engine.run_exclusive(export_frames, engine,
                                                hashes, layout, per, shards)
            stamp_frame_crcs(frames)
            per_shard_bytes: dict = {}
            for f in frames:
                sh = f.obj.get("shard") or {"index": "merged"}
                k = str(sh["index"])
                per_shard_bytes[k] = (per_shard_bytes.get(k, 0)
                                      + int(np.asarray(f.raw).nbytes))
            pipe = InjectPipeline(decode_eng)
            for f in frames:
                meta = dict(f.obj)
                meta["_raw"] = f.raw
                await pipe.add_frame(meta)
            injected = await pipe.finish()
            handoff_s = time.perf_counter() - t0
            handoff = {
                "blocks": len(hashes), "injected": injected,
                "sharded_frames": all(f.obj.get("shard") is not None
                                      for f in frames),
                "shard_layout": list(cache_shard_layout(decode_eng)),
                "per_shard_bytes": per_shard_bytes,
                "wall_s": round(handoff_s, 4),
            }
        finally:
            await decode_eng.stop()
    finally:
        await engine.stop()

    for d in (fused, perstep):
        d.pop("tokens")
    result = {
        "devices": len(jax.devices()),
        "tp": 2,
        "geometry": [seqs, prompt, gen],
        "decode_multistep": int(ms_saved),
        "fused": fused,
        "perstep": perstep,
        "fused_speedup": (round(fused["tok_s"] / perstep["tok_s"], 3)
                          if perstep["tok_s"] > 0 else None),
        "token_parity": parity,
        "multistep_fallbacks": fallbacks,
        "mesh_fallbacks": int(fallbacks.get("mesh", 0)),
        "handoff": handoff,
    }
    _note("mesh_sharded", fused_tok_s=fused["tok_s"],
          perstep_tok_s=perstep["tok_s"],
          fused_dpt=fused["decode_dispatches_per_token"],
          perstep_dpt=perstep["decode_dispatches_per_token"],
          parity=parity, handoff_blocks=handoff["blocks"])
    return result


CONSTR_SEQS = int(os.environ.get("BENCH_CONSTR_SEQS", "4"))
CONSTR_PROMPT = int(os.environ.get("BENCH_CONSTR_PROMPT", "16"))
CONSTR_GEN = int(os.environ.get("BENCH_CONSTR_GEN", "48"))


async def _measure_constrained_decode() -> dict:
    """Constrained-decode leg: penalties, logit bias, and guided decoding
    riding the fused multistep block, measured as a same-run
    fused-vs-per-step A/B on a MIXED cohort (plain + penalized + biased +
    guided rows in one batch) plus an unconstrained fused baseline.

    Records tok/s, dispatches/token, and the per-reason fallback deltas;
    the acceptance gate is {penalties, guided} == 0 on the fused
    constrained leg with tok/s within ~1.3x of the unconstrained cohort.
    ``BENCH_CONSTRAINED_OUT`` names a standalone artifact
    (``BENCH_constrained_r08.json``)."""
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.preprocessor.tokenizer import HfTokenizer
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.utils.testing import make_test_tokenizer

    seqs, prompt, gen = CONSTR_SEQS, CONSTR_PROMPT, CONSTR_GEN
    page = 4
    tok = HfTokenizer(make_test_tokenizer())
    eos = tok.token_to_id("<eos>")
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    engine = JaxEngine.random_init(cfg, JaxEngineConfig(
        num_pages=seqs * ((prompt + gen) // page + 2) + 16,
        page_size=page, max_num_seqs=seqs,
        max_prefill_chunk=min(64, prompt), max_prefill_seqs=seqs,
        max_context=prompt + gen + 32,
        min_prefill_bucket=min(16, prompt), min_decode_bucket=seqs,
        # size the ring buffer for the cohort: every generated token is a
        # distinct window entry in the worst case, so W < gen would
        # exhaust mid-run and the row would degrade to per-step
        penalty_window=2 * gen))
    engine.enable_guided(tok.token_bytes(), [eos])

    schema = {"type": "object",
              "properties": {"mood": {"enum": ["up", "dn"]},
                             "n": {"type": "integer"}},
              "required": ["mood", "n"]}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=prompt).tolist()
               for _ in range(seqs)]

    MIXED = ("plain", "penalized", "biased", "guided")

    def cohort(label: str, kinds):
        rows = []
        for i in range(seqs):
            kind = kinds[i % len(kinds)]
            sopts, eos_ids, ign = {}, [], True
            if kind == "penalized":
                sopts = dict(frequency_penalty=0.8,
                             repetition_penalty=1.3)
            elif kind == "biased":
                sopts = dict(logit_bias={19: 2.5, 47: -100.0})
            elif kind == "guided":
                sopts = dict(guided={"mode": "json_schema",
                                     "schema": schema})
                eos_ids, ign = [eos], False
            rows.append(PreprocessedRequest(
                token_ids=prompts[i], request_id=f"c{label}{i}",
                stop_conditions=StopConditions(max_tokens=gen,
                                               ignore_eos=ign),
                sampling_options=SamplingOptions(temperature=0.0,
                                                 **sopts),
                eos_token_ids=eos_ids))
        return rows

    async def leg(label: str, kinds) -> dict:
        fb0 = dict(engine.scheduler.multistep_fallbacks)
        tokens: dict = {}

        async def drive(i: int, req) -> None:
            out = []
            async for f in engine.generate(req):
                assert f.error is None, f.error
                out.extend(f.token_ids)
            tokens[i] = out

        rows = cohort(label, kinds)
        d0, b0 = engine.decode_dispatches, engine.multistep_blocks
        t0 = time.perf_counter()
        await asyncio.gather(*[drive(i, r) for i, r in enumerate(rows)])
        wall = time.perf_counter() - t0
        total = sum(len(t) for t in tokens.values())
        fb1 = engine.scheduler.multistep_fallbacks
        return {
            "tok_s": round(total / wall, 1),
            "decode_dispatches_per_token": round(
                (engine.decode_dispatches - d0) / max(1, total), 4),
            "fused_blocks": engine.multistep_blocks - b0,
            "fallback_deltas": {
                k: fb1.get(k, 0) - fb0.get(k, 0)
                for k in set(fb0) | set(fb1)
                if fb1.get(k, 0) != fb0.get(k, 0)},
            "tokens": tokens,
        }

    PLAIN, GUIDED = ("plain",), ("plain", "plain", "plain", "guided")
    try:
        # two warm passes per cohort: some decode shapes (batch tails,
        # chained-block restarts) only compile on the second pass
        for lb, kinds in (("w", MIXED), ("w2", MIXED), ("wu", PLAIN),
                          ("wu2", PLAIN), ("wg", GUIDED), ("wg2", GUIDED)):
            await leg(lb, kinds)
        fused = await leg("f", MIXED)
        plain = await leg("u", PLAIN)
        guided = await leg("g", GUIDED)
        ms_saved = engine.multistep
        engine.multistep = 1              # same-run per-step A/B
        try:
            await leg("wp", MIXED)        # warm the per-step programs
            await leg("wp2", MIXED)
            perstep = await leg("p", MIXED)
        finally:
            engine.multistep = ms_saved
    finally:
        await engine.stop()

    parity = fused["tokens"] == perstep["tokens"]
    for d in (fused, plain, guided, perstep):
        d.pop("tokens")
    result = {
        "geometry": [seqs, prompt, gen],
        "decode_multistep": int(ms_saved),
        "fused_constrained": fused,
        "fused_unconstrained": plain,
        "fused_guided_cohort": guided,
        "perstep_constrained": perstep,
        "fused_speedup": (round(fused["tok_s"] / perstep["tok_s"], 3)
                          if perstep["tok_s"] > 0 else None),
        "constrained_vs_plain": (
            round(plain["tok_s"] / fused["tok_s"], 3)
            if fused["tok_s"] > 0 else None),
        "guided_vs_plain": (
            round(plain["tok_s"] / guided["tok_s"], 3)
            if guided["tok_s"] > 0 else None),
        "token_parity": parity,
        "constrained_fallbacks": {
            k: fused["fallback_deltas"].get(k, 0)
            for k in ("penalties", "penalty_window", "guided",
                      "guided_table")},
    }
    _note("constrained_decode", fused_tok_s=fused["tok_s"],
          plain_tok_s=plain["tok_s"], perstep_tok_s=perstep["tok_s"],
          parity=parity)
    out_path = os.environ.get("BENCH_CONSTRAINED_OUT")
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(result, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return result


# drain-leg geometry: streams in flight when the scale-down lands, and
# tokens per stream (long enough that every stream straddles the handoff)
DRAIN_STREAMS = int(os.environ.get("BENCH_DRAIN_STREAMS", "6"))
DRAIN_TOKENS = int(os.environ.get("BENCH_DRAIN_TOKENS", "24"))


async def _measure_drain() -> dict:
    """Graceful-drain leg (ROADMAP item 4, the scale-down half of "zero
    lost streams"): a real coordinator + two decode workers + a routed
    frontend pipeline, with one worker SIGTERM'd while every stream is
    mid-decode.  The drained worker freezes its in-flight sequences into
    pinned-KV resume tokens; survivors pull and continue from the next
    token.  Records streams lost (must be 0), resume-vs-replay handoff
    counts, how many resumed rows admitted with their full prefix cached
    (zero recomputed prefill tokens), and the inter-token gap
    distribution — ``itg_p99_ms`` prices the handoff stall the user sees
    against ``itg_p50_ms``, the undisturbed decode cadence."""
    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.transfer import serve_kv_export
    from dynamo_tpu.llm.pipeline import RemotePipeline
    from dynamo_tpu.llm.register import register_llm, serve_engine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.push_router import PushRouter
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.utils.faults import WorkerDrain
    from dynamo_tpu.utils.testing import make_test_card
    from dynamo_tpu.worker.disagg import KV_EXPORT_ENDPOINT
    from dynamo_tpu.worker.drain import ResumeAdmission
    from dynamo_tpu.worker.metrics import get_worker_metrics

    eng_cfg = JaxEngineConfig(num_pages=256, page_size=4, max_num_seqs=8,
                              max_prefill_chunk=64, max_context=512,
                              min_prefill_bucket=4, decode_multistep=1)

    def paced(engine, seconds=0.01):
        # slow each step so the drain deterministically lands mid-stream
        orig = engine._execute_plan
        engine._execute_plan = lambda plan: (time.sleep(seconds),
                                             orig(plan))[1]
        return engine

    async def start_worker(address):
        import jax

        drt = await DistributedRuntime.create(coordinator=address)
        engine = paced(JaxEngine.random_init(ModelConfig.tiny(), eng_cfg))
        # commit the page pool to its device NOW: the first KV inject
        # commits it anyway (explicit device_put), and the jit cache keys
        # on committedness — left uncommitted, the survivor would
        # recompile its whole program set right after the first resume
        # pull lands, burying the handoff gap under XLA compiles
        pg = engine.pages
        engine.pages = ([jax.device_put(p, next(iter(p.devices())))
                         for p in pg] if isinstance(pg, list)
                        else jax.device_put(pg, next(iter(pg.devices()))))
        comp = drt.namespace("bench").component("decode")
        await comp.endpoint(KV_EXPORT_ENDPOINT).serve(serve_kv_export(engine))
        ra = ResumeAdmission(
            engine, kv_client=await comp.endpoint(KV_EXPORT_ENDPOINT)
            .client())
        served = await serve_engine(comp.endpoint("generate"), engine,
                                    resume_admission=ra)
        await register_llm(drt, comp.endpoint("generate"),
                           make_test_card(name="bench-drain",
                                          kv_cache_block_size=4))
        lease = await drt.primary_lease()
        return WorkerDrain(drt, engine, served=[served],
                           resume_extras={"instance_id": lease.lease_id})

    wm = get_worker_metrics()
    resumes0 = wm.migration_replays.labels("resume")._value.get()
    replays0 = wm.migration_replays.labels("replay")._value.get()
    coord = await Coordinator(port=0).start()
    workers, fe = [], None
    try:
        workers = [await start_worker(coord.address) for _ in range(2)]
        fe = await DistributedRuntime.create(coordinator=coord.address)
        client = await (fe.namespace("bench").component("decode")
                        .endpoint("generate").client())
        await client.wait_for_instances(2, timeout=10)
        pipeline = RemotePipeline(
            make_test_card(name="bench-drain", kv_cache_block_size=4),
            PushRouter(client), migration_limit=3)

        def prime_grid(engine):
            """Compile the full (kind x batch-bucket x width-bucket)
            program grid this engine can hit while absorbing a handoff,
            via direct synthetic dispatches (no requests).  A survivor's
            batch composition after adopting resumed rows is
            timing-dependent, so request-level warmup cannot cover the
            space — and any shape missed shows up as a multi-second XLA
            compile right where the gap metric is measured."""
            import jax

            P = engine.table_width
            B = 1
            while B <= eng_cfg.max_num_seqs:
                for S in (4, 8, 16):
                    jax.block_until_ready(engine._invoke_step(
                        "step", _step_arrays(P, B, S), 0))
                    jax.block_until_ready(engine._invoke_step(
                        "mixed", _step_arrays(P, B, S), 0))
                jax.block_until_ready(engine._invoke_step(
                    "step", _step_arrays(P, B, 1), 0))
                jax.block_until_ready(engine._invoke_step(
                    "chained", _step_arrays(P, B, 1), 0))
                B *= 2

        # prime off the event loop (each compile blocks ~1s; lease
        # renewal and keepalive must keep running underneath)
        for w in workers:
            await asyncio.to_thread(prime_grid, w.engine)

        async def warm(i: int, tokens):
            req = PreprocessedRequest(
                token_ids=list(tokens), request_id=f"warm{i}",
                stop_conditions=StopConditions(max_tokens=4,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            async for _ in pipeline.engine_stream(req):
                pass

        # a light request-level pass compiles the non-step glue (embed,
        # sampling upload) on both workers
        base = list(range(1, 14))
        await asyncio.gather(*[warm(4 * i + j, (base, base[:4])[j % 2])
                               for j in range(4) for i in range(2)])

        stamps: list[list[float]] = [[] for _ in range(DRAIN_STREAMS)]
        finals: list = [None] * DRAIN_STREAMS
        started = [asyncio.Event() for _ in range(DRAIN_STREAMS)]

        async def drive(i: int):
            req = PreprocessedRequest(
                token_ids=list(range(1 + i, 14 + i)),
                request_id=f"drain{i}",
                stop_conditions=StopConditions(max_tokens=DRAIN_TOKENS,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))
            async for out in pipeline.engine_stream(req):
                stamps[i].extend([time.perf_counter()] * len(out.token_ids))
                if len(stamps[i]) >= 3:
                    started[i].set()
                if out.finish_reason is not None:
                    finals[i] = out
            started[i].set()

        tasks = [asyncio.ensure_future(drive(i))
                 for i in range(DRAIN_STREAMS)]
        await asyncio.gather(*[asyncio.wait_for(ev.wait(), 60)
                               for ev in started])
        # scale down whichever worker holds streams right now
        busy = next((w for w in workers if w.engine.scheduler.active),
                    workers[0])
        t0 = time.perf_counter()
        counts = await busy.sigterm()
        drain_s = time.perf_counter() - t0
        await asyncio.gather(*tasks)

        lost = sum(1 for i, f in enumerate(finals)
                   if f is None or len(stamps[i]) < DRAIN_TOKENS)
        # resumed rows that admitted with their whole computed prefix
        # cached — i.e. zero prefill tokens recomputed by the survivor
        # (every prompt above is exactly 13 tokens long)
        full_cache = sum(1 for f in finals
                         if f is not None and (f.cached_tokens or 0) >= 13)
        if os.environ.get("BENCH_DRAIN_DEBUG"):
            for i, s in enumerate(stamps):
                worst = max((b - a, k) for k, (a, b)
                            in enumerate(zip(s, s[1:])))
                print(f"drain-debug stream {i}: {len(s)} tokens, worst "
                      f"gap {worst[0] * 1e3:.0f}ms at token {worst[1] + 1}"
                      f" (t={s[worst[1] + 1] - t0:+.2f}s vs drain)",
                      file=sys.stderr, flush=True)
        gaps = sorted(b - a for s in stamps if len(s) > 1
                      for a, b in zip(s, s[1:]))
        pick = lambda q: (gaps[min(len(gaps) - 1, int(q * len(gaps)))]  # noqa: E731
                          if gaps else None)
        result = {
            "streams": DRAIN_STREAMS,
            "streams_lost": lost,
            "migrated_resume": int(counts.get("resume", 0)),
            "migrated_replay": int(counts.get("replay", 0)),
            "absorbed_resume": int(
                wm.migration_replays.labels("resume")._value.get()
                - resumes0),
            "absorbed_replay": int(
                wm.migration_replays.labels("replay")._value.get()
                - replays0),
            "resumed_full_cache": full_cache,
            "drain_s": round(drain_s, 3),
            "itg_p50_ms": (round(pick(0.50) * 1e3, 2)
                           if gaps else None),
            "itg_p99_ms": (round(pick(0.99) * 1e3, 2)
                           if gaps else None),
            "itg_max_ms": round(gaps[-1] * 1e3, 2) if gaps else None,
        }
        _note("drain", **{k: v for k, v in result.items()
                          if k != "streams"})
        return result
    finally:
        for w in workers:
            try:
                await w._close()
            except Exception:  # noqa: BLE001 — already closed by sigterm
                pass
        if fe is not None:
            await fe.close()
        await coord.stop()


# coordinator-failover leg geometry: live streams mid-trace when the
# primary dies, and tokens per stream (long enough to straddle the window)
COORD_FAILOVER_STREAMS = int(os.environ.get("BENCH_COORD_STREAMS", "8"))
COORD_FAILOVER_TOKENS = int(os.environ.get("BENCH_COORD_TOKENS", "60"))


async def _measure_coord_failover() -> dict:
    """Coordinator-failover leg (ROADMAP item 4, the control-plane half of
    "zero lost streams"): a replicated coordinator pair under a routed
    2-worker topology, with the PRIMARY kill -9'd while every stream is
    mid-flight.  Streams ride direct worker RPC connections, so none may
    be lost; the leg prices what the control plane does cost — promotion
    latency, failover-to-ready (every process reconnected AND discovery
    answering from the new primary), resync count, and lease re-grants
    (must be 0: the standby mirrors the boot epoch, so the resync takes
    the probe path — no re-grant storm).  A same-run cold-restart sub-leg
    (single coordinator, kill -9 + instant state-wiped respawn — the PR 3
    path at its best) is the baseline the failover number must beat."""
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.utils.faults import CoordinatorOutage, CoordinatorPair


    async def gen(payload, ctx):
        # stand-in decode stream: the leg measures the control plane, so
        # token compute is a paced counter, not an engine
        for t in range(int(payload["n"])):
            await asyncio.sleep(float(payload.get("delay_s", 0.02)))
            yield {"tok": t}

    async def topology(addresses, n_workers):
        drts = []
        for _ in range(n_workers):
            drt = await DistributedRuntime.create(coordinator=addresses)
            drts.append(drt)
            ep = drt.namespace("bench").component("cf").endpoint("generate")
            await ep.serve(gen)
        fe = await DistributedRuntime.create(coordinator=addresses)
        drts.append(fe)
        ep = fe.namespace("bench").component("cf").endpoint("generate")
        client = await ep.client()
        insts = await client.wait_for_instances(n_workers, timeout=10)
        return drts, fe, ep, client, insts

    async def ready_after(drts, ep, n_workers, t0):
        """Outage-to-ready: the frontend's first successful discovery scan
        answering with the FULL fleet.  An in-flight call on the dead
        connection fails (never answers stale), so a success here is by
        construction served by the new/restarted primary — and seeing all
        workers means their registrations survived or were resynced."""
        fe_coord = drts[-1].coord
        while True:
            try:
                items = await fe_coord.get_prefix(ep.instance_prefix)
                if len(items) >= n_workers:
                    return time.perf_counter() - t0
            except ConnectionError:
                pass
            await asyncio.sleep(0.02)

    # -- failover leg: replicated pair, kill -9 the primary mid-trace
    pair = await CoordinatorPair(promote_after_s=0.6).start()
    drts = []
    try:
        drts, fe, ep, client, insts = await topology(pair.addresses, 2)
        relocations = []
        for drt in drts:
            lease = drt._primary_lease
            if lease is not None:
                lease.on_relocated(
                    lambda o, n: relocations.append((o, n)))
        got = [0] * COORD_FAILOVER_STREAMS
        started = [asyncio.Event() for _ in range(COORD_FAILOVER_STREAMS)]

        async def drive(i):
            stream = await client.direct(
                {"n": COORD_FAILOVER_TOKENS, "delay_s": 0.03},
                insts[i % len(insts)].instance_id)
            async for _f in stream:
                got[i] += 1
                if got[i] >= 2:
                    started[i].set()
            started[i].set()

        tasks = [asyncio.ensure_future(drive(i))
                 for i in range(COORD_FAILOVER_STREAMS)]
        await asyncio.gather(*[asyncio.wait_for(ev.wait(), 30)
                               for ev in started])
        resyncs0 = sum(d.coord.resyncs_total for d in drts)
        t0 = time.perf_counter()
        await pair.kill9_primary()
        await pair.wait_promoted(timeout=30)
        promote_s = time.perf_counter() - t0
        ready_s = await asyncio.wait_for(
            ready_after(drts, ep, 2, t0), timeout=60)
        await asyncio.gather(*tasks)
        lost = sum(1 for g in got if g < COORD_FAILOVER_TOKENS)
        failover = {
            "streams": COORD_FAILOVER_STREAMS,
            "streams_lost": lost,
            "promote_s": round(promote_s, 3),
            "ready_s": round(ready_s, 3),
            "resyncs": sum(d.coord.resyncs_total for d in drts) - resyncs0,
            "lease_regrants": len(relocations),
        }
    finally:
        for drt in drts:
            await drt.close()
        await pair.stop()

    # -- baseline: single coordinator, kill -9 + supervisor respawn (the
    # PR 3 path).  The dwell models the supervisor restart delay — the
    # irreducible cost replication removes: with no standby the control
    # plane is down for the WHOLE dwell, then pays the wiped-state resync
    # (fresh epoch -> lease re-grant storm + registration replay)
    respawn_s = float(os.environ.get("BENCH_COORD_RESPAWN_S", "1.0"))
    coord = await Coordinator(port=0).start()
    outage = CoordinatorOutage(coord)
    drts = []
    try:
        drts, fe, ep, client, insts = await topology(coord.address, 1)
        cold_relocations = []
        for drt in drts:
            lease = drt._primary_lease
            if lease is not None:
                lease.on_relocated(
                    lambda o, n: cold_relocations.append((o, n)))
        t0 = time.perf_counter()
        await outage.kill()
        await asyncio.sleep(respawn_s)
        await outage.restart(wipe_state=True)
        cold_ready_s = await asyncio.wait_for(
            ready_after(drts, ep, 1, t0), timeout=60)
    finally:
        for drt in drts:
            await drt.close()
        await coord.stop()

    result = {
        **failover,
        "cold_restart_ready_s": round(cold_ready_s, 3),
        "cold_restart_respawn_s": respawn_s,
        "cold_restart_regrants": len(cold_relocations),
        # PR 3's measured cold-restart resync at TTL 5s, for the trend line
        "pr3_cold_restart_ref_s": 3.2,
    }
    _note("coord_failover", **{k: v for k, v in result.items()
                               if k != "streams"})
    return result


# fleet-supervisor leg geometry: phased cohort trace (low -> burst -> low)
# and the per-stream token cap (keeps mocker streams ~hundreds of ms so
# every scale event lands with live streams in flight)
FLEET_PHASES = os.environ.get("BENCH_FLEET_PHASES",
                              "3rps:6s,12rps:14s,3rps:8s")
FLEET_TOKEN_CAP = int(os.environ.get("BENCH_FLEET_TOKENS", "48"))
FLEET_MAX_DECODE = int(os.environ.get("BENCH_FLEET_MAX_DECODE", "4"))
FLEET_INFLIGHT_CAP = int(os.environ.get("BENCH_FLEET_INFLIGHT", "96"))


async def _measure_fleet() -> dict:
    """Fleet-supervisor leg (ROADMAP item 4, the closing proof): the
    planner's LocalConnector drives a REAL multi-worker mocker fleet
    through every lifecycle event PRs 14-16 built, in one continuous
    phased cohort trace — planner scale-up on the burst (readiness-
    gated), a worker kill -9 mid-burst auto-healed by the supervisor, a
    coordinator-primary kill -9 absorbed by the hot standby, and a
    planner-driven drain scale-down when the burst subsides.  The
    headline number is ``streams_lost`` and it must be 0 for EVERY
    event: drain takes the migration path, kill -9 takes the replay
    path.  Cohorts carry real sampling shapes (penalties, guided-json)
    so migrated requests exercise the no-fallback decode surface."""
    import aiohttp

    from dynamo_tpu.llm.pipeline import RemotePipeline
    from dynamo_tpu.planner.connectors import LocalConnector
    from dynamo_tpu.planner.metrics import get_planner_metrics
    from dynamo_tpu.planner.perf_interpolation import PerfInterpolator
    from dynamo_tpu.planner.planner_core import (
        Planner, PlannerConfig, SloSpec, TrafficSample)
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.push_router import PushRouter
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.runtime.system_server import SystemServer
    from dynamo_tpu.trace_gen import (
        TraceConfig, default_cohorts, generate, parse_phases)
    from dynamo_tpu.utils.faults import CoordinatorPair, stub_worker_cmd
    from dynamo_tpu.utils.testing import make_test_card


    pm = get_planner_metrics()
    crashes0 = pm.worker_crashes_total.labels("decode")._value.get()
    holds0 = pm.crash_loop_holds_total._value.get()
    ups0 = pm.decisions_total.labels("up")._value.get()
    downs0 = pm.decisions_total.labels("down")._value.get()

    phases = parse_phases(FLEET_PHASES)
    trace = list(generate(TraceConfig(
        num_requests=100_000, block_size=4, seed=7,
        phases=phases, cohorts=default_cohorts())))
    low_end = phases[0][1]
    high_end = low_end + phases[1][1]

    pair = await CoordinatorPair(promote_after_s=0.6).start()
    mocker_cmd = [
        sys.executable, "-m", "dynamo_tpu.mocker.main",
        "--coordinator", pair.addresses, "--component", "fleet",
        "--speedup-ratio", "1", "--page-size", "4",
        "--num-pages", "8192", "--max-num-seqs", "64",
        "--max-context", "16384",
    ]
    conn = LocalConnector(
        stub_worker_cmd(), mocker_cmd,
        extra_env={"JAX_PLATFORMS": "cpu"},
        supervise_interval_s=0.1, probe_interval_s=0.05,
        backoff_base_s=0.2, backoff_cap_s=1.0)

    # synthetic decode surface calibrated to the phase rates: at the itl
    # SLO the per-replica concurrency budget is 8, so the low phase needs
    # 1 replica and the 12 rps burst needs 4 (with 1.15x headroom)
    interp = PerfInterpolator({
        "prefill": [{"isl": 64, "ttft_s": 0.01, "tokens_per_s": 1e6},
                    {"isl": 4096, "ttft_s": 0.02, "tokens_per_s": 1e6}],
        "decode": [{"concurrency": 1, "itl_s": 0.04, "tokens_per_s": 25},
                   {"concurrency": 8, "itl_s": 0.05, "tokens_per_s": 160},
                   {"concurrency": 32, "itl_s": 0.2, "tokens_per_s": 160}],
    })

    class DriverSource:
        """Planner MetricsSource fed by the driver's own issue counters —
        the bench process IS the frontend here."""

        def __init__(self):
            self.n = 0
            self.isl = 0.0
            self.osl = 0.0
            self._t = time.monotonic()

        def record(self, isl: int, osl: int) -> None:
            self.n += 1
            self.isl += isl
            self.osl += osl

        async def sample(self) -> TrafficSample:
            now = time.monotonic()
            dt = max(1e-6, now - self._t)
            self._t = now
            n, isl, osl = self.n, self.isl, self.osl
            self.n, self.isl, self.osl = 0, 0.0, 0.0
            if n == 0:
                return TrafficSample(0.0, 0.0, 0.0)
            return TrafficSample(n / dt, isl / n, osl / n)

    source = DriverSource()
    planner = Planner(
        PlannerConfig(interval_s=1.5, predictor="constant",
                      min_prefill=0, max_prefill=0,
                      min_decode=1, max_decode=FLEET_MAX_DECODE),
        SloSpec(ttft_s=0.5, itl_s=0.05), interp, source, conn)

    # planner metrics served the production way: a system server over the
    # planner registry, scraped over HTTP at the end of the leg
    system = SystemServer(port=0, registry=pm.registry)
    system.health.register("planner", ready=True)
    await system.start()

    fe = None
    replicas_peak = 0
    stats = {"issued": 0, "completed": 0, "shed": 0, "lost": 0}
    errors: list = []
    ttfts: list = []
    inflight = 0
    events: dict = {}

    async def poll(cond, timeout, what):
        t0 = time.monotonic()
        while not cond():
            if time.monotonic() - t0 > timeout:
                raise TimeoutError(f"fleet leg: timed out waiting for {what}")
            await asyncio.sleep(0.1)

    try:
        # bootstrap: one decode replica, readiness-gated before any traffic
        await conn.scale(0, 1)
        await conn.wait_ready("decode", 1, timeout=120)
        fe = await DistributedRuntime.create(coordinator=pair.addresses)
        client = await (fe.namespace("dynamo").component("fleet")
                        .endpoint("generate").client())
        await client.wait_for_instances(1, timeout=30)
        card = make_test_card(name="mock-model", kv_cache_block_size=4)
        pipeline = RemotePipeline(card, PushRouter(client), migration_limit=5)

        def to_request(row, idx):
            isl = min(int(row["input_length"]), 12_000)
            osl = max(1, min(int(row["output_length"]), FLEET_TOKEN_CAP))
            s = row.get("sampling") or {}
            guided = None
            rf = s.get("response_format")
            if isinstance(rf, dict) and rf.get("type") == "json_object":
                guided = {"mode": "json"}
            req = PreprocessedRequest(
                token_ids=[(i * 7 + idx) % 29_000 + 1 for i in range(isl)],
                request_id=f"fleet-{idx}",
                stop_conditions=StopConditions(max_tokens=osl,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(
                    temperature=s.get("temperature"),
                    frequency_penalty=s.get("frequency_penalty"),
                    presence_penalty=s.get("presence_penalty"),
                    guided=guided))
            return req, isl, osl

        async def drive_one(row, idx):
            nonlocal inflight
            stats["issued"] += 1
            if inflight >= FLEET_INFLIGHT_CAP:
                stats["shed"] += 1
                return
            inflight += 1
            req, isl, osl = to_request(row, idx)
            source.record(isl, osl)
            t0 = time.perf_counter()
            first = None
            toks = 0
            try:
                async for out in pipeline.engine_stream(req):
                    if out.token_ids and first is None:
                        first = time.perf_counter() - t0
                    toks += len(out.token_ids)
                if toks >= osl:
                    stats["completed"] += 1
                    if first is not None:
                        ttfts.append(first)
                else:
                    stats["lost"] += 1
                    errors.append(f"short stream {req.request_id}: "
                                  f"{toks}/{osl}")
            except Exception as e:  # noqa: BLE001 — a lost stream is data
                stats["lost"] += 1
                errors.append(f"{req.request_id}: {str(e)[:120]}")
            finally:
                inflight -= 1

        async def chaos_script():
            """The event sequence, pegged to fleet state (not wall time):
            scale-up observed -> worker kill -9 -> heal observed ->
            coordinator kill -9 -> promotion observed."""
            await poll(lambda: conn.counts()["decode"] >= 2,
                       timeout=high_end + 30,
                       what="planner scale-up to >=2 ready replicas")
            events["scale_up_replicas"] = conn.counts()["decode"]

            victims = [h for h in conn._fleets["decode"]
                       if h.ready and not h.stopping]
            victim = victims[0]
            victim.proc.kill()  # kill -9: no drain, streams must replay
            events["killed_worker"] = f"decode-g{victim.gen}"
            crash_floor = crashes0 + 1
            await poll(lambda: (pm.worker_crashes_total.labels("decode")
                                ._value.get() >= crash_floor),
                       timeout=30, what="supervisor to log the kill -9")
            await poll(lambda: conn.counts()["decode"] >= 2,
                       timeout=60, what="crash-heal respawn to readiness")
            events["healed"] = True

            t0 = time.perf_counter()
            await pair.kill9_primary()
            await pair.wait_promoted(timeout=30)
            events["promote_s"] = round(time.perf_counter() - t0, 3)

        planner.start()
        chaos = asyncio.ensure_future(chaos_script())
        tasks = []
        t_start = time.monotonic()
        for idx, row in enumerate(trace):
            delay = t_start + row["timestamp"] / 1000.0 - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            replicas_peak = max(replicas_peak, conn.counts()["decode"])
            tasks.append(asyncio.ensure_future(drive_one(row, idx)))
        trace_wall = time.monotonic() - t_start
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=120)
        await asyncio.wait_for(chaos, timeout=60)

        # the burst is over: the planner must now drain the fleet back
        # down to 1 replica (graceful scale-down, not a kill)
        await poll(lambda: conn.alive_counts()["decode"] <= 1,
                   timeout=30, what="planner-driven drain scale-down")
        await conn.quiesce()
        events["drained_to"] = conn.counts()["decode"]
        await planner.stop()

        # migration replays absorbed by the survivors, from their own
        # worker /metrics (the connector gave each worker a system port)
        replays = 0.0
        async with aiohttp.ClientSession() as http:
            for h in conn._fleets["decode"]:
                try:
                    async with http.get(
                            f"http://127.0.0.1:{h.port}/metrics",
                            timeout=aiohttp.ClientTimeout(total=3)) as r:
                        body = await r.text()
                    for line in body.splitlines():
                        if (line.startswith(
                                "dynamo_worker_migration_replays_total")
                                and not line.startswith("#")):
                            replays += float(line.rsplit(" ", 1)[1])
                except Exception:  # noqa: BLE001 — scrape is best-effort
                    pass
            async with http.get(
                    f"http://127.0.0.1:{system.port}/metrics",
                    timeout=aiohttp.ClientTimeout(total=3)) as r:
                planner_scrape = await r.text()

        ttfts.sort()
        result = {
            "phases": FLEET_PHASES,
            "requests": stats["issued"],
            "completed": stats["completed"],
            "shed": stats["shed"],
            "streams_lost": stats["lost"],
            "sustained_rps": round(stats["completed"] / max(trace_wall, 1e-9),
                                   2),
            "ttft_p99_s": (round(ttfts[int(len(ttfts) * 0.99) - 1], 3)
                           if ttfts else None),
            "replicas_peak": replicas_peak,
            "scale_up_replicas": events.get("scale_up_replicas"),
            "healed_crashes": int(
                pm.worker_crashes_total.labels("decode")._value.get()
                - crashes0),
            "crash_loop_holds": int(
                pm.crash_loop_holds_total._value.get() - holds0),
            "decisions_up": int(
                pm.decisions_total.labels("up")._value.get() - ups0),
            "decisions_down": int(
                pm.decisions_total.labels("down")._value.get() - downs0),
            "promote_s": events.get("promote_s"),
            "drained_to": events.get("drained_to"),
            "migration_replays": int(replays),
            "planner_metrics_on_http": (
                "dynamo_planner_replicas" in planner_scrape
                and "dynamo_planner_worker_crashes_total" in planner_scrape),
            "errors": errors[:5],
        }
        _note("fleet", **{k: v for k, v in result.items() if k != "errors"})
        return result
    finally:
        with contextlib.suppress(Exception):
            await planner.stop()
        with contextlib.suppress(Exception):
            await conn.close(force=True)
        if fe is not None:
            with contextlib.suppress(Exception):
                await fe.close()
        with contextlib.suppress(Exception):
            await system.stop()
        with contextlib.suppress(Exception):
            await pair.stop()


ROUTING_REQS = int(os.environ.get("BENCH_ROUTING_REQS", "32"))
ROUTING_CONC = int(os.environ.get("BENCH_ROUTING_CONC", "8"))
ROUTING_STALL = os.environ.get("BENCH_ROUTING_STALL", "0.25,0.45")


async def _measure_routing() -> dict:
    """Failure-aware routing leg: a same-run cost-vs-round-robin A/B over
    a 4-worker mocker fleet where one worker sits behind a ChaosProxy in
    per-connection tail-latency mode (``delay_jitter`` — the slow-but-
    alive worker keepalive cannot see).  The round-robin leg keeps
    sending it every 4th request and eats the stalls; the cost leg
    hedges the slow first token, learns the worker's EWMA TTFT from the
    lost race, opens its breaker via slow-call accounting, and routes
    around it.  Headline: cost p99 TTFT < RR p99 TTFT in the same run,
    with zero lost streams on both legs, the breaker open/close visible
    on /metrics, and the decision's score inputs retrievable from
    /v1/traces."""
    import socket

    import aiohttp

    from dynamo_tpu.http.service import HttpService
    from dynamo_tpu.llm.model_manager import ModelManager, ModelWatcher
    from dynamo_tpu.llm.register import register_llm, serve_engine
    from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.push_router import RouterMode
    from dynamo_tpu.runtime.resilience import (
        RouterPolicyConfig, get_router_stats)
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.utils.faults import ChaosProxy
    from dynamo_tpu.utils.testing import make_test_card


    smin, smax = (float(x) for x in ROUTING_STALL.split(","))
    coord = await Coordinator(port=0).start()
    drts: list = []
    engines: list = []
    proxy = None

    async def start_worker(env=None):
        saved = {}
        if env:
            for k, v in env.items():
                saved[k] = os.environ.get(k)
                os.environ[k] = v
        try:
            drt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(drt)
            engine = MockerEngine(MockEngineArgs(
                num_pages=2048, page_size=4, max_num_seqs=16,
                max_prefill_chunk=64, max_context=2048,
                speedup_ratio=100.0))
            engines.append(engine)
            ep = (drt.namespace("dynamo").component("routing")
                  .endpoint("generate"))
            await serve_engine(
                ep, engine,
                stats_provider=lambda e=engine: e.stats().to_dict())
            await register_llm(drt, ep, make_test_card(
                name="mock-model", kv_cache_block_size=4))
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    async def run_leg(mode, policy_config=None):
        fe = await DistributedRuntime.create(coordinator=coord.address)
        manager = ModelManager()
        watcher = ModelWatcher(fe, manager, router_mode=mode,
                               policy_config=policy_config)
        await watcher.start()
        service = await HttpService(manager, host="127.0.0.1",
                                    port=0).start()
        base = f"http://127.0.0.1:{service.port}"
        ttfts: list = []
        errors: list = []
        lost = 0
        sem = asyncio.Semaphore(ROUTING_CONC)

        async def one(i, session):
            nonlocal lost
            # leg-distinct prompts so the KV-free mocker never shortcuts
            body = {"model": "mock-model",
                    "messages": [{"role": "user",
                                  "content": f"{mode.value} probe {i} "
                                             + "lorem ipsum dolor " * 4}],
                    "max_tokens": 4, "stream": True}
            async with sem:
                t0 = time.perf_counter()
                first = None
                try:
                    async with session.post(
                            f"{base}/v1/chat/completions", json=body,
                            timeout=aiohttp.ClientTimeout(total=90)) as r:
                        async for line in r.content:
                            if (line.startswith(b"data:")
                                    and b"[DONE]" not in line
                                    and first is None):
                                first = time.perf_counter() - t0
                    if first is None:
                        lost += 1
                    else:
                        ttfts.append(first)
                except Exception as e:  # noqa: BLE001 — a lost stream is data
                    lost += 1
                    errors.append(f"{mode.value}-{i}: {str(e)[:120]}")

        scrape = {"metrics": "", "trace_attrs_ok": False}
        try:
            async with aiohttp.ClientSession() as session:
                await asyncio.gather(*[one(i, session)
                                       for i in range(ROUTING_REQS)])
                async with session.get(
                        f"{base}/metrics",
                        timeout=aiohttp.ClientTimeout(total=5)) as r:
                    scrape["metrics"] = await r.text()
                # decision score inputs must be retrievable post-hoc from
                # the flight recorder
                async with session.get(
                        f"{base}/v1/traces?limit=5",
                        timeout=aiohttp.ClientTimeout(total=5)) as r:
                    summaries = (await r.json()).get("traces", [])
                for s in summaries:
                    async with session.get(
                            f"{base}/v1/traces/{s['trace_id']}",
                            timeout=aiohttp.ClientTimeout(total=5)) as r:
                        detail = await r.text()
                    if '"router.policy"' in detail and \
                            '"router.instance"' in detail:
                        scrape["trace_attrs_ok"] = True
                        break
        finally:
            await service.stop()
            await watcher.stop()
            await fe.close()
        ttfts.sort()
        pick = lambda q: (round(ttfts[min(len(ttfts) - 1,  # noqa: E731
                                          int(len(ttfts) * q))], 3)
                          if ttfts else None)
        return {"completed": len(ttfts), "streams_lost": lost,
                "ttft_p50_s": pick(0.50), "ttft_p95_s": pick(0.95),
                "ttft_p99_s": pick(0.99), "errors": errors[:3]}, scrape

    try:
        for _ in range(3):
            await start_worker()
        # the slow worker: RPC pinned to a pre-picked port, announcing the
        # ChaosProxy's address instead (DYN_RPC_ADVERTISE) so every RPC —
        # requests, stats scrapes — pays the proxy's per-connection stall
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        upstream_port = s.getsockname()[1]
        s.close()
        proxy = await ChaosProxy(f"127.0.0.1:{upstream_port}").start()
        await start_worker(env={
            "DYN_RPC_PORT": str(upstream_port),
            "DYN_RPC_ADVERTISE": f"127.0.0.1:{proxy.port}"})
        proxy.delay_jitter(1.0, smin, smax, seed=9)

        rr, _ = await run_leg(RouterMode.ROUND_ROBIN)

        st = get_router_stats()
        tr0 = dict(st.breaker_transitions)
        hg0 = dict(st.hedges)
        rt0 = dict(st.retries)
        # slow-call threshold == hedge delay: a primary that loses the
        # hedge race has by construction been silent longer than the
        # delay, so one lost race opens its breaker (failures=1) — while
        # healthy first tokens (~tens of ms) stay far below it
        hedge_delay = max(0.1, smin * 0.5)
        cost_cfg = RouterPolicyConfig(
            breaker_failures=1, breaker_cooldown_s=2.0,
            breaker_slow_ttft_s=hedge_delay,
            retry_budget_ratio=0.2, hedge=True,
            hedge_delay_s=hedge_delay, stats_interval_s=0.3)
        cost, scrape = await run_leg(RouterMode.COST, cost_cfg)

        st = get_router_stats()
        result = {
            "requests_per_leg": ROUTING_REQS,
            "stall_s": [smin, smax],
            "rr": rr,
            "cost": cost,
            "breaker_opens": (st.breaker_transitions.get("open", 0)
                              - tr0.get("open", 0)),
            "hedges": {k: st.hedges.get(k, 0) - hg0.get(k, 0)
                       for k in ("fired", "won", "lost", "denied",
                                 "expired")},
            "retries": {k: st.retries.get(k, 0) - rt0.get(k, 0)
                        for k in ("connect", "denied")},
            "breaker_metric_seen": (
                "dynamo_frontend_router_breaker_state" in scrape["metrics"]
                and "dynamo_frontend_router_breaker_transitions_total"
                in scrape["metrics"]),
            "trace_attrs_ok": scrape["trace_attrs_ok"],
            "cost_vs_rr_p99": (round(rr["ttft_p99_s"] / cost["ttft_p99_s"], 2)
                               if rr["ttft_p99_s"] and cost["ttft_p99_s"]
                               else None),
        }
        _note("routing", **{k: v for k, v in result.items()
                            if k not in ("rr", "cost")})
        out_path = os.environ.get("BENCH_ROUTING_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2, sort_keys=True)
        return result
    finally:
        if proxy is not None:
            with contextlib.suppress(Exception):
                await proxy.stop()
        for e in engines:
            with contextlib.suppress(Exception):
                await e.stop()
        for d in drts:
            with contextlib.suppress(Exception):
                await d.close()
        with contextlib.suppress(Exception):
            await coord.stop()


# step-flight-recorder leg geometry: generated tokens per row
STEPTRACE_GEN = int(os.environ.get("BENCH_STEPTRACE_GEN", "48"))


async def _measure_steptrace() -> dict:
    """Step flight recorder leg (observability PR): fused decode on a
    tiny engine with the per-dispatch ring (``engine/steptrace.py``)
    capturing every step.

    Two phases on one engine:

    1. warm a small cohort's jit buckets, then RERUN the same shape on a
       fresh recorder — zero compile events expected (detection must not
       false-positive on warmed buckets);
    2. drive a cohort shape the engine has NEVER seen (bigger batch,
       longer prompts) mid-trace — the cold prefill/decode buckets must
       surface as compile events attributable to specific StepRecords.

    Results land in the run's JSON (``steptrace``) and — when
    ``BENCH_STEPTRACE_OUT`` names a path — in a standalone artifact
    (``BENCH_steptrace_r10.json``)."""
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.steptrace import StepRecorder
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)

    gen = STEPTRACE_GEN
    from dynamo_tpu.models.config import ModelConfig
    cfg = ModelConfig.tiny()
    engine = JaxEngine.random_init(cfg, JaxEngineConfig(
        num_pages=160, page_size=4, max_num_seqs=6, max_prefill_chunk=32,
        max_prefill_seqs=6, max_context=128, min_prefill_bucket=8,
        decode_multistep=8))
    rng = np.random.default_rng(11)

    async def drive(rid: str, prompt: list, n_gen: int) -> int:
        req = PreprocessedRequest(
            token_ids=prompt, request_id=rid,
            stop_conditions=StopConditions(max_tokens=n_gen,
                                           ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        n = 0
        async for out in engine.generate(req):
            n += len(out.token_ids)
        return n

    async def cohort(label: str, n_seqs: int, prompt_len: int):
        prompts = [rng.integers(1, cfg.vocab_size, size=prompt_len).tolist()
                   for _ in range(n_seqs)]
        t0 = time.perf_counter()
        counts = await asyncio.gather(*[
            drive(f"st-{label}-{i}", p, gen)
            for i, p in enumerate(prompts)])
        return sum(counts), time.perf_counter() - t0

    try:
        # phase 1: warm the small-cohort buckets (prefill bucket 8,
        # decode batch 2), then rerun the SAME shape on a fresh recorder
        await cohort("warm", 2, 8)
        trace = StepRecorder(capacity=4096)
        engine.steptrace = trace
        await cohort("rerun", 2, 8)
        warm_rerun_events = sum(trace.compile_events.values())

        # phase 2: a shape the engine has NEVER run — bigger batch and a
        # longer prompt cross into cold prefill/decode buckets, so the
        # first dispatches compile MID-TRACE on the live recorder
        await cohort("cold", 6, 24)
        agg = trace.aggregates()
        midrun_events = (sum(agg["compile_events"].values())
                         - warm_rerun_events)
        snap = trace.snapshot(limit=4096)
        compile_recs = [r for r in snap["records"] if r["compile_ms"] > 0]
        compile_info = {
            "warm_rerun_events": warm_rerun_events,
            "midrun_events": midrun_events,
            "midrun_compile_ms_max": round(max(
                (r["compile_ms"] for r in compile_recs), default=0.0), 1),
            "compile_records": len(compile_recs),
            "compile_kinds": sorted({r["kind"] for r in compile_recs}),
        }
        aggregates_info = {
            "records": snap["total"],
            "kinds": sorted(agg["duration"].keys()),
            "occupancy_samples": sum(
                c for _, _, c in agg["occupancy"].values()),
            "gap_samples": agg["gap"][2],
            "pool_free": agg["pool_free"],
            "pool_pinned": agg["pool_pinned"],
        }

        result = {"compile": compile_info, "aggregates": aggregates_info}
        _note("steptrace", midrun_compiles=midrun_events,
              warm_rerun_events=warm_rerun_events)
        out_path = os.environ.get("BENCH_STEPTRACE_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2, sort_keys=True)
        return result
    finally:
        with contextlib.suppress(Exception):
            await engine.stop()


def _time_step_kind(engine, kind: str, B: int, S: int,
                    reps: int = 30) -> float:
    """Median wall time of one jitted step dispatched via _invoke_step
    with garbage-page synthetic arrays (compile included in warmup)."""
    import jax

    a = _step_arrays(engine.table_width, B, S)
    jax.block_until_ready(engine._invoke_step(kind, a, 0))
    times = []
    for i in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(engine._invoke_step(kind, a, i + 1))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# long-context leg: tier-resident context lengths measured for TTFT
# scaling (override with BENCH_LONGCTX="4096,32768"; the smoke test in
# tests/test_bench.py shortens it to stay inside the CI budget)
LONGCTX_CONTEXTS = (4096, 16384, 32768, 65536)


async def _measure_long_context() -> dict:
    """Long-context serving leg (ROADMAP item 3, the packing-prefetch
    scheduler): TTFT vs context length with the prompt's KV resident in
    the HOST TIER, not HBM — the tier-resident re-serve a long-context
    deployment lives on.

    Builds its own tiny-model tiered engine (the leg measures the
    tiering/prefetch machinery, not model compute), seeds the host tier
    with synthesized content-addressed blocks for each prompt, and times
    ``generate()``: TTFT = first-chunk onboard + lookahead promotion
    racing the chunked-prefill cursor (adopted blocks skip compute) + the
    final chunk. Records ``ttft_vs_context`` and ``prefetch_hit_rate``;
    TTFT growing SUB-linearly vs the context growth is the acceptance
    signal (``sublinear``), and the scatter-dispatch tap per point shows
    promotion landed in bounded windows, not one admission stall."""
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.transfer import BlockPayload
    from dynamo_tpu.kvbm import TieredEngine, TieredKvConfig
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.tokens import compute_block_hash_for_seq

    raw = os.environ.get("BENCH_LONGCTX")
    contexts = ([int(x) for x in raw.split(",") if x.strip()]
                if raw else list(LONGCTX_CONTEXTS))
    page = 4
    max_ctx = contexts[-1] + 128
    cfg = ModelConfig.tiny(dtype="float32",
                           max_position_embeddings=max_ctx)
    eng = JaxEngine.random_init(cfg, JaxEngineConfig(
        num_pages=max_ctx // page + 512, page_size=page, max_num_seqs=2,
        max_prefill_chunk=512, max_context=max_ctx,
        min_prefill_bucket=512))
    tiered = TieredEngine(eng, TieredKvConfig(host_budget_bytes=1 << 30))
    if tiered.prefetch is None:
        raise RuntimeError("prefetch disabled (DYN_KV_PREFETCH_DEPTH=0); "
                           "long-context leg needs it")
    rng = np.random.default_rng(7)
    ref = eng.pages[0] if isinstance(eng.pages, list) else eng.pages
    L = (len(eng.pages) if isinstance(eng.pages, list)
         else eng.pages.shape[0])
    # one shared zero block: the leg measures promotion bandwidth and
    # scheduling, not KV content (decode over it is still a real step)
    blk = np.zeros((L,) + tuple(ref.shape[-4:]), np.dtype(ref.dtype))

    def req(toks, rid):
        return PreprocessedRequest(
            token_ids=toks, request_id=rid,
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))

    points = []
    try:
        # compile the prefill/decode shapes outside the timed points
        warm = rng.integers(1, cfg.vocab_size, size=600).tolist()
        async for _ in tiered.generate(req(warm, "lc-warm")):
            pass
        for ctx in contexts:
            toks = rng.integers(1, cfg.vocab_size, size=ctx).tolist()
            hashes = compute_block_hash_for_seq(toks, page)
            parent = None
            for h in hashes:
                tiered.host.put(BlockPayload(
                    block_hash=h, local_hash=h, parent_hash=parent,
                    data=blk))
                parent = h
            s0 = tiered.kvbm_stats()
            d0 = eng.page_scatter_dispatches
            t0 = time.perf_counter()
            first = None
            async for out in tiered.generate(req(toks, f"lc{ctx}")):
                if out.token_ids and first is None:
                    first = time.perf_counter() - t0
            s1 = tiered.kvbm_stats()
            hits = s1["kvbm_prefetch_hits"] - s0["kvbm_prefetch_hits"]
            late = s1["kvbm_prefetch_late"] - s0["kvbm_prefetch_late"]
            point = {
                "tokens": ctx,
                "ttft_s": round(first, 3) if first is not None else None,
                "prefetch_hits": int(hits),
                "prefetch_late": int(late),
                "adopted": int(s1["kvbm_prefetch_adopted_blocks"]
                               - s0["kvbm_prefetch_adopted_blocks"]),
                "scatter_dispatches": eng.page_scatter_dispatches - d0,
            }
            points.append(point)
            _note("longctx_point", **point)
    finally:
        await tiered.stop()

    stats = tiered.kvbm_stats()
    promoted = stats["kvbm_prefetch_hits"] + stats["kvbm_prefetch_late"]
    hit_rate = (stats["kvbm_prefetch_hits"] / promoted) if promoted else 0.0
    timed = [p for p in points if p["ttft_s"]]
    sub = None
    if len(timed) >= 2 and timed[0]["ttft_s"] > 0:
        ttft_ratio = timed[-1]["ttft_s"] / timed[0]["ttft_s"]
        ctx_ratio = timed[-1]["tokens"] / timed[0]["tokens"]
        # <1.0 means TTFT grew slower than the context did
        sub = round(ttft_ratio / ctx_ratio, 3)
    return {
        "tier": "host",
        "page_size": page,
        "ttft_vs_context": points,
        "prefetch_hit_rate": round(hit_rate, 3),
        # ttft-growth / context-growth; sublinear iff < 1.0
        "ttft_scaling": sub,
        "sublinear": bool(sub is not None and sub < 1.0),
    }


SHARED_PREFIX_REQS = 12       # requests in the shared-prefix cohort trace
SHARED_PREFIX_GROUPS = 3      # distinct shared prefixes ("system prompts")
SHARED_PREFIX_BLOCKS = 96     # blocks of shared prefix per group
SHARED_PREFIX_TAIL_CAP = 8    # cap on per-request unique tail blocks


async def _measure_shared_prefix() -> dict:
    """Fleet-wide KV reuse leg (ISSUE 20): a HOT worker publishes its
    prefix snapshot into the coordinator-backed global index; a COLD
    worker serving the same shared-prefix cohort trace onboards each
    prompt's KV over G4 peer pulls instead of recomputing it.

    Three arms over the SAME trace (trace_gen cohorts, one shared-prefix
    cohort): the hot worker re-serving with its cache warm (the TTFT
    floor), a cold worker with the index + peer fetch on, and a cold
    worker with neither (the recompute baseline). TTFT is compared on
    FIRST-TOUCH requests — the first request of each prefix group, where
    the cold worker has nothing local and the pull-vs-recompute choice
    actually shows (later same-group requests are warm-by-locality in
    every arm). Acceptance: cold-with-index first-touch p50 lands within
    1.5x the hot p50 and beats the index-off baseline; the
    peer-onboarded vs recomputed byte split and the ``admission_onboard``
    kv_transfer spans land in the result JSON."""
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.kv_router.global_index import (
        GlobalPrefixIndexReader, GlobalPrefixPublisher)
    from dynamo_tpu.kvbm import TieredEngine, TieredKvConfig
    from dynamo_tpu.kvbm.manager import serve_tiered_kv_export
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.runtime.runtime import DistributedRuntime
    from dynamo_tpu.trace_gen import CohortSpec, TraceConfig, generate
    from dynamo_tpu.utils.tracing import get_tracer
    from dynamo_tpu.worker.disagg import KV_EXPORT_ENDPOINT

    n_reqs = int(os.environ.get("BENCH_SHARED_REQS", SHARED_PREFIX_REQS))
    groups = int(os.environ.get("BENCH_SHARED_GROUPS",
                                SHARED_PREFIX_GROUPS))
    shared = int(os.environ.get("BENCH_SHARED_BLOCKS",
                                SHARED_PREFIX_BLOCKS))
    page = 4
    tail_cap = SHARED_PREFIX_TAIL_CAP
    max_ctx = (shared + tail_cap) * page + 32
    # a step up from ModelConfig.tiny()'s defaults: recompute must cost
    # real prefill FLOPs or the pull-vs-recompute comparison measures
    # only dispatch overhead (still runs in ms on CPU). Compute scales
    # through hidden/heads/mlp while kv_heads x head_dim stays small, so
    # the KV bytes a pull moves stay at a realistic compute:bytes ratio
    cfg = ModelConfig.tiny(dtype="float32", max_position_embeddings=max_ctx,
                           num_layers=8, hidden_size=512, num_heads=16,
                           intermediate_size=1536, head_dim=32)

    # the shared-prefix cohort trace: every request opens with its
    # group's common prefix, then a short unique tail. One cohort per
    # group (each owning a single prefix) so every group really appears
    # in a short trace; abstract block ids map deterministically to token
    # blocks so same-group requests share REAL token prefixes (and
    # therefore chain hashes) across all arms.
    trace = list(generate(TraceConfig(
        num_requests=n_reqs, block_size=page, seed=11,
        cohorts=[CohortSpec(f"shared{g}", weight=1.0, num_groups=1,
                            shared_blocks=shared, unique_blocks_mean=3.0,
                            output_len_mean=4.0)
                 for g in range(groups)])))
    rows = []
    seen_prefix = set()
    for r in trace:
        ids = r["hash_ids"][:shared + tail_cap]
        rows.append({
            "toks": [1 + (h * 1_000_003 + j * 7_919) % (cfg.vocab_size - 1)
                     for h in ids for j in range(page)],
            "first_touch": ids[0] not in seen_prefix,
        })
        seen_prefix.add(ids[0])
    distinct = len({h for r in trace
                    for h in r["hash_ids"][:shared + tail_cap]})

    def build():
        eng = JaxEngine.random_init(cfg, JaxEngineConfig(
            num_pages=distinct + 3 * (shared + tail_cap) + 64,
            page_size=page,
            max_num_seqs=2, max_prefill_chunk=128, max_context=max_ctx,
            min_prefill_bucket=128))
        return TieredEngine(eng, TieredKvConfig(
            host_budget_bytes=1 << 30)), eng

    def req(toks, rid):
        return PreprocessedRequest(
            token_ids=list(toks), request_id=rid,
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))

    async def ttft_pass(engine, tag):
        out = []
        for i, row in enumerate(rows):
            t0 = time.perf_counter()
            first = None
            async for o in engine.generate(req(row["toks"], f"{tag}{i}")):
                if o.token_ids and first is None:
                    first = time.perf_counter() - t0
            out.append({"ttft_s": first, "first_touch": row["first_touch"]})
        return out

    med = lambda xs: (sorted(xs)[len(xs) // 2] if xs else None)  # noqa: E731
    rng = np.random.default_rng(3)
    # compile warmer: full-length prompt of tokens OUTSIDE the trace's
    # space, so every arm pays its prefill/decode compiles off the clock
    # without touching the measured prefixes. The PULL warmer is a second
    # such prompt, warmed on the hot worker and then generated once by
    # the cold index-on worker after peer fetch is enabled: the one-time
    # RPC connect + inject-scatter compiles land off the clock, exactly
    # like the prefill/decode compile warmers
    warm_toks = rng.integers(1, cfg.vocab_size,
                             size=(shared + tail_cap) * page).tolist()
    # TWO pull-warm sequences: the pull path (gather jit on the exporter,
    # inject-scatter jit on the puller, stream plumbing) needs two reps
    # per padded width before it is steady (measured: 537ms/73ms/5.7ms
    # for identical consecutive pulls)
    warm_pulls = [rng.integers(1, cfg.vocab_size,
                               size=(shared + tail_cap) * page).tolist()
                  for _ in range(2)]

    coord = await Coordinator(port=0).start()
    drts = []
    tiereds = []
    client = pub = reader = None
    # transfer tuning a shared-prefix deployment would run with (see
    # docs/deployment.md "KV-transfer tuning"): wider frames + scatter
    # windows cut per-pull dispatch count — no decode traffic competes
    # for the exclusive window in this leg. Only defaults: an explicit
    # env setting wins, and the keys are restored after the leg.
    tuned = {"DYN_KV_FRAME_BLOCKS": "32", "DYN_KV_SCATTER_BLOCKS": "32"}
    tuned = {k: v for k, v in tuned.items() if k not in os.environ}
    os.environ.update(tuned)
    try:
        # hot worker: serves + warms the trace, publishes its snapshot
        a_drt = await DistributedRuntime.create(coordinator=coord.address)
        drts.append(a_drt)
        a_tiered, a_eng = build()
        tiereds.append(a_tiered)
        a_lease = await a_drt.primary_lease()
        pub = GlobalPrefixPublisher(a_drt.kv_store(), a_lease.lease_id)
        await pub.start()
        a_eng.kv_event_cb = \
            lambda evs: [pub.apply_event(ev) for ev in evs]
        ep_a = (a_drt.namespace("ns").component("tpu")
                .endpoint(KV_EXPORT_ENDPOINT))
        await ep_a.serve(serve_tiered_kv_export(a_tiered))
        async for _ in a_tiered.generate(req(warm_toks, "sp-warm-a")):
            pass
        for wi, toks in enumerate(warm_pulls):
            async for _ in a_tiered.generate(req(toks, f"sp-pw-a{wi}")):
                pass
        for i, row in enumerate(rows):  # the fleet's warm traffic
            async for _ in a_tiered.generate(req(row["toks"], f"spw{i}")):
                pass
        hot = await ttft_pass(a_tiered, "sph")
        await pub.flush()
        _note("shared_prefix_hot", p50=med(
            [r["ttft_s"] for r in hot if r["ttft_s"]]))

        # cold worker, index ON: G4 peer fetch + global-index holder order
        b_drt = await DistributedRuntime.create(coordinator=coord.address)
        drts.append(b_drt)
        b_tiered, b_eng = build()
        tiereds.append(b_tiered)
        ep_b = (b_drt.namespace("ns").component("tpu")
                .endpoint(KV_EXPORT_ENDPOINT))
        await ep_b.serve(serve_tiered_kv_export(b_tiered))
        b_lease = await b_drt.primary_lease()
        # compile warm BEFORE peer fetch is on (a blind pull for the
        # warmer's unheld blocks would pollute the onboard split)
        async for _ in b_tiered.generate(req(warm_toks, "sp-warm-b")):
            pass
        client = await ep_b.client()
        await client.wait_for_instances(2, timeout=10)
        b_tiered.enable_peer_fetch(client,
                                   self_instance_id=b_lease.lease_id)
        reader = GlobalPrefixIndexReader(b_drt.kv_store())
        await reader.start()
        await reader.refresh()
        b_tiered.enable_global_index(reader)
        # pull warmer (see above): two rounds of a ladder of off-the-clock
        # peer pulls whose deltas (1, 2, 4, 8, 16 blocks) cover every
        # power-of-two padded width the gather/scatter jits bucket to —
        # a timed pull of ANY size then reuses a steady program on both
        # sides (one round is not enough: see warm_pulls above)
        for wi, toks in enumerate(warm_pulls):
            n_warm = len(toks) // page
            ladder = [c for c in (1, 3, 7, 15, 31) if c < n_warm] + [n_warm]
            for li, c in enumerate(ladder):
                async for _ in b_tiered.generate(
                        req(toks[:c * page], f"sp-pw-b{wi}-{li}")):
                    pass
        base = {k: getattr(b_tiered, k) for k in (
            "onboard_peer_blocks", "onboard_peer_bytes",
            "onboard_recompute_blocks", "onboard_recompute_bytes")}
        tracer = get_tracer()
        ring_before = set(tracer._ring.keys())
        cold_on = await ttft_pass(b_tiered, "spc")
        onboard_spans = sum(
            1 for tid, t in tracer._ring.items() if tid not in ring_before
            for s in t.get("spans", [])
            if s.get("name") == "kv_transfer"
            and (s.get("attrs") or {}).get("path") == "admission_onboard")
        _note("shared_prefix_cold_on",
              peer_blocks=b_tiered.onboard_peer_blocks,
              recompute_blocks=b_tiered.onboard_recompute_blocks)

        # cold worker, index OFF: same trace, pure local recompute
        c_tiered, _c_eng = build()
        tiereds.append(c_tiered)
        async for _ in c_tiered.generate(req(warm_toks, "sp-warm-c")):
            pass
        cold_off = await ttft_pass(c_tiered, "spo")

        hot_p50 = med([r["ttft_s"] for r in hot if r["ttft_s"]])
        on_ft = [r["ttft_s"] for r in cold_on
                 if r["first_touch"] and r["ttft_s"]]
        off_ft = [r["ttft_s"] for r in cold_off
                  if r["first_touch"] and r["ttft_s"]]
        on_p50, off_p50 = med(on_ft), med(off_ft)
        result = {
            "requests": n_reqs,
            "groups": groups,
            "shared_blocks": shared,
            "page_size": page,
            "first_touch": len(on_ft),
            "hot_ttft_p50_s": round(hot_p50, 4),
            "cold_on_ttft_p50_s": round(on_p50, 4),
            "cold_off_ttft_p50_s": round(off_p50, 4),
            "cold_on_ttft_all_p50_s": round(med(
                [r["ttft_s"] for r in cold_on if r["ttft_s"]]), 4),
            "cold_off_ttft_all_p50_s": round(med(
                [r["ttft_s"] for r in cold_off if r["ttft_s"]]), 4),
            "cold_vs_hot_p50": round(on_p50 / hot_p50, 3),
            "index_on_vs_off_p50": round(on_p50 / off_p50, 3),
            "peer_onboarded_blocks":
                b_tiered.onboard_peer_blocks - base["onboard_peer_blocks"],
            "peer_onboarded_bytes":
                b_tiered.onboard_peer_bytes - base["onboard_peer_bytes"],
            "recompute_blocks": (b_tiered.onboard_recompute_blocks
                                 - base["onboard_recompute_blocks"]),
            "recompute_bytes": (b_tiered.onboard_recompute_bytes
                                - base["onboard_recompute_bytes"]),
            "index_workers": len(reader.workers()),
            "index_blocks": reader.num_blocks(a_lease.lease_id),
            "onboard_spans": onboard_spans,
            "cold_within_1p5x_hot": bool(on_p50 <= 1.5 * hot_p50),
            "on_beats_off": bool(on_p50 < off_p50),
        }
        _note("shared_prefix", **{k: result[k] for k in (
            "hot_ttft_p50_s", "cold_on_ttft_p50_s", "cold_off_ttft_p50_s",
            "cold_vs_hot_p50", "on_beats_off")})
        out_path = os.environ.get("BENCH_SHARED_PREFIX_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(result, f, indent=2, sort_keys=True)
        return result
    finally:
        for k in tuned:
            os.environ.pop(k, None)
        with contextlib.suppress(Exception):
            if client is not None:
                await client.close()
        for closer in (reader, pub):
            if closer is not None:
                with contextlib.suppress(Exception):
                    await closer.close()
        for t in tiereds:
            with contextlib.suppress(Exception):
                await t.stop()
        for d in drts:
            with contextlib.suppress(Exception):
                await d.close()
        with contextlib.suppress(Exception):
            await coord.stop()


# target bytes per transport measurement: small samples measure framing
# overhead, not bandwidth (round 3: 1 MB samples made a 6 GB/s plane
# read as 0.2) — stream >=128 MB through the real block geometry
TRANSPORT_TARGET_BYTES = 128 * 1024 * 1024
TRANSPORT_REPS = 5


def _bench_frames(engine, target_bytes: int = TRANSPORT_TARGET_BYTES):
    """Synthetic wire frames shaped like this engine's KV blocks (shared by
    the wire/bulk transport measurements so their GB/s are comparable).
    Frame count/width sized so one full fetch moves >=target_bytes
    (the serving geometry: a 3B-model block is ~1.8 MB, so a 64-block prefix
    fetch is ~117 MB — measuring less benchmarks the framing, not the
    plane)."""
    import numpy as np

    ref = engine.pages[0] if isinstance(engine.pages, list) else engine.pages
    L = (len(engine.pages) if isinstance(engine.pages, list)
         else engine.pages.shape[0])
    blk_shape = (L,) + tuple(ref.shape[-4:])  # [L, 2, Hkv, ps, Dh]
    # payload in the CACHE dtype — what a real export ships (the inject
    # half otherwise pays a synthetic dtype conversion no deployment pays)
    page_dtype = np.dtype(ref.dtype)
    blk_bytes = int(np.prod(blk_shape)) * page_dtype.itemsize
    n_frames = 8
    per_frame = max(4, -(-target_bytes // (n_frames * blk_bytes)))
    chunk = np.ones((per_frame,) + blk_shape, page_dtype)
    meta = {"blocks": [[i, i, None] for i in range(per_frame)],
            "dtype": str(chunk.dtype), "block_shape": list(blk_shape)}
    return meta, chunk, n_frames


async def _time_transport(label: str, fetch_once, total_bytes: int) -> float:
    """Warm once, then median of TRANSPORT_REPS timed fetches; returns GB/s.
    ``fetch_once()`` -> bytes got."""
    got = await fetch_once()  # warm (connection setup, first-touch pages)
    assert got == total_bytes, (got, total_bytes)
    times = []
    for _ in range(TRANSPORT_REPS):
        t0 = time.perf_counter()
        got = await fetch_once()
        times.append(time.perf_counter() - t0)
        assert got == total_bytes, (got, total_bytes)
    dt = statistics.median(times)
    gbps = total_bytes / dt / 1e9
    print(f"bench: kv {label} {total_bytes / 1e6:.0f} MB in {dt * 1e3:.0f}ms"
          f" (median of {TRANSPORT_REPS}) -> {gbps:.2f} GB/s",
          file=sys.stderr, flush=True)
    return round(gbps, 2)


async def _measure_kv_bulk(engine) -> float:
    """Bulk data plane bandwidth (GB/s): synthetic block frames through
    runtime/bulk.py's raw-socket plane (unix-first — the transport disagg
    actually uses between colocated workers)."""
    from dynamo_tpu.runtime.bulk import BulkServer, bulk_fetch, release_buffer

    meta, chunk, n_frames = _bench_frames(engine)

    def handler(payload):
        for _ in range(n_frames):
            yield meta, chunk

    server = BulkServer(
        unix_path=f"/tmp/dynamo_bench_bulk_{os.getpid()}.sock").start()
    server.register("kv", handler)

    def fetch_sync() -> int:
        got = 0

        def on_frame(_m, raw):
            nonlocal got
            got += len(raw)
            release_buffer(raw)  # steady state: consumer returns buffers

        bulk_fetch(server.address, "kv", {}, on_frame=on_frame)
        return got

    async def fetch_once() -> int:
        return await asyncio.to_thread(fetch_sync)

    try:
        return await _time_transport("bulk", fetch_once,
                                     n_frames * chunk.nbytes)
    finally:
        server.stop()


async def _measure_kv_wire(engine) -> float:
    """KV-block wire bandwidth (GB/s): the same frames as batched two-part
    frames through a REAL RpcServer/RpcConnection loopback — the RPC
    fallback path (the device gather is timed separately by
    _measure_kv_inject)."""
    from dynamo_tpu.runtime.codec import Raw
    from dynamo_tpu.runtime.rpc import RpcConnection, RpcServer

    meta, chunk, n_frames = _bench_frames(engine)

    async def handler(payload, ctx):
        for _ in range(n_frames):
            yield Raw(meta, chunk)

    server = await RpcServer().start()
    server.register("kv_wire_bench", handler)
    client = await RpcConnection(server.address).connect()

    async def fetch_once() -> int:
        from dynamo_tpu.runtime.codec import release_buffer

        got = 0
        stream = await client.request("kv_wire_bench", {})
        async for frame in stream:
            got += len(frame["_raw"])
            release_buffer(frame["_raw"])  # steady state: buffers recycle
        return got

    try:
        return await _time_transport("wire", fetch_once,
                                     n_frames * chunk.nbytes)
    finally:
        await client.close()
        await server.stop()


def _measure_kv_inject(engine) -> float:
    """KV-block injection bandwidth (GB/s) via the ICI-path donated scatter
    (gathered device array -> jitted in-place scatter, no host bounce).
    64 serving-geometry blocks (~117 MB on the 3B config), median of 5."""
    import jax

    n_blk = 1
    while n_blk * 2 <= min(64, engine.allocator.num_pages - 2):
        n_blk *= 2
    ids = list(range(1, n_blk + 1))
    data = engine.dispatch_gather_pages(ids)
    jax.block_until_ready(data)
    engine.scatter_pages_device(ids, data)  # compile warmup
    ref = engine.pages[0] if isinstance(engine.pages, list) else engine.pages
    jax.block_until_ready(ref)
    times = []
    for _ in range(TRANSPORT_REPS):
        t0 = time.perf_counter()
        engine.scatter_pages_device(ids, data)
        ref = (engine.pages[0] if isinstance(engine.pages, list)
               else engine.pages)
        jax.block_until_ready(ref)
        times.append(time.perf_counter() - t0)
    dt = statistics.median(times)
    nbytes = data.size * data.dtype.itemsize
    gbps = nbytes / dt / 1e9
    print(f"bench: kv inject {n_blk} blocks ({nbytes / 1e6:.1f} MB) "
          f"in {dt * 1e3:.1f}ms (median of {TRANSPORT_REPS}) "
          f"-> {gbps:.1f} GB/s", file=sys.stderr, flush=True)
    return round(gbps, 2)


def _measure_kv_direct(engine):
    """Device-direct transfer-plane bandwidth (GB/s): the jax transfer
    server loopback — gathered device pages offered and pulled back into
    the same client with NO host numpy in the KV path (the NIXL RDMA
    role, ``engine/transfer.DeviceTransferPlane``). Returns None when
    the backend's client does not support the transfer server (recorded,
    not fatal)."""
    import jax

    try:
        from dynamo_tpu.engine.transfer import DeviceTransferPlane

        n_blk = 1
        while n_blk * 2 <= min(64, engine.allocator.num_pages - 2):
            n_blk *= 2
        ids = list(range(1, n_blk + 1))
        data = engine.dispatch_gather_pages(ids)
        jax.block_until_ready(data)
        plane = DeviceTransferPlane()  # the ladder's production plane
        times = []
        for rep in range(TRANSPORT_REPS + 1):  # first rep warms the conn
            t0 = time.perf_counter()
            offer = plane.offer_array(data)
            pulled = plane.pull(offer)
            plane.ack(offer["uuid"])
            del pulled
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times[1:])
        nbytes = data.size * data.dtype.itemsize
        gbps = nbytes / dt / 1e9
        print(f"bench: kv direct {n_blk} blocks ({nbytes / 1e6:.1f} MB) "
              f"in {dt * 1e3:.1f}ms (median of {TRANSPORT_REPS}) "
              f"-> {gbps:.2f} GB/s", file=sys.stderr, flush=True)
        return round(gbps, 2)
    except Exception as e:  # noqa: BLE001 — optional plane, record absence
        print(f"bench: kv direct plane unavailable: {e}",
              file=sys.stderr, flush=True)
        return None


async def _measure_kv_bulk_inject(engine):
    """END-TO-END disagg KV handoff bandwidth (GB/s): the prefill->decode
    path a real disagg deployment takes — bulk-socket fetch of
    serving-geometry LAYER-MAJOR frames driven through the REAL staged
    inject pipeline (``engine/transfer.InjectPipeline``): stage into the
    preallocated host buffer, async upload onto the cache sharding, and
    batched donated scatters into the live page table, overlapped with
    the remaining wire transfer. Returns ``(gbps, phases_ms)`` where
    ``phases_ms`` localizes the time to recv/stage/upload/scatter (last
    rep) so a BENCH_r*.json regression points at a phase, not a number."""
    import jax

    from dynamo_tpu.engine.transfer import InjectPipeline, pump_bulk_frames
    from dynamo_tpu.runtime.bulk import BulkServer

    # scatter targets: a fixed window of real page ids, reused per commit
    # (the commit override below bypasses the allocator — the bench reuses
    # the same synthetic hashes every rep). On the tiny smoke config (few
    # pages, tiny blocks) a 128 MB stream would mean thousands of windowed
    # commits per rep — scale the payload down there; the 3B tiers keep
    # the full-size stream.
    n_ids = min(64, engine.allocator.num_pages - 2)
    target = (TRANSPORT_TARGET_BYTES if n_ids >= 64
              else 16 * 1024 * 1024)
    meta, chunk, n_frames = _bench_frames(engine, target)
    per_frame = chunk.shape[0]
    n_ids = min(per_frame, n_ids)
    ids = list(range(1, n_ids + 1))
    # layer-major wire frames (schema v3): [L, per_frame, 2, Hkv, ps, Dh]
    import numpy as np
    chunk = np.ascontiguousarray(np.moveaxis(chunk, 0, 1))
    meta = dict(meta)
    meta["layout"] = "layer"
    # commit window sized in BYTES, not blocks: the serving tiers have
    # ~MB blocks (64-block windows land in the tens of MB), but the tiny
    # smoke config has ~KB blocks — a block-count window there would mean
    # thousands of per-window upload/commit round trips per rep, and the
    # e2e number would measure event-loop overhead instead of the pipeline
    blk_bytes = chunk.nbytes // per_frame
    win_blocks = max(n_ids, min(per_frame,
                                (32 * 1024 * 1024) // blk_bytes))

    server = BulkServer(
        unix_path=f"/tmp/dynamo_bench_e2e_{os.getpid()}.sock").start()
    server.register("kv", lambda payload: (
        (meta, chunk) for _ in range(n_frames)))

    # fixed-id commit targets, CYCLED over the real page-id range (the
    # tiny tier streams far more blocks than the cache has pages): every
    # received block pays the scatter in ONE batched dispatch per window,
    # without consuming the page pool on a synthetic stream
    ids_cycle = np.asarray(
        (ids * ((win_blocks + n_ids - 1) // n_ids))[:win_blocks], np.int32)

    def commit(eng, metas, data):
        w = ids_cycle[:len(metas)]
        if isinstance(data, jax.Array):
            eng.scatter_pages_device(w, data)
        else:
            eng.scatter_pages_host(w, data)
        return len(metas)

    phases = {}

    async def fetch_once() -> int:
        got = 0
        pipe = InjectPipeline(engine, window=win_blocks, commit=commit)

        def on_meta(_m, nbytes):
            nonlocal got
            got += nbytes

        # the REAL stream-and-stage machinery disagg uses (backpressure,
        # abort, zero-copy buffer ownership all included)
        recv_s = await pump_bulk_frames(pipe, server.address, "kv", {},
                                        "", 60.0, on_meta)
        await pipe.finish()
        # commits dispatch async; the rep time includes the device
        # actually finishing the writes
        pages = (engine.pages[0] if isinstance(engine.pages, list)
                 else engine.pages)
        jax.block_until_ready(pages)
        phases.clear()
        phases.update(pipe.timings)
        phases["recv_s"] = recv_s
        return got

    try:
        gbps = await _time_transport("e2e (bulk+inject)", fetch_once,
                                     n_frames * chunk.nbytes)
        phases_ms = {k[:-2]: round(v * 1e3, 1)
                     for k, v in sorted(phases.items())}
        print(f"bench: kv e2e phases (last rep, ms): "
              f"recv {phases_ms.get('recv', 0)} "
              f"stage {phases_ms.get('stage', 0)} "
              f"upload {phases_ms.get('upload', 0)} "
              f"scatter {phases_ms.get('scatter', 0)}",
              file=sys.stderr, flush=True)
        return gbps, phases_ms
    finally:
        server.stop()


async def _leg_engine(args, result: dict) -> None:
    """The closed-batch leg on one engine: build -> prime -> measure ->
    same-run fused-vs-per-step and mixed-vs-legacy A/Bs -> KV transport
    planes. ``vs_baseline`` is steady decode tok/s over the HBM roofline
    for this model and batch (each decode step streams the parameters
    plus the batch's live KV context); it exists only at full size."""
    import numpy as np

    t0 = time.perf_counter()
    engine, cfg, geometry = _build_engine(args.tiny, args.attn_impl)
    seqs, prompt, gen, pfs = geometry
    _note("engine_built", tiny=args.tiny, attn_impl=engine.attn_impl,
          s=round(time.perf_counter() - t0, 1))

    _prime_programs(engine, seqs, prompt, pfs)

    try:
        m = await _measure_engine(engine, cfg, geometry, "main")
        # fused-vs-per-step decode A/B on the SAME engine (decode/chained
        # programs are already primed, so the per-step leg pays no
        # compile)
        m_ps = None
        if getattr(engine, "supports_multistep", False):
            ms_saved = engine.multistep
            engine.multistep = 1   # supports_multistep -> False
            try:
                m_ps = await _measure_engine(engine, cfg, geometry,
                                             "perstep")
            finally:
                engine.multistep = ms_saved
        # continuous-arrival mixed-batch leg: Poisson onboarding with a
        # same-run mixed-vs-legacy A/B (the regime the steady-state
        # measurement cannot see)
        mixed_arrivals = {
            "jax": await _measure_mixed_arrivals(engine, cfg.vocab_size)}
        # mocker sub-leg: the mocker's dispatch-cost model exposes the
        # scheduling-policy effect as counts on any host
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
        mock = MockerEngine(MockEngineArgs(
            max_prefill_chunk=64, max_prefill_seqs=4, max_num_seqs=8,
            num_pages=1024, page_size=16))
        try:
            mixed_arrivals["mocker"] = await _measure_mixed_arrivals(
                mock, 32000)
        finally:
            await mock.stop()
        # transport measurements, serialized with the step loop per the
        # engine.pages contract
        kv_gbps = await engine.run_exclusive(_measure_kv_inject, engine)
        kv_wire_gbps = await _measure_kv_wire(engine)
        kv_bulk_gbps = await _measure_kv_bulk(engine)
        kv_e2e_gbps, kv_e2e_phases = await _measure_kv_bulk_inject(engine)
        kv_direct_gbps = await asyncio.to_thread(_measure_kv_direct, engine)
        param_bytes = tree_bytes(engine.params)
    finally:
        await engine.stop()

    vs_baseline = None
    if not args.tiny:
        kv_per_tok = (2 * cfg.num_kv_heads * cfg.head_dim * cfg.num_layers
                      * np.dtype(cfg.dtype).itemsize)
        step_bytes = param_bytes + seqs * (prompt + gen / 2) * kv_per_tok
        roofline_tok_s = detect_bandwidth() * 1e9 / step_bytes * seqs
        vs_baseline = round(m["tok_per_s"] / roofline_tok_s, 4)

    result.update({
        "metric": ("decode_throughput_tiny" if args.tiny
                   else f"decode_throughput_llama3b_bs{seqs}"),
        "value": round(m["tok_per_s"], 1),
        "unit": "tokens/sec",
        "vs_baseline": vs_baseline,
        "attn_impl": engine.attn_impl,
        "param_bytes": param_bytes,
        "kv_inject_gbps": kv_gbps,
        "kv_wire_gbps": kv_wire_gbps,
        "kv_bulk_gbps": kv_bulk_gbps,
        "kv_e2e_gbps": kv_e2e_gbps,
        # per-phase ms (last rep): localizes an e2e regression to the
        # recv/stage/upload/scatter leg without rerunning anything
        "kv_e2e_phase_ms": kv_e2e_phases,
        "kv_direct_gbps": kv_direct_gbps,
        "prefill_tok_s": round(m["prefill_tok_s"], 1),
        "ttft_p50_s": round(m["ttft_p50"], 3),
        "warmup_s": round(m["warmup_s"], 1),
        # decode dispatch fusion: the configured width and the measured
        # dispatches-per-token of the main (fused) run (~1/width when
        # fusion engages; 1.0 when everything fell back)
        "decode_multistep": int(getattr(engine, "multistep", 1)),
        "decode_dispatches_per_token": round(
            m["decode_dispatches"] / max(1, m["total_generated"]), 4),
        "mixed_arrivals": mixed_arrivals,
    })
    if m_ps is not None:
        result["decode_ab"] = {
            "fused_tok_s": round(m["tok_per_s"], 1),
            "perstep_tok_s": round(m_ps["tok_per_s"], 1),
            "fused_speedup": (round(m["tok_per_s"] / m_ps["tok_per_s"], 3)
                              if m_ps["tok_per_s"] > 0 else None),
            "perstep_dispatches_per_token": round(
                m_ps["decode_dispatches"]
                / max(1, m_ps["total_generated"]), 4),
            "perstep_ttft_p50_s": round(m_ps["ttft_p50"], 3),
        }


async def _measure_variant(args, label: str, **build_kw):
    """Build one more engine at the run's size (another attn_impl, int8
    weights), prime it, and take the closed-batch measurement."""
    engine, cfg, geo = _build_engine(args.tiny, **build_kw)
    try:
        param_bytes = tree_bytes(engine.params)
        _prime_programs(engine, geo[0], geo[1], geo[3], label=label)
        m = await _measure_engine(engine, cfg, geo, label)
    finally:
        await engine.stop()
    return m, param_bytes, engine.attn_impl


async def _leg_quant(args, result: dict) -> None:
    """int8 W8A8-dynamic weights (ops/quant.py): decode is bound by the
    parameter stream, so halving it is the lever this leg prices."""
    m, q_bytes, _impl = await _measure_variant(
        args, "quant", attn_impl=args.attn_impl, quantize="int8")
    result["quant"] = {"mode": "int8",
                       "param_bytes": q_bytes,
                       "decode_tok_s": round(m["tok_per_s"], 1),
                       "prefill_tok_s": round(m["prefill_tok_s"], 1),
                       "ttft_p50_s": round(m["ttft_p50"], 3)}


async def _leg_spec(args, result: dict) -> None:
    """Time the [B, K+1] verify step against the [B, 1] decode step
    DIRECTLY (synthetic arrays, no scheduler). A random-weight model
    accepts ~nothing, so end-to-end spec tok/s would measure the model,
    not the machinery; the step-time ratio gives breakeven acceptance
    (spec wins when 1 + E[accepted] > t_verify/t_decode) and the ceiling
    at full acceptance."""
    K = 4
    engine, _cfg, geo = _build_engine(args.tiny, args.attn_impl,
                                      spec_tokens=K)
    try:
        t_dec = _time_step_kind(engine, "step", geo[0], 1)
        t_ver = _time_step_kind(engine, "spec", geo[0], K + 1)
    finally:
        await engine.stop()
    result["spec"] = {
        "k": K,
        "decode_step_ms": round(t_dec * 1e3, 2),
        "verify_step_ms": round(t_ver * 1e3, 2),
        "step_ratio": round(t_ver / t_dec, 3),
        "breakeven_acceptance": round(
            max(0.0, (t_ver / t_dec - 1.0)) / K, 3),
        "speedup_at_full_acceptance": round((1 + K) * t_dec / t_ver, 2),
    }


def _result_leg(key: str, fn):
    async def leg(args, result: dict) -> None:
        result[key] = await fn()
    return leg


# name -> coroutine(args, result) that adds its keys to ``result``. The
# robustness legs run on mockers or tiny engines whatever the size flag
# says; they report counts and this host's wall-clock, not device metrics.
LEGS = {
    "engine": _leg_engine,
    "longctx": _result_leg("longctx", _measure_long_context),
    "mesh_sharded": _result_leg("mesh_sharded", _measure_mesh_sharded),
    "constrained_decode": _result_leg(
        "constrained_decode", _measure_constrained_decode),
    "drain": _result_leg("drain", _measure_drain),
    "coord_failover": _result_leg("coord_failover",
                                  _measure_coord_failover),
    "fleet": _result_leg("fleet", _measure_fleet),
    "routing": _result_leg("routing", _measure_routing),
    "steptrace": _result_leg("steptrace", _measure_steptrace),
    "shared_prefix": _result_leg("shared_prefix",
                                 _measure_shared_prefix),
    "quant": _leg_quant,
    "spec": _leg_spec,
}
# the two legs that build further full-size engines run only when named
DEFAULT_LEGS = [leg for leg in LEGS if leg not in ("quant", "spec")]


async def run_legs(args) -> dict:
    result = {**device_info(), "tiny": args.tiny, "legs": args.legs}
    for name in args.legs:
        _note("leg", name=name)
        await LEGS[name](args, result)
        # the object so far: a later leg that dies leaves these lines
        print(json.dumps(result), flush=True)
    return result


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--legs", default=",".join(DEFAULT_LEGS),
                   help="comma-separated legs to run, in order "
                        f"(all: {','.join(LEGS)})")
    p.add_argument("--tiny", action="store_true",
                   help="toy model at a toy geometry on whatever platform "
                        "JAX_PLATFORMS names (how the tests run the legs "
                        "on the CPU); without it the run needs a TPU")
    p.add_argument("--attn-impl", default="auto",
                   help="engine attn_impl (auto/pallas/scan)")
    args = p.parse_args(argv)
    args.legs = [leg for leg in args.legs.split(",") if leg]
    unknown = [leg for leg in args.legs if leg not in LEGS]
    if unknown:
        p.error(f"unknown legs {unknown}; choose from {list(LEGS)}")
    return args


def main() -> None:
    args = _parse_args()
    from dynamo_tpu.utils.platform import (
        enable_compilation_cache, pin_platform)

    enable_compilation_cache(pin_platform())
    _note("device", **device_info())
    asyncio.run(run_legs(args))


if __name__ == "__main__":
    main()
