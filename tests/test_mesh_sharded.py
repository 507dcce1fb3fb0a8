"""Mesh-sharded fast path: fused multistep + mixed dispatch on
tensor-parallel engines, and the shard-aware KV handoff (wire v5).

Everything here runs on the forced multi-device CPU mesh (conftest's
``--xla_force_host_platform_device_count=8``) — the same GSPMD
partitioning paths XLA uses on a real TPU slice. The contracts pinned:

- ``supports_multistep`` no longer gates off when ``cfg.mesh`` is set:
  fused blocks dispatch on a tp mesh with BIT-IDENTICAL tokens to the
  per-step mesh path and the single-device engine (greedy AND
  fixed-seed), and ``multistep_fallback_total`` records NO ``mesh``
  reason (the satellite regression guard). Multi-host lockstep
  (``step_tap``) remains a real fallback.
- Mixed dispatch + fused blocks coexist on a sharded engine under
  staggered arrivals (the PR 9 gate-lift, now mesh-side).
- The disagg KV handoff between two sharded engines negotiates per-shard
  wire frames: each shard slice streams to its destination shard's
  device, numerics survive the roundtrip, and v4-or-mismatched pullers
  fall back to merged frames.
"""

import asyncio
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel import tp_sharding
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

pytestmark = pytest.mark.mesh

ENGINE_KW = dict(num_pages=64, page_size=4, max_num_seqs=4,
                 max_prefill_chunk=16, max_context=160,
                 min_prefill_bucket=4)


def make_req(tokens, rid, max_tokens=24, seed=None, temp=0.0, **sopts):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temp, seed=seed,
                                         **sopts))


async def run_tokens(engine, tokens, rid, **kw):
    out = []
    async for f in engine.generate(make_req(tokens, rid, **kw)):
        assert f.error is None, f.error
        out.extend(f.token_ids)
    return out


def build_tp2(cfg, shard, **over):
    """A tp=2 engine with ``cfg.mesh`` SET (the worker-main shape that
    used to trip the fused-path mesh gate), fresh params per engine so
    donation never aliases across engines."""
    kw = dict(ENGINE_KW)
    kw.update(over)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return JaxEngine(cfg, params, JaxEngineConfig(
        mesh=shard.mesh, shard_params_fn=shard.shard_params,
        shard_pages_fn=shard.shard_pages, **kw))


@pytest.fixture(scope="module")
def tp2():
    """(cfg, ModelSharding) for a 2-way tensor-parallel tiny model on the
    forced CPU mesh — the satellite fixture sharded tier-1 tests hang off."""
    assert len(jax.devices()) >= 2, "conftest forces an 8-device CPU mesh"
    cfg = ModelConfig.tiny()  # Hkv=2, I=128 -> tp=2 divides both
    return cfg, tp_sharding(cfg, 2)


class TestShardedFusedParity:
    """Sharded token-parity suite: mesh fused vs mesh per-step vs
    single-device, greedy and fixed-seed."""

    async def test_greedy_parity_fused_perstep_single(self, tp2):
        cfg, shard = tp2
        prompt = list(range(1, 10))

        single = JaxEngine.random_init(cfg, JaxEngineConfig(**ENGINE_KW))
        try:
            want = await run_tokens(single, prompt, "single")
        finally:
            await single.stop()

        fused = build_tp2(cfg, shard)
        try:
            assert fused.supports_multistep
            assert fused.multistep_unsupported_reason is None
            got_fused = await run_tokens(fused, prompt, "fused")
            assert fused.multistep_blocks > 0, \
                "no fused block dispatched on the mesh engine"
        finally:
            await fused.stop()

        perstep = build_tp2(cfg, shard, decode_multistep=1)
        try:
            got_perstep = await run_tokens(perstep, prompt, "perstep")
            assert perstep.multistep_blocks == 0
        finally:
            await perstep.stop()

        assert got_fused == got_perstep == want

    async def test_seeded_parity_fused_perstep_single(self, tp2):
        cfg, shard = tp2
        prompt = list(range(3, 12))
        kw = dict(seed=1234, temp=0.9, max_tokens=20)

        single = JaxEngine.random_init(cfg, JaxEngineConfig(**ENGINE_KW))
        try:
            want = await run_tokens(single, prompt, "sg", **kw)
        finally:
            await single.stop()
        fused = build_tp2(cfg, shard)
        try:
            got_fused = await run_tokens(fused, prompt, "fs", **kw)
            assert fused.multistep_blocks > 0
        finally:
            await fused.stop()
        perstep = build_tp2(cfg, shard, decode_multistep=1)
        try:
            got_perstep = await run_tokens(perstep, prompt, "ps", **kw)
        finally:
            await perstep.stop()
        assert got_fused == got_perstep == want

    async def test_constrained_parity_fused_perstep_single(self, tp2):
        """Penalties + logit bias ride the fused block ON THE MESH: the
        ring-buffer carry keys stay replicated (no implicit reshard
        changes numerics) and tokens match per-step and single-device
        bit-for-bit, seeded sampling included."""
        cfg, shard = tp2
        prompt = list(range(2, 11))
        kw = dict(seed=77, temp=0.9, max_tokens=20,
                  frequency_penalty=0.6, repetition_penalty=1.3,
                  logit_bias={19: 2.5, 47: -100.0})

        single = JaxEngine.random_init(cfg, JaxEngineConfig(**ENGINE_KW))
        try:
            want = await run_tokens(single, prompt, "cs", **kw)
        finally:
            await single.stop()
        fused = build_tp2(cfg, shard)
        try:
            got_fused = await run_tokens(fused, prompt, "cf", **kw)
            assert fused.multistep_blocks > 0, \
                "constrained row refused the fused path on the mesh"
            fb = dict(fused.scheduler.multistep_fallbacks)
            assert fb.get("penalties", 0) == 0, fb
            assert fb.get("penalty_window", 0) == 0, fb
        finally:
            await fused.stop()
        perstep = build_tp2(cfg, shard, decode_multistep=1)
        try:
            got_perstep = await run_tokens(perstep, prompt, "cp", **kw)
        finally:
            await perstep.stop()
        assert got_fused == got_perstep == want

    async def test_no_mesh_fallback_reason_on_sharded_engine(self, tp2):
        """The satellite regression guard: a sharded engine with fusion
        configured refuses NOTHING for being sharded — the ``mesh``
        reason is gone from the scheduler counters AND from the metric
        family's pre-seeded labels."""
        from dynamo_tpu.worker.metrics import (WorkerMetrics,
                                               engine_dispatch_stats)
        from prometheus_client import CollectorRegistry

        cfg, shard = tp2
        eng = build_tp2(cfg, shard)
        try:
            await run_tokens(eng, list(range(1, 8)), "nf")
            assert eng.multistep_blocks > 0
            assert "mesh" not in eng.scheduler.multistep_fallbacks
            wm = WorkerMetrics(CollectorRegistry())
            wm.engine.attach(lambda: engine_dispatch_stats(eng))
            families = {f.name: f for f in wm.registry.collect()}
            fb = families["dynamo_worker_multistep_fallback"]
            by_reason = {s.labels["reason"]: s.value for s in fb.samples
                         if s.name.endswith("_total")}
            assert "mesh" not in by_reason
            assert by_reason.get("multihost", 0.0) == 0.0
        finally:
            await eng.stop()

    async def test_multihost_step_tap_still_falls_back(self, tp2):
        """step_tap (multi-host lockstep) remains a REAL fallback: the
        block carry is device-resident and cannot be broadcast as host
        arrays."""
        cfg, shard = tp2
        eng = build_tp2(cfg, shard)
        try:
            eng.step_tap = lambda kind, arrays, step: None
            assert not eng.supports_multistep
            assert eng.multistep_unsupported_reason == "multihost"
            await run_tokens(eng, list(range(1, 8)), "mh")
            assert eng.multistep_blocks == 0
            assert eng.scheduler.multistep_fallbacks.get("multihost", 0) > 0
        finally:
            await eng.stop()


class TestShardedMixedDispatch:
    async def test_mixed_and_fused_coexist_under_arrivals(self, tp2):
        """The PR 9 gate-lift applies on the mesh too: a second request
        arriving mid-decode onboards through mixed dispatches while fused
        blocks keep running — no per-step fallback, no mesh reason."""
        cfg, shard = tp2
        eng = build_tp2(cfg, shard, max_num_seqs=2)
        started = asyncio.Event()

        async def leader():
            n = 0
            async for f in eng.generate(
                    make_req(list(range(1, 8)), "lead", max_tokens=32)):
                n += len(f.token_ids)
                if n >= 4:
                    started.set()
            started.set()

        async def follower():
            await started.wait()
            await run_tokens(eng, list(range(21, 40)), "follow",
                             max_tokens=8)

        try:
            await asyncio.gather(leader(), follower())
            assert eng.multistep_blocks > 0
            assert eng.mixed_steps > 0
            assert "mesh" not in eng.scheduler.multistep_fallbacks
        finally:
            await eng.stop()

    async def test_the_block_behind_an_admission_chains_on_the_mesh(
            self, tp2):
        """The hand-over's outputs are replicated like a block's carry, so
        on the mesh too the block behind a mixed step takes its first
        tokens from the step's on-device output, and the streams are those
        of an engine that resolves every mixed step inside its dispatch
        (greedy and seeded; requests queued together with
        ``max_prefill_seqs=1`` are admitted one mixed step apart)."""
        cfg, shard = tp2

        class SyncSteps(JaxEngine):
            supports_step_chain = False

        streams = []
        for cls in (JaxEngine, SyncSteps):
            params = llama.init_params(cfg, jax.random.PRNGKey(0))
            eng = cls(cfg, params, JaxEngineConfig(
                mesh=shard.mesh, shard_params_fn=shard.shard_params,
                shard_pages_fn=shard.shard_pages, max_prefill_seqs=1,
                decode_multistep=4, **ENGINE_KW))
            try:
                streams.append(await asyncio.gather(
                    run_tokens(eng, range(1, 10), "a", max_tokens=20),
                    run_tokens(eng, range(20, 31), "b", max_tokens=9),
                    run_tokens(eng, range(40, 47), "c", max_tokens=12,
                               seed=5, temp=0.8)))
                behind = eng.scheduler.chained_blocks["mixed"]
                assert (behind > 0) == (cls is JaxEngine), behind
            finally:
                await eng.stop()
        assert streams[0] == streams[1]


    async def test_a_run_of_mixed_steps_chains_on_the_mesh(self, tp2):
        """``_fill_impl``'s output is replicated, as the host's upload of
        the token array is: on the mesh too a run's mixed steps are
        enqueued one behind the other, each reading its decode rows'
        tokens from the one in front, and the streams are those of an
        engine that resolves every mixed step inside its dispatch (greedy
        and seeded; a prompt of three chunks with a request waiting behind
        it, one prompt an admission pass)."""
        cfg, shard = tp2

        class SyncSteps(JaxEngine):
            supports_step_chain = False

        streams = []
        for cls in (JaxEngine, SyncSteps):
            params = llama.init_params(cfg, jax.random.PRNGKey(0))
            eng = cls(cfg, params, JaxEngineConfig(
                mesh=shard.mesh, shard_params_fn=shard.shard_params,
                shard_pages_fn=shard.shard_pages, max_prefill_seqs=1,
                decode_multistep=4, min_prefill_seqs_bucket=4,
                min_decode_bucket=4, **ENGINE_KW))
            try:
                streams.append(await asyncio.gather(
                    run_tokens(eng, range(1, 6), "a", max_tokens=24),
                    run_tokens(eng, range(40, 47), "b", max_tokens=24,
                               seed=5, temp=0.8),
                    run_tokens(eng, range(50, 100), "c", max_tokens=6),
                    run_tokens(eng, range(20, 24), "d", max_tokens=6)))
                chained = eng.scheduler.chained_steps["mixed"]
                assert (chained >= 2) == (cls is JaxEngine), chained
                assert (chained > 0) == (cls is JaxEngine), chained
            finally:
                await eng.stop()
        assert streams[0] == streams[1]


class TestPallasPerShard:
    """On a mesh the GQA Pallas kernels run once per tp shard under
    shard_map (``JaxEngine._per_shard``): the TPU compiler refuses a Mosaic
    call left to GSPMD ("Mosaic kernels cannot be automatically
    partitioned"). Here the kernels run in interpret mode on the CPU mesh;
    the native compile is chip_smoke.py's tp variant."""

    @pytest.mark.async_timeout(240)
    async def test_tp_and_dp_tp_match_single_device_scan(self):
        cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=128)
        kw = dict(ENGINE_KW, page_size=8)
        prompts = [list(range(1 + i, 30 + 3 * i)) for i in range(3)]

        async def serve(engine):
            try:
                return await asyncio.gather(*[
                    run_tokens(engine, p, f"r{i}", max_tokens=12)
                    for i, p in enumerate(prompts)])
            finally:
                await engine.stop()

        want = await serve(JaxEngine.random_init(
            cfg, JaxEngineConfig(attn_impl="scan", **kw)))
        from dynamo_tpu.parallel.mesh import MeshSpec, make_mesh
        from dynamo_tpu.parallel.sharding import ModelSharding
        for spec in (MeshSpec(tp=2), MeshSpec(dp=2, tp=2)):
            shard = ModelSharding(cfg, make_mesh(
                spec, devices=jax.devices()[:spec.size]))
            engine = build_tp2(cfg, shard, attn_impl="pallas", **kw)
            assert engine.attn_impl == "pallas"
            got = await serve(engine)
            assert engine.multistep_blocks > 0 and engine.mixed_steps > 0
            assert got == want, spec

    def test_an_attn_impl_asked_for_by_name_is_honoured_or_refused(self):
        # head_dim 16: the kernels cannot run; "auto" would settle on scan
        cfg = ModelConfig.tiny()
        with pytest.raises(ValueError, match="head_dim%128"):
            JaxEngine.random_init(cfg, JaxEngineConfig(
                attn_impl="pallas", **ENGINE_KW))
        # the MLA kernels have no shard_map wrapper: refused on a mesh
        mla = ModelConfig.tiny(
            model_type="deepseek_v2", num_heads=4, num_kv_heads=1,
            head_dim=128, kv_lora_rank=128, qk_rope_head_dim=16,
            qk_nope_head_dim=32, v_head_dim=32, first_k_dense_replace=2)
        shard = tp_sharding(mla, 2)
        with pytest.raises(ValueError, match="cannot run on a mesh"):
            JaxEngine.random_init(mla, JaxEngineConfig(
                attn_impl="pallas", mesh=shard.mesh,
                shard_params_fn=shard.shard_params,
                shard_pages_fn=shard.shard_pages,
                **dict(ENGINE_KW, page_size=8)))


class TestShardAwareHandoff:
    """Per-shard KV wire frames (wire v5) between two sharded engines."""

    async def test_negotiation_helpers(self, tp2):
        from dynamo_tpu.engine.transfer import (cache_shard_layout,
                                                kv_shard_payload,
                                                resolve_wire)
        cfg, shard = tp2
        eng = build_tp2(cfg, shard)
        try:
            assert cache_shard_layout(eng) == (2, 3)  # Hkv axis of
            # [L, n, 2, Hkv, ps, Dh]
            assert kv_shard_payload(eng) == {"shards": 2, "shard_axis": 3}
            # wire v5 + matching advert -> per-shard; v4 or no advert -> not
            assert resolve_wire({"wire": 5, "shards": 2, "shard_axis": 3},
                                1)[3] == (2, 3)
            assert resolve_wire({"wire": 5}, 1)[3] is None
            assert resolve_wire({"wire": 4, "shards": 2, "shard_axis": 3},
                                1)[3] is None
            # multihost engines never advertise (no broadcast for shard
            # frames)
            eng.step_tap = lambda *a: None
            assert kv_shard_payload(eng) == {}
            eng.step_tap = None
        finally:
            await eng.stop()

    async def _prefill_hashes(self, eng, prompt):
        req = make_req(prompt, f"pf{id(eng):x}", max_tokens=2)
        req.prefill_only = True
        final = None
        async for f in eng.generate(req):
            if f.finish_reason is not None:
                final = f
        return [b[0] for b in final.kv_transfer_params["blocks"]]

    async def test_shard_to_shard_roundtrip(self, tp2):
        """E2E through the real RPC serving handler: per-shard frames,
        crc-stamped, assembled shard-by-shard onto the destination mesh;
        numerics and greedy continuation identical."""
        from dynamo_tpu.engine.transfer import (InjectPipeline,
                                                kv_shard_payload,
                                                serve_kv_export,
                                                verify_frame)
        cfg, shard = tp2
        a, b = build_tp2(cfg, shard), build_tp2(cfg, shard)
        try:
            prompt = list(range(1, 14))  # 3 full pages
            want = await run_tokens(a, prompt, "solo", max_tokens=6)
            hashes = await self._prefill_hashes(a, prompt)
            assert len(hashes) == 3

            handler = serve_kv_export(a)
            frames = []
            async for f in handler({"block_hashes": hashes, "wire": 5,
                                    **kv_shard_payload(b)}, None):
                frames.append(f)
            # one frame per (window, shard): every frame carries shard
            # meta + a crc over ITS slice
            assert len(frames) == 2
            assert [f.obj["shard"]["index"] for f in frames] == [0, 1]
            per_shard = {}
            pipe = InjectPipeline(b)

            def scribble(raw):
                # the production pull paths release each wire buffer to a
                # pool that REUSES it for the next same-sized frame: model
                # that by trashing the bytes the instant the pipeline
                # hands the buffer back — a staged slice still aliasing
                # it would commit garbage KV (and fail the byte-exact
                # check below)
                np.asarray(raw).view(np.uint8)[...] = 0xAB

            for f in frames:
                meta = dict(f.obj)
                meta["_raw"] = f.raw
                verify_frame(meta, f.raw)  # crc32 stamped per shard frame
                idx = meta["shard"]["index"]
                per_shard[idx] = per_shard.get(idx, 0) + f.raw.nbytes
                await pipe.add_frame(meta, release=scribble)
            assert await pipe.finish() == 3
            assert set(per_shard) == {0, 1}
            assert per_shard[0] == per_shard[1] > 0

            # byte-exact KV on the destination shards
            ga = await a.run_exclusive(
                a.gather_pages_host, [a.allocator._by_hash[h]
                                      for h in hashes])
            gb = await b.run_exclusive(
                b.gather_pages_host, [b.allocator._by_hash[h]
                                      for h in hashes])
            assert np.array_equal(ga, gb)

            out = []
            cached = None
            async for f in b.generate(make_req(prompt, "cont",
                                               max_tokens=6)):
                out.extend(f.token_ids)
                if f.finish_reason is not None:
                    cached = f.cached_tokens
            assert cached == 12  # prefix revived, not recomputed
            assert out == want
        finally:
            await a.stop()
            await b.stop()

    async def test_v4_puller_gets_merged_frames(self, tp2):
        """A puller that speaks wire <= 4 (or negotiated nothing) gets the
        host-gathered merged frames from a sharded exporter — the clean
        single-frame fallback — and can inject them through the normal
        staged path."""
        from dynamo_tpu.engine.transfer import (InjectPipeline,
                                                serve_kv_export)
        cfg, shard = tp2
        a = build_tp2(cfg, shard)
        b = JaxEngine.random_init(cfg, JaxEngineConfig(**ENGINE_KW))
        try:
            prompt = list(range(1, 14))
            hashes = await self._prefill_hashes(a, prompt)
            handler = serve_kv_export(a)
            frames = []
            async for f in handler({"block_hashes": hashes, "wire": 4},
                                   None):
                frames.append(f)
            assert len(frames) == 1
            assert frames[0].obj.get("shard") is None
            pipe = InjectPipeline(b)
            meta = dict(frames[0].obj)
            meta["_raw"] = frames[0].raw
            await pipe.add_frame(meta)
            assert await pipe.finish() == 3
        finally:
            await a.stop()
            await b.stop()

    async def test_mismatched_layout_falls_back_merged(self, tp2):
        from dynamo_tpu.engine.transfer import export_frames
        cfg, shard = tp2
        a = build_tp2(cfg, shard)
        try:
            hashes = await self._prefill_hashes(a, list(range(1, 14)))
            # a tp=4 puller against this tp=2 exporter: merged frames
            frames = await a.run_exclusive(export_frames, a, hashes,
                                           "layer", 16, (4, 3))
            assert frames and all(f.obj.get("shard") is None
                                  for f in frames)
        finally:
            await a.stop()

    async def test_truncated_shard_stream_raises_and_resumes_clean(
            self, tp2):
        """Losing a shard slice mid-window is a transport fault: finish()
        raises (the puller's resume ladder re-pulls), nothing partial is
        committed, and a clean re-pull succeeds."""
        from dynamo_tpu.engine.transfer import (InjectPipeline,
                                                export_frames,
                                                kv_shard_payload)
        cfg, shard = tp2
        a, b = build_tp2(cfg, shard), build_tp2(cfg, shard)
        try:
            hashes = await self._prefill_hashes(a, list(range(1, 14)))
            frames = await a.run_exclusive(
                export_frames, a, hashes, "layer", 16,
                (kv_shard_payload(b)["shards"],
                 kv_shard_payload(b)["shard_axis"]))
            assert len(frames) == 2
            pipe = InjectPipeline(b)
            meta = dict(frames[0].obj)
            meta["_raw"] = frames[0].raw
            await pipe.add_frame(meta)   # shard 0 only; shard 1 "lost"
            with pytest.raises(ConnectionError):
                await pipe.finish()
            assert pipe.injected == 0
            assert all(h not in b.allocator._by_hash for h in hashes)

            pipe2 = InjectPipeline(b)
            for f in frames:
                meta = dict(f.obj)
                meta["_raw"] = f.raw
                await pipe2.add_frame(meta)
            assert await pipe2.finish() == 3
        finally:
            await a.stop()
            await b.stop()

    async def test_shard_frame_rejected_by_standalone_inject(self, tp2):
        from dynamo_tpu.engine.transfer import (export_frames,
                                                inject_frame,
                                                kv_shard_payload)
        cfg, shard = tp2
        a = build_tp2(cfg, shard)
        try:
            hashes = await self._prefill_hashes(a, list(range(1, 14)))
            pay = kv_shard_payload(a)
            frames = await a.run_exclusive(
                export_frames, a, hashes, "layer", 16,
                (pay["shards"], pay["shard_axis"]))
            meta = dict(frames[0].obj)
            meta["_raw"] = frames[0].raw
            with pytest.raises(ValueError):
                await a.run_exclusive(inject_frame, a, meta)
        finally:
            await a.stop()


class TestShardingSpecsTool:
    @pytest.mark.async_timeout(120)
    async def test_check_sharding_specs_green(self):
        """The CI drift gate itself (its own subprocess: the tool forces
        its own 2-device CPU backend before importing jax)."""
        tool = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools",
            "check_sharding_specs.py")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the tool sets its own device count
        proc = await asyncio.create_subprocess_exec(
            sys.executable, tool, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        out, err = await proc.communicate()
        assert proc.returncode == 0, (out.decode(), err.decode())
        assert b"sharding specs OK" in out
