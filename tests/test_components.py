"""Tests: system server, standalone metrics component, standalone router,
and the single-process run CLI (batch mode, real subprocess)."""

import asyncio
import json
import os
import subprocess
import sys

import aiohttp
import pytest

from dynamo_tpu.runtime.system_server import SystemHealth, SystemServer


class TestSystemServer:
    async def test_health_gating_and_live(self):
        health = SystemHealth()
        health.register("engine", ready=False)
        server = await SystemServer(health=health, host="127.0.0.1").start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            async with aiohttp.ClientSession() as s:
                r = await s.get(f"{base}/health")
                assert r.status == 503
                r = await s.get(f"{base}/live")
                assert r.status == 200
                health.set_ready("engine")
                r = await s.get(f"{base}/health")
                assert r.status == 200
                body = await r.json()
                assert body["subsystems"] == {"engine": True}
        finally:
            await server.stop()

    def test_from_env_gate(self, monkeypatch):
        monkeypatch.delenv("DYN_SYSTEM_ENABLED", raising=False)
        assert SystemServer.from_env() is None
        monkeypatch.setenv("DYN_SYSTEM_ENABLED", "1")
        monkeypatch.setenv("DYN_SYSTEM_PORT", "0")
        assert SystemServer.from_env() is not None


class TestMetricsComponent:
    async def test_scrape_and_events_to_prometheus(self):
        from dynamo_tpu.components.metrics import MetricsAggregator
        from dynamo_tpu.kv_router.router import kv_hit_rate_subject
        from dynamo_tpu.mocker import MockEngineArgs, MockerEngine
        from dynamo_tpu.llm.register import serve_engine
        from dynamo_tpu.protocols.events import KVHitRateEvent
        from dynamo_tpu.runtime.coordinator import Coordinator
        from dynamo_tpu.runtime.runtime import DistributedRuntime

        coord = await Coordinator(port=0).start()
        drts = []
        try:
            wdrt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(wdrt)
            engine = MockerEngine(MockEngineArgs(
                num_pages=32, page_size=4, speedup_ratio=1000.0))
            ep = wdrt.namespace("ns").component("tpu").endpoint("generate")
            def stats_with_extras():
                # augment with the optional planes the aggregator exports
                # (spec acceptance + MoE dispatch drops)
                d = engine.stats().to_dict()
                d["spec_decode_stats"] = {
                    "num_spec_tokens": 4, "num_drafts": 3,
                    "num_draft_tokens": 12, "num_accepted_tokens": 7}
                d["worker_stats"]["moe_dropped_tokens"] = 5
                return d

            await serve_engine(ep, engine,
                               stats_provider=stats_with_extras)

            mdrt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(mdrt)
            agg = await MetricsAggregator(mdrt, "ns", "tpu",
                                          interval_s=0.1).start()
            await mdrt.publish_event(
                kv_hit_rate_subject("ns", "tpu"),
                KVHitRateEvent(worker_id=1, isl_blocks=10,
                               overlap_blocks=4).to_dict())
            for _ in range(100):
                from prometheus_client import generate_latest
                text = generate_latest(agg.registry).decode()
                # require actual SAMPLES (a labelled series), not just the
                # HELP/TYPE headers every registered gauge always emits
                if ("dynamo_worker_spec_accepted_tokens{worker=" in text
                        and "dynamo_router_isl_blocks_total 10.0" in text):
                    break
                await asyncio.sleep(0.1)
            text = generate_latest(agg.registry).decode()
            assert "dynamo_worker_kv_total_blocks" in text
            assert "dynamo_router_isl_blocks_total 10.0" in text
            assert 'dynamo_worker_spec_accepted_tokens{worker=' in text
            assert "7.0" in text.split(
                "dynamo_worker_spec_accepted_tokens{")[1][:40]
            assert "dynamo_worker_moe_dropped_tokens{" in text
            await agg.stop()
            await engine.stop()
        finally:
            for d in drts:
                await d.close()
            await coord.stop()


class TestStandaloneRouter:
    async def test_routes_via_router_endpoint(self):
        from dynamo_tpu.components.router import serve_router
        from dynamo_tpu.mocker import MockEngineArgs, MockerEngine
        from dynamo_tpu.llm.register import serve_engine
        from dynamo_tpu.runtime.coordinator import Coordinator
        from dynamo_tpu.runtime.runtime import DistributedRuntime
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions)

        coord = await Coordinator(port=0).start()
        drts = []
        try:
            wdrt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(wdrt)
            engine = MockerEngine(MockEngineArgs(
                num_pages=32, page_size=4, speedup_ratio=1000.0))
            ep = wdrt.namespace("ns").component("tpu").endpoint("generate")
            await serve_engine(ep, engine,
                               stats_provider=lambda: engine.stats().to_dict())

            rdrt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(rdrt)
            router = await serve_router(rdrt, "ns", "tpu", "router",
                                        block_size=4, stats_interval=0.2)

            cdrt = await DistributedRuntime.create(coordinator=coord.address)
            drts.append(cdrt)
            client = await (cdrt.namespace("ns").component("router")
                            .endpoint("generate").client())
            await client.wait_for_instances(1, timeout=10)
            req = PreprocessedRequest(
                token_ids=list(range(1, 10)), request_id="r1",
                stop_conditions=StopConditions(max_tokens=4),
                sampling_options=SamplingOptions(temperature=0.0))
            iid = client.instance_ids()[0]
            stream = await client.direct(req.to_dict(), iid)
            frames = [f async for f in stream]
            toks = [t for f in frames for t in f.get("token_ids", [])]
            assert len(toks) == 4
            await router.close()
            await engine.stop()
        finally:
            for d in drts:
                await d.close()
            await coord.stop()


class TestRunCli:
    def test_batch_mode_with_mocker(self, tmp_path):
        prompts = tmp_path / "prompts.jsonl"
        out = tmp_path / "out.jsonl"
        prompts.write_text(
            "\n".join(json.dumps({"prompt": f"hello world {i}",
                                  "max_tokens": 4}) for i in range(5)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "dynamo_tpu.run",
             "in=batch:" + str(prompts), "out=mocker",
             "--output", str(out)],
            capture_output=True, text=True, timeout=120,
            cwd="/root/repo", env=env)
        assert proc.returncode == 0, proc.stderr
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert len(lines) == 5
        assert lines[0]["index"] == 0
        assert "5/5 prompts" in proc.stderr


class TestComposableOperators:
    """llm/operators.py: the pipeline graph role (pipeline/nodes.rs) —
    operators link around a sink; custom stages compose without forking
    the pipeline classes."""

    async def test_custom_operator_composes_and_migration_retries(self):
        from dynamo_tpu.llm.operators import (
            MigrationOperator, Operator, link)
        from dynamo_tpu.protocols.common import (
            FinishReason, LLMEngineOutput, PreprocessedRequest,
            SamplingOptions, StopConditions)
        from dynamo_tpu.runtime.rpc import StreamEndedError

        calls = {"n": 0}

        async def flaky_sink(req):
            # first attempt dies after 2 tokens; retry (with those tokens
            # appended) completes
            calls["n"] += 1
            if calls["n"] == 1:
                yield LLMEngineOutput(token_ids=[10])
                yield LLMEngineOutput(token_ids=[11])
                raise StreamEndedError("worker died")
            assert req.token_ids[-2:] == [10, 11]  # continuation carried
            yield LLMEngineOutput(token_ids=[12],
                                  finish_reason=FinishReason.LENGTH)

        seen = []

        class Audit(Operator):
            async def call(self, request, next_source):
                async for out in next_source(request):
                    seen.extend(out.token_ids)
                    yield out

        source = link([Audit(), MigrationOperator(2)], flaky_sink)
        req = PreprocessedRequest(
            token_ids=[1, 2, 3], request_id="r",
            stop_conditions=StopConditions(max_tokens=8),
            sampling_options=SamplingOptions())
        got = []
        async for out in source(req):
            got.extend(out.token_ids)
            if out.finish_reason is not None:
                break
        assert got == [10, 11, 12]
        assert seen == [10, 11, 12]  # the custom stage observed every frame
        assert calls["n"] == 2

    async def test_migration_exhaustion_yields_error_frame(self):
        from dynamo_tpu.llm.operators import MigrationOperator, link
        from dynamo_tpu.protocols.common import (
            FinishReason, LLMEngineOutput, PreprocessedRequest,
            SamplingOptions, StopConditions)
        from dynamo_tpu.runtime.rpc import StreamEndedError

        async def dead_sink(req):
            raise StreamEndedError("always down")
            yield  # pragma: no cover

        source = link([MigrationOperator(1)], dead_sink)
        req = PreprocessedRequest(
            token_ids=[1], request_id="r",
            stop_conditions=StopConditions(max_tokens=4),
            sampling_options=SamplingOptions())
        frames = [f async for f in source(req)]
        assert frames[-1].finish_reason == FinishReason.ERROR
        assert "migrations" in frames[-1].error
