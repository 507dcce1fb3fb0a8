"""HTTP frontend tests: in-process pipeline + full distributed e2e.

Parity in approach with reference ``lib/llm/tests/http-service.rs`` (service +
counting engines, SSE assertions, metrics) and the discovery e2e.
"""

import asyncio
import json
import time

import aiohttp
import pytest

from dynamo_tpu.engine.base import EchoEngine
from dynamo_tpu.http.service import HttpService
from dynamo_tpu.llm.model_manager import ModelManager, ModelWatcher
from dynamo_tpu.llm.pipeline import LocalEnginePipeline
from dynamo_tpu.llm.register import register_llm, serve_engine
from dynamo_tpu.protocols.sse import SseDecoder
from dynamo_tpu.runtime.runtime import DistributedRuntime
from dynamo_tpu.utils.testing import make_test_card


@pytest.fixture
def card():
    return make_test_card(name="echo-model")


async def make_local_service(card):
    manager = ModelManager()
    manager.add(card.name, LocalEnginePipeline(card, EchoEngine()))
    service = await HttpService(manager, host="127.0.0.1", port=0).start()
    return service


async def test_models_endpoint(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{service.port}/v1/models") as r:
                assert r.status == 200
                body = await r.json()
                assert [m["id"] for m in body["data"]] == ["echo-model"]
            async with s.get(f"http://127.0.0.1:{service.port}/health") as r:
                assert (await r.json())["status"] == "healthy"
    finally:
        await service.stop()


async def test_chat_completion_aggregated(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "echo-model",
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 100,
            }
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json=payload) as r:
                assert r.status == 200
                body = await r.json()
        assert body["object"] == "chat.completion"
        # echo engine returns the templated prompt tokens
        assert body["choices"][0]["message"]["content"] == \
            "<|user|>hello<|end|><|assistant|>"
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] > 0
    finally:
        await service.stop()


async def test_chat_completion_streaming_sse(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "echo-model",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
                "stream_options": {"include_usage": True},
            }
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json=payload) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/event-stream")
                decoder = SseDecoder()
                events = []
                async for chunk in r.content.iter_any():
                    events.extend(decoder.feed(chunk))
        assert events[-1].is_done
        chunks = [e.json() for e in events[:-1]]
        text = "".join(c["choices"][0]["delta"].get("content", "")
                       for c in chunks if c.get("choices"))
        assert text == "<|user|>hi<|end|><|assistant|>"
        finishes = [c["choices"][0].get("finish_reason")
                    for c in chunks if c.get("choices")]
        assert "length" in finishes
        usage = [c for c in chunks if c.get("usage")]
        assert usage and usage[-1]["usage"]["completion_tokens"] > 0
    finally:
        await service.stop()


async def test_completions_endpoint(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {"model": "echo-model", "prompt": "abc", "max_tokens": 100}
            async with s.post(f"http://127.0.0.1:{service.port}/v1/completions",
                              json=payload) as r:
                assert r.status == 200
                body = await r.json()
        assert body["object"] == "text_completion"
        assert body["choices"][0]["text"] == "abc"
    finally:
        await service.stop()


async def test_unknown_model_404(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {"model": "nope", "messages": [{"role": "user", "content": "x"}]}
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json=payload) as r:
                assert r.status == 404
                assert "not found" in (await r.json())["error"]["message"]
    finally:
        await service.stop()


async def test_malformed_request_400(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              data=b"not json") as r:
                assert r.status == 400
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json={"model": "echo-model"}) as r:  # no messages
                assert r.status == 400
    finally:
        await service.stop()


async def test_metrics_exposed(card):
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {"model": "echo-model",
                       "messages": [{"role": "user", "content": "hi"}]}
            await (await s.post(
                f"http://127.0.0.1:{service.port}/v1/chat/completions",
                json=payload)).read()
            async with s.get(f"http://127.0.0.1:{service.port}/metrics") as r:
                text = await r.text()
        assert 'dynamo_frontend_requests_total{endpoint="chat",model="echo-model",status="200"} 1.0' in text
        assert "dynamo_frontend_time_to_first_token_seconds" in text
    finally:
        await service.stop()


# -- Milestone A: full distributed slice -----------------------------------


async def test_e2e_frontend_discovers_remote_echo_worker(card):
    """frontend (HTTP + watcher) + echo worker over a real coordinator."""
    worker_drt = await DistributedRuntime.create("127.0.0.1:1", standalone=True)
    coord = worker_drt._embedded.address
    frontend_drt = await DistributedRuntime.create(coord)
    service = None
    watcher = None
    try:
        # worker side
        ep = worker_drt.namespace("dynamo").component("echo").endpoint("generate")
        await serve_engine(ep, EchoEngine())
        await register_llm(worker_drt, ep, card)

        # frontend side
        manager = ModelManager()
        watcher = await ModelWatcher(frontend_drt, manager).start()
        service = await HttpService(manager, host="127.0.0.1", port=0).start()

        for _ in range(50):
            if card.name in manager:
                break
            await asyncio.sleep(0.05)
        assert card.name in manager

        async with aiohttp.ClientSession() as s:
            payload = {"model": card.name,
                       "messages": [{"role": "user", "content": "remote"}]}
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json=payload) as r:
                assert r.status == 200
                body = await r.json()
        assert body["choices"][0]["message"]["content"] == \
            "<|user|>remote<|end|><|assistant|>"
    finally:
        if service:
            await service.stop()
        if watcher:
            await watcher.stop()
        await frontend_drt.close()
        await worker_drt.close()


async def test_e2e_model_removed_when_worker_dies(card):
    worker_drt = await DistributedRuntime.create("127.0.0.1:1", standalone=True)
    coord = worker_drt._embedded.address
    frontend_drt = await DistributedRuntime.create(coord)
    watcher = None
    try:
        ep = worker_drt.namespace("dynamo").component("echo").endpoint("generate")
        served = await serve_engine(ep, EchoEngine())
        entry = await register_llm(worker_drt, ep, card)

        manager = ModelManager()
        watcher = await ModelWatcher(frontend_drt, manager).start()
        for _ in range(50):
            if card.name in manager:
                break
            await asyncio.sleep(0.05)
        assert card.name in manager

        # worker deregisters (graceful): revoke lease removes the model entry
        lease = await worker_drt.primary_lease()
        await lease.revoke()
        worker_drt._primary_lease = None
        for _ in range(50):
            if card.name not in manager:
                break
            await asyncio.sleep(0.05)
        assert card.name not in manager
    finally:
        if watcher:
            await watcher.stop()
        await frontend_drt.close()
        await worker_drt.close()


def _seq_tokens(prompt_len: int, n: int):
    """Deterministic continuation: token i depends only on its absolute
    position, so a migrated request (prompt extended by generated tokens)
    continues the exact same sequence on the new worker."""
    return [32 + ((prompt_len + i) % 64) for i in range(n)]


async def test_e2e_migration_on_worker_crash(card):
    """A worker that dies mid-stream: the migration operator re-issues the
    request (with generated tokens appended) to the surviving worker, and the
    client observes one seamless, uncorrupted token stream."""
    from dynamo_tpu.protocols.common import FinishReason, LLMEngineOutput

    drt1 = await DistributedRuntime.create("127.0.0.1:1", standalone=True)
    coord = drt1._embedded.address
    drt2 = await DistributedRuntime.create(coord)
    frontend_drt = await DistributedRuntime.create(coord)
    service = None
    watcher = None
    try:
        # worker 1: generates 2 tokens of the sequence, then crashes
        ep1 = drt1.namespace("dynamo").component("seq").endpoint("generate")

        async def dying_handler(payload, ctx):
            toks = _seq_tokens(len(payload["token_ids"]), 2)
            for t in toks:
                yield LLMEngineOutput(token_ids=[t]).to_dict()
            await drt1.rpc_server.stop()  # crash mid-stream: no final frame

        await ep1.serve(dying_handler)
        await register_llm(drt1, ep1, card)

        # worker 2: healthy, completes the sequence
        ep2 = drt2.namespace("dynamo").component("seq").endpoint("generate")

        async def healthy_handler(payload, ctx):
            n = payload["stop_conditions"]["max_tokens"]
            for t in _seq_tokens(len(payload["token_ids"]), n):
                yield LLMEngineOutput(token_ids=[t]).to_dict()
            yield LLMEngineOutput(finish_reason=FinishReason.LENGTH).to_dict()

        await ep2.serve(healthy_handler)
        await register_llm(drt2, ep2, card)

        manager = ModelManager()
        watcher = await ModelWatcher(frontend_drt, manager).start()
        service = await HttpService(manager, host="127.0.0.1", port=0).start()
        for _ in range(50):
            if card.name in manager:
                break
            await asyncio.sleep(0.05)

        # issue several requests; whichever lands on the dying worker must
        # migrate and still deliver the complete 6-token sequence
        from dynamo_tpu.preprocessor import HfTokenizer
        tk = HfTokenizer.from_json(card.tokenizer_json)
        async with aiohttp.ClientSession() as s:
            migrated = 0
            for i in range(4):
                prompt = f"p{i}"
                prompt_len = len(tk.encode(prompt))
                expected = tk.decode(_seq_tokens(prompt_len, 6))
                async with s.post(
                        f"http://127.0.0.1:{service.port}/v1/completions",
                        json={"model": card.name, "prompt": prompt,
                              "max_tokens": 6}) as r:
                    assert r.status == 200
                    body = await r.json()
                assert body["choices"][0]["text"] == expected, \
                    f"request {i} corrupted: {body['choices'][0]['text']!r}"
    finally:
        if service:
            await service.stop()
        if watcher:
            await watcher.stop()
        await frontend_drt.close()
        await drt2.close()
        await drt1.close()


async def test_annotations_sse_events(card):
    """nvext.annotations=[formatted_prompt, token_ids] ride as named SSE events."""
    service = await make_local_service(card)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "echo-model",
                "messages": [{"role": "user", "content": "q"}],
                "stream": True,
                "nvext": {"annotations": ["formatted_prompt", "token_ids"]},
            }
            async with s.post(f"http://127.0.0.1:{service.port}/v1/chat/completions",
                              json=payload) as r:
                decoder = SseDecoder()
                events = []
                async for chunk in r.content.iter_any():
                    events.extend(decoder.feed(chunk))
        named = {e.event: json.loads(e.data) for e in events if e.event}
        assert named["formatted_prompt"] == "<|user|>q<|end|><|assistant|>"
        assert isinstance(named["token_ids"], list) and named["token_ids"]
    finally:
        await service.stop()


@pytest.mark.parametrize("path,body", [
    ("/v1/completions", {"prompt": [1, 2, 3, 4]}),
    ("/v1/chat/completions",
     {"messages": [{"role": "user", "content": "hello"}]}),
])
@pytest.mark.parametrize("stream", [True, False])
async def test_a_caller_that_hangs_up_before_the_first_token_ends_its_request(
        card, path, body, stream):
    """The engine's generator is closed when the caller goes, not when the
    first token (a whole prompt's prefill later) cannot be written."""
    ended = []

    class SlowToStart(EchoEngine):
        async def generate(self, request, ctx=None):
            try:
                async for out in super().generate(request, ctx):
                    yield out
            finally:
                ended.append(time.monotonic())

    manager = ModelManager()
    manager.add(card.name, LocalEnginePipeline(card, SlowToStart(delay_s=5.0)))
    service = await HttpService(manager, host="127.0.0.1", port=0).start()
    try:
        async with aiohttp.ClientSession() as s:
            async def call():
                async with s.post(
                        f"http://127.0.0.1:{service.port}{path}",
                        json={"model": "echo-model", "max_tokens": 4,
                              "stream": stream, **body}) as r:
                    await r.read()
            t0 = time.monotonic()
            task = asyncio.ensure_future(call())
            await asyncio.sleep(0.3)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        while not ended and time.monotonic() - t0 < 4.0:
            await asyncio.sleep(0.05)
        assert ended and ended[0] - t0 < 2.0
    finally:
        await service.stop()
