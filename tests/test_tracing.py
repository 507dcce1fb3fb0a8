"""Distributed tracing tests: span stitching across real RPC hops, the
disagg per-stage breakdown, the flight-recorder endpoints, stage
histograms, migration trace continuity, and the metrics<->docs drift gate.
"""

import asyncio
import json
import os
import sys

import aiohttp
import pytest

from dynamo_tpu.engine.base import EchoEngine, EngineBase
from dynamo_tpu.http.service import HttpService
from dynamo_tpu.llm.model_manager import ModelManager, ModelWatcher
from dynamo_tpu.llm.register import engine_handler, register_llm, serve_engine
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
)
from dynamo_tpu.runtime.rpc import RpcConnection, RpcServer, request_headers
from dynamo_tpu.runtime.runtime import DistributedRuntime
from dynamo_tpu.runtime.system_server import SystemServer
from dynamo_tpu.utils.testing import make_test_card
from dynamo_tpu.utils.tracing import (
    SPANS_FRAME_KEY,
    Tracer,
    get_tracer,
    set_tracer,
)


@pytest.fixture
def card():
    return make_test_card(name="echo-model")


@pytest.fixture(autouse=True)
def fresh_tracer():
    """Each test gets its own process tracer (the global one accumulates
    listener/ring state across tests otherwise)."""
    tracer = Tracer(service="test", capacity=256, slow_s=0.0,
                    export_path="", enabled=True)
    set_tracer(tracer)
    yield tracer
    set_tracer(None)


def spans_by_name(record):
    out = {}
    for s in record["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


# -- unit: tracer core ------------------------------------------------------


def test_ring_eviction_and_pagination(fresh_tracer):
    t = Tracer(service="u", capacity=3)
    ids = []
    for i in range(5):
        root = t.start_trace("http_request", attrs={"request_id": f"r{i}"})
        root.finish()
        ids.append(root.trace_id)
    assert t.get_trace(ids[0]) is None  # evicted
    assert t.get_trace(ids[1]) is None
    assert t.get_trace(ids[4]) is not None
    page = t.traces(limit=2, offset=0)
    assert page["total"] == 3
    # newest first
    assert [x["trace_id"] for x in page["traces"]] == [ids[4], ids[3]]
    page2 = t.traces(limit=2, offset=2)
    assert [x["trace_id"] for x in page2["traces"]] == [ids[2]]


def test_slow_sampling_always_keeps_errored():
    t = Tracer(service="u", capacity=10, slow_s=10.0)
    fast = t.start_trace("http_request")
    fast.finish()
    assert t.get_trace(fast.trace_id) is None  # sampled out (too fast)
    assert t.dropped_traces == 1
    bad = t.start_trace("http_request")
    bad.set_error("boom")
    bad.finish()
    assert t.get_trace(bad.trace_id) is not None  # errored: always kept


def test_span_nesting_and_context(fresh_tracer):
    t = fresh_tracer
    root = t.start_trace("http_request", attrs={"request_id": "r"})
    with t.span("tokenize") as tok:
        assert t.current_span() is tok
        assert tok.parent_span_id == root.span_id
    assert t.current_span() is root
    headers = t.current_headers()
    assert headers["trace_id"] == root.trace_id
    assert headers["parent_span_id"] == root.span_id
    root.finish()
    rec = t.get_trace(root.trace_id)
    assert {s["name"] for s in rec["spans"]} == {"http_request", "tokenize"}


# -- span stitching across a real RPC hop -----------------------------------


async def test_rpc_hop_parent_child_stitching(fresh_tracer):
    """A server handler's hop span must parent to the caller's current span
    via the auto-injected trace headers, and its shipped spans must stitch
    into the caller's recorder."""
    tracer = fresh_tracer
    server = await RpcServer(host="127.0.0.1").start()

    async def handler(payload, ctx):
        hop = tracer.start_hop("worker.generate", headers=ctx.headers,
                               attrs={"request_id": ctx.request_id})
        with tracer.span("prefill"):
            await asyncio.sleep(0.01)
        final = {"done": True, SPANS_FRAME_KEY: tracer.finish_hop(hop)}
        yield final

    server.register("ep", handler)
    conn = await RpcConnection(server.address).connect()
    try:
        root = tracer.start_trace("http_request",
                                  attrs={"request_id": "rid-1"})
        stream = await conn.request("ep", {"x": 1},
                                    request_headers(request_id="rid-1"))
        frames = [f async for f in stream]
        assert frames[0]["done"] is True
        tracer.adopt(frames[0].pop(SPANS_FRAME_KEY))
        root.finish()
        rec = tracer.get_trace(root.trace_id)
        by = spans_by_name(rec)
        assert set(by) == {"http_request", "worker.generate", "prefill"}
        hop = by["worker.generate"][0]
        assert hop["parent_span_id"] == by["http_request"][0]["span_id"]
        assert by["prefill"][0]["parent_span_id"] == hop["span_id"]
        # the server saw the frontend-minted request id, not a stream sid
        assert hop["attrs"]["request_id"] == "rid-1"
    finally:
        await conn.close()
        await server.stop()


# -- flight-recorder HTTP endpoints -----------------------------------------


async def test_traces_endpoints_pagination_and_eviction(fresh_tracer):
    tracer = Tracer(service="sys", capacity=4)
    ids = []
    for i in range(6):
        root = tracer.start_trace("http_request",
                                  attrs={"request_id": f"r{i}"})
        root.finish()
        ids.append(root.trace_id)
    system = await SystemServer(host="127.0.0.1", tracer=tracer).start()
    try:
        base = f"http://127.0.0.1:{system.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/traces?limit=2&offset=0") as r:
                assert r.status == 200
                body = await r.json()
            assert body["total"] == 4  # ring capacity
            assert [t["trace_id"] for t in body["traces"]] == \
                [ids[5], ids[4]]
            async with s.get(f"{base}/v1/traces?limit=2&offset=2") as r:
                body2 = await r.json()
            assert [t["trace_id"] for t in body2["traces"]] == \
                [ids[3], ids[2]]
            async with s.get(f"{base}/v1/traces/{ids[5]}") as r:
                assert r.status == 200
                full = await r.json()
            assert full["spans"][0]["name"] == "http_request"
            # evicted -> 404
            async with s.get(f"{base}/v1/traces/{ids[0]}") as r:
                assert r.status == 404
            async with s.get(f"{base}/v1/traces?limit=bogus") as r:
                assert r.status == 400
    finally:
        await system.stop()


# -- HTTP e2e: stitched trace + X-Request-Id + stage histograms -------------


async def test_http_e2e_stitched_trace_and_request_id(card, fresh_tracer):
    """frontend + remote echo worker: one stitched trace retrievable from
    the frontend's /v1/traces/{id}; X-Request-Id returned; per-stage
    histogram labels on the frontend /metrics."""
    worker_drt = await DistributedRuntime.create("127.0.0.1:1",
                                                 standalone=True)
    coord = worker_drt._embedded.address
    frontend_drt = await DistributedRuntime.create(coord)
    service = watcher = None
    try:
        ep = worker_drt.namespace("dynamo").component("echo") \
            .endpoint("generate")
        await serve_engine(ep, EchoEngine())
        await register_llm(worker_drt, ep, card)

        manager = ModelManager()
        watcher = await ModelWatcher(frontend_drt, manager).start()
        service = await HttpService(manager, host="127.0.0.1",
                                    port=0).start()
        for _ in range(50):
            if card.name in manager:
                break
            await asyncio.sleep(0.05)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/chat/completions",
                              json={"model": card.name,
                                    "messages": [{"role": "user",
                                                  "content": "trace me"}],
                                    "max_tokens": 8}) as r:
                assert r.status == 200
                rid = r.headers.get("X-Request-Id")
                assert rid
                await r.json()
            # find the trace by request id, fetch the full tree
            async with s.get(f"{base}/v1/traces") as r:
                listing = await r.json()
            match = [t for t in listing["traces"]
                     if t["request_id"] == rid]
            assert match, listing
            trace_id = match[0]["trace_id"]
            async with s.get(f"{base}/v1/traces/{trace_id}") as r:
                assert r.status == 200
                rec = await r.json()
            by = spans_by_name(rec)
            # frontend-local stages + the worker hop + its shipped stages
            for name in ("http_request", "tokenize", "detokenize",
                         "worker.generate", "queue", "prefill", "decode"):
                assert name in by, (name, sorted(by))
            hop = by["worker.generate"][0]
            assert hop["parent_span_id"] == by["http_request"][0]["span_id"]
            assert by["decode"][0]["parent_span_id"] == hop["span_id"]
            # no duplicate span ids (hop fragment merged with adoption)
            ids = [s["span_id"] for s in rec["spans"]]
            assert len(ids) == len(set(ids))
            # stage histogram labels on the frontend /metrics
            async with s.get(f"{base}/metrics") as r:
                metrics = await r.text()
            for stage in ("tokenize", "detokenize", "queue", "prefill",
                          "decode"):
                assert (f'dynamo_tpu_stage_duration_seconds_count'
                        f'{{stage="{stage}"}}') in metrics, stage
    finally:
        if service:
            await service.stop()
        if watcher:
            await watcher.stop()
        await frontend_drt.close()
        await worker_drt.close()


# -- migration: trace continuity across a mid-stream worker loss ------------


def _seq_tokens(prompt_len, n):
    return [32 + ((prompt_len + i) % 64) for i in range(n)]


class _SeqEngine(EngineBase):
    """Deterministic position-keyed continuation (same convention as the
    migration e2e in test_http_service)."""

    async def generate(self, request, ctx=None):
        n = request.stop_conditions.max_tokens or 4
        for t in _seq_tokens(len(request.token_ids), n):
            yield LLMEngineOutput(token_ids=[t])
        yield LLMEngineOutput(finish_reason=FinishReason.LENGTH,
                              prompt_tokens=len(request.token_ids),
                              completion_tokens=n)


async def test_migration_trace_continuity(card, fresh_tracer):
    """A worker dying mid-stream: the replayed request keeps the same
    trace; the root records a migration event and the surviving worker's
    hop span joins the same tree; the survivor counts the replay."""
    from dynamo_tpu.worker.metrics import get_worker_metrics

    drt1 = await DistributedRuntime.create("127.0.0.1:1", standalone=True)
    coord = drt1._embedded.address
    drt2 = await DistributedRuntime.create(coord)
    frontend_drt = await DistributedRuntime.create(coord)
    service = watcher = None
    try:
        ep1 = drt1.namespace("dynamo").component("seq").endpoint("generate")

        async def dying_handler(payload, ctx):
            toks = _seq_tokens(len(payload["token_ids"]), 2)
            for t in toks:
                yield LLMEngineOutput(token_ids=[t]).to_dict()
            await drt1.rpc_server.stop()  # crash mid-stream: no final frame

        await ep1.serve(dying_handler)
        await register_llm(drt1, ep1, card)

        ep2 = drt2.namespace("dynamo").component("seq").endpoint("generate")
        await serve_engine(ep2, _SeqEngine())
        await register_llm(drt2, ep2, card)

        manager = ModelManager()
        watcher = await ModelWatcher(frontend_drt, manager).start()
        service = await HttpService(manager, host="127.0.0.1",
                                    port=0).start()
        for _ in range(50):
            if card.name in manager:
                break
            await asyncio.sleep(0.05)

        replays_before = get_worker_metrics().migration_replays.labels(
            "replay")._value.get()
        base = f"http://127.0.0.1:{service.port}"
        migrated_rid = None
        async with aiohttp.ClientSession() as s:
            for i in range(4):  # whichever lands on the dying worker
                async with s.post(f"{base}/v1/completions",
                                  json={"model": card.name,
                                        "prompt": f"p{i}",
                                        "max_tokens": 6}) as r:
                    assert r.status == 200
                    rid = r.headers["X-Request-Id"]
                    await r.json()
                rec = None
                for t in get_tracer().traces(limit=10)["traces"]:
                    if t["request_id"] == rid:
                        rec = get_tracer().get_trace(t["trace_id"])
                root = rec["spans"][0]
                events = [e for s in rec["spans"]
                          for e in s.get("events", [])]
                if any(e["name"] == "migration" for e in events):
                    migrated_rid = rid
                    # the replay reached the survivor under the SAME trace:
                    # its hop span (shipped on the replay's final frame)
                    # is part of this tree
                    hops = [s for s in rec["spans"]
                            if s["name"] == "worker.generate"]
                    assert hops, sorted(s["name"] for s in rec["spans"])
                    assert all(h["trace_id"] == root["trace_id"]
                               for h in hops)
                    break
        assert migrated_rid is not None, "no request hit the dying worker"
        assert get_worker_metrics().migration_replays.labels(
            "replay")._value.get() > replays_before
    finally:
        if service:
            await service.stop()
        if watcher:
            await watcher.stop()
        await frontend_drt.close()
        await drt2.close()
        await drt1.close()


# -- disagg: the acceptance criterion ---------------------------------------


@pytest.mark.e2e
async def test_disagg_trace_has_all_stage_spans(fresh_tracer):
    """A request served through the disagg path produces one stitched trace
    containing queue, prefill (remote leg), kv_transfer, and decode child
    spans whose durations sum to within the recorded request duration; the
    same stages land in the worker-side stage histogram."""
    from prometheus_client import generate_latest

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.transfer import serve_kv_export
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.worker.disagg import (
        KV_EXPORT_ENDPOINT, DisaggDecodeHandler)
    from dynamo_tpu.worker.metrics import get_worker_metrics

    tracer = fresh_tracer
    wm = get_worker_metrics()
    wm.attach_tracer(tracer)
    cfg = JaxEngineConfig(num_pages=64, page_size=4, max_num_seqs=4,
                          max_prefill_chunk=32, max_context=128)
    prompt = list(range(1, 14))

    coord = await Coordinator(port=0).start()
    drts, handler, served = [], None, None
    try:
        pre_drt = await DistributedRuntime.create(coordinator=coord.address)
        drts.append(pre_drt)
        pre_engine = JaxEngine.random_init(ModelConfig.tiny(), cfg)
        comp = pre_drt.namespace("ns").component("prefill")
        await serve_engine(comp.endpoint("generate"), pre_engine)
        await comp.endpoint(KV_EXPORT_ENDPOINT).serve(
            serve_kv_export(pre_engine))

        dec_drt = await DistributedRuntime.create(coordinator=coord.address)
        drts.append(dec_drt)
        dec_engine = JaxEngine.random_init(ModelConfig.tiny(), cfg)
        handler = await DisaggDecodeHandler(
            dec_engine, dec_drt, "ns", "prefill").start()
        await handler._gen_client.wait_for_instances(1, timeout=10)
        dec_ep = dec_drt.namespace("ns").component("tpu") \
            .endpoint("generate")
        await dec_engine.start()
        served = await dec_ep.serve(engine_handler(handler))

        # "frontend": a third runtime calls the decode worker over RPC
        fe_drt = await DistributedRuntime.create(coordinator=coord.address)
        drts.append(fe_drt)
        client = await fe_drt.namespace("ns").component("tpu") \
            .endpoint("generate").client()
        await client.wait_for_instances(1, timeout=10)

        root = tracer.start_trace("http_request",
                                  attrs={"request_id": "disagg-1"})
        req = PreprocessedRequest(token_ids=prompt, request_id="disagg-1")
        req.stop_conditions.max_tokens = 6
        req.sampling_options.temperature = 0.0
        stream = await client.direct(
            req.to_dict(), client.instance_ids()[0],
            request_headers(request_id="disagg-1"))
        frames = []
        async for payload in stream:
            if isinstance(payload, dict) and SPANS_FRAME_KEY in payload:
                tracer.adopt(payload.pop(SPANS_FRAME_KEY))
            frames.append(LLMEngineOutput.from_dict(payload))
        assert frames and frames[-1].finish_reason is not None
        assert not frames[-1].error
        root.finish()

        rec = tracer.get_trace(root.trace_id)
        assert rec is not None
        by = spans_by_name(rec)
        for name in ("http_request", "worker.generate", "queue", "prefill",
                     "kv_transfer", "decode"):
            assert name in by, (name, sorted(by))
        # the remote-prefill leg is marked and disjoint from kv_transfer
        remote_prefills = [s for s in by["prefill"]
                           if (s.get("attrs") or {}).get("remote")]
        assert remote_prefills
        # two hops: decode worker (child of the root) and the prefill
        # worker (child of the decode worker's remote-prefill span)
        hops = {s["span_id"]: s for s in by["worker.generate"]}
        root_span = by["http_request"][0]
        decode_hop = [h for h in hops.values()
                      if h["parent_span_id"] == root_span["span_id"]][0]
        prefill_hop = [h for h in hops.values() if h is not decode_hop][0]
        assert prefill_hop["parent_span_id"] == \
            remote_prefills[0]["span_id"]
        # the decode hop's DIRECT stage children are the request's
        # sequential phases: their durations sum to within the recorded
        # request duration (the acceptance criterion)
        stages = [s for s in rec["spans"]
                  if s.get("parent_span_id") == decode_hop["span_id"]
                  and s["name"] in ("queue", "prefill", "kv_transfer",
                                    "decode")]
        assert {s["name"] for s in stages} >= \
            {"queue", "prefill", "kv_transfer", "decode"}
        stage_sum = sum(s["duration_s"] for s in stages)
        assert stage_sum <= rec["duration_s"] * 1.05 + 0.05, \
            (stage_sum, rec["duration_s"])
        # all spans belong to the one trace
        assert {s["trace_id"] for s in rec["spans"]} == {root.trace_id}
        # worker-side: stage histogram carries the disagg stages, and KV
        # bytes were counted on the RPC fallback plane
        metrics = generate_latest(wm.registry).decode()
        for stage in ("queue", "prefill", "kv_transfer", "decode"):
            assert (f'dynamo_tpu_stage_duration_seconds_count'
                    f'{{stage="{stage}"}}') in metrics, stage
        assert 'dynamo_worker_disagg_kv_bytes_total' \
            '{direction="pulled",plane="rpc"}' in metrics
    finally:
        if handler is not None:
            await handler.stop()
        for d in drts:
            await d.close()
        await coord.stop()


# -- tools ------------------------------------------------------------------


def test_metrics_documented():
    """docs/observability.md and the registries cannot drift (satellite:
    the checker runs in the tier-1 pass as a fast unit test)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import check_metrics_docs
    assert check_metrics_docs.main(["check_metrics_docs.py"]) == 0


def test_trace2perfetto_conversion(tmp_path, fresh_tracer):
    tracer = fresh_tracer
    root = tracer.start_trace("http_request", attrs={"request_id": "r1"})
    with tracer.span("tokenize"):
        pass
    sp = tracer.start_span("decode")
    sp.add_event("migration", attempt=1)
    sp.finish()
    root.finish()
    rec = tracer.get_trace(root.trace_id)
    src = tmp_path / "traces.jsonl"
    src.write_text(json.dumps(rec) + "\n")
    out = tmp_path / "trace.json"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import trace2perfetto
    assert trace2perfetto.main([str(src), "-o", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == \
        {"http_request", "tokenize", "decode"}
    assert any(e["ph"] == "i" and e["name"] == "migration" for e in events)
    assert any(e["ph"] == "M" for e in events)  # process_name metadata
    # unknown trace id errors cleanly
    assert trace2perfetto.main([str(src), "--trace-id", "nope",
                                "-o", str(out)]) == 1


def test_jsonl_export(tmp_path):
    path = tmp_path / "export.jsonl"
    t = Tracer(service="x", capacity=4, export_path=str(path))
    for _ in range(2):
        t.start_trace("http_request").finish()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 2 and all(l["spans"] for l in lines)


def test_log_records_carry_trace_context(fresh_tracer, capsys):
    import logging as pylog

    from dynamo_tpu.utils.logging import JsonlFormatter, TraceContextFilter
    rec = pylog.LogRecord("t", pylog.INFO, __file__, 1, "hello", (), None)
    root = fresh_tracer.start_trace("http_request",
                                    attrs={"request_id": "rid-9"})
    try:
        assert TraceContextFilter().filter(rec) is True
        out = json.loads(JsonlFormatter().format(rec))
        assert out["trace_id"] == root.trace_id
        assert out["request_id"] == "rid-9"
    finally:
        root.finish()


# -- the worker's startup trace ----------------------------------------------

STARTUP_STAGES = ["startup.imports", "startup.weights", "startup.engine",
                  "startup.prime", "startup.register"]


def test_startup_trace_runs_from_process_start_to_ready():
    import time

    from dynamo_tpu.utils.tracing import StartupTrace, process_start_unix
    # the OS's start time of this process: before now, and the same twice
    born = process_start_unix()
    assert 0.0 < time.time() - born < 7 * 86400
    assert abs(process_start_unix() - born) < 0.05
    startup = StartupTrace()
    startup.stage_since_start("startup.imports")
    for name in STARTUP_STAGES[1:4]:
        with startup.stage(name) as stage_attrs:
            time.sleep(0.002)
            if name == "startup.engine":
                stage_attrs["sample.top_candidates"] = "direct"
    startup.stage_until_ready("startup.register")
    time.sleep(0.002)
    t = Tracer(service="worker", capacity=4)
    startup.finish(t, attrs={"model": "m"})
    summary = t.traces()["traces"][0]
    assert summary["name"] == "startup" and summary["num_spans"] == 6
    rec = t.get_trace(summary["trace_id"])
    root = next(s for s in rec["spans"] if s["name"] == "startup")
    assert root["kind"] == "root" and root["attrs"] == {"model": "m"}
    assert root["start_unix"] == pytest.approx(born, abs=0.05)
    kids = [s for s in rec["spans"] if s["name"] != "startup"]
    assert [s["name"] for s in kids] == STARTUP_STAGES     # in order
    assert all(s["parent_span_id"] == root["span_id"] for s in kids)
    # what a stage's block wrote into its attributes is on its span
    assert [s.get("attrs") or {} for s in kids] == [
        {}, {}, {"sample.top_candidates": "direct"}, {}, {}]
    assert kids[0]["start_unix"] == root["start_unix"]
    for a, b in zip(kids, kids[1:]):
        assert a["end_unix"] <= b["start_unix"]
    # the last stage and the root end together, at ready
    assert kids[-1]["end_unix"] == root["end_unix"]
    # a disabled tracer takes nothing and does not mind
    StartupTrace().finish(Tracer(enabled=False))


@pytest.mark.async_timeout(240)
async def test_worker_writes_its_startup_trace_when_ready(tmp_path):
    """A real ``worker.main`` process: by its ready line the ``startup``
    trace is in ``DYN_TRACE_EXPORT``, its five children in order, starting
    with the process and ending at ready."""
    import time

    from dynamo_tpu.runtime.coordinator import Coordinator
    from dynamo_tpu.utils.testing import make_test_model_dir
    from tests.procutils import ManagedProcess
    model_dir = make_test_model_dir(str(tmp_path / "m"), vocab_size=512)
    export = tmp_path / "worker.traces.jsonl"
    coord = await Coordinator(port=0).start()
    t_spawn = time.time()
    worker = ManagedProcess(
        ["dynamo_tpu.worker.main", "--coordinator",
         f"127.0.0.1:{coord.port}", "--model-path", model_dir,
         "--model-name", "s-model", "--random-weights",
         "--page-size", "4", "--num-pages", "64", "--max-num-seqs", "4",
         "--max-prefill-chunk", "32", "--max-context", "256"],
        name="startup-worker", ready_line="jax worker serving",
        timeout=180.0, env_overrides={"DYN_TRACE_EXPORT": str(export)})
    try:
        async with worker:
            t_ready = time.time()
            with open(export) as f:
                records = [json.loads(line) for line in f]
    finally:
        await coord.stop()
    rec = next(r for r in records if r["name"] == "startup")
    assert rec["service"] == "worker"
    kids = [s for s in rec["spans"] if s["name"] != "startup"]
    assert [s["name"] for s in kids] == STARTUP_STAGES
    assert t_spawn - 0.1 <= rec["start_unix"] <= t_spawn + 2.0
    end = rec["start_unix"] + rec["duration_s"]
    assert end <= t_ready + 0.1 and kids[-1]["end_unix"] == \
        pytest.approx(end, abs=1e-3)
    # which form the sampler's selection takes at this vocabulary (512),
    # and which the prefill-carrying steps take (the CPU's XLA path)
    # ... and the kinds of cache the engine keeps (one paged pool: no
    # linear-attention layer, no state pool), that q, k and v come from
    # one stored matrix (one device, a GQA family), and the table of stages the
    # step programs are traced under, for a reader of the device trace
    from dynamo_tpu.engine import stages
    assert kids[2]["attrs"] == {"sample.top_candidates": "direct",
                                "cache.kinds": "paged[L=2,Hkv=2,Dh=16]",
                                "prefill.form": "padded:attn_impl",
                                "qkv": "fused",
                                "stages": stages.as_attribute()}
    # the stages cover the start-up: imports and the engine build dominate
    assert sum(s["duration_s"] for s in kids) >= 0.8 * rec["duration_s"]
