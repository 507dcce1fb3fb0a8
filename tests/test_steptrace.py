"""Engine step flight recorder: ring bounds/eviction/knobs, compile-event
detection on fresh jit buckets, fleet-accounting metric rendering, frontend
SLO/goodput outcomes, mocker parity, the /v1/steptrace endpoint, and the
Perfetto step-timeline merge.
"""

import asyncio
import json
import os
import sys
import time

import aiohttp
from prometheus_client import generate_latest
import pytest

from dynamo_tpu.engine.steptrace import (
    StepRecorder,
    get_step_recorder,
    set_step_recorder,
)


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Each test gets its own process step recorder (engines pick up the
    global singleton at construction)."""
    rec = StepRecorder(capacity=256)
    set_step_recorder(rec)
    yield rec
    set_step_recorder(None)


def stamp(rec, kind="decode", device_ms=5.0, **kw):
    """One dispatch whose result arrives ``device_ms`` after its enqueue
    began (the duration histogram observes device time, at ready)."""
    defaults = dict(rows=2, batch=4, tokens_real=2, tokens_padded=4,
                    dispatch_ms=5.0)
    defaults.update(kw)
    # enqueued once the previous result is in, so it has the device alone
    t0 = max(time.perf_counter(), rec._last_ready)
    r = rec.record(kind, enqueue=t0, **defaults)
    rec.note_ready(r, t0 + device_ms / 1e3, time.time())
    return r


# -- unit: the ring ---------------------------------------------------------


class TestRing:
    def test_bounds_and_newest_first_pagination(self):
        rec = StepRecorder(capacity=4)
        for i in range(10):
            stamp(rec, rows=i)
        snap = rec.snapshot(limit=100)
        assert snap["total"] == 10 and snap["capacity"] == 4
        assert snap["count"] == 4  # oldest 6 overwritten
        assert [r["seq"] for r in snap["records"]] == [9, 8, 7, 6]
        page = rec.snapshot(limit=2, offset=2)
        assert [r["seq"] for r in page["records"]] == [7, 6]
        assert rec.snapshot(limit=2, offset=100)["records"] == []

    def test_slots_reused_in_place(self):
        rec = StepRecorder(capacity=2)
        r0 = stamp(rec, fallback="pages")
        rec.note_compile("decode", 1.2, r0)
        stamp(rec)
        stamp(rec)  # wraps onto r0's slot
        assert r0.seq == 2
        # wrap must clear the per-dispatch patch fields, not inherit them
        assert r0.compile_ms == 0.0 and r0.fallback == ""

    def test_ring_size_knob(self, monkeypatch):
        monkeypatch.setenv("DYN_STEPTRACE_RING", "7")
        rec = StepRecorder()
        assert rec.capacity == 7
        assert "enabled" not in rec.snapshot()   # there is no off switch

    def test_unpack_and_compile_patching(self):
        rec = StepRecorder(capacity=8)
        r = stamp(rec, kind="multistep", width=8, gap_ms=2.0)
        rec.note_unpack(r, 0.5, 0.2)
        rec.note_compile("multistep", 2.5, r)
        d = rec.snapshot(limit=1)["records"][0]
        assert d["fetch_ms"] == 0.5 and d["process_ms"] == 0.2
        assert d["unpack_ms"] == d["fetch_ms"] + d["process_ms"]
        assert d["compile_ms"] == 2500.0
        assert "_enqueue" not in d  # the private stamp is not exported
        rec.note_unpack(None, 1.0, 1.0)  # absent record is a no-op
        rec.note_ready(None, 1.0, 1.0)

    def test_aggregates_shape(self):
        rec = StepRecorder(capacity=8)
        stamp(rec, kind="decode", tokens_real=2, tokens_padded=4,
              gap_ms=1.0, pool_free=33, pool_pinned=3)
        # no occupancy sample for an unpadded dispatch; pool gauges track
        # the most recent dispatch's plan-time state
        stamp(rec, kind="prefill", tokens_padded=0, pool_free=33,
              pool_pinned=3)
        agg = rec.aggregates()
        cum, s, n = agg["duration"]["decode"]
        assert cum[-1] == ("+Inf", 1) and n == 1 and s == pytest.approx(0.005)
        assert "prefill" not in agg["occupancy"]
        _, osum, on = agg["occupancy"]["decode"]
        assert on == 1 and osum == pytest.approx(0.5)
        assert agg["gap"][2] == 1
        assert agg["pool_free"] == 33 and agg["pool_pinned"] == 3


# -- fleet accounting on /metrics -------------------------------------------


def test_metric_rendering():
    from prometheus_client import generate_latest

    from dynamo_tpu.worker.metrics import WorkerMetrics
    wm = WorkerMetrics()
    # pre-attach: full schema, zero-valued (dashboards + docs drift gate)
    out = generate_latest(wm.registry).decode()
    assert ('dynamo_worker_step_duration_seconds_bucket'
            '{kind="multistep",le="+Inf"} 0.0') in out
    assert 'dynamo_worker_compile_events_total{kind="prefill"} 0.0' in out
    assert 'dynamo_worker_step_gap_seconds_count 0.0' in out
    rec = StepRecorder(capacity=8)
    r = stamp(rec, kind="multistep", width=8, tokens_real=16,
              tokens_padded=64, gap_ms=0.3, pool_free=50, pool_pinned=5)
    rec.note_compile("multistep", 2.0, r)
    wm.steptrace.attach(rec.aggregates)
    out = generate_latest(wm.registry).decode()
    assert ('dynamo_worker_step_duration_seconds_count'
            '{kind="multistep"} 1.0') in out
    assert ('dynamo_worker_step_occupancy_bucket'
            '{kind="multistep",le="0.25"} 1.0') in out
    assert 'dynamo_worker_step_gap_seconds_count 1.0' in out
    assert 'dynamo_worker_page_pool_free_pages 50.0' in out
    assert 'dynamo_worker_page_pool_pinned_pages 5.0' in out
    assert 'dynamo_worker_compile_events_total{kind="multistep"} 1.0' in out
    assert 'dynamo_worker_compile_seconds_total{kind="multistep"} 2.0' in out


# -- compile detection on a real engine -------------------------------------


from dynamo_tpu.protocols.common import (  # noqa: E402
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)


def make_req(tokens, rid, max_tokens=6):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0))


async def collect(engine, req):
    return [f async for f in engine.generate(req)]


class TestCompileDetection:
    async def test_fresh_jit_bucket_becomes_compile_event(self, fresh_recorder):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        eng = JaxEngine.random_init(
            ModelConfig.tiny(),
            JaxEngineConfig(num_pages=64, page_size=4, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=64,
                            min_prefill_bucket=4))
        try:
            frames = await collect(eng, make_req([1, 2, 3, 4, 5], "c1"))
            rec = eng.steptrace
            assert rec is fresh_recorder
            assert rec.total > 0
            kinds = {r["kind"] for r in rec.snapshot(limit=256)["records"]}
            assert "prefill" in kinds
            # the very first prefill/decode dispatches compiled their jit
            # buckets: events counted AND attributed to step records
            assert sum(rec.compile_events.values()) >= 1
            assert sum(rec.compile_seconds.values()) > 0
            assert any(r["compile_ms"] > 0
                       for r in rec.snapshot(limit=256)["records"])
            # ... and to the request's frames (StageStitcher turns these
            # into an xla_compile span event on the stitched trace)
            timed = [f.timings for f in frames if f.timings]
            assert any("compile_ms" in t for t in timed)
            assert any(t.get("compile_events", 0) >= 1 for t in timed)
            events_before = dict(rec.compile_events)
            # an identical-shape request hits every warmed bucket: no new
            # compile events (the detector keys on (fn, B, S), not calls)
            await collect(eng, make_req([9, 8, 7, 6, 5], "c2"))
            assert rec.compile_events == events_before
        finally:
            await eng.stop()

    async def test_records_carry_plan_and_gap(self, fresh_recorder):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        eng = JaxEngine.random_init(
            ModelConfig.tiny(),
            JaxEngineConfig(num_pages=64, page_size=4, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=64,
                            min_prefill_bucket=4))
        try:
            await collect(eng, make_req([1, 2, 3], "g1", max_tokens=8))
            recs = fresh_recorder.snapshot(limit=256)["records"]
            assert all(r["dispatch_ms"] > 0 for r in recs)
            # consecutive dispatches of one request measure the host gap
            assert any(r["gap_ms"] > 0 for r in recs)
            assert any(r["tokens_padded"] >= r["tokens_real"] > 0
                       for r in recs)
        finally:
            await eng.stop()


# -- mocker parity + endpoint -----------------------------------------------


class TestMockerParityAndEndpoint:
    async def test_mocker_stamps_the_same_ring(self, fresh_recorder):
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
        eng = MockerEngine(MockEngineArgs(
            num_pages=64, page_size=4, max_num_seqs=8, max_context=256,
            speedup_ratio=1000.0))
        try:
            await collect(eng, make_req(range(1, 10), "m1", max_tokens=8))
            assert eng.steptrace is fresh_recorder
            snap = fresh_recorder.snapshot(limit=256)
            assert snap["total"] > 0
            kinds = {r["kind"] for r in snap["records"]}
            assert "prefill" in kinds
        finally:
            await eng.stop()

    async def test_v1_steptrace_endpoint(self, fresh_recorder):
        from dynamo_tpu.runtime.system_server import SystemServer
        stamp(fresh_recorder, kind="prefill")
        stamp(fresh_recorder, kind="decode", fallback="pages")
        server = await SystemServer(port=0,
                                    steptrace=fresh_recorder).start()
        try:
            async with aiohttp.ClientSession() as s:
                url = f"http://127.0.0.1:{server.port}/v1/steptrace"
                async with s.get(url, params={"limit": "1"}) as r:
                    assert r.status == 200
                    body = await r.json()
                assert body["total"] == 2 and body["count"] == 1
                assert body["records"][0]["kind"] == "decode"
                assert body["records"][0]["fallback"] == "pages"
                async with s.get(url, params={"limit": "x"}) as r:
                    assert r.status == 400
        finally:
            await server.stop()

    async def test_endpoint_404_without_recorder(self):
        from dynamo_tpu.runtime.system_server import SystemServer
        server = await SystemServer(port=0).start()
        try:
            async with aiohttp.ClientSession() as s:
                url = f"http://127.0.0.1:{server.port}/v1/steptrace"
                async with s.get(url) as r:
                    assert r.status == 404
        finally:
            await server.stop()


# -- frontend SLO / goodput -------------------------------------------------


class TestSloOutcomes:
    def _timer(self, m, model="m"):
        from dynamo_tpu.http.metrics import RequestTimer
        return RequestTimer(m, model, "chat")

    def _count(self, m, target, outcome):
        return m.registry.get_sample_value(
            "dynamo_frontend_slo_total",
            {"target": target, "outcome": outcome})

    def test_met_and_goodput(self):
        from dynamo_tpu.http.metrics import FrontendMetrics
        m = FrontendMetrics(slo_ttft_s=5.0, slo_itl_s=5.0)
        t = self._timer(m)
        t.on_token(1)
        t.on_token(2)
        t.done("200")
        assert self._count(m, "ttft", "met") == 1
        assert self._count(m, "itl", "met") == 1
        assert m.registry.get_sample_value(
            "dynamo_frontend_goodput_tokens_total", {"model": "m"}) == 3

    def test_violated_worst_gap_no_goodput(self):
        from dynamo_tpu.http.metrics import FrontendMetrics
        m = FrontendMetrics(slo_ttft_s=5.0, slo_itl_s=0.005)
        t = self._timer(m)
        t.on_token(1)
        time.sleep(0.02)  # one slow gap in an otherwise instant stream
        t.on_token(1)
        t.on_token(1)
        t.done("200")
        assert self._count(m, "ttft", "met") == 1
        assert self._count(m, "itl", "violated") == 1
        assert not m.registry.get_sample_value(
            "dynamo_frontend_goodput_tokens_total", {"model": "m"})

    def test_shed_counts_against_enabled_targets(self):
        from dynamo_tpu.http.metrics import FrontendMetrics
        m = FrontendMetrics(slo_ttft_s=1.0)  # itl target disabled
        m.record_slo_shed()
        assert self._count(m, "ttft", "shed") == 1
        assert self._count(m, "itl", "shed") == 0

    def test_disabled_targets_judge_nothing(self):
        from dynamo_tpu.http.metrics import FrontendMetrics
        m = FrontendMetrics()  # bare: the check_metrics_docs contract
        t = self._timer(m)
        t.on_token(1)
        t.on_token(1)
        t.done("200")
        for target in ("ttft", "itl"):
            for outcome in ("met", "violated", "shed"):
                assert self._count(m, target, outcome) == 0
        assert not m.registry.get_sample_value(
            "dynamo_frontend_goodput_tokens_total", {"model": "m"})

    def test_http_service_threads_slo_and_sheds(self):
        from dynamo_tpu.http.service import HttpService
        from dynamo_tpu.llm.model_manager import ModelManager
        svc = HttpService(ModelManager(), slo_ttft_s=0.5, slo_itl_s=0.05,
                          max_inflight=1)
        assert svc.metrics.slo_ttft_s == 0.5
        svc._shed_or_admit("m", "chat")       # admitted
        resp = svc._shed_or_admit("m", "chat")  # shed at high water
        assert resp is not None and resp.status == 503
        assert self._count(svc.metrics, "ttft", "shed") == 1
        assert self._count(svc.metrics, "itl", "shed") == 1


# -- trace keep-last + request_id lookup ------------------------------------


class TestTraceKeepLast:
    def test_request_id_lookup_survives_sampling(self):
        from dynamo_tpu.utils.tracing import Tracer
        t = Tracer(service="t", capacity=8, slow_s=60.0)  # drops everything
        root = t.start_trace("http_request",
                             attrs={"request_id": "rid-fast"})
        root.finish()
        assert t.traces()["total"] == 0  # sampled out of the main ring
        hits = t.traces(request_id="rid-fast")
        assert hits["total"] == 1
        assert hits["traces"][0]["request_id"] == "rid-fast"
        # the full tree is retrievable too
        assert t.get_trace(root.trace_id) is not None
        assert t.traces(request_id="rid-other")["total"] == 0

    def test_keep_last_ring_bounded(self, monkeypatch):
        monkeypatch.setenv("DYN_TRACE_KEEP_LAST", "3")
        from dynamo_tpu.utils.tracing import Tracer
        t = Tracer(service="t", capacity=8, slow_s=60.0)
        assert t.keep_last == 3
        roots = []
        for i in range(5):
            r = t.start_trace("http_request",
                              attrs={"request_id": f"r{i}"})
            r.finish()
            roots.append(r)
        assert len(t._keep_last) == 3
        assert t.traces(request_id="r0")["total"] == 0  # evicted
        assert t.traces(request_id="r4")["total"] == 1

    def test_no_double_listing_when_in_both_rings(self):
        from dynamo_tpu.utils.tracing import Tracer
        t = Tracer(service="t", capacity=8, slow_s=0.0)  # ring keeps it
        root = t.start_trace("http_request",
                             attrs={"request_id": "rid-slow"})
        root.finish()
        assert t.traces(request_id="rid-slow")["total"] == 1


# -- perfetto merge ---------------------------------------------------------


def test_perfetto_steptrace_merge(tmp_path, fresh_recorder):
    from dynamo_tpu.utils.tracing import Tracer
    tracer = Tracer(service="frontend", capacity=8)
    root = tracer.start_trace("http_request", attrs={"request_id": "p1"})
    with tracer.span("decode"):
        pass
    root.finish()
    src = tmp_path / "traces.jsonl"
    src.write_text(json.dumps(tracer.get_trace(root.trace_id)) + "\n")

    r1 = stamp(fresh_recorder, kind="multistep", width=8, gap_ms=0.4,
               dispatch_ms=5.0)
    fresh_recorder.note_compile("multistep", 1.5, r1)
    stamp(fresh_recorder, kind="decode", fallback="guided")
    steps = tmp_path / "steps.json"
    steps.write_text(json.dumps(fresh_recorder.snapshot(limit=10)))

    out = tmp_path / "merged.json"
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import trace2perfetto
    assert trace2perfetto.main([str(src), "--steptrace", str(steps),
                                "-o", str(out)]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    procs = [e for e in events if e.get("name") == "process_name"]
    assert any(e["args"]["name"] == "engine-steps" for e in procs)
    step_pid = next(e["pid"] for e in procs
                    if e["args"]["name"] == "engine-steps")
    span_pids = {e["pid"] for e in procs if e["args"]["name"] == "frontend"}
    assert step_pid not in span_pids  # own track, shared timeline
    steps_x = [e for e in events
               if e["ph"] == "X" and e["pid"] == step_pid]
    assert len(steps_x) == 2
    by_name = {e["name"]: e for e in steps_x}
    comp = by_name["multistepx8"]
    assert "compile" in comp["cat"] and comp["args"]["compile_ms"] == 1500.0
    fb = by_name["decode"]
    assert "fallback" in fb["cat"] and fb["args"]["fallback"] == "guided"
    # step events share the wall-clock timeline with the request spans,
    # and span the DEVICE time: they end when the result was on the host
    rec = fresh_recorder.snapshot(limit=10)["records"][0]
    assert any(e["ts"] + e["dur"] == pytest.approx(rec["ready_unix"] * 1e6)
               for e in steps_x)
    # dur = device_ms in microseconds, not the enqueue (dispatch_ms)
    assert all(e["dur"] == pytest.approx(5.0 * 1e3) for e in steps_x)
    assert comp["args"]["dispatch_ms"] == 5.0


# -- device time per dispatch, the loop's phases, the profile hook ----------


class TestDeviceTime:
    async def test_chained_overlap_splits_the_wall_time(self, fresh_recorder):
        """An engine whose device runs fused blocks of 60 ms in order and
        whose fetch blocks until the block is done: the loop enqueues block
        N+1 before it fetches block N, and each block's ``device_ms`` is
        its own 60 ms - the two sum to the wall time from the first
        enqueue to the second result, neither counts the other."""
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
        block_s = 0.06

        class QueuedDevice(MockerEngine):
            free_at = 0.0           # when the device finishes what it has

            def dispatch_multistep(self, plan, prev_handle=None):
                self.args.speedup_ratio = 0      # no sleep in the enqueue
                handle = super().dispatch_multistep(plan, prev_handle)
                self.free_at = max(time.perf_counter(),
                                   self.free_at) + block_s
                return handle + (self.free_at,)

            def fetch_packed_block(self, handle):
                time.sleep(max(0.0, handle[2] - time.perf_counter()))
                return super().fetch_packed_block(handle[:2])

        eng = QueuedDevice(MockEngineArgs(
            num_pages=64, page_size=4, max_num_seqs=4, max_context=256,
            decode_multistep=8, speedup_ratio=100.0))
        try:
            await collect(eng, make_req([1, 2, 3, 4, 5], "ov", max_tokens=40))
        finally:
            await eng.stop()
        recs = fresh_recorder.snapshot(limit=256)["records"][::-1]
        blocks = [r for r in recs if r["kind"] == "multistep"]
        chained = [r for r in blocks if r["chained"]]
        assert len(blocks) >= 3 and chained
        for r in blocks:
            # the enqueue returns at once; the device time is the block's
            assert r["dispatch_ms"] < 0.5 * block_s * 1e3
            assert r["device_ms"] == pytest.approx(block_s * 1e3, rel=0.25)
            assert r["ready_unix"] >= r["t_unix"]
            assert r["unpack_ms"] == r["fetch_ms"] + r["process_ms"]
            assert r["fetch_ms"] > 0 and r["process_ms"] > 0
        # a chained block was enqueued before its predecessor's result came
        i = blocks.index(chained[0])
        prev, nxt = blocks[i - 1], blocks[i]
        assert nxt["t_unix"] < prev["ready_unix"]
        wall_ms = (nxt["ready_unix"] - prev["ready_unix"]) * 1e3 \
            + prev["device_ms"]
        assert prev["device_ms"] + nxt["device_ms"] == \
            pytest.approx(wall_ms, abs=2.0)
        # synchronous kinds: the result is on the host when the dispatch
        # returns, and there is nothing to fetch
        sync = [r for r in recs if r["kind"] in ("prefill", "mixed")]
        assert sync and all(r["fetch_ms"] == 0.0 and r["device_ms"] > 0
                            and r["unpack_ms"] == r["process_ms"]
                            for r in sync)
        # the duration histogram holds device time, observed at ready
        agg = fresh_recorder.aggregates()
        _cum, total, n = agg["duration"]["multistep"]
        assert n == len(blocks)
        assert total == pytest.approx(
            sum(r["device_ms"] for r in blocks) / 1e3)
        assert set(agg["loop_wait_s"]) == {"idle", "blocked"}

    async def test_idle_wait_is_counted(self, fresh_recorder):
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine
        eng = MockerEngine(MockEngineArgs(num_pages=64, page_size=4,
                                          max_num_seqs=4, max_context=256,
                                          speedup_ratio=100.0))
        try:
            await collect(eng, make_req([1, 2, 3], "i1", max_tokens=4))
            await asyncio.sleep(0.15)     # the loop waits for a request
            await collect(eng, make_req([4, 5, 6], "i2", max_tokens=4))
        finally:
            await eng.stop()
        assert fresh_recorder.loop_wait_s["idle"] >= 0.1
        assert fresh_recorder.loop_wait_s["blocked"] == 0.0
        from prometheus_client import generate_latest

        from dynamo_tpu.worker.metrics import WorkerMetrics
        wm = WorkerMetrics()
        wm.steptrace.attach(fresh_recorder.aggregates)
        out = generate_latest(wm.registry).decode()
        assert 'dynamo_worker_loop_wait_seconds_total{state="blocked"} 0.0' \
            in out
        assert 'dynamo_worker_loop_wait_seconds_total{state="idle"}' in out

    async def test_records_name_their_program(self, fresh_recorder):
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        eng = JaxEngine.random_init(
            ModelConfig.tiny(),
            JaxEngineConfig(num_pages=64, page_size=4, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=64,
                            min_prefill_bucket=4, decode_multistep=8))
        try:
            await collect(eng, make_req([1, 2, 3, 4, 5], "p1", max_tokens=12))
        finally:
            await eng.stop()
        recs = fresh_recorder.snapshot(limit=256)["records"]
        programs = {r["kind"]: r["program"] for r in recs}
        # the step program and its bucket, from the key the engine builds
        assert programs["prefill"].startswith("step[")
        assert programs["multistep"].startswith("multistep")
        assert all(r["program"].endswith("]") for r in recs)


class TestBlockBehindAPackedStep:
    async def test_the_step_keeps_its_kind_and_the_two_share_no_time(
            self, fresh_recorder):
        """An engine on the kernels (interpreted), one prompt an admission
        pass: the mixed step that admits the second request returns at its
        enqueue and keeps ``kind`` ``mixed`` and its ``packed[T,R]``
        program; the block behind it is ``chained`` behind ``mixed``, was
        enqueued before the step's result arrived, and the two records'
        ``device_ms`` share no time: they add up to the span from the
        step's enqueue to the block's arrival."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        eng = JaxEngine.random_init(
            ModelConfig.tiny(head_dim=128, num_heads=2, num_kv_heads=1,
                             hidden_size=128),
            JaxEngineConfig(num_pages=64, page_size=8, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=128,
                            min_prefill_bucket=16, min_prefill_seqs_bucket=2,
                            min_decode_bucket=2, decode_multistep=4,
                            max_prefill_seqs=1, attn_impl="pallas"))
        assert eng.padded_reason is None
        try:
            await asyncio.gather(
                collect(eng, make_req([1, 2, 3, 4, 5], "a", max_tokens=12)),
                collect(eng, make_req(range(20, 31), "b", max_tokens=6)))
        finally:
            await eng.stop()
        recs = fresh_recorder.snapshot(limit=64)["records"][::-1]
        (step, block), = [(a, b) for a, b in zip(recs, recs[1:])
                          if b["chained_behind"] == "mixed"]
        assert step["kind"] == "mixed"
        assert step["program"] == "packed[16,2]"
        assert not step["chained"] and step["chained_behind"] == ""
        # asynchronous where it chains: the call is the enqueue, the
        # result was fetched afterwards
        assert step["fetch_ms"] > 0.0
        assert step["dispatch_ms"] < step["device_ms"]
        assert block["kind"] == "multistep" and block["chained"]
        assert block["program"] == "multistep4[2]"
        assert block["t_unix"] < step["ready_unix"] <= block["ready_unix"]
        # the block's time starts where the step's result arrived ...
        assert block["device_ms"] == pytest.approx(
            (block["ready_unix"] - step["ready_unix"]) * 1e3, abs=5.0)
        # ... so the two sum to the span from the step's enqueue (the
        # device had nothing in front of it: the block before the step
        # had been fetched) to the block's arrival
        enqueue_unix = step["t_unix"] - step["dispatch_ms"] / 1e3
        assert step["device_ms"] + block["device_ms"] == pytest.approx(
            (block["ready_unix"] - enqueue_unix) * 1e3, abs=5.0)
        # every other record keeps the field empty but the blocks chained
        # behind a block
        assert {r["chained_behind"] for r in recs
                if r["kind"] != "multistep"} == {""}
        assert all(r["chained_behind"] == ("block" if r["chained"] else "")
                   for r in recs if r["kind"] == "multistep" and r is not block)


    async def test_a_packed_step_behind_a_packed_step(self, fresh_recorder):
        """A run of three packed steps (a prompt of three full chunks, a
        request waiting behind it): the two steps chained behind a step
        keep ``kind`` ``mixed`` and the program's name, carry ``chained``
        and ``chained_behind`` ``mixed``, were enqueued before the result
        in front of them arrived, and no two records of the run count the
        same time: ``device_ms`` of a chained step runs from the previous
        result's arrival to its own."""
        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        eng = JaxEngine.random_init(
            ModelConfig.tiny(head_dim=128, num_heads=2, num_kv_heads=1,
                             hidden_size=128),
            JaxEngineConfig(num_pages=64, page_size=8, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=128,
                            min_prefill_bucket=16, min_prefill_seqs_bucket=2,
                            min_decode_bucket=2, decode_multistep=4,
                            max_prefill_seqs=1, attn_impl="pallas"))
        try:
            await asyncio.gather(
                collect(eng, make_req([1, 2, 3, 4, 5], "a", max_tokens=20)),
                collect(eng, make_req(range(10, 60), "b", max_tokens=4)),
                collect(eng, make_req([7, 8, 9], "c", max_tokens=4)))
        finally:
            await eng.stop()
        recs = fresh_recorder.snapshot(limit=64)["records"][::-1]
        first = next(i for i, r in enumerate(recs) if r["kind"] == "mixed")
        run, block = recs[first:first + 3], recs[first + 3]
        assert [(r["kind"], r["program"], r["chained"], r["chained_behind"])
                for r in run] == [
            ("mixed", "packed[32,2]", False, ""),
            ("mixed", "packed[32,2]", True, "mixed"),
            ("mixed", "packed[32,2]", True, "mixed")]
        assert block["kind"] == "multistep" and block["chained"]
        assert block["chained_behind"] == "mixed"
        # b's chunk of 16 and a's token: the decode kernel's one row
        assert all(r["tokens_real"] == 17 and r["decode_kernel_rows"] == 1
                   for r in run)
        for a, b in zip(run + [block], (run + [block])[1:]):
            # enqueued while the one in front ran; its time starts where
            # that one's result arrived
            assert b["t_unix"] < a["ready_unix"] <= b["ready_unix"]
            assert b["device_ms"] == pytest.approx(
                (b["ready_unix"] - a["ready_unix"]) * 1e3, abs=5.0)
            assert a["fetch_ms"] > 0.0
        enqueue_unix = run[0]["t_unix"] - run[0]["dispatch_ms"] / 1e3
        assert sum(r["device_ms"] for r in run + [block]) == pytest.approx(
            (block["ready_unix"] - enqueue_unix) * 1e3, abs=8.0)
        # no program but the run's own compiled behind a chained step's
        # record: the hand-over's first call is on the first chained one
        assert run[1]["compile_ms"] > 0.0 and run[2]["compile_ms"] == 0.0


class TestHeadStart:
    """``Phase.in_thread(..., head_start=s)``: the call is on its thread
    before the loop does anything else, for at most ``s`` seconds."""

    async def test_the_call_returns_before_the_loops_other_work(self):
        rec = StepRecorder(capacity=8)
        order = []

        async def frames():
            order.append("frames")

        other = asyncio.ensure_future(frames())   # ready to run already
        ph = rec.phase("dispatch", 0, "multistep")
        out = await ph.in_thread(lambda x: order.append("dispatch") or x, 7,
                                 head_start=1.0)
        await other
        assert out == 7 and order == ["dispatch", "frames"]
        assert ph.ready > 0.0 and ph.ms >= 0.0

    async def test_a_call_that_outlasts_it_is_awaited(self):
        rec = StepRecorder(capacity=8)
        ran = []

        async def frames():
            ran.append(time.perf_counter())

        other = asyncio.ensure_future(frames())

        def slow():
            time.sleep(0.2)
            return time.perf_counter()

        ph = rec.phase("dispatch", 0, "multistep")
        t0 = time.perf_counter()
        done = await ph.in_thread(slow, head_start=0.02)
        await other
        # the loop was held for the head start, not for the call
        assert 0.015 <= ran[0] - t0 < 0.15 and done - t0 >= 0.2

    async def test_an_error_in_the_call_is_raised(self):
        rec = StepRecorder(capacity=8)

        def boom():
            raise ValueError("no")

        with pytest.raises(ValueError):
            await rec.phase("dispatch", 0, "multistep").in_thread(
                boom, head_start=0.5)


class TestDispatchStages:
    """The host's side of a dispatch taken apart (``steptrace.stage``): a
    fake engine marks its stages inside a threaded ``dispatch`` phase, as
    ``jax_engine.py`` does, and the ring keeps their sums."""

    @staticmethod
    def fake_dispatch(synchronous):
        from dynamo_tpu.engine.steptrace import stage

        def call():
            with stage("assemble"):
                time.sleep(0.004)
            with stage("upload"):
                time.sleep(0.002)
            with stage("assemble"):          # a helper's host half: sums
                time.sleep(0.003)
            with stage("upload"):
                time.sleep(0.001)
            with stage("enqueue"):
                time.sleep(0.002)
            if synchronous:
                with stage("wait"):
                    time.sleep(0.01)
            return "handle"
        return call

    @pytest.mark.parametrize("kind,synchronous", [("multistep", False),
                                                  ("mixed", True)])
    async def test_the_stages_add_up_to_the_dispatch(self, kind,
                                                     synchronous):
        rec = StepRecorder(capacity=8)
        ph = rec.phase("dispatch", rec.total, kind)
        assert await ph.in_thread(self.fake_dispatch(synchronous)) == "handle"
        r = rec.record(kind, dispatch_ms=ph.ms, enqueue=ph.t0, phase=ph)
        d = r.to_dict()
        # a stage opened twice keeps the sum of both
        assert d["assemble_ms"] >= 7.0 and d["upload_ms"] >= 3.0
        assert d["enqueue_ms"] >= 2.0
        five = sum(d[f"{k}_ms"] for k in ("handover", "assemble", "upload",
                                          "enqueue", "resume"))
        assert d["handover_ms"] > 0.0 and d["resume_ms"] > 0.0
        # what is left of dispatch_ms is the wait for the result
        wait_ms = d["dispatch_ms"] - five
        assert wait_ms >= -0.1
        if synchronous:
            assert ph.wait_s >= 0.01 and wait_ms >= ph.wait_s * 1000.0
            six = five + ph.wait_s * 1000.0
        else:
            assert ph.wait_s == 0.0
            six = five
        # the six stages cover the phase but for the bookkeeping between
        assert 0.9 * d["dispatch_ms"] <= six <= d["dispatch_ms"] + 0.1

    async def test_a_stage_outside_any_phase_is_a_null_context(self):
        from dynamo_tpu.engine import steptrace
        assert steptrace.stage("assemble") is steptrace._NO_STAGE
        with steptrace.stage("upload"):
            pass                      # priming, tests, bench.py: nothing
        rec = StepRecorder(capacity=8)
        ph = rec.phase("dispatch", 0, "decode")
        await ph.in_thread(lambda: None)
        # and no phase stays current on a thread once its call returned
        seen = await asyncio.to_thread(
            lambda: getattr(steptrace._current, "phase", None))
        assert seen is None

    async def test_a_stage_inside_a_stage_stays_the_outer_ones(self):
        from dynamo_tpu.engine.steptrace import stage
        rec = StepRecorder(capacity=8)

        def call():
            with stage("assemble"):
                with stage("upload"):
                    time.sleep(0.005)

        ph = rec.phase("dispatch", 0, "mixed")
        await ph.in_thread(call)
        assert ph.upload_s == 0.0 and ph.assemble_s >= 0.005
        assert ph.assemble_s * 1000.0 <= ph.ms

    async def test_fetch_resume_lands_on_its_own_record(self):
        """``note_unpack`` patches the record of the dispatch that was
        fetched, after the NEXT dispatch has been stamped."""
        rec = StepRecorder(capacity=8)
        first = rec.record("multistep", dispatch_ms=1.0)
        second = rec.record("multistep", dispatch_ms=1.0, chained=True)
        fetch = rec.phase("fetch", first.seq, "multistep")
        await fetch.in_thread(time.sleep, 0.001)
        rec.note_unpack(first, fetch.ms, 0.5, fetch.resume_ms)
        newest, older = rec.snapshot(limit=2)["records"]
        assert older["seq"] == first.seq and newest["seq"] == second.seq
        assert older["fetch_resume_ms"] == fetch.resume_ms > 0.0
        assert older["fetch_resume_ms"] <= older["fetch_ms"]
        assert newest["fetch_resume_ms"] == 0.0
        # a reused slot starts from zero again
        for _ in range(8):
            r = rec.record("decode")
        assert r.fetch_resume_ms == 0.0 and r.assemble_ms == 0.0

    async def test_the_histogram_renders_the_six_stages(self):
        from prometheus_client import CollectorRegistry, generate_latest
        from dynamo_tpu.engine.steptrace import STAGES
        from dynamo_tpu.worker.metrics import StepTraceCollector
        reg = CollectorRegistry()
        coll = StepTraceCollector(reg)
        name = "dynamo_worker_dispatch_stage_seconds"
        text = generate_latest(reg).decode()
        for st in STAGES:     # the schema is there before any dispatch
            assert f'{name}_count{{stage="{st}"}} 0.0' in text
        rec = StepRecorder(capacity=8)
        coll.attach(rec.aggregates)
        ph = rec.phase("dispatch", 0, "mixed")
        await ph.in_thread(self.fake_dispatch(True))
        rec.record("mixed", dispatch_ms=ph.ms, phase=ph)
        ph = rec.phase("dispatch", 1, "multistep")
        await ph.in_thread(self.fake_dispatch(False))
        rec.record("multistep", dispatch_ms=ph.ms, phase=ph)
        text = generate_latest(reg).decode()
        assert len(STAGES) == 6
        for st in STAGES:
            # ``wait`` opened in the synchronous dispatch alone
            n = 1.0 if st == "wait" else 2.0
            assert f'{name}_count{{stage="{st}"}} {n}' in text
        assert f'{name}_bucket{{le="+Inf",stage="assemble"}} 2.0' in text

    async def test_a_call_inside_its_head_start_resumes_at_once(self):
        rec = StepRecorder(capacity=8)
        ph = rec.phase("dispatch", 0, "multistep")
        await ph.in_thread(time.sleep, 0.002, head_start=0.5)
        # the loop's thread was waiting for it: no other callback ran
        # between the call's return and the coroutine's next line
        assert 0.0 < ph.resume_ms < 5.0
        assert ph.handover_ms < 5.0 and ph.ms >= 2.0

    async def test_a_late_resume_is_said_in_the_log(self, caplog,
                                                    monkeypatch):
        from dynamo_tpu.engine import steptrace
        from dynamo_tpu.utils import aio
        monkeypatch.setattr(aio, "LAG_WARN_S", 0.01)
        rec = StepRecorder(capacity=8)

        async def hog():              # the loop is away when the call ends
            time.sleep(0.05)

        ph = rec.phase("fetch", 41, "multistep")
        with caplog.at_level("WARNING", logger=steptrace.__name__):
            other = asyncio.ensure_future(hog())
            await ph.in_thread(time.sleep, 0.001)
            await other
        assert ph.resume_ms > 10.0
        (line,) = [r.getMessage() for r in caplog.records]
        assert "resume of seq 41" in line and "loop.fetch" in line
        caplog.clear()
        with caplog.at_level("WARNING", logger=steptrace.__name__):
            await rec.phase("dispatch", 42, "mixed").in_thread(
                time.sleep, 0.001)
        assert not caplog.records


async def test_the_event_loops_heartbeat_says_when_it_was_late(caplog,
                                                               monkeypatch):
    from dynamo_tpu.http.metrics import FrontendMetrics
    from dynamo_tpu.utils import aio
    from dynamo_tpu.worker.metrics import WorkerMetrics
    monkeypatch.setattr(aio, "LAG_PERIOD_S", 0.01)
    monkeypatch.setattr(aio, "LAG_WARN_S", 0.03)
    # one name on both /metrics
    for metrics in (FrontendMetrics(), WorkerMetrics()):
        assert b"dynamo_event_loop_lag_seconds_count 0.0" in generate_latest(
            metrics.registry)
    seen = []
    with caplog.at_level("WARNING", logger=aio.__name__):
        task = asyncio.ensure_future(aio.watch_loop_lag(seen.append, "worker"))
        await asyncio.sleep(0.035)        # three or so beats on time
        on_time = len(seen)
        assert on_time >= 2 and not caplog.records
        time.sleep(0.08)                  # the loop is away
        await asyncio.sleep(0.02)
        await aio.reap_task(task)
    assert max(seen[:on_time]) < 0.03 and max(seen) >= 0.05
    (line,) = [r.getMessage() for r in caplog.records]
    assert "worker's loop was away" in line


def test_module_names_the_benchmark_matches_on():
    """``benchmarks/layer_metrics/step.decode_hbm_share.py`` finds the
    decode programs on the trace's ``XLA Modules`` line by name: the
    one-step program and the fused block (a closure: ``unknown``) keep the
    names they had when the benchmark was accepted, whatever scopes and
    kernel names are added inside them; the token-packed prefill-carrying
    program has a name the decode reader does NOT match (it starts with
    neither), so a prefill step is never counted as decode."""
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.models.config import ModelConfig

    class Lowered(Exception):
        pass

    # the Pallas kernels' geometry (head_dim % 128, page_size % 8); only
    # lowered here, in interpret mode, never run
    eng = JaxEngine.random_init(
        ModelConfig.tiny(head_dim=128, num_heads=2, num_kv_heads=1,
                         hidden_size=128),
        JaxEngineConfig(num_pages=16, page_size=8, max_num_seqs=4,
                        max_prefill_chunk=16, max_context=64,
                        attn_impl="pallas", decode_multistep=8))
    names = {}

    def spy(label, fn):
        def call(*args, **kw):
            # "module @jit__step_impl attributes {...": lowered, never run
            names[label] = fn.lower(*args, **kw).as_text().split(None, 2)[1]
            raise Lowered
        return call

    assert eng.padded_reason is None               # the Pallas path packs
    eng._jit_step = spy("step", eng._jit_step)
    eng._jit_packed = spy("packed", eng._jit_packed)
    eng._jit_ms[8] = spy("multistep", eng._get_jit_multistep(8))
    B, S = 4, 8
    arrays = {"toks": np.zeros((B, S), np.int32),
              "pos": np.tile(np.arange(S, dtype=np.int32), (B, 1)),
              "table": np.zeros((B, eng.table_width), np.int32),
              "total": np.full(B, S, np.int32),
              "new": np.full(B, S, np.int32),
              "temp": np.zeros(B, np.float32),
              "top_k": np.zeros(B, np.int32),
              "top_p": np.ones(B, np.float32)}
    packed = dict(arrays, toks=np.zeros((1, 32), np.int32),
                  pos=np.zeros((1, 32), np.int32))
    # a padded mixed step IS the one-step program
    for kind, a in (("step", arrays), ("mixed", arrays),
                    ("packed", packed)):
        with pytest.raises(Lowered):
            eng.execute_arrays(kind, a, 0)
    with pytest.raises(Lowered):
        eng.prime_multistep(B, widths=[8])
    assert names == {"step": "@jit__step_impl",
                     "packed": "@jit__packed_step_impl",
                     "multistep": "@jit__unknown"}
    # what the decode reader matches (DECODE_PROGRAMS in
    # benchmarks/layer_metrics/step.decode_hbm_share.py) leaves it out
    assert not names["packed"][1:].startswith(("jit__step_impl",
                                               "jit__unknown"))


class TestProfileHook:
    async def test_profile_carries_the_loops_phases_by_seq(
            self, fresh_recorder):
        """``POST /v1/profile`` on the worker's system server: the profile
        it writes holds ``loop.plan``/``loop.dispatch``/``loop.fetch``
        events whose ``seq`` are the ring's, each dispatch under its
        kind; one profile at a time."""
        import glob

        from jax.profiler import ProfileData

        from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.models.config import ModelConfig
        from dynamo_tpu.runtime.system_server import SystemServer
        eng = JaxEngine.random_init(
            ModelConfig.tiny(),
            JaxEngineConfig(num_pages=64, page_size=4, max_num_seqs=4,
                            max_prefill_chunk=16, max_context=64,
                            min_prefill_bucket=4, decode_multistep=8))
        server = await SystemServer(host="127.0.0.1", port=0,
                                    steptrace=eng.steptrace).start()
        url = f"http://127.0.0.1:{server.port}/v1/profile"
        try:
            # warm the buckets, so the profile holds steady-state steps
            await collect(eng, make_req([1, 2, 3, 4, 5], "w", max_tokens=20))
            first_seq = fresh_recorder.total
            async with aiohttp.ClientSession() as http:
                async def post(body):
                    async with http.post(url, json=body) as r:
                        return r.status, await r.json()
                for bad in ({"seconds": 0}, {"seconds": 1e9},
                            {"seconds": "x"}):
                    assert (await post(bad))[0] == 400
                taking = asyncio.ensure_future(post({"seconds": 1.0}))
                await asyncio.sleep(0.3)
                assert (await post({"seconds": 0.1}))[0] == 409
                await collect(eng, make_req([9, 8, 7, 6, 5], "p",
                                            max_tokens=20))
                status, reply = await taking
            assert status == 200 and reply["seconds"] == 1.0
            assert reply["stop_unix"] - reply["start_unix"] >= 1.0
        finally:
            await server.stop()
            await eng.stop()
        import shutil
        paths = glob.glob(os.path.join(reply["dir"], "**", "*.xplane.pb"),
                          recursive=True)
        assert paths, reply
        events = []
        for plane in ProfileData.from_file(paths[0]).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("loop."):
                        stats = dict(ev.stats)
                        # an empty kind (a plan, a wait) leaves no stat
                        events.append((ev.name, stats["seq"],
                                       stats.get("kind", "")))
        shutil.rmtree(reply["dir"], ignore_errors=True)
        ring = {r["seq"]: r["kind"]
                for r in fresh_recorder.snapshot(limit=256)["records"]
                if r["seq"] >= first_seq}
        assert ring
        for phase in ("loop.plan", "loop.dispatch", "loop.fetch",
                      "loop.process"):
            seqs = {seq for name, seq, _k in events if name == phase}
            assert seqs & set(ring), phase
        # every dispatch and fetch annotation of a recorded dispatch
        # carries that record's kind
        for name, seq, kind in events:
            if name in ("loop.dispatch", "loop.fetch") and seq in ring:
                assert kind == ring[seq], (name, seq)
        # the wait for the second request shows as loop.idle
        assert any(name == "loop.idle" for name, _s, _k in events)


def test_xplane_scopes_reads_the_scope_from_event_metadata(tmp_path):
    """``tools/xplane_scopes.py`` on a small synthetic trace: the scope is
    the ``tf_op`` stat of the operation's event METADATA on the device
    plane's ``XLA Ops`` line (where a TPU profile keeps it); loops are
    left out, operations are summed and sorted by time."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    for i, name in enumerate(("hlo_category", "tf_op", "source"), start=1):
        plane.stat_metadata[i].name = name

    def op(mid, display, category, scope="", source=""):
        md = plane.event_metadata[mid]
        md.name, md.display_name = f"%{display} = ...", display
        for stat_id, value in ((1, category), (2, scope), (3, source)):
            if value:
                md.stats.add(metadata_id=stat_id, str_value=value)
    op(1, "copy.124", "data formatting",
       "jit(<unknown>)/while/body/layer.kv_write/scatter:", "attention.py:77")
    op(2, "fusion.181", "convolution fusion",
       "jit(_packed_step_impl)/while/body/layer.ffn/dot_general:")
    op(3, "while.38", "while")
    line = plane.lines.add(name="XLA Ops")
    for mid, ps in ((3, 9_000_000), (1, 4_000_000), (2, 3_000_000),
                    (1, 4_000_000)):
        line.events.add(metadata_id=mid, duration_ps=ps)
    space.planes.add(name="/host:CPU").lines.add(name="python3")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import xplane_scopes
    rows = xplane_scopes.top_ops(str(tmp_path))
    assert [(r[2], r[1]) for r in rows] == [("copy.124", 2),
                                            ("fusion.181", 1)]
    assert rows[0][0] == pytest.approx(8e-6)
    assert rows[0][4].endswith("layer.kv_write/scatter")
    assert rows[0][5] == "attention.py:77" and rows[1][5] == ""
    assert xplane_scopes.main([str(path), "--top", "1"]) == 0
