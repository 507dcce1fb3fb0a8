"""Tests for the serving engine: page allocator, scheduler, JaxEngine e2e.

Model for coverage: the reference's engine-behavior tests live inside vLLM;
its own suites test the mocker scheduler (``lib/llm/src/mocker/scheduler.rs``)
and KV manager. Here the engine is native, so these tests cover admission,
chunked prefill, prefix reuse, eviction events, preemption, stop conditions,
and streamed generation on the tiny model (CPU).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.pages import OutOfPages, PageAllocator
from dynamo_tpu.engine.scheduler import (
    DecodeBatch,
    Phase,
    PrefillBatch,
    Scheduler,
    SchedulerConfig,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tokens import TokenBlockSequence


# ---------------------------------------------------------------- allocator

def seq_hashes(tokens, page_size=4):
    return TokenBlockSequence(tokens, block_size=page_size).blocks


class TestPageAllocator:
    def test_allocate_and_free_cycle(self):
        a = PageAllocator(num_pages=5, page_size=4)
        pages = a.allocate(4)
        assert sorted(pages) == [1, 2, 3, 4]
        assert a.num_free == 0
        with pytest.raises(OutOfPages):
            a.allocate(1)
        a.release(pages)
        assert a.num_free == 4

    def test_commit_emits_stored_event(self):
        a = PageAllocator(num_pages=5, page_size=4)
        [p] = a.allocate(1)
        blk = seq_hashes([1, 2, 3, 4])[0]
        a.commit(p, blk.block_hash, blk.local_hash, None)
        evs = a.drain_events()
        assert len(evs) == 1
        assert evs[0].stored_blocks[0].block_hash == blk.block_hash
        assert not a.drain_events()

    def test_prefix_match_revives_lru(self):
        a = PageAllocator(num_pages=5, page_size=4)
        blocks = seq_hashes([1, 2, 3, 4, 5, 6, 7, 8])
        pages = a.allocate(2)
        for p, b in zip(pages, blocks):
            a.commit(p, b.block_hash, b.local_hash,
                     b.parent_hash if b.position else None)
        a.release(pages)  # refcount 0 -> LRU, still matchable
        assert a.peek_prefix([b.block_hash for b in blocks]) == 2
        m = a.match_prefix([b.block_hash for b in blocks])
        assert m.page_ids == pages

    def test_eviction_emits_removed_and_breaks_match(self):
        a = PageAllocator(num_pages=3, page_size=4)
        blocks = seq_hashes([1, 2, 3, 4, 5, 6, 7, 8])
        pages = a.allocate(2)
        for p, b in zip(pages, blocks):
            a.commit(p, b.block_hash, b.local_hash,
                     b.parent_hash if b.position else None)
        a.release(pages)
        a.drain_events()
        # allocating both pages again must evict both cached blocks (LRU)
        a.allocate(2)
        evs = a.drain_events()
        removed = [h for e in evs for h in e.removed_block_hashes]
        assert set(removed) == {b.block_hash for b in blocks}
        m = a.match_prefix([b.block_hash for b in blocks])
        assert m.num_pages == 0

    def test_duplicate_commit_frees_quietly(self):
        a = PageAllocator(num_pages=4, page_size=4)
        blk = seq_hashes([1, 2, 3, 4])[0]
        [p1] = a.allocate(1)
        [p2] = a.allocate(1)
        a.commit(p1, blk.block_hash, blk.local_hash, None)
        a.commit(p2, blk.block_hash, blk.local_hash, None)
        evs = a.drain_events()
        assert sum(len(e.stored_blocks) for e in evs) == 1  # registered once
        a.release([p2])  # duplicate page frees, registry untouched
        assert a.match_prefix([blk.block_hash]).page_ids == [p1]

    def test_clear_evicts_cached(self):
        a = PageAllocator(num_pages=3, page_size=4)
        blk = seq_hashes([1, 2, 3, 4])[0]
        [p] = a.allocate(1)
        a.commit(p, blk.block_hash, blk.local_hash, None)
        a.release([p])
        a.clear()
        evs = a.drain_events()
        assert any(e.all_blocks_cleared for e in evs)
        assert a.match_prefix([blk.block_hash]).num_pages == 0
        assert a.num_free == 2


# ---------------------------------------------------------------- scheduler

def make_req(tokens, rid="r1", max_tokens=8, **kw):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens, **kw),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[0])


def advance(sched, plan):
    """on_step_done + the token append the engine would do for last chunks."""
    sched.on_step_done(plan)
    if hasattr(plan, "chunks"):
        for c in plan.chunks:
            if c.is_last:
                c.seq.tokens.append(9)
                c.seq.generated.append(9)
        for s in getattr(plan, "decode_seqs", ()):
            s.tokens.append(9)
            s.generated.append(9)
    else:
        for s in plan.seqs:
            s.tokens.append(9)
            s.generated.append(9)


class TestScheduler:
    def make(self, num_pages=17, page_size=4, **cfg):
        alloc = PageAllocator(num_pages, page_size)
        base = dict(max_num_seqs=4, max_prefill_chunk=8)
        base.update(cfg)
        return Scheduler(alloc, SchedulerConfig(**base)), alloc

    def test_chunked_prefill_then_decode(self):
        sched, _ = self.make()
        sched.add_request(make_req(range(1, 13), "a"))  # 12 tokens, budget=8
        p1 = sched.schedule()
        assert isinstance(p1, PrefillBatch) and len(p1.chunks) == 1
        assert p1.chunks[0].length == 8 and not p1.chunks[0].is_last
        sched.on_step_done(p1)
        p2 = sched.schedule()
        assert isinstance(p2, PrefillBatch)
        assert p2.chunks[0].length == 4 and p2.chunks[0].is_last
        sched.on_step_done(p2)
        seq = p2.chunks[0].seq
        assert seq.phase == Phase.RUNNING
        seq.tokens.append(99)  # engine appends sampled token
        seq.generated.append(99)
        d = sched.schedule()
        assert isinstance(d, DecodeBatch) and d.seqs == [seq]

    def test_prefill_decode_alternation(self):
        # the legacy split path (mixed_batch=False): strict alternation.
        # Mixed-dispatch scheduling is covered in test_mixed_batch.py.
        sched, _ = self.make(mixed_batch=False)
        sched.add_request(make_req(range(1, 5), "a"))
        advance(sched, sched.schedule())
        sched.add_request(make_req(range(1, 5), "b"))
        kinds = []
        for _ in range(2):
            plan = sched.schedule()
            kinds.append(type(plan))
            advance(sched, plan)
        assert set(kinds) == {PrefillBatch, DecodeBatch}

    def test_concurrent_prompts_share_prefill_steps(self):
        """Four waiting prompts must not serialize into four prefill steps:
        the token budget packs them two per step."""
        sched, _ = self.make()
        for i in range(4):
            sched.add_request(make_req(range(10 * i + 1, 10 * i + 5), f"s{i}"))
        p1 = sched.schedule()
        assert isinstance(p1, PrefillBatch)
        assert [c.length for c in p1.chunks] == [4, 4]  # budget 8 = 2 prompts
        assert all(c.is_last for c in p1.chunks)
        advance(sched, p1)
        # alternation gives decode a turn, then the remaining two prefill
        d = sched.schedule()
        assert isinstance(d, DecodeBatch) and len(d.seqs) == 2
        advance(sched, d)
        # with mixed dispatch (default) the remaining two prefill chunks
        # ride ONE step together with the running decode rows
        p2 = sched.schedule()
        assert len(p2.chunks) == 2
        assert {c.seq.request.request_id for c in p2.chunks} == {"s2", "s3"}

    def test_decode_cadence_bounded_during_long_prefill(self):
        """A long prompt arriving must not starve running decodes: on the
        legacy split path, prefill chunks and decode steps alternate
        one-for-one (mixed dispatch advances both per step instead —
        test_mixed_batch.py)."""
        sched, _ = self.make(mixed_batch=False)
        sched.add_request(make_req(range(1, 5), "short"))
        advance(sched, sched.schedule())  # short is RUNNING
        sched.add_request(make_req(range(100, 124), "long"))  # 24 tok = 3 chunks
        kinds = []
        for _ in range(6):
            plan = sched.schedule()
            kinds.append(PrefillBatch if isinstance(plan, PrefillBatch)
                         else DecodeBatch)
            advance(sched, plan)
        # strict one-for-one alternation (either phase), 3 of each
        assert kinds.count(PrefillBatch) == 3
        assert all(a != b for a, b in zip(kinds, kinds[1:]))

    def test_prefix_reuse_on_second_request(self):
        sched, alloc = self.make()
        prompt = list(range(1, 13))
        sched.add_request(make_req(prompt, "a"))
        plan = sched.schedule()
        sched.on_step_done(plan)
        plan = sched.schedule()
        sched.on_step_done(plan)
        sched.finish(plan.chunks[0].seq)  # pages -> LRU, 3 committed blocks
        sched.add_request(make_req(prompt, "b"))
        plan = sched.schedule()
        assert isinstance(plan, PrefillBatch)
        chunk = plan.chunks[0]
        # 12 tokens = 3 blocks cached, but at least 1 token must recompute:
        # usable cached = 8 tokens (2 full pages)
        assert chunk.seq.cached_tokens == 8
        assert chunk.start == 8 and chunk.length == 4

    def test_preemption_on_page_pressure(self):
        sched, alloc = self.make(num_pages=4, page_size=4)  # 3 usable pages
        # two 3-token prompts (1 page each; admission looks one step
        # ahead, and that step still fits the page), then both need a
        # 2nd page
        sched.add_request(make_req(range(1, 4), "a", max_tokens=16))
        sched.add_request(make_req(range(11, 14), "b", max_tokens=16))
        plan = sched.schedule()
        assert isinstance(plan, PrefillBatch) and len(plan.chunks) == 2
        advance(sched, plan)  # both RUNNING at len 4
        plan = sched.schedule()
        assert isinstance(plan, DecodeBatch) and len(plan.seqs) == 2
        advance(sched, plan)  # both at len 5 -> need page 2
        # decode: one free page left; "a" (older) gets it, "b" is preempted
        plan = sched.schedule()
        assert isinstance(plan, DecodeBatch)
        assert [s.request.request_id for s in plan.seqs] == ["a"]
        assert sched.num_preemptions == 1
        assert len(sched.waiting) == 1

    def test_metrics_shape(self):
        sched, _ = self.make()
        m = sched.metrics()
        assert m.worker_stats.request_total_slots == 4
        assert m.kv_stats.kv_total_blocks == 16


# ------------------------------------------------------------------ engine

def tiny_engine(**kw):
    cfg = ModelConfig.tiny()
    defaults = dict(num_pages=64, page_size=4, max_num_seqs=4,
                    max_prefill_chunk=16, max_context=64,
                    min_prefill_bucket=4)
    defaults.update(kw)
    return JaxEngine.random_init(cfg, JaxEngineConfig(**defaults))


async def collect(engine, req):
    frames = []
    async for out in engine.generate(req):
        frames.append(out)
    return frames


class TestLoopDeath:
    async def test_loop_death_errors_streams_instead_of_hanging(self):
        """An exception in the loop's HOST-side bookkeeping (outside the
        per-plan try blocks) must terminate every open stream with an
        ERROR frame — not leave them waiting on a queue nobody fills."""
        eng = tiny_engine()
        try:
            boom = RuntimeError("bookkeeping bug")

            def bad_process(plan, *a, **k):
                raise boom

            eng._process = bad_process
            req = make_req([1, 2, 3, 4, 5], "r1", max_tokens=4)
            req.eos_token_ids = []
            frames = await asyncio.wait_for(collect(eng, req), timeout=20)
            assert frames[-1].finish_reason == FinishReason.ERROR
            assert "engine loop died" in frames[-1].error
            assert eng._loop_task.done()
            # a request arriving AFTER the death must fail fast too, not
            # enqueue onto a scheduler no loop will ever drain
            late = make_req([1, 2, 3], "late", max_tokens=2)
            frames2 = await asyncio.wait_for(collect(eng, late), timeout=10)
            assert frames2[-1].finish_reason == FinishReason.ERROR
            assert "loop is dead" in frames2[-1].error
        finally:
            await eng.stop()


class TestJaxEngine:
    async def test_generates_max_tokens(self):
        eng = tiny_engine()
        try:
            req = make_req([1, 2, 3, 4, 5], "r1", max_tokens=6)
            req.eos_token_ids = []  # random weights may emit any token
            frames = await collect(eng, req)
            toks = [t for f in frames for t in f.token_ids]
            assert len(toks) == 6
            final = frames[-1]
            assert final.finish_reason == FinishReason.LENGTH
            assert final.prompt_tokens == 5
            assert final.completion_tokens == 6
        finally:
            await eng.stop()

    async def test_greedy_determinism_and_prefix_cache(self):
        eng = tiny_engine()
        try:
            req1 = make_req(list(range(1, 10)), "r1", max_tokens=5)
            req1.eos_token_ids = []
            f1 = await collect(eng, req1)
            req2 = make_req(list(range(1, 10)), "r2", max_tokens=5)
            req2.eos_token_ids = []
            f2 = await collect(eng, req2)
            t1 = [t for f in f1 for t in f.token_ids]
            t2 = [t for f in f2 for t in f.token_ids]
            assert t1 == t2  # greedy => identical
            assert f2[-1].cached_tokens == 8  # 9-token prompt, 2 full pages
        finally:
            await eng.stop()

    async def test_concurrent_requests(self):
        eng = tiny_engine()
        try:
            reqs = []
            for i in range(4):
                r = make_req([i + 1, i + 2, i + 3, i + 4], f"c{i}", max_tokens=4)
                r.eos_token_ids = []
                reqs.append(r)
            results = await asyncio.gather(*[collect(eng, r) for r in reqs])
            for frames in results:
                toks = [t for f in frames for t in f.token_ids]
                assert len(toks) == 4
        finally:
            await eng.stop()

    async def test_stop_token(self):
        eng = tiny_engine()
        try:
            # discover greedy first token, then stop on it
            probe = make_req([5, 6, 7], "p", max_tokens=1)
            probe.eos_token_ids = []
            first = (await collect(eng, probe))[-1].token_ids
            req = make_req([5, 6, 7], "s", max_tokens=8)
            req.eos_token_ids = []
            req.stop_conditions.stop_token_ids = first
            frames = await collect(eng, req)
            assert frames[-1].finish_reason == FinishReason.STOP
            assert frames[-1].completion_tokens == 1
        finally:
            await eng.stop()

    async def test_oversized_prompt_fails_cleanly(self):
        eng = tiny_engine()
        try:
            req = make_req(list(range(100)), "big")
            frames = await collect(eng, req)
            assert frames[-1].finish_reason == FinishReason.ERROR
        finally:
            await eng.stop()

    async def test_kv_events_published(self):
        eng = tiny_engine()
        events = []
        eng.kv_event_cb = events.extend
        try:
            req = make_req(list(range(1, 10)), "e", max_tokens=4)
            req.eos_token_ids = []
            await collect(eng, req)
            stored = [b for e in events for b in e.stored_blocks]
            assert stored  # prompt blocks were committed and published
        finally:
            await eng.stop()

    async def test_cancel_mid_stream_and_while_waiting(self):
        class Ctx:
            cancelled = False

        eng = tiny_engine()
        try:
            ctx = Ctx()
            req = make_req([1, 2, 3], "cx", max_tokens=1000)
            req.eos_token_ids = []
            frames = []
            async for out in eng.generate(req, ctx=ctx):
                frames.append(out)
                ctx.cancelled = True  # cancel after the first frame
            assert frames[-1].finish_reason == FinishReason.CANCELLED

            # cancel while still WAITING (queue head blocked is hard to force;
            # cancelling before the loop picks it up exercises the reap path)
            ctx2 = Ctx()
            ctx2.cancelled = True
            req2 = make_req([4, 5, 6], "cw", max_tokens=1000)
            req2.eos_token_ids = []
            frames2 = [f async for f in eng.generate(req2, ctx=ctx2)]
            assert frames2[-1].finish_reason == FinishReason.CANCELLED
        finally:
            await eng.stop()

    async def test_preemption_resume_correctness(self):
        """A preempted sequence must resume and produce the same greedy
        tokens it would have produced without contention."""
        solo = tiny_engine()
        try:
            ref = make_req(list(range(11, 18)), "solo", max_tokens=9)
            ref.eos_token_ids = []
            want = [t for f in await collect(solo, ref) for t in f.token_ids]
        finally:
            await solo.stop()

        # 7 usable pages; each request eventually needs 4 -> contention
        eng = tiny_engine(num_pages=8, max_context=32)
        try:
            a = make_req(list(range(1, 8)), "a", max_tokens=9)
            b = make_req(list(range(11, 18)), "b", max_tokens=9)
            a.eos_token_ids = []
            b.eos_token_ids = []
            ra, rb = await asyncio.gather(collect(eng, a), collect(eng, b))
            for frames in (ra, rb):
                toks = [t for f in frames for t in f.token_ids]
                assert len(toks) == 9
                assert frames[-1].finish_reason == FinishReason.LENGTH
            got = [t for f in rb for t in f.token_ids]
            assert got == want
        finally:
            await eng.stop()

    async def test_engine_stats(self):
        eng = tiny_engine()
        try:
            m = eng.stats()
            assert m.kv_stats.kv_total_blocks == 63
        finally:
            await eng.stop()


class TestPipelinedDecode:
    """Chained decode (step N+1 consumes step N's on-device token) must be
    token-for-token identical to step-at-a-time execution under greedy
    sampling, across staggered stream ends and prefix-cache revives."""

    async def _run(self, pipeline: bool):
        # decode_multistep=1: this class tests the per-step CHAIN machinery
        # specifically (the fused block path would supersede it; it has its
        # own suite in tests/test_multistep.py)
        eng = tiny_engine(pipeline_decode=pipeline, decode_multistep=1)
        try:
            reqs = []
            for i, n in enumerate((3, 7, 12)):
                r = make_req([i + 1, i + 2, i + 3, i + 4, i + 5],
                             f"p{i}", max_tokens=n)
                r.eos_token_ids = []
                reqs.append(r)
            results = await asyncio.gather(*[collect(eng, r) for r in reqs])
            toks = [[t for f in frames for t in f.token_ids]
                    for frames in results]
            return toks, eng.chained_decode_steps
        finally:
            await eng.stop()

    async def test_equivalence_and_chaining_happened(self):
        toks_on, chained = await self._run(True)
        toks_off, chained_off = await self._run(False)
        assert toks_on == toks_off
        assert [len(t) for t in toks_on] == [3, 7, 12]
        assert chained > 0          # the pipelined run actually chained
        assert chained_off == 0

    async def test_chained_page_growth_across_boundary(self):
        # page_size=4: decode crosses page boundaries repeatedly while
        # chained, exercising the +1 look-ahead growth of ``Scheduler.plan_behind``
        eng = tiny_engine(pipeline_decode=True, num_pages=32,
                          decode_multistep=1)
        try:
            r = make_req([1, 2, 3], "g", max_tokens=21)
            r.eos_token_ids = []
            frames = await collect(eng, r)
            toks = [t for f in frames for t in f.token_ids]
            assert len(toks) == 21
            assert frames[-1].finish_reason == FinishReason.LENGTH
            assert eng.chained_decode_steps > 10
        finally:
            await eng.stop()

    async def test_exclusive_work_flushes_pending(self):
        # run_exclusive while a chained stream is mid-flight: the loop must
        # flush the pending step before running the exclusive fn
        eng = tiny_engine(pipeline_decode=True)
        try:
            r = make_req([9, 8, 7], "x", max_tokens=16)
            r.eos_token_ids = []
            task = asyncio.ensure_future(collect(eng, r))
            await asyncio.sleep(0.2)
            seen = await eng.run_exclusive(lambda e: e.allocator.num_free, eng)
            assert isinstance(seen, int)
            frames = await task
            assert len([t for f in frames for t in f.token_ids]) == 16
        finally:
            await eng.stop()


class TestPrefillFetchSkipping:
    async def test_intermediate_chunks_skip_readback(self):
        """Only prefill steps containing a LAST chunk fetch results; the
        intermediate chunks of a long prompt dispatch without the
        device->host round trip (their sampled values are never read)."""
        eng = tiny_engine(max_prefill_chunk=4, min_prefill_bucket=4,
                          num_pages=32, max_context=64)
        fetches = {"n": 0, "blocks": 0}
        orig = eng.fetch_packed
        orig_block = eng.fetch_packed_block

        def counting(packed):
            fetches["n"] += 1
            return orig(packed)

        def counting_block(handle):
            fetches["blocks"] += 1
            return orig_block(handle)

        eng.fetch_packed = counting
        eng.fetch_packed_block = counting_block
        try:
            # 14-token prompt / 4-token chunks -> 4 prefill steps, only the
            # final one needs a fetch; the 2 remaining decode tokens ride
            # one fused block (or 2 per-step fetches when fusion narrows)
            r = make_req(list(range(1, 15)), "long", max_tokens=3)
            r.eos_token_ids = []
            frames = await collect(eng, r)
            toks = [t for f in frames for t in f.token_ids]
            assert len(toks) == 3
            # per-step fetches: exactly 1 — the last prefill chunk (which
            # samples token 1); the three intermediate prefill chunks
            # fetched nothing. Tokens 2+3 (remaining budget 2) ride ONE
            # fused block fetch.
            assert fetches["n"] == 1, fetches
            assert fetches["blocks"] == 1, fetches
        finally:
            await eng.stop()

    async def test_long_prompt_tokens_unchanged(self):
        """Greedy output across chunked prefill must be identical to a
        one-chunk prefill of the same prompt (fetch skipping must not
        perturb anything)."""
        prompt = list(range(1, 15))

        async def run(chunk):
            eng = tiny_engine(max_prefill_chunk=chunk,
                              min_prefill_bucket=4, num_pages=32,
                              max_context=64)
            try:
                r = make_req(prompt, "p", max_tokens=4)
                r.eos_token_ids = []
                frames = await collect(eng, r)
                return [t for f in frames for t in f.token_ids]
            finally:
                await eng.stop()

        assert await run(4) == await run(16)
