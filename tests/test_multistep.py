"""Fused multi-step decode: N decode steps per jitted dispatch with
on-device sampling and stop checks (engine/jax_engine._multistep_impl +
engine/scheduler.plan_multistep).

The contract under test: the fused path is BIT-IDENTICAL to per-step
decode — greedy and fixed-seed sampling, EOS / max_tokens / stop-token
stops landing mid-block, cancellation mid-block — while costing ~M/width
dispatches for M tokens (the dispatch-count regression guard), and the
scheduler narrows the fuse width wherever the device could not honor the
semantics (stop strings, budgets, page pressure, penalties/guided).
"""

import asyncio

import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.pages import PageAllocator
from dynamo_tpu.engine.scheduler import (
    DecodeBatch,
    MultiStepBatch,
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


def make_req(tokens, rid="r1", max_tokens=8, eos=(), samp=None, **stop_kw):
    return PreprocessedRequest(
        token_ids=list(tokens), request_id=rid,
        stop_conditions=StopConditions(max_tokens=max_tokens, **stop_kw),
        sampling_options=samp or SamplingOptions(temperature=0.0),
        eos_token_ids=list(eos))


def tiny_engine(**kw):
    cfg = ModelConfig.tiny()
    defaults = dict(num_pages=64, page_size=4, max_num_seqs=4,
                    max_prefill_chunk=16, max_context=64,
                    min_prefill_bucket=4)
    defaults.update(kw)
    return JaxEngine.random_init(cfg, JaxEngineConfig(**defaults))


async def collect(engine, req, ctx=None):
    frames = []
    async for out in engine.generate(req, ctx=ctx):
        frames.append(out)
    return frames


def toks_of(frames):
    return [t for f in frames for t in f.token_ids]


async def run_many(reqs, **engine_kw):
    """Run requests concurrently on a fresh engine; returns
    ([tokens per req], [finish reason per req], engine counters)."""
    eng = tiny_engine(**engine_kw)
    try:
        results = await asyncio.gather(*[collect(eng, r) for r in reqs])
        return ([toks_of(f) for f in results],
                [f[-1].finish_reason for f in results],
                {"dispatches": eng.decode_dispatches,
                 "blocks": eng.multistep_blocks})
    finally:
        await eng.stop()


def reqs_staggered(samp=None, lens=(5, 11, 18), eos=(), **stop_kw):
    out = []
    for i, n in enumerate(lens):
        out.append(make_req([i + 1, i + 2, i + 3, i + 4, i + 5], f"m{i}",
                            max_tokens=n, eos=eos,
                            samp=samp() if samp else None, **stop_kw))
    return out


class TestTokenParity:
    """Fused vs per-step must be token-for-token identical."""

    async def _both(self, mk_reqs, **kw):
        fused_t, fused_r, c = await run_many(mk_reqs(), decode_multistep=8,
                                             **kw)
        step_t, step_r, c0 = await run_many(mk_reqs(), decode_multistep=1,
                                            **kw)
        assert c["blocks"] > 0          # the fused path actually ran
        assert c0["blocks"] == 0
        assert fused_t == step_t
        assert fused_r == step_r
        return fused_t, fused_r, c

    async def test_greedy_staggered_lengths(self):
        toks, reasons, c = await self._both(reqs_staggered)
        assert [len(t) for t in toks] == [5, 11, 18]

    async def test_seeded_sampling_parity(self):
        def samp():
            return SamplingOptions(temperature=1.0, seed=4242)

        toks, _r, _c = await self._both(
            lambda: reqs_staggered(samp=samp))
        assert [len(t) for t in toks] == [5, 11, 18]

    async def test_seed_replay_matches_solo_run(self):
        # a seeded request must produce the same tokens fused-batched as
        # per-step solo: seeded draws key on token position, not on step
        # counters or fuse width
        def one():
            return [make_req([7, 8, 9], "solo", max_tokens=12,
                             samp=SamplingOptions(temperature=0.9,
                                                  seed=77))]

        fused, _, c = await run_many(one(), decode_multistep=8)
        solo, _, _ = await run_many(one(), decode_multistep=1)
        assert c["blocks"] > 0
        assert fused == solo

    async def test_eos_mid_block(self):
        # probe the greedy trajectory, then declare the token produced at
        # a mid-block index to be EOS: both paths must cut at the same
        # place with FinishReason.EOS
        probe, _, _ = await run_many(reqs_staggered(lens=(16, 16, 16)),
                                     decode_multistep=1)
        eos_tok = probe[0][4]   # 5th token: mid-block for width 8

        def mk():
            return reqs_staggered(lens=(16, 16, 16), eos=[eos_tok])

        toks, reasons, _ = await self._both(mk)
        assert len(toks[0]) <= 16
        assert toks[0][-1] == eos_tok
        assert reasons[0] == FinishReason.EOS

    async def test_stop_token_mid_block_with_min_tokens(self):
        probe, _, _ = await run_many(reqs_staggered(lens=(16,)),
                                     decode_multistep=1)
        stop_tok = probe[0][2]   # appears early; min_tokens must gate it
        early = probe[0].index(stop_tok)

        def mk():
            return reqs_staggered(lens=(16,), stop_token_ids=[stop_tok],
                                  min_tokens=early + 2)

        toks, reasons, _ = await self._both(mk)
        assert len(toks[0]) >= early + 2
        if reasons[0] == FinishReason.STOP:
            assert toks[0][-1] == stop_tok

    async def test_max_tokens_mid_block(self):
        # budgets that are not multiples of the width stop mid-block
        toks, reasons, _ = await self._both(
            lambda: reqs_staggered(lens=(3, 9, 13)))
        assert [len(t) for t in toks] == [3, 9, 13]
        assert all(r == FinishReason.LENGTH for r in reasons)

    async def test_stop_string_block_boundary(self):
        """A row with detokenizer-level stop strings narrows the width to
        the lookback; the host-side 'string matched' signal (the backend
        closing the stream) arriving at a block boundary must terminate
        cleanly and reclaim pages — the engine-side half of StopJail."""
        eng = tiny_engine(decode_multistep=8)
        free0 = eng.allocator.num_free
        widths = []
        orig_dm = eng.dispatch_multistep

        def recording(plan, prev_handle=None):
            widths.append(plan.width)
            return orig_dm(plan, prev_handle)

        eng.dispatch_multistep = recording
        try:
            r = make_req([1, 2, 3], "ss", max_tokens=40, stop=["XYZ"])
            got = []
            # consume 5 tokens (an odd count: with lookback width 2 the
            # 'match' lands spanning a block boundary), then close — the
            # backend's StopJail does exactly this on a string match
            async for out in eng.generate(r):
                got.extend(out.token_ids)
                if len(got) >= 5:
                    break
            assert len(got) >= 5
            # narrowed: no wide block ran while the stop-string row was in
            # the batch (stop_str_lookback caps the fuse width at 2)
            assert widths and all(w <= 2 for w in widths), widths
            # pages reclaimed on the next plan pass
            for _ in range(100):
                if eng.allocator.num_free == free0:
                    break
                await asyncio.sleep(0.02)
            assert eng.allocator.num_free == free0
        finally:
            await eng.stop()

    async def test_cancel_mid_block_reclaims_pages(self):
        class Ctx:
            cancelled = False

        eng = tiny_engine(decode_multistep=8)
        free0 = eng.allocator.num_free
        try:
            ctx = Ctx()
            r = make_req([1, 2, 3], "cx", max_tokens=1000)
            frames = []
            async for out in eng.generate(r, ctx=ctx):
                frames.append(out)
                ctx.cancelled = True   # cancel after the first frame
            assert frames[-1].finish_reason == FinishReason.CANCELLED
            # pages for the dead row reclaimed by the next plan pass
            for _ in range(100):
                if eng.allocator.num_free == free0:
                    break
                await asyncio.sleep(0.02)
            assert eng.allocator.num_free == free0
            # the engine still serves after the mid-block cancellation
            ok = await collect(eng, make_req([4, 5, 6], "after",
                                             max_tokens=6))
            assert len(toks_of(ok)) == 6
        finally:
            await eng.stop()


class TestStreamEnds:
    async def test_a_streams_end_keeps_the_chain_and_the_width(self):
        """Four rows, one much shorter: its end neither narrows the other
        rows' blocks nor breaks their chain (the ring shows every block at
        the full width and chained: the one behind the last admission
        behind that mixed step, the others behind a block), and every
        row's tokens equal the per-step run's."""
        def mk():
            return reqs_staggered(lens=(7, 41, 41, 41))

        step_t, step_r, _ = await run_many(mk(), decode_multistep=1)
        eng = tiny_engine(decode_multistep=4)
        try:
            results = await asyncio.gather(*[collect(eng, r) for r in mk()])
            assert [toks_of(f) for f in results] == step_t
            assert [f[-1].finish_reason for f in results] == step_r
            recs = sorted(eng.steptrace.snapshot(limit=256)["records"],
                          key=lambda r: r["seq"])
            last_admission = max(i for i, r in enumerate(recs)
                                 if r["kind"] != "multistep")
            recs = recs[last_admission + 1:]
            assert len(recs) >= 9
            assert all(r["width"] == 4 and r["rows"] == 4 for r in recs)
            assert all(r["chained"] for r in recs)
            assert [r["chained_behind"] for r in recs] == ["mixed"] + [
                "block"] * (len(recs) - 1)
            assert recs[-1]["running"] == 3
        finally:
            await eng.stop()
        assert eng.allocator.num_free == eng.allocator.num_pages - 1


class TestDispatchCount:
    async def test_m_tokens_cost_m_over_n_plus_c_dispatches(self):
        """The regression guard of the fused path: M decoded tokens must
        cost <= M/N + c dispatches (N = fuse width; c covers the budget-
        narrowed tail blocks and the final per-step remainder)."""
        M, N = 32, 8
        eng = tiny_engine(decode_multistep=N, max_context=64)
        try:
            r = make_req([1, 2, 3], "g", max_tokens=M)
            frames = await collect(eng, r)
            toks = toks_of(frames)
            assert len(toks) == M
            # token 1 comes from prefill; M-1 from decode dispatches
            assert eng.decode_dispatches <= M // N + 3, (
                eng.decode_dispatches, eng.multistep_blocks)
            assert eng.multistep_blocks >= 3
        finally:
            await eng.stop()

    async def test_dispatch_tap_feeds_worker_metric(self):
        from dynamo_tpu.worker.metrics import engine_dispatch_stats
        eng = tiny_engine(decode_multistep=8)
        try:
            await collect(eng, make_req([1, 2, 3], "t", max_tokens=16))
            stats = engine_dispatch_stats(eng)
            assert stats["decode_dispatches"] >= 1
            assert stats["decode_multistep_blocks"] >= 1
            assert stats["decode_dispatches"] == eng.decode_dispatches
        finally:
            await eng.stop()

    async def test_decode_span_attrs_on_final_frame(self):
        eng = tiny_engine(decode_multistep=8)
        try:
            frames = await collect(eng, make_req([1, 2, 3], "a",
                                                 max_tokens=16))
            last = frames[-1]
            assert last.timings is not None
            # 16 tokens: 1 from prefill + 15 decode; fused blocks keep
            # dispatches well under steps
            assert last.timings["decode_steps"] == 15
            assert last.timings["decode_dispatches"] < 15
        finally:
            await eng.stop()


class TestSchedulerWidth:
    """Unit tests of the fuse-width computation (no device involved)."""

    def make(self, num_pages=33, page_size=4, **cfg):
        alloc = PageAllocator(num_pages, page_size)
        base = dict(max_num_seqs=4, max_prefill_chunk=32,
                    decode_multistep=8)
        base.update(cfg)
        s = Scheduler(alloc, SchedulerConfig(**base))
        s.max_context_hint = 128
        return s, alloc

    def to_running(self, sched, req):
        sched.add_request(req)
        plan = sched.schedule()
        assert isinstance(plan, PrefillBatch)
        sched.on_step_done(plan)
        seq = plan.chunks[-1].seq
        assert seq.phase == Phase.RUNNING
        seq.tokens.append(9)
        seq.generated.append(9)
        return seq

    def test_full_width_and_page_preallocation(self):
        sched, _ = self.make()
        seq = self.to_running(sched, make_req(range(1, 6), "a",
                                              max_tokens=32))
        d = sched.schedule()
        assert isinstance(d, DecodeBatch)
        ms = sched.plan_multistep(d)
        assert isinstance(ms, MultiStepBatch)
        assert ms.width == 8
        assert ms.start_lens == [len(seq)]
        # pages for every written position (sl-1 .. sl+6) pre-allocated
        assert len(seq.page_ids) * sched.page_size >= len(seq) + ms.width - 1

    def test_budget_narrows_and_pow2_floors(self):
        sched, _ = self.make()
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=7))
        ms = sched.plan_multistep(sched.schedule())
        # remaining budget 6 -> pow2 floor 4
        assert ms is not None and ms.width == 4
        assert ms.budgets == [6]

    def test_budget_too_small_falls_back(self):
        sched, _ = self.make()
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=2))
        assert sched.plan_multistep(sched.schedule()) is None

    def running(self, sched, *max_tokens):
        for i, n in enumerate(max_tokens):
            sched.add_request(make_req(range(1, 6), "abc"[i], max_tokens=n))
        plan = sched.schedule()
        assert isinstance(plan, PrefillBatch)
        assert len(plan.chunks) == len(max_tokens)
        sched.on_step_done(plan)
        seqs = [c.seq for c in plan.chunks]
        for seq in seqs:
            seq.tokens.append(9)
            seq.generated.append(9)
        return seqs

    def test_short_row_does_not_narrow_the_block(self):
        # the row with the most left sets the width; the other stops at
        # its budget on the device and gets pages for what it writes only
        sched, _ = self.make()
        a, b = self.running(sched, 4, 32)
        ms = sched.plan_multistep(sched.schedule())
        assert ms is not None and ms.width == 8
        assert ms.budgets == [3, 31]
        assert len(a.page_ids) * sched.page_size >= len(a) + 3 - 1
        assert len(a.page_ids) < len(b.page_ids)
        assert sched.multistep_fallbacks == {}

    def test_row_ending_in_flight_rides_the_chain_dead(self):
        sched, _ = self.make()
        a, b, _c = self.running(sched, 4, 64, 64)
        ms = sched.plan_multistep(sched.schedule())
        assert ms.width == 8 and len(ms.seqs) == 3
        # "a" ends inside the block in flight: dead row, no pages, and the
        # chain goes on at full width
        pages_a = list(a.page_ids)
        nxt = sched.plan_behind(ms)
        assert nxt is not None and nxt.width == 8 and nxt.chained
        assert nxt.budgets[0] == 0 and nxt.budgets[1] == 63 - 8
        assert a.page_ids == pages_a
        # the host learns of it (tokens appended, row finished): a row
        # that spent its budget stays in the chain while most rows live
        for seq in ms.seqs:
            for _ in range(3 if seq is a else 8):
                seq.tokens.append(7)
                seq.generated.append(7)
        sched.finish(a)
        after = sched.plan_behind(nxt)
        assert after is not None and after.seqs == nxt.seqs
        assert after.budgets[0] == 0
        # but not a row the host alone ended: the device has it alive
        sched.finish(b)
        assert sched.plan_behind(after) is None

    def test_chain_breaks_when_half_the_rows_are_dead(self):
        sched, _ = self.make()
        a, b = self.running(sched, 4, 64)
        ms = sched.plan_multistep(sched.schedule())
        for _ in range(3):
            a.tokens.append(7)
            a.generated.append(7)
        sched.finish(a)
        assert sched.plan_behind(ms) is None

    def test_stop_string_lookback_caps_width(self):
        sched, _ = self.make()
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=32,
                                        stop=["foo"]))
        ms = sched.plan_multistep(sched.schedule())
        assert ms is not None and ms.width == 2

    def test_penalties_and_guided_fall_back(self):
        sched, _ = self.make()
        r = make_req(range(1, 6), "a", max_tokens=32,
                     samp=SamplingOptions(temperature=0.0,
                                          frequency_penalty=1.0))
        self.to_running(sched, r)
        assert sched.plan_multistep(sched.schedule()) is None

        sched2, _ = self.make()
        r2 = make_req(range(1, 6), "g", max_tokens=32,
                      samp=SamplingOptions(temperature=0.0,
                                           guided={"mode": "json"}))
        self.to_running(sched2, r2)
        assert sched2.plan_multistep(sched2.schedule()) is None

    def test_seeds_and_min_p_stay_eligible(self):
        sched, _ = self.make()
        r = make_req(range(1, 6), "s", max_tokens=32,
                     samp=SamplingOptions(temperature=1.0, seed=3,
                                          min_p=0.05))
        self.to_running(sched, r)
        ms = sched.plan_multistep(sched.schedule())
        assert ms is not None and ms.width == 8

    def test_page_pressure_narrows_width(self):
        # 3 usable pages, page_size 4: a 6-token running seq holds 2;
        # width 8 needs pages through position len+6 — more than remain;
        # the planner narrows instead of preempting
        sched, alloc = self.make(num_pages=4)
        seq = self.to_running(sched, make_req(range(1, 6), "a",
                                              max_tokens=32))
        ms = sched.plan_multistep(sched.schedule())
        if ms is not None:
            assert ms.width < 8
            need = (seq.page_ids and len(seq.page_ids)
                    * sched.page_size >= len(seq) + ms.width - 1)
            assert need
        # and per-step decode still possible either way
        assert sched.schedule() is not None

    def test_spec_mode_refuses(self):
        sched, _ = self.make(spec_tokens=4)
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=32))
        d = DecodeBatch(seqs=[s for s in sched.active.values()])
        assert sched.plan_multistep(d) is None

    def test_waiting_request_blocks_fusion_legacy_only(self):
        # LEGACY mode (mixed_batch=False): anything waiting refuses the
        # fuse (the PR 8 gate) and the refusal is recorded by reason
        sched, _ = self.make(max_num_seqs=1, mixed_batch=False)
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=32))
        sched.add_request(make_req(range(1, 6), "b", max_tokens=8))
        d = sched.schedule()
        if isinstance(d, DecodeBatch):
            assert sched.plan_multistep(d) is None
            assert sched.multistep_fallbacks.get("waiters", 0) >= 1

    def test_waiting_request_no_longer_blocks_fusion_mixed(self):
        # with mixed dispatch on (default) the gate is LIFTED: a waiter
        # that cannot be admitted (no free slot) no longer forces the
        # running batch down the per-step path — arrivals onboard through
        # the mixed steps between blocks instead
        sched, _ = self.make(max_num_seqs=1)
        seq = self.to_running(sched, make_req(range(1, 6), "a",
                                              max_tokens=32))
        sched.add_request(make_req(range(1, 6), "b", max_tokens=8))
        d = sched.schedule()
        assert isinstance(d, DecodeBatch)  # "b" has no slot: pure decode
        ms = sched.plan_multistep(d)
        assert ms is not None and ms.width == 8
        assert ms.seqs == [seq]

    def test_penalty_window_admits_and_narrows(self):
        # W=8; distinct entries = logit_bias {1,2,3} + generated {9} = 4,
        # nothing in flight -> 4 free ring-buffer slots: the block narrows
        # to width 4 instead of refusing
        sched, _ = self.make(penalty_window=8)
        r = make_req(range(1, 6), "p", max_tokens=32,
                     samp=SamplingOptions(temperature=0.0,
                                          frequency_penalty=1.0,
                                          logit_bias={1: 1.0, 2: 1.0,
                                                      3: 1.0}))
        self.to_running(sched, r)
        ms = sched.plan_multistep(sched.schedule())
        assert ms is not None and ms.width == 4
        assert sched.multistep_fallbacks == {}

    def test_penalty_window_exhausted_refuses(self):
        # W=4 fully consumed by 3 bias entries + 1 generated token: fewer
        # than 2 free slots left, so the row cannot ride even the
        # narrowest block — refused under its own reason, not "penalties"
        sched, _ = self.make(penalty_window=4)
        r = make_req(range(1, 6), "p", max_tokens=32,
                     samp=SamplingOptions(temperature=0.0,
                                          presence_penalty=0.5,
                                          logit_bias={1: 1.0, 2: 1.0,
                                                      3: 1.0}))
        seq = self.to_running(sched, r)
        assert sched.plan_multistep(sched.schedule()) is None
        assert sched.multistep_fallbacks == {"penalty_window": 1}
        assert seq.multistep_fallbacks == 1

    def test_guided_fuse_check_routes_reasons(self):
        def mk(check):
            sched, _ = self.make(guided_fuse_check=check)
            r = make_req(range(1, 6), "g", max_tokens=32,
                         samp=SamplingOptions(temperature=0.0,
                                              guided={"mode": "json"}))
            self.to_running(sched, r)
            return sched

        # no device-lowering hook wired at all: the legacy "guided" refusal
        s = mk(None)
        assert s.plan_multistep(s.schedule()) is None
        assert s.multistep_fallbacks == {"guided": 1}
        # hook reports the grammar's transition table blew the byte cap
        s = mk(lambda seq: False)
        assert s.plan_multistep(s.schedule()) is None
        assert s.multistep_fallbacks == {"guided_table": 1}
        # hook vouches for a device table: the row fuses at full width
        s = mk(lambda seq: True)
        ms = s.plan_multistep(s.schedule())
        assert ms is not None and ms.width == 8


def mk_constrained(seeded=False):
    t = 0.9 if seeded else 0.0
    kw = dict(seed=11) if seeded else {}
    return [
        make_req([1, 2, 3, 4, 5], "plain", max_tokens=14,
                 samp=SamplingOptions(temperature=t, **kw)),
        make_req([2, 3, 4, 5, 6], "freq", max_tokens=14,
                 samp=SamplingOptions(temperature=t,
                                      frequency_penalty=0.9, **kw)),
        make_req([3, 4, 5, 6, 7], "rep", max_tokens=14,
                 samp=SamplingOptions(temperature=t,
                                      repetition_penalty=1.4, **kw)),
        make_req([4, 5, 6, 7, 8], "bias", max_tokens=14,
                 samp=SamplingOptions(temperature=t,
                                      logit_bias={17: 3.5, 41: -100.0},
                                      **kw)),
    ]


async def run_many_fb(reqs, **engine_kw):
    """run_many plus the scheduler's per-reason fallback counters."""
    eng = tiny_engine(**engine_kw)
    try:
        results = await asyncio.gather(*[collect(eng, r) for r in reqs])
        return ([toks_of(f) for f in results],
                dict(eng.scheduler.multistep_fallbacks),
                eng.multistep_blocks)
    finally:
        await eng.stop()


class TestConstrainedParity:
    """Penalties and logit bias ride the fused block (device ring buffer
    in the scan carry) bit-identically to the per-step path — no
    "penalties" refusals on the trace."""

    async def _both(self, mk):
        fused, fb, blocks = await run_many_fb(mk(), decode_multistep=8)
        step, _fb0, blocks0 = await run_many_fb(mk(), decode_multistep=1)
        assert blocks > 0 and blocks0 == 0
        assert fused == step
        assert fb.get("penalties", 0) == 0, fb
        assert fb.get("penalty_window", 0) == 0, fb
        return fused

    async def test_mixed_cohort_greedy(self):
        toks = await self._both(lambda: mk_constrained(False))
        assert all(len(t) == 14 for t in toks)

    async def test_mixed_cohort_seeded(self):
        await self._both(lambda: mk_constrained(True))

    async def test_penalty_bites_inside_the_block(self):
        # deterministic semantics check, not just parity: a +100 bias
        # forces the first greedy pick, then a huge presence penalty must
        # ban that token for the REST OF THE BLOCK — proving the window
        # update happens inside the scan, not once per dispatch
        toks, fb, blocks = await run_many_fb(
            [make_req([1, 2, 3], "b", max_tokens=12,
                      samp=SamplingOptions(temperature=0.0,
                                           presence_penalty=200.0,
                                           logit_bias={7: 100.0}))],
            decode_multistep=8)
        assert blocks > 0
        assert fb.get("penalties", 0) == 0, fb
        assert toks[0][0] == 7
        assert 7 not in toks[0][1:]

    async def test_migration_resume_preserves_window(self):
        # per-step reference trajectory, uninterrupted
        def samp():
            return SamplingOptions(temperature=0.0, frequency_penalty=0.9)

        full, _, _ = await run_many_fb(
            [make_req([1, 2, 3, 4, 5], "m", max_tokens=16, samp=samp())],
            decode_multistep=1)
        assert len(full[0]) == 16

        # resume after 6 generated tokens: the migration hop folds them
        # into the prompt and marks the count (llm/operators.py) — the
        # penalty window must still count them
        def resumed():
            r = make_req([1, 2, 3, 4, 5] + full[0][:6], "m", max_tokens=10,
                         samp=samp())
            r.resumed_tokens = 6
            return [r]

        fused, fb, blocks = await run_many_fb(resumed(),
                                              decode_multistep=8)
        step, _, blocks0 = await run_many_fb(resumed(), decode_multistep=1)
        assert blocks > 0 and blocks0 == 0
        assert fb.get("penalties", 0) == 0, fb
        assert fb.get("penalty_window", 0) == 0, fb
        assert fused == step
        # the hop is seamless: resumed continuation == uninterrupted tail
        assert fused[0] == full[0][6:]

    async def test_cancel_penalized_mid_block_releases_slot(self):
        class Ctx:
            cancelled = False

        eng = tiny_engine(decode_multistep=8)
        free0 = eng.allocator.num_free
        try:
            ctx = Ctx()
            r = make_req([1, 2, 3], "cx", max_tokens=1000,
                         samp=SamplingOptions(temperature=0.0,
                                              frequency_penalty=0.9))
            async for out in eng.generate(r, ctx=ctx):
                ctx.cancelled = True   # cancel after the first frame
            for _ in range(100):
                if eng.allocator.num_free == free0:
                    break
                await asyncio.sleep(0.02)
            assert eng.allocator.num_free == free0
            # the engine still serves penalized rows afterwards, and the
            # next dispatch drains the release marker: no cached sampling
            # composition may still reference the dead row
            ok = await collect(eng, make_req(
                [4, 5, 6], "after", max_tokens=6,
                samp=SamplingOptions(temperature=0.0,
                                     presence_penalty=0.3)))
            assert len(toks_of(ok)) == 6
            with eng._released_lock:
                assert "cx" not in eng._released
            if eng._samp_cache is not None:
                assert all(rid != "cx" for rid, _ in eng._samp_cache[0][1])
            assert "cx" not in eng._guided_reqs
        finally:
            await eng.stop()


class SyncSteps(JaxEngine):
    """The engine with every mixed step resolved inside its dispatch:
    the synchronous path a chained run is compared with."""
    supports_step_chain = False


class FailsBehindAStep(JaxEngine):
    """The first block chained behind a mixed step fails at its dispatch."""
    failed = False

    def dispatch_multistep(self, plan, prev_handle=None):
        if plan.behind == "mixed" and not self.failed:
            self.failed = True
            raise RuntimeError("planted: block behind a step")
        return super().dispatch_multistep(plan, prev_handle)


class CancelsBehindAStep(JaxEngine):
    """Cancels ``victim`` the moment the first block chained behind a
    mixed step is enqueued: both programs are in flight, neither result
    is on the host."""
    victim = None

    def dispatch_multistep(self, plan, prev_handle=None):
        handle = super().dispatch_multistep(plan, prev_handle)
        if plan.behind == "mixed" and self.victim is not None:
            self.scheduler.cancel(self.victim)
            self.victim = None
        return handle


def staggered_engine(cls=JaxEngine, **kw):
    """One prompt an admission pass: requests queued together are
    admitted one mixed step apart, so the sequence of plans is fixed by
    the requests alone (no clock in it)."""
    defaults = dict(num_pages=64, page_size=4, max_num_seqs=4,
                    max_prefill_chunk=16, max_context=64,
                    decode_multistep=4, max_prefill_seqs=1,
                    # one bucket for the pair of rows the cases serve, as
                    # a deployment pins its buckets: a block holds the
                    # same program with a dead row riding as without
                    min_prefill_bucket=16, min_prefill_seqs_bucket=2,
                    min_decode_bucket=2)
    model = ModelConfig.tiny()
    if kw.pop("form", "padded") == "packed":
        # on the kernels (interpreted here) the prefill-carrying steps run
        # token-packed; their geometry: head_dim % 128, page_size % 8
        model = ModelConfig.tiny(head_dim=128, num_heads=2, num_kv_heads=1,
                                 hidden_size=128)
        defaults.update(page_size=8, max_context=128, attn_impl="pallas")
    defaults.update(kw)
    return cls.random_init(model, JaxEngineConfig(**defaults))


async def serve_staggered(reqs, cls=JaxEngine, setup=None, **kw):
    """([frames per request], the run's ring oldest first, the engine)."""
    from dynamo_tpu.engine.steptrace import StepRecorder, set_step_recorder
    ring = set_step_recorder(StepRecorder(512))
    eng = staggered_engine(cls, **kw)
    if setup is not None:
        setup(eng)
    try:
        frames = await asyncio.gather(*[collect(eng, r) for r in reqs])
    finally:
        await eng.stop()
    return frames, ring.snapshot(limit=512)["records"][::-1], eng


def streamed(frames):
    """What a client saw of each request: tokens, their log-probabilities,
    and how it ended."""
    return [([t for f in fs for t in f.token_ids],
             [lp for f in fs for lp in (f.log_probs or [])],
             fs[-1].finish_reason) for fs in frames]


def behind_mixed(ring):
    """(the mixed record, the block record) of every block the ring shows
    chained behind a mixed step."""
    return [(a, b) for a, b in zip(ring, ring[1:])
            if b["chained_behind"] == "mixed"]


# r0's 6th token comes from the first mixed step (its prefill gives one,
# the block behind it four): the token a case makes r0's last
R0_AT_THE_STEP = 5


def two(r0_kw=None, r1_prompt=11, samp=None):
    def mk():
        return [make_req([1, 2, 3, 4, 5], "r0", samp=samp() if samp else None,
                         **{"max_tokens": 20, **(r0_kw or {})}),
                make_req(list(range(20, 20 + r1_prompt)), "r1", max_tokens=9,
                         samp=samp() if samp else None)]
    return mk


class TestChainBehindMixed:
    """A fused block chained behind the mixed step that ends an admission
    run (``Scheduler.chains_behind`` / ``plan_behind``,
    ``JaxEngine._handover_impl``): the step returns at its enqueue, the
    block takes its first tokens from the step's on-device output, and
    every request streams what the synchronous path streams."""

    # what the synchronous path streams for the plain pair ``two()``,
    # served once for the cases that compare with it
    _plain = None

    @classmethod
    async def plain_synchronous(cls):
        if cls._plain is None:
            frames, ring, eng = await serve_staggered(two()(), SyncSteps)
            assert eng.scheduler.chained_blocks["mixed"] == 0
            assert not behind_mixed(ring)
            assert all(r["fetch_ms"] == 0.0 for r in ring
                       if r["kind"] == "mixed")
            cls._plain = streamed(frames)
        return cls._plain

    async def _both(self, mk, want=None, **kw):
        got, ring, eng = await serve_staggered(mk(), **kw)
        if want is None:
            frames, _ring0, eng0 = await serve_staggered(mk(), SyncSteps,
                                                         **kw)
            want = streamed(frames)
            assert eng0.allocator.num_free == eng0.allocator.num_pages - 1
        # tokens, log-probabilities and endings, request for request (the
        # pinned row bucket keeps a block one program with a dead row
        # riding or a row gone: no rounding between the two paths)
        assert streamed(got) == want
        # nothing leaks, whichever path ended a row
        assert eng.allocator.num_free == eng.allocator.num_pages - 1
        return streamed(got), ring, eng

    @pytest.mark.parametrize("sampling", ["greedy", "seeded"])
    async def test_a_joining_prompt_and_a_decode_row(self, sampling):
        """r1's whole prompt rides the step: the block behind it holds
        both rows, r1 from ``prompt_len + 1``."""
        samp = None if sampling == "greedy" else (
            lambda: SamplingOptions(temperature=1.0, seed=4242))
        out, ring, eng = await self._both(
            two(samp=samp),
            want=None if samp else await self.plain_synchronous())
        assert [len(t) for t, _lp, _r in out] == [20, 9]
        (step, block), = behind_mixed(ring)
        assert step["kind"] == "mixed" and step["program"].startswith("mixed[")
        # the step returned at its enqueue: its result was fetched later
        assert step["fetch_ms"] > 0.0 and not step["chained"]
        assert block["chained"] and block["rows"] == 2
        assert block["program"] == "multistep4[2]"
        assert eng.scheduler.chained_blocks == {"block": 2, "mixed": 1}
        assert not any(eng.scheduler.chain_refusals.values())
        # the block behind the step is the only one built on the device
        # from a step; no block was built from host state behind one
        assert [r["chained_behind"] for r in ring
                if r["kind"] == "multistep"] == ["", "mixed", "block",
                                                 "block", ""]

    @pytest.mark.parametrize("ends_by", ["stop_token", "budget"])
    async def test_a_row_the_steps_token_ends_rides_dead(self, ends_by):
        """r0's token from the step is its last (a stop token; the end of
        its budget): the device has the row dead from the block's start,
        the host appends nothing for it, r1 runs on beside it."""
        if ends_by == "stop_token":
            r0 = (await self.plain_synchronous())[0][0]
            last = r0[R0_AT_THE_STEP]
            assert last not in r0[:R0_AT_THE_STEP]
            mk = two(r0_kw={"stop_token_ids": [last]})
            reason = FinishReason.STOP
        else:
            mk = two(r0_kw={"max_tokens": R0_AT_THE_STEP + 1})
            reason = FinishReason.LENGTH
        out, ring, eng = await self._both(mk)
        assert len(out[0][0]) == R0_AT_THE_STEP + 1 and out[0][2] == reason
        assert len(out[1][0]) == 9
        (step, block), = behind_mixed(ring)
        # the synchronous path's block would hold r1 alone
        assert block["rows"] == 2 and block["width"] == 4
        assert eng.scheduler.chained_blocks["mixed"] == 1

    async def test_an_intermediate_chunk_stays_out(self):
        """r1's prompt takes two steps: the block behind the first holds
        r0 alone, the block behind the second both."""
        out, ring, eng = await self._both(two(r1_prompt=21))
        assert [len(t) for t, _lp, _r in out] == [20, 9]
        rows = [(a["rows"], b["rows"]) for a, b in behind_mixed(ring)]
        assert rows == [(2, 1), (2, 2)]
        assert eng.scheduler.chained_blocks["mixed"] == 2

    async def test_every_step_of_a_run_but_the_first_chains(self):
        """A queue behind a long prompt: the run's first mixed step
        returns at its enqueue with the run's second chained behind it
        (``TestMixedBehindMixed``), and the block behind the run's last;
        nothing is refused as ``run``."""
        def mk():
            return [make_req([1, 2, 3, 4, 5], "r0", max_tokens=24),
                    make_req(list(range(10, 50)), "r1", max_tokens=6),
                    make_req([7, 8, 9], "r2", max_tokens=6)]

        frames, ring, eng = await serve_staggered(mk())
        assert [len(t) for t, _lp, _r in streamed(frames)] == [24, 6, 6]
        kinds = [(r["kind"], r["fetch_ms"] > 0.0, r["chained_behind"])
                 for r in ring[2:5]]
        assert kinds == [("mixed", True, ""), ("mixed", True, "mixed"),
                         ("multistep", True, "mixed")]
        assert eng.scheduler.chain_refusals["run"] == 0
        assert eng.scheduler.chained_steps == {"mixed": 1}
        assert eng.scheduler.admission_runs == \
            {"queue": 1, "rows": 0, "pages": 0, "partial": 2}

    async def test_a_penalised_row_keeps_the_synchronous_path(self):
        """A device penalty window is preloaded from host tokens, which
        would lack the one in flight: the chain is refused and counted,
        every step and block is the synchronous path's."""
        def samp():
            return SamplingOptions(temperature=0.0, frequency_penalty=0.7)

        frames, ring, eng = await serve_staggered(two(samp=samp)())
        assert [len(t) for t, _lp, _r in streamed(frames)] == [20, 9]
        assert not behind_mixed(ring)
        assert eng.scheduler.chain_refusals["pcarry"] == 1
        assert eng.scheduler.chained_blocks["mixed"] == 0
        assert all(r["fetch_ms"] == 0.0 for r in ring
                   if r["kind"] == "mixed")
        from dynamo_tpu.worker.metrics import engine_dispatch_stats
        stats = engine_dispatch_stats(eng)
        assert stats["chain_refusals"]["pcarry"] == 1
        assert stats["chained_blocks"]["block"] > 0

    async def test_a_cancel_while_both_programs_are_in_flight(self):
        """r0 is cancelled with the step and the block behind it both
        enqueued and neither fetched: it ends CANCELLED with the tokens it
        had, r1 streams what it streams alone beside r0, nothing leaks."""
        def victim(eng):
            eng.victim = "r0"

        got, ring, eng = await serve_staggered(two()(), CancelsBehindAStep,
                                               setup=victim)
        got, want = streamed(got), await self.plain_synchronous()
        assert len(behind_mixed(ring)) == 1
        assert got[0][2] == FinishReason.CANCELLED
        # the five tokens it had streamed before the step; the step's own
        # and the block's are dropped
        assert got[0][0] == want[0][0][:R0_AT_THE_STEP]
        assert got[1] == want[1]
        assert eng.allocator.num_free == eng.allocator.num_pages - 1

    async def test_a_dispatch_error_in_the_chained_block(self):
        """The block behind the step fails at its dispatch: the step is
        finished first (each row gets the token the step sampled), then
        the block's rows fail; the engine serves on."""
        eng = staggered_engine(FailsBehindAStep)
        try:
            frames = await asyncio.gather(*[collect(eng, r)
                                            for r in two()()])
            got, want = streamed(frames), await self.plain_synchronous()
            assert eng.failed
            assert got[0][0] == want[0][0][:R0_AT_THE_STEP + 1]
            assert got[1][0] == want[1][0][:1]
            for fs in frames:
                assert fs[-1].finish_reason == FinishReason.ERROR
                assert "planted" in fs[-1].error
            assert eng.allocator.num_free == eng.allocator.num_pages - 1
            after = await collect(eng, make_req([4, 5, 6], "after",
                                                max_tokens=6))
            assert len(toks_of(after)) == 6
        finally:
            await eng.stop()


class TestChainBehindMixedPlan:
    """The scheduler's side, without an engine."""

    def make(self, **cfg):
        cfg.setdefault("decode_multistep", 4)
        cfg.setdefault("max_prefill_seqs", 1)
        cfg.setdefault("max_prefill_chunk", 16)
        sched = Scheduler(PageAllocator(33, 4), SchedulerConfig(**cfg))
        sched.max_context_hint = 64
        return sched

    def step_with(self, sched, r0, r1):
        """r0 running with one token out, r1 admitted into a mixed step."""
        from dynamo_tpu.engine.scheduler import MixedStepBatch
        sched.add_request(r0)
        first = sched.schedule()
        assert isinstance(first, PrefillBatch)
        sched.on_step_done(first)
        a = first.chunks[0].seq
        a.tokens.append(9)
        a.generated.append(9)
        sched.add_request(r1)
        assert isinstance(sched.schedule(), DecodeBatch)
        step = sched.schedule()
        assert isinstance(step, MixedStepBatch)
        return step, a

    def test_rows_lengths_and_the_map_into_the_steps_output(self):
        sched = self.make()
        step, a = self.step_with(
            sched, make_req([1, 2, 3, 4, 5], "a", max_tokens=12),
            make_req(list(range(20, 31)), "b", max_tokens=3,
                     min_tokens=3))
        b = step.chunks[0].seq
        assert sched.chains_behind(step)
        free = sched.alloc.num_free
        plan = sched.plan_behind(step)
        # by arrival, as the decode plan would hold them; the step's
        # packed rows are its chunks, then its decode rows
        assert plan.seqs == [a, b] and plan.src_rows == [1, 0]
        assert plan.chained and plan.behind == "mixed"
        assert plan.start_lens == [len(a) + 1, len(b) + 1] == [7, 12]
        # the token in flight is counted: a has 12 - 1 - 1 left, b
        # 3 - 1, and b's min_tokens gate has 2 to go
        assert plan.budgets == [10, 2] and plan.min_gates == [0, 2]
        assert plan.width == 4
        # pages for what each row writes: a 4 positions from 6, b 2 from 11
        assert len(a.page_ids) == 3 and len(b.page_ids) == 4
        assert sched.alloc.num_free == free - 2
        # the admission run is over, as after ``schedule()``'s decode plan
        assert sched.admission_runs["queue"] == 1 and sched._run_steps == 0
        assert sched.chained_blocks == {"block": 0, "mixed": 1}

    @pytest.mark.parametrize("why", ["pcarry", "guided", "rows", "budget",
                                     "pages", "off"])
    def test_a_refusal_is_counted_by_its_reason(self, why):
        samp = {"pcarry": SamplingOptions(temperature=0.0,
                                          presence_penalty=0.5),
                "guided": SamplingOptions(temperature=0.0,
                                          guided={"type": "json_object"})
                }.get(why)
        sched = self.make(**({"decode_multistep": 1} if why == "off"
                             else {}))
        step, a = self.step_with(
            sched, make_req([1, 2, 3, 4, 5], "a",
                            max_tokens=3 if why == "budget" else 12),
            make_req(list(range(20, 31)), "b",
                     max_tokens=2 if why == "budget" else 8, samp=samp))
        if why == "rows":
            a.cancelled = True
        if why in ("budget", "pages"):
            assert sched.chains_behind(step)
            if why == "pages":
                held = sched.alloc.allocate(sched.alloc.num_free)
                assert held
            before = [len(s.page_ids) for s in step.seqs]
            assert sched.plan_behind(step) is None
            assert [len(s.page_ids) for s in step.seqs] == before
            # the run is still open: ``schedule()`` closes it
            assert sched._run_steps == 1
        else:
            assert not sched.chains_behind(step)
        want = {"guided": "pcarry", "off": None}.get(why, why)
        assert sched.chain_refusals == {
            r: int(r == want) for r in sched.chain_refusals}
        assert sched.chained_blocks["mixed"] == 0
        # a chain not taken is no fallback from the fused path
        assert sched.multistep_fallbacks == {}

    def test_the_hand_over_on_the_device(self):
        """``_handover_impl``: the first token from the step's column 0
        by the row map; a row lives unless that token is a stop id with
        its ``min_tokens`` gate passed, or its budget is spent; pad rows
        are dead."""
        import numpy as np
        eng = tiny_engine(decode_multistep=4)
        packed = np.zeros((4, 2), np.int32)
        packed[:, 0] = [50, 60, 70, 80]
        #               row: stop hit, gate open | hit, gate shut | spent |
        #               plain | pad
        rows = np.array([[3, 3, 1, 0, 0],            # src
                         [6, 6, 9, 4, 0],            # pos
                         [7, 7, 10, 5, 1],           # total
                         [5, 5, 0, 5, 0],            # budget
                         [0, 2, 0, 0, 0]], np.int32)  # min_gate
        stop_ids = np.full((5, 2), -1, np.int32)
        stop_ids[0] = stop_ids[1] = [80, -1]
        stop_ids[3] = [99, 51]
        c = eng._get_jit_handover()(packed, rows, stop_ids)
        assert c["tok"][:, 0].tolist() == [80, 80, 60, 50, 50]
        assert c["alive"].tolist() == [False, True, False, True, False]
        assert c["pos"][:, 0].tolist() == [6, 6, 9, 4, 0]
        assert c["total"].tolist() == [7, 7, 10, 5, 1]
        assert c["budget"].tolist() == [5, 5, 0, 5, 0]
        assert c["min_gate"].tolist() == [0, 2, 0, 0, 0]
        assert c["tok"].shape == (5, 1) and c["alive"].dtype == bool


# -- a mixed step chained behind a mixed step -------------------------------


def pair(ra_kw=None, samp=None):
    """Two rows decoding (ra, rb), a prompt of four steps (rc: three full
    chunks of 16 and two tokens) and a request that waits behind it (rd):
    the run that admits rc is three mixed steps long and ends in a block.
    ra's 11th token and rb's 6th come from the run's first step."""
    def mk():
        def s():
            return samp() if samp else None
        return [make_req([1, 2, 3, 4, 5], "ra", samp=s(),
                         **{"max_tokens": 30, **(ra_kw or {})}),
                make_req([6, 7, 8, 9], "rb", max_tokens=30, samp=s()),
                make_req(list(range(10, 60)), "rc", max_tokens=6, samp=s()),
                make_req([7, 8, 9], "rd", max_tokens=6, samp=s())]
    return mk


RA_AT_THE_RUN = 10      # ra's tokens before the run's first step
# one row bucket for two rows to four, as a deployment pins its buckets: a
# step holds the same program with a dead row riding as without (another
# bucket is another program, and rounds the last digit its own way)
PAIR_KW = dict(min_prefill_seqs_bucket=4, min_decode_bucket=4)


def mixed_run(ring):
    """The first run of consecutive mixed records longer than one."""
    run = []
    for r in ring:
        if r["kind"] == "mixed":
            run.append(r)
        elif len(run) > 1:
            break
        else:
            run = []
    return run


async def both(mk, want=None, **kw):
    """Serve ``mk()``'s requests on the chaining engine and on the
    synchronous one: tokens, log-probabilities and endings equal, request
    for request, and nothing leaks on either."""
    cls = kw.pop("cls", JaxEngine)
    kw = {**PAIR_KW, **kw}
    got, ring, eng = await serve_staggered(mk(), cls, **kw)
    if want is None:
        frames, ring0, eng0 = await serve_staggered(mk(), SyncSteps, **kw)
        want = streamed(frames)
        assert not any(r["chained_behind"] == "mixed" for r in ring0)
        assert eng0.scheduler.chained_steps == {"mixed": 0}
        assert eng0.allocator.num_free == eng0.allocator.num_pages - 1
    assert streamed(got) == want
    assert eng.allocator.num_free == eng.allocator.num_pages - 1
    return streamed(got), ring, eng


class CancelsBehindAMixedStep(JaxEngine):
    """Cancels ``victim`` the moment the first mixed step chained behind a
    mixed step is enqueued: both steps are in flight, neither result is
    on the host."""
    victim = None

    def dispatch_step(self, plan, prev_handle=None):
        handle = super().dispatch_step(plan, prev_handle)
        if plan.behind == "mixed" and self.victim is not None:
            self.scheduler.cancel(self.victim)
            self.victim = None
        return handle


class FailsBehindAMixedStep(JaxEngine):
    """The first mixed step chained behind a mixed step fails at its
    dispatch."""
    failed = False

    def dispatch_step(self, plan, prev_handle=None):
        if plan.behind == "mixed" and not self.failed:
            self.failed = True
            raise RuntimeError("planted: step behind a step")
        return super().dispatch_step(plan, prev_handle)


class TestMixedBehindMixed:
    """Inside an admission run the next mixed step is planned and enqueued
    while the one before it runs (``Scheduler.plan_behind``,
    ``JaxEngine._fill_impl``): its decode rows' tokens are read from that
    step's output on the device, and every request streams what the
    synchronous path streams."""

    _plain = {}

    @classmethod
    async def plain_synchronous(cls, form="padded"):
        """What the synchronous path streams for ``pair()``."""
        if form not in cls._plain:
            frames, _ring, _eng = await serve_staggered(
                pair()(), SyncSteps, form=form, **PAIR_KW)
            cls._plain[form] = streamed(frames)
        return cls._plain[form]

    @pytest.mark.parametrize("form", ["padded", "packed"])
    @pytest.mark.parametrize("sampling", ["greedy", "seeded"])
    async def test_a_run_of_three_steps_ends_in_a_chained_block(
            self, form, sampling):
        """mixed -> mixed -> mixed -> block on the device: only the run's
        first step is planned from host state; tokens and
        log-probabilities are the synchronous path's."""
        samp = None if sampling == "greedy" else (
            lambda: SamplingOptions(temperature=1.0, seed=4242))
        out, ring, eng = await both(
            pair(samp=samp), form=form,
            want=None if samp else await self.plain_synchronous(form))
        assert [len(t) for t, _lp, _r in out] == [30, 30, 6, 6]
        run = mixed_run(ring)
        assert [(r["chained"], r["chained_behind"]) for r in run] == [
            (False, ""), (True, "mixed"), (True, "mixed")]
        # every step returned at its enqueue; its result was fetched later
        assert all(r["fetch_ms"] > 0.0 for r in run)
        # one program for the run, the one the synchronous path runs
        prog = "packed[" if form == "packed" else "mixed["
        assert len({r["program"] for r in run}) == 1
        assert run[0]["program"].startswith(prog)
        # rc's chunk and the two decode rows, a token each
        assert [(r["rows"], r["tokens_real"]) for r in run] == [(3, 18)] * 3
        block = ring[ring.index(run[-1]) + 1]
        assert block["kind"] == "multistep" and block["rows"] == 2
        assert block["chained_behind"] == "mixed"
        assert eng.scheduler.chained_steps == {"mixed": 2}
        assert not any(eng.scheduler.chain_refusals.values())
        assert eng.scheduler.admission_runs == \
            {"queue": 1, "rows": 0, "pages": 0, "partial": 3}

    @pytest.mark.parametrize("form", ["padded", "packed"])
    async def test_a_prompt_whose_last_chunk_rides_joins_the_next_step(
            self, form):
        """Two prompts an admission pass: r2's whole prompt rides the
        run's first step beside r3's first chunk, and r2 is a decode row
        of the chained step behind it, its first token read on the
        device."""
        def mk():
            return [make_req([1, 2, 3, 4, 5], "r0", max_tokens=30),
                    make_req([6, 7, 8, 9], "r1", max_tokens=30),
                    make_req(list(range(10, 18)), "r2", max_tokens=12),
                    make_req(list(range(20, 60)), "r3", max_tokens=6),
                    make_req([7, 8, 9], "r4", max_tokens=6),
                    make_req([3, 8, 9], "r5", max_tokens=6)]

        out, ring, eng = await both(mk, form=form, max_prefill_seqs=2)
        assert [len(t) for t, _lp, _r in out] == [30, 30, 12, 6, 6, 6]
        run = mixed_run(ring)
        assert [r["chained_behind"] for r in run] == ["", "mixed", "mixed"]
        # two chunks of 8 and two decode rows; then r3's chunk of 16 and
        # three decode rows, r2 among them
        assert [(r["rows"], r["tokens_real"]) for r in run] == [
            (4, 18), (4, 19), (4, 19)]
        assert eng.scheduler.chained_steps == {"mixed": 2}

    @pytest.mark.parametrize("ends_by", [
        "stop_token", "stop_token_of_a_chained_step", "budget",
        "min_tokens_gate"])
    async def test_a_row_the_token_in_flight_ends(self, ends_by):
        """ra's token from a step of the run is its last. A stop id the
        host cannot know: ra rides the next step with that token and
        what it samples there is dropped. The end of its budget, which
        the host knows: ra is left out, as from the synchronous plan. A
        stop id under a ``min_tokens`` gate: ra lives on. Its pages are
        freed and taken again (rd's) and everything reads as on the
        synchronous path."""
        ra = (await self.plain_synchronous())[0][0]
        at = RA_AT_THE_RUN + (ends_by == "stop_token_of_a_chained_step")
        last = ra[at]
        assert last not in ra[:at]
        kw, n, reason, rows = {
            "stop_token": ({"stop_token_ids": [last]}, at + 1,
                           FinishReason.STOP, [3, 3, 2]),
            "stop_token_of_a_chained_step": (
                {"stop_token_ids": [last]}, at + 1, FinishReason.STOP,
                [3, 3, 3]),
            "budget": ({"max_tokens": at + 1}, at + 1, FinishReason.LENGTH,
                       [3, 2, 2]),
            "min_tokens_gate": ({"stop_token_ids": [last],
                                 "min_tokens": at + 2}, 30,
                                FinishReason.LENGTH, [3, 3, 3]),
        }[ends_by]
        out, ring, eng = await both(pair(ra_kw=kw))
        assert len(out[0][0]) == n and out[0][2] == reason
        assert [len(t) for t, _lp, _r in out[1:]] == [30, 6, 6]
        # the synchronous path's second step holds rc's chunk and rb; a
        # row a stop id ended rides ONE more step and the chain goes on
        # without it
        run = mixed_run(ring)
        assert [r["rows"] for r in run] == rows
        assert all(r["chained_behind"] == "mixed" for r in run[1:])
        assert not any(eng.scheduler.chain_refusals.values())

    @pytest.mark.parametrize("victim", ["ra", "rc"])
    async def test_a_cancel_while_two_steps_are_in_flight(self, victim):
        """A decode row (ra) or the prompt in mid-prefill (rc) is
        cancelled with the run's first two steps both enqueued and
        neither fetched: it ends CANCELLED, once, with the tokens it had;
        the chain breaks there (``rows``) and the others stream what the
        synchronous path streams."""
        def setup(eng):
            eng.victim = victim

        got, ring, eng = await serve_staggered(
            pair()(), CancelsBehindAMixedStep, setup=setup, **PAIR_KW)
        want = await self.plain_synchronous()
        i = ["ra", "rb", "rc", "rd"].index(victim)
        assert got[i][-1].finish_reason == FinishReason.CANCELLED
        assert sum(f.finish_reason is not None for f in got[i]) == 1
        got = streamed(got)
        # the step's own token is dropped with the cancel
        assert got[i][0] == (want[i][0][:RA_AT_THE_RUN] if victim == "ra"
                             else [])
        for j in range(4):
            if j != i:
                # (with rc gone the steps that follow have other shapes
                # than the plain run's: another program rounds a
                # log-probability's last digit its own way)
                assert got[j][0] == want[j][0] and got[j][2] == want[j][2]
                if victim == "ra":
                    assert got[j] == want[j]
        run = mixed_run(ring)
        assert [r["chained_behind"] for r in run[:2]] == ["", "mixed"]
        assert eng.scheduler.chain_refusals["rows"] == 1
        assert eng.allocator.num_free == eng.allocator.num_pages - 1

    async def test_a_dispatch_error_in_the_chained_step(self):
        """The step behind the run's first fails at its dispatch: the
        first is finished (each row gets the token it sampled), then the
        failed step's rows end in ERROR; the engine serves on."""
        eng = staggered_engine(FailsBehindAMixedStep, **PAIR_KW)
        try:
            frames = await asyncio.gather(*[collect(eng, r)
                                            for r in pair()()])
            got, want = streamed(frames), await self.plain_synchronous()
            assert eng.failed
            assert got[0][0] == want[0][0][:RA_AT_THE_RUN + 1]
            assert got[1][0] == want[1][0][:6]
            assert got[2][0] == []
            for fs in frames[:3]:
                assert fs[-1].finish_reason == FinishReason.ERROR
                assert "planted" in fs[-1].error
            assert got[3] == want[3]
            assert eng.allocator.num_free == eng.allocator.num_pages - 1
        finally:
            await eng.stop()

    async def test_a_penalised_row_keeps_the_synchronous_path(self):
        """A penalty window is built on the host from tokens that would
        lack the one in flight: every step of the run is resolved inside
        its dispatch, counted as ``pcarry``."""
        def samp():
            return SamplingOptions(temperature=0.0, presence_penalty=0.6)

        frames, ring, eng = await serve_staggered(pair(samp=samp)(),
                                                  **PAIR_KW)
        frames0, ring0, _eng0 = await serve_staggered(
            pair(samp=samp)(), SyncSteps, **PAIR_KW)
        assert streamed(frames) == streamed(frames0)
        assert [(r["kind"], r["program"], r["rows"]) for r in ring] == \
            [(r["kind"], r["program"], r["rows"]) for r in ring0]
        assert all(r["fetch_ms"] == 0.0 and not r["chained"]
                   for r in ring if r["kind"] == "mixed")
        assert eng.scheduler.chained_steps == {"mixed": 0}
        assert eng.scheduler.chain_refusals["pcarry"] == len(
            [r for r in ring if r["kind"] == "mixed"])

    def test_the_tokens_are_filled_in_on_the_device(self):
        """``_fill_impl``: each decode row's slot of the token array, of
        either form, gets column 0 of its row of the previous step's
        output; pad entries (a slot past the end) change nothing."""
        import numpy as np
        eng = tiny_engine(decode_multistep=4)
        prev = np.zeros((4, 2), np.int32)
        prev[:, 0] = [50, 60, 70, 80]
        fn = eng._get_jit_fill()
        # packed [1, T]: a chunk of 5 tokens, then rows at slots 5 and 6
        toks = np.zeros((1, 8), np.int32)
        toks[0, :5] = [1, 2, 3, 4, 5]
        fill = np.array([[5, 6, 8, 8], [3, 0, 0, 0]], np.int32)
        assert np.asarray(fn(toks, prev, fill)).tolist() == \
            [[1, 2, 3, 4, 5, 80, 50, 0]]
        # padded [B, S]: row i starts at i * S
        toks = np.zeros((4, 4), np.int32)
        toks[0] = [1, 2, 3, 4]
        fill = np.array([[4, 8, 16, 16], [1, 2, 0, 0]], np.int32)
        assert np.asarray(fn(toks, prev, fill)).tolist() == \
            [[1, 2, 3, 4], [60, 0, 0, 0], [70, 0, 0, 0], [0, 0, 0, 0]]

    def test_the_counter_and_the_reasons_are_on_the_scrape_at_zero(self):
        from prometheus_client import CollectorRegistry

        from dynamo_tpu.engine.scheduler import CHAIN_REFUSALS
        from dynamo_tpu.worker.metrics import (WorkerMetrics,
                                               engine_dispatch_stats)
        wm = WorkerMetrics(CollectorRegistry())
        assert type(wm.engine).CHAIN_REFUSALS == CHAIN_REFUSALS

        def value(name, **labels):
            return wm.registry.get_sample_value(name, labels)

        assert value("dynamo_worker_mixed_chained_total",
                     behind="mixed") == 0.0
        for reason in CHAIN_REFUSALS:
            assert value("dynamo_worker_multistep_chain_refused_total",
                         reason=reason) == 0.0

        class Eng:
            scheduler = Scheduler(PageAllocator(9, 4), SchedulerConfig())
        Eng.scheduler.chained_steps["mixed"] = 5
        Eng.scheduler.chain_refusals["run"] = 2
        wm.engine.attach(lambda: engine_dispatch_stats(Eng))
        assert value("dynamo_worker_mixed_chained_total",
                     behind="mixed") == 5.0
        assert value("dynamo_worker_multistep_chain_refused_total",
                     reason="run") == 2.0


def _resolve(sched, plan, token=7):
    """What the loop's ``_process`` / ``_process_multistep`` /
    ``_process_passes`` do to the scheduler: account for the step, append
    the tokens it sampled (no stop id among them: an end the host cannot
    foresee is no part of a plan), end a row whose budget or context that
    spends. A pass dispatch commits a block every two passes."""
    from dynamo_tpu.engine.scheduler import GenPassBatch

    def give(seq):
        seq.tokens.append(token)
        seq.generated.append(token)
        if sched._out_of_budget(seq):
            sched.finish(seq)

    B = sched.cfg.gen_block
    if isinstance(plan, GenPassBatch):
        for seq in plan.seqs:
            for _ in range(plan.width // 2):
                if seq.phase is not Phase.RUNNING:
                    break
                for _ in range(len(seq) - seq.num_computed, B):
                    give(seq)
                    if seq.phase is not Phase.RUNNING:
                        break
                else:
                    seq.num_computed += B
                    sched._commit_full_pages(seq)
        return
    if isinstance(plan, MultiStepBatch):
        took = [min(plan.width, n) if s.phase is Phase.RUNNING else 0
                for s, n in zip(plan.seqs, plan.budgets)]
        sched.on_multistep_done(plan, took)
        for seq, n in zip(plan.seqs, took):
            for _ in range(n):
                give(seq)
        sched.commit_block(plan)
        return
    sched.on_step_done(plan)
    rows = [c.seq for c in getattr(plan, "chunks", ())
            if c.is_last and B == 1]
    rows += list(getattr(plan, "decode_seqs", None)
                 or (plan.seqs if isinstance(plan, DecodeBatch) else ()))
    for seq in rows:
        if seq.phase is Phase.RUNNING:
            give(seq)


def _books(sched, which="all"):
    """Everything a plan behind a step may touch; ``which="shared"``:
    what two schedulers that made the same plans share (not which pages
    a row got - one of them freed a row's before it grew another's, the
    other after - nor the counters of the chains themselves). The
    counters of refusals are no part of it: a refusal is counted."""
    seqs = list(sched.active.values()) + list(sched.waiting)
    # (a chain of pass dispatches reserves further ahead with every link:
    # ``GenPassBatch.inflight`` adds up along it, so its rows hold the
    # pages the host's plan would give them and then some)
    causal = sched.cfg.gen_block == 1
    shared = (sched._run_steps, sched.admission_run_steps, sched.mixed_plans,
              sched._prefer_prefill, sched._steps_since_decode,
              dict(sched.admission_runs),
              causal and sched.alloc.num_free, sorted(sched._free_slots),
              {s.request.request_id: (causal and len(s.page_ids),
                                      s.num_computed, s.phase)
               for s in seqs},
              [s.request.request_id for s in sched.waiting])
    if which == "shared":
        return shared
    # (``_admit_stop`` is an admission pass's own: written by each, read
    # at once, and a plan behind a step makes no pass)
    return shared + (dict(sched.chained_steps), dict(sched.chained_blocks),
                     sched._chain_run, sched._admit_stop,
                     {s.request.request_id: (list(s.page_ids),
                                             s.table_version)
                      for s in seqs})


def _shape(plan):
    """A plan by what the engine makes of it, read once the step in
    front of it has resolved: a mixed step's chunks, and the rows that
    decode, in order, each with the position it feeds and the pages it
    holds; of a block also its width and each row's budget and gate.
    Where a chained plan differs from the host's BY DESIGN, and nowhere
    else, the difference is taken out here: a block behind a step keeps
    a row the host has meanwhile seen end, dead from its start (budget
    0; the host's plan does not hold it), and a chained pass dispatch
    takes its rows' positions and budgets from the device's carry, not
    from the lagging host state its plan records (and reserves pages
    further ahead, ``_books``)."""
    from dynamo_tpu.engine.scheduler import GenPassBatch, MixedStepBatch

    def rid(s):
        return s.request.request_id

    if isinstance(plan, GenPassBatch):
        return ("passes", plan.width,
                [rid(s) for s in plan.seqs if s.phase is Phase.RUNNING])
    if isinstance(plan, MultiStepBatch):
        dead = [n for s, n in zip(plan.seqs, plan.budgets)
                if s.phase is not Phase.RUNNING]
        assert not any(dead) and (plan.chained or not dead)
        return ("block", plan.width,
                [(rid(s), sl, n, g, len(s.page_ids)) for s, sl, n, g in zip(
                    plan.seqs, plan.start_lens, plan.budgets, plan.min_gates)
                 if s.phase is Phase.RUNNING])
    if isinstance(plan, MixedStepBatch):
        return ([(rid(c.seq), c.start, c.length, c.is_last)
                 for c in plan.chunks],
                [(rid(s), len(s) - 1, len(s.page_ids))
                 for s in plan.decode_seqs])
    if isinstance(plan, DecodeBatch):
        return ("decode", [(rid(s), len(s) - 1, len(s.page_ids))
                           for s in plan.seqs])
    return (type(plan).__name__,
            [(rid(c.seq), c.start, c.length, c.is_last)
             for c in getattr(plan, "chunks", ())])


def _host_plan(sched):
    """The host's own next dispatch, as the loop makes it: ``schedule()``,
    a pure-decode plan upgraded where a block is to be had."""
    plan = sched.schedule()
    if isinstance(plan, DecodeBatch) and (sched.cfg.decode_multistep > 1
                                          or sched.cfg.gen_block > 1):
        ms = sched.plan_multistep(plan)
        if ms is not None or sched.cfg.gen_block > 1:
            plan = ms
    return plan


# (what is in flight, what is chained behind it) -> the configurations
# that take that boundary, and the kinds of step the engine chains behind
BOUNDARIES = {
    # (a chain of decode steps breaks at every arrival and at every row
    # that ends: five requests for six rows, none of one token)
    ("decode", "decode"): (dict(decode_multistep=1, requests=5,
                                budgets=(3, 5, 8, 13, 21)), ("decode",)),
    # (and a chain of blocks: rows with several blocks to go)
    ("block", "block"): (dict(requests=5, budgets=(8, 13, 21, 34, 55)),
                         ("block",)),
    # (a run goes on where prompts of several chunks stand in a queue)
    ("mixed", "mixed"): (dict(prompts=(17, 30, 41, 50, 64)), ("mixed",)),
    ("mixed", "block"): ({}, ("block", "mixed")),
}
FLAVOURS = {
    "plain": {},
    # a recurrent state beside the pages: a slot a row, no prefix cache
    "recurrent": dict(state_slots=6),
    # generation by diffusion over blocks: pass dispatches, waves of
    # prefill steps (the legacy alternation), no mixed step
    "blocks_of_4": dict(gen_block=4, mixed_batch=False),
}
ORACLE_CASES = [(b, "plain") for b in BOUNDARIES] + [
    (("mixed", "mixed"), "recurrent"), (("mixed", "block"), "recurrent"),
    (("block", "block"), "recurrent"), (("block", "block"), "blocks_of_4")]


def _kind(plan):
    from dynamo_tpu.engine.scheduler import MixedStepBatch
    return ("block" if isinstance(plan, MultiStepBatch) else
            "mixed" if isinstance(plan, MixedStepBatch) else
            "decode" if isinstance(plan, DecodeBatch) else "")


class TestPlanBehind:
    """``Scheduler.plan_behind``, the scheduler's side of every chain,
    without an engine."""

    def make(self, pages=129, **cfg):
        cfg.setdefault("decode_multistep", 4)
        cfg.setdefault("max_prefill_seqs", 1)
        cfg.setdefault("max_prefill_chunk", 16)
        sched = Scheduler(PageAllocator(pages, 4), SchedulerConfig(**cfg))
        sched.max_context_hint = 96
        return sched

    def queue(self, seed, requests=14, budgets=(1, 2, 3, 5, 8, 13),
              prompts=(3, 5, 9, 17, 30, 41, 50), **cfg):
        import random
        rng = random.Random(seed)
        sched = self.make(max_num_seqs=6, max_prefill_seqs=2, **cfg)
        for i in range(requests):
            n = rng.choice(prompts)
            sched.add_request(make_req(
                [rng.randrange(5, 200) for _ in range(n)], f"q{i}",
                max_tokens=rng.choice(budgets)))
        return sched

    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize(
        "boundary,flavour", ORACLE_CASES,
        ids=[f"{a}->{b}-{f}" for (a, b), f in ORACLE_CASES])
    def test_the_plan_is_the_one_the_host_would_make(self, boundary,
                                                     flavour, seed):
        """On a seeded random queue, served once with every step
        resolved before the next is planned (B) and once with the next
        planned while the step is in flight (A), at every boundary the
        device chains across: ``plan_behind`` returns the plan
        ``schedule()`` returns once the step has resolved - chunks, rows,
        their order, positions, budgets, pages (``_shape``) - finds each
        row's token where the step put it, and leaves the same books; a
        refusal leaves them untouched, and ``schedule()`` then returns
        that plan."""
        cfg, kinds = BOUNDARIES[boundary]
        cfg = {**cfg, **FLAVOURS[flavour]}
        a, b = self.queue(seed, **cfg), self.queue(seed, **cfg)
        took = {}
        plan_a, plan_b = _host_plan(a), _host_plan(b)
        for _ in range(600):
            if plan_a is None:
                break
            # (the step in front of a chained plan has resolved by now:
            # both sides hold the tokens it fed on)
            if _shape(plan_a)[::2] == ("passes", []):
                # by design: a pass dispatch chained from the lagging
                # host state, whose rows have all ended on the device
                # meanwhile, runs dead; the host's plan has none
                assert plan_a.chained
                _resolve(a, plan_a)
                plan_a = a.plan_behind(plan_a, kinds) or _host_plan(a)
                continue
            assert _shape(plan_a) == _shape(plan_b)
            _resolve(b, plan_b)
            plan_b = _host_plan(b)
            before = _books(a)
            nxt = a.plan_behind(plan_a, kinds)
            if nxt is None:
                # (a block the pool was short for keeps what it took for
                # the width it tried: the pages its rows need next)
                if not a.multistep_fallbacks.get("pages"):
                    assert _books(a) == before
            else:
                at = (_kind(plan_a), _kind(nxt))
                took[at] = took.get(at, 0) + 1
                assert at[0] in kinds and at[1]
                self.finds_its_tokens(plan_a, nxt)
            _resolve(a, plan_a)
            plan_a = nxt if nxt is not None else _host_plan(a)
            assert _books(a, "shared") == _books(b, "shared")
            assert a.alloc.num_free <= b.alloc.num_free
        assert plan_a is None and plan_b is None
        assert not a.active and not a.waiting
        assert took.get(boundary, 0) >= 2, took
        assert a.chained_steps == {"mixed": took.get(("mixed", "mixed"), 0)}
        assert a.chained_blocks["mixed"] == took.get(("mixed", "block"), 0)
        if "mixed" not in kinds:
            assert not any(a.chain_refusals.values())

    @staticmethod
    def finds_its_tokens(prev, nxt):
        """Each row of ``nxt`` finds its newest token in ``prev``'s
        output: behind a mixed step by ``src_rows`` (chunk rows, then
        decode rows), behind a decode step or a block row for row."""
        from dynamo_tpu.engine.scheduler import MixedStepBatch
        rows = getattr(nxt, "decode_seqs", None) or nxt.seqs
        if isinstance(prev, MixedStepBatch):
            assert nxt.behind == "mixed"
            at = {id(s): i for i, s in enumerate(prev.seqs)}
            assert nxt.src_rows == [at[id(s)] for s in rows]
        else:
            assert rows == prev.seqs and not getattr(nxt, "src_rows", None)
            assert getattr(nxt, "behind", "") == (
                "block" if isinstance(prev, MultiStepBatch)
                and not hasattr(prev, "tails") else "")

    def step_with(self, sched, a_kw=None, b_prompt=40, b_samp=None,
                  a_prompt=5):
        """a running with one token out, b's first chunk admitted into a
        mixed step, c waiting behind it."""
        from dynamo_tpu.engine.scheduler import MixedStepBatch
        sched.add_request(make_req(list(range(1, a_prompt + 1)), "a",
                                   **{"max_tokens": 12, **(a_kw or {})}))
        first = sched.schedule()
        _resolve(sched, first)
        sched.add_request(make_req(list(range(20, 20 + b_prompt)), "b",
                                   max_tokens=8, samp=b_samp))
        sched.add_request(make_req([7, 8, 9], "c", max_tokens=4))
        assert isinstance(sched.schedule(), DecodeBatch)
        step = sched.schedule()
        assert isinstance(step, MixedStepBatch) and not step.behind
        return step

    def test_rows_positions_and_the_map_into_the_steps_output(self):
        sched = self.make()
        step = self.step_with(sched)
        a, b = step.decode_seqs[0], step.chunks[0].seq
        assert sched.chains_behind(step)
        free, pages = sched.alloc.num_free, len(a.page_ids)
        nxt = sched.plan_behind(step)
        assert [(c.seq, c.start, c.length, c.is_last)
                for c in nxt.chunks] == [(b, 16, 16, False)]
        # a feeds the token in flight at position len(a) = 6: the second
        # page's last slot is 7, nothing to grow; the step's packed rows
        # are its chunks, then its decode rows
        assert nxt.decode_seqs == [a] and nxt.src_rows == [1]
        assert nxt.behind == "mixed"
        assert len(a.page_ids) == pages and sched.alloc.num_free == free
        assert (sched._run_steps, sched.mixed_plans) == (2, 2)
        assert sched.chained_steps == {"mixed": 1}
        # the step behind it: b's last chunk; then the run is over
        _resolve(sched, step)
        block = sched.plan_behind(nxt)
        # (b's last 8 tokens do not fill a step: b stays in prefill)
        assert isinstance(block, MultiStepBatch) and block.behind == "mixed"
        assert block.seqs == [a] and block.src_rows == [1]
        assert sched._run_steps == 0

    @pytest.mark.parametrize("why", ["rows", "rows_later", "pcarry",
                                     "pages", "budget", "off"])
    def test_a_refusal_changes_nothing_and_is_counted(self, why):
        from dynamo_tpu.engine.scheduler import MixedStepBatch
        samp = (SamplingOptions(temperature=0.0, presence_penalty=0.5)
                if why == "pcarry" else None)
        sched = self.make(**({"decode_multistep": 1} if why == "off"
                             else {}))
        step = self.step_with(
            sched, a_kw={"max_tokens": 2} if why == "budget" else None,
            b_samp=samp,
            # (a row of 8 tokens feeds position 8 next: a third page)
            a_prompt=7 if why == "pages" else 5)
        a = step.decode_seqs[0]
        if why == "rows":
            a.cancelled = True
        # asked before the step is dispatched
        assert sched.chains_behind(step) == (
            why not in ("rows", "pcarry", "off"))
        if why == "rows_later":
            a.cancelled = True
        elif why == "pages":
            assert sched.alloc.allocate(sched.alloc.num_free)
        want = {"rows_later": "rows", "off": None}.get(why, why)
        counted = {r: int(r == want) for r in sched.chain_refusals}
        if why in ("rows", "pcarry"):
            assert sched.chain_refusals == counted
            sched.chain_refusals[want] = 0
        before = _books(sched)
        assert sched.plan_behind(step) is None
        assert sched.chain_refusals == counted
        assert _books(sched) == before
        assert sched.chained_steps == {"mixed": 0}
        assert sched.multistep_fallbacks == {}
        if why not in ("pages", "rows", "rows_later"):
            # the synchronous path: the step resolves, the host plans
            _resolve(sched, step)
            nxt = sched.schedule()
            assert isinstance(nxt, (MixedStepBatch, DecodeBatch,
                                    PrefillBatch))
            assert not getattr(nxt, "behind", "")

    @pytest.mark.parametrize("why", ["queue_fell", "part_filled"])
    def test_a_run_that_does_not_go_on_ends_in_the_block(self, why):
        """What follows a step is decided when it is planned, from what
        the host holds then: where the queue fell while the step ran, or
        what is left of the prompt does not fill a step, the host's next
        plan is the pure-decode one, and the block is chained."""
        sched = self.make()
        step = self.step_with(sched,
                              b_prompt=20 if why == "part_filled" else 40)
        a = step.decode_seqs[0]
        assert sched.chains_behind(step)
        if why == "queue_fell":
            sched.cancel("c")
        plan = sched.plan_behind(step)
        assert isinstance(plan, MultiStepBatch) and plan.behind == "mixed"
        # b's prompt is still in prefill: the block holds a alone
        assert plan.seqs == [a] and plan.src_rows == [1]
        assert plan.start_lens == [len(a) + 1]
        assert not any(sched.chain_refusals.values())
        assert sched.chained_steps == {"mixed": 0}
        assert sched._run_steps == 0 and sched._prefer_prefill


class TestMockerBlockPath:
    async def test_mocker_fused_tokens_match_per_step(self):
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine

        async def run(ms):
            eng = MockerEngine(MockEngineArgs(
                speedup_ratio=100.0, decode_multistep=ms))
            try:
                reqs = [make_req([i + 1, i + 2, i + 3], f"k{i}",
                                 max_tokens=n)
                        for i, n in enumerate((4, 9, 14))]
                results = await asyncio.gather(
                    *[collect(eng, r) for r in reqs])
                return ([toks_of(f) for f in results],
                        eng.multistep_blocks)
            finally:
                await eng.stop()

        fused, blocks = await run(8)
        per_step, blocks0 = await run(1)
        assert blocks > 0 and blocks0 == 0
        assert fused == per_step
        assert [len(t) for t in fused] == [4, 9, 14]


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
