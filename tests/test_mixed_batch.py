"""Ragged mixed-batch attention + mixed prefill/decode dispatch (ISSUE 11).

The contract under test: with DYN_MIXED_BATCH on, prefill chunks and
decode rows advance in ONE token-budgeted dispatch
(``engine/scheduler.MixedStepBatch``), fused multi-step decode keeps
running while arrivals onboard (the PR 8 "no waiters/prefills" gate is
lifted), and the token streams stay BIT-IDENTICAL to the legacy
prefill-XOR-decode alternation under greedy and fixed-seed sampling.
The ragged attention op (``ops.attention.ragged_paged_attention`` flat
reference + ``ops/pallas/ragged.py`` kernel over the same packed layout)
matches the dense per-row oracle on ragged row shapes.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import JaxEngine, JaxEngineConfig
from dynamo_tpu.engine.pages import PageAllocator
from dynamo_tpu.engine.scheduler import (
    DecodeBatch,
    MixedStepBatch,
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
                    min_prefill_bucket=4, decode_multistep=8)
    defaults.update(kw)
    return JaxEngine.random_init(cfg, JaxEngineConfig(**defaults))


async def collect(engine, req, ctx=None):
    frames = []
    async for out in engine.generate(req, ctx=ctx):
        frames.append(out)
    return frames


def toks_of(frames):
    return [t for f in frames for t in f.token_ids]


# -- ragged attention numerics -------------------------------------------


class TestRaggedOp:
    """The flat-layout reference op vs the dense per-row oracle."""

    def _setup(self, seed=0):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        L, N, Hkv, ps, Dh, Hq, P = 2, 32, 2, 8, 128, 4, 12
        pages = jnp.asarray(
            rng.normal(size=(L, N, 2, Hkv, ps, Dh)).astype(np.float32))
        table = jnp.asarray(rng.integers(1, N, size=(3, P)).astype(np.int32))
        # ragged rows: a mid-prompt chunk, a decode step, a fresh chunk
        q_lens = np.array([7, 1, 5], np.int32)
        kv_lens = np.array([23, 9, 5], np.int32)
        q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]) \
            .astype(np.int32)
        T = int(q_lens.sum()) + 3       # tail padding
        q = jnp.asarray(rng.normal(size=(T, Hq, Dh)).astype(np.float32))
        return pages, table, q, q_starts, q_lens, kv_lens

    def test_flat_ragged_matches_dense_per_row(self):
        import jax.numpy as jnp

        from dynamo_tpu.ops.attention import (paged_attention,
                                              ragged_paged_attention)
        pages, table, q, q_starts, q_lens, kv_lens = self._setup()
        out = ragged_paged_attention(
            q, pages, 1, table, jnp.asarray(q_starts), jnp.asarray(q_lens),
            jnp.asarray(kv_lens), 0.09)
        for i in range(3):
            s, ln, kv = int(q_starts[i]), int(q_lens[i]), int(kv_lens[i])
            pos = jnp.arange(kv - ln, kv)[None]
            ref = paged_attention(q[s:s + ln][None], pages, 1,
                                  table[i:i + 1], pos,
                                  jnp.asarray([kv], jnp.int32), 0.09)[0]
            assert float(jnp.max(jnp.abs(out[s:s + ln] - ref))) < 2e-5
        # pad slots are zeroed, not garbage
        assert float(jnp.max(jnp.abs(out[int(q_lens.sum()):]))) == 0.0

    @pytest.mark.parametrize("window,softcap", [
        (None, None), (6, None), (None, 30.0), (17, 50.0)])
    def test_pallas_packed_kernel_matches_xla_reference(self, window,
                                                        softcap):
        """The packed kernel (interpreted) against the flat reference on
        the same packed layout: no row is aligned to a query block, the
        decode row takes one slot, a chunk straddles two blocks."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.attention import ragged_paged_attention
        from dynamo_tpu.ops.pallas.ragged import (
            ragged_mixed_attention_packed)
        pages, table, q, q_starts, q_lens, kv_lens = self._setup()
        pages = pages.astype(jnp.bfloat16)
        q = q.astype(jnp.bfloat16)
        args = (q, pages, 1, table, jnp.asarray(q_starts),
                jnp.asarray(q_lens), jnp.asarray(kv_lens), 0.09)
        out = ragged_mixed_attention_packed(
            *args, window=window, softcap=softcap, interpret=True)
        ref = ragged_paged_attention(
            *args, window=None if window is None else jnp.asarray(window),
            softcap=softcap)
        assert out.shape == q.shape and out.dtype == q.dtype
        n = int(q_lens.sum())
        err = float(jnp.max(jnp.abs(out[:n].astype(jnp.float32)
                                    - ref[:n].astype(jnp.float32))))
        assert err < 0.05, err
        # slots of no row write zeros
        assert float(jnp.max(jnp.abs(out[n:].astype(jnp.float32)))) == 0.0

    def test_packed_kernel_over_many_blocks_and_pad_rows(self):
        """A packed axis of several query blocks: chunks that straddle
        block edges, decode rows packed behind them, pad rows (no tokens)
        and whole blocks past the last real token."""
        import jax.numpy as jnp

        from dynamo_tpu.ops.attention import ragged_paged_attention
        from dynamo_tpu.ops.pallas.ragged import (
            ragged_mixed_attention_packed)
        rng = np.random.default_rng(3)
        L, N, Hkv, ps, Dh, Hq, P = 2, 64, 2, 8, 128, 4, 24
        pages = jnp.asarray(rng.normal(size=(L, N, 2, Hkv, ps, Dh))
                            .astype(np.float32)).astype(jnp.bfloat16)
        q_lens = np.array([70, 150, 1, 1, 1, 0, 0], np.int32)
        kv_lens = np.array([133, 150, 9, 77, 1, 1, 1], np.int32)
        table = jnp.asarray(rng.integers(1, N, size=(len(q_lens), P))
                            .astype(np.int32))
        q_starts = (np.cumsum(q_lens) - q_lens).astype(np.int32)
        for T in (512, 230):       # 230: not a multiple of the block
            q = jnp.asarray(rng.normal(size=(T, Hq, Dh))
                            .astype(np.float32)).astype(jnp.bfloat16)
            args = (q, pages, 1, table, jnp.asarray(q_starts),
                    jnp.asarray(q_lens), jnp.asarray(kv_lens), 0.09)
            out = ragged_mixed_attention_packed(*args, interpret=True)
            ref = ragged_paged_attention(*args)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            assert out.shape == (T, Hq, Dh) and err < 0.05, (T, err)


# -- engine parity: mixed dispatch vs legacy alternation ------------------


class TestMixedParity:
    """Mixed-dispatch token streams must be bit-identical to the legacy
    split path: tokens depend on each row's own context, greedy argmax
    and position-keyed seeded draws see identical logits either way."""

    async def _run(self, mixed: bool, samp=None):
        eng = tiny_engine(mixed_batch=mixed)
        try:
            first_started = asyncio.Event()

            async def staggered(req):
                # deterministic overlap: the second/third requests arrive
                # once the first has tokens flowing (decode + prefill
                # genuinely contend, without wall-clock sleeps)
                await first_started.wait()
                return await collect(eng, req)

            async def leader(req):
                frames = []
                async for out in eng.generate(req):
                    frames.append(out)
                    if sum(len(f.token_ids) for f in frames) >= 2:
                        first_started.set()
                first_started.set()
                return frames

            reqs = [make_req([1, 2, 3, 4, 5], "m0", max_tokens=18,
                             samp=samp() if samp else None),
                    make_req([9, 8, 7, 6, 5, 4, 3, 2, 1] * 2, "m1",
                             max_tokens=11, samp=samp() if samp else None),
                    make_req([5, 5, 5, 5], "m2", max_tokens=6,
                             samp=samp() if samp else None)]
            results = await asyncio.gather(
                leader(reqs[0]), staggered(reqs[1]), staggered(reqs[2]))
            return ([toks_of(f) for f in results],
                    [f[-1].finish_reason for f in results],
                    {"mixed_steps": eng.mixed_steps,
                     "blocks": eng.multistep_blocks})
        finally:
            await eng.stop()

    async def test_greedy_parity(self):
        m_toks, m_r, mc = await self._run(True)
        l_toks, l_r, lc = await self._run(False)
        assert mc["mixed_steps"] > 0       # the mixed path actually ran
        assert lc["mixed_steps"] == 0
        assert m_toks == l_toks
        assert m_r == l_r
        assert [len(t) for t in m_toks] == [18, 11, 6]

    async def test_seeded_parity(self):
        def samp():
            return SamplingOptions(temperature=0.9, seed=1234)

        m_toks, _mr, mc = await self._run(True, samp)
        l_toks, _lr, _lc = await self._run(False, samp)
        assert mc["mixed_steps"] > 0
        assert m_toks == l_toks

    async def test_fused_blocks_active_while_arrivals_onboard(self):
        # the acceptance gate of the lifted multistep gate: fused blocks
        # AND mixed dispatches both run in one overlapping-arrival session
        _toks, _r, c = await self._run(True)
        assert c["blocks"] > 0 and c["mixed_steps"] > 0

    async def test_prefill_finishes_mid_mixed_step_emits_first_token(self):
        eng = tiny_engine(mixed_batch=True)
        try:
            started = asyncio.Event()

            async def leader():
                frames = []
                async for out in eng.generate(
                        make_req([1, 2, 3], "lead", max_tokens=30)):
                    frames.append(out)
                    started.set()
                return frames

            async def follower():
                await started.wait()
                # one-chunk prompt: its final (only) chunk lands inside a
                # mixed step while "lead" decodes — the first token must
                # be emitted from that same dispatch
                frames = await collect(
                    eng, make_req([4, 5, 6, 7], "foll", max_tokens=5))
                return frames

            lead, foll = await asyncio.gather(leader(), follower())
            assert len(toks_of(foll)) == 5
            assert len(toks_of(lead)) == 30
            assert eng.mixed_steps > 0
        finally:
            await eng.stop()

    async def test_cancel_mid_prefill_reclaims_pages(self):
        class Ctx:
            cancelled = False

        eng = tiny_engine(mixed_batch=True, max_prefill_chunk=4,
                          max_context=64)
        free0 = eng.allocator.num_free
        try:
            started = asyncio.Event()

            async def leader():
                frames = []
                async for out in eng.generate(
                        make_req([1, 2, 3], "ld", max_tokens=24)):
                    frames.append(out)
                    started.set()
                return frames

            async def victim():
                await started.wait()
                ctx = Ctx()
                ctx.cancelled = True    # cancelled while chunks in flight
                return await collect(
                    eng, make_req(list(range(1, 30)), "vt", max_tokens=8),
                    ctx=ctx)

            lead, vic = await asyncio.gather(leader(), victim())
            assert vic[-1].finish_reason == FinishReason.CANCELLED
            assert len(toks_of(lead)) == 24
            for _ in range(100):
                if eng.allocator.num_free == free0:
                    break
                await asyncio.sleep(0.02)
            assert eng.allocator.num_free == free0
        finally:
            await eng.stop()


# -- scheduler unit tests -------------------------------------------------


class TestMixedScheduling:
    def make(self, num_pages=33, page_size=4, **cfg):
        alloc = PageAllocator(num_pages, page_size)
        base = dict(max_num_seqs=4, max_prefill_chunk=8,
                    decode_multistep=8)
        base.update(cfg)
        s = Scheduler(alloc, SchedulerConfig(**base))
        s.max_context_hint = 128
        return s, alloc

    def to_running(self, sched, req):
        sched.add_request(req)
        while True:
            plan = sched.schedule()
            assert plan is not None
            sched.on_step_done(plan)
            seqs = plan.seqs
            seq = seqs[-1]
            for s in seqs:
                if s.phase is Phase.RUNNING and not s.generated:
                    s.tokens.append(9)
                    s.generated.append(9)
            if all(s.phase is Phase.RUNNING for s in sched.active.values()):
                return seq

    def _advance(self, sched, plan):
        """Resolve one plan the way the engine loop would: accounting,
        then append a token for every row that sampled one."""
        sched.on_step_done(plan)
        sampled = []
        if isinstance(plan, (PrefillBatch, MixedStepBatch)):
            sampled += [c.seq for c in plan.chunks if c.is_last]
            sampled += list(getattr(plan, "decode_seqs", ()))
        elif isinstance(plan, DecodeBatch):
            sampled += plan.seqs
        for s in sampled:
            if s.phase is Phase.RUNNING:
                s.tokens.append(9)
                s.generated.append(9)

    def test_mixed_plan_packs_chunks_and_decode_rows(self):
        sched, _ = self.make()
        running = self.to_running(sched, make_req(range(1, 6), "a",
                                                  max_tokens=32))
        sched.add_request(make_req(range(20, 31), "b", max_tokens=8))
        # the alternation's decode half comes first after to_running's
        # prefill step; the NEXT plan must be the mixed step
        plan = sched.schedule()
        if isinstance(plan, DecodeBatch):
            self._advance(sched, plan)
            plan = sched.schedule()
        assert isinstance(plan, MixedStepBatch)
        assert [c.seq.request.request_id for c in plan.chunks] == ["b"]
        assert plan.decode_seqs == [running]
        # token budget honored by the chunk packing
        assert sum(c.length for c in plan.chunks) <= 8
        n0 = running.num_computed
        sched.on_step_done(plan)
        assert running.num_computed == n0 + 1        # decode row advanced
        assert plan.chunks[0].seq.num_computed == 8  # chunk advanced

    def test_mixed_alternates_with_pure_decode(self):
        # while a multi-chunk prefill is in flight, plans alternate
        # mixed / pure-decode — the pure half is what fuses
        sched, _ = self.make()
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=64))
        sched.add_request(make_req(range(1, 30), "b", max_tokens=8))
        kinds = []
        for _ in range(4):
            plan = sched.schedule()
            kinds.append(type(plan).__name__)
            self._advance(sched, plan)
        assert "MixedStepBatch" in kinds[:2]
        assert "DecodeBatch" in kinds[:2]

    def test_spec_mode_disables_mixed(self):
        sched, _ = self.make(spec_tokens=4)
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=32))
        sched.add_request(make_req(range(1, 6), "b", max_tokens=8))
        plan = sched.schedule()
        assert not isinstance(plan, MixedStepBatch)

    def test_decode_progress_guarantee_legacy(self):
        # legacy alternation + deep waiting queue + K=3: at most 2
        # consecutive decode-free plans while decode rows exist
        sched, _ = self.make(mixed_batch=False, decode_progress_every=3,
                             max_prefill_seqs=1, max_num_seqs=8,
                             num_pages=257)
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=1000))
        for i in range(8):
            sched.add_request(make_req(range(1, 20), f"w{i}",
                                       max_tokens=1000))
        streak, max_streak = 0, 0
        for _ in range(24):
            plan = sched.schedule()
            if plan is None:
                break
            self._advance(sched, plan)
            if isinstance(plan, (DecodeBatch, MixedStepBatch)):
                streak = 0
            else:
                streak += 1
                max_streak = max(max_streak, streak)
        assert max_streak == 2          # the K-1 bound held, AND
        #                                 consecutive prefills DID happen
        #                                 (burst TTFT preference)

    def test_decode_progress_default_keeps_alternation(self):
        sched, _ = self.make(mixed_batch=False, max_prefill_seqs=1,
                             max_num_seqs=8, num_pages=257)
        self.to_running(sched, make_req(range(1, 6), "a", max_tokens=1000))
        for i in range(6):
            sched.add_request(make_req(range(1, 20), f"w{i}",
                                       max_tokens=1000))
        kinds = []
        for _ in range(6):
            plan = sched.schedule()
            assert plan is not None
            self._advance(sched, plan)
            kinds.append("D" if isinstance(plan, DecodeBatch) else "P")
        assert "".join(kinds).count("PP") == 0   # strict alternation

    def test_fallback_reasons_recorded(self):
        sched, _ = self.make()
        r = make_req(range(1, 6), "p", max_tokens=32,
                     samp=SamplingOptions(temperature=0.0,
                                          frequency_penalty=1.0))
        seq = self.to_running(sched, r)
        d = sched.schedule()
        assert isinstance(d, DecodeBatch)
        assert sched.plan_multistep(d) is None
        assert sched.multistep_fallbacks == {"penalties": 1}
        assert seq.multistep_fallbacks == 1

        sched2, _ = self.make()
        r2 = make_req(range(1, 6), "g", max_tokens=32,
                      samp=SamplingOptions(temperature=0.0,
                                           guided={"mode": "json"}))
        self.to_running(sched2, r2)
        assert sched2.plan_multistep(sched2.schedule()) is None
        assert sched2.multistep_fallbacks == {"guided": 1}


# -- admission runs (ISSUE 35) ---------------------------------------------


def _sched(num_pages=257, page_size=4, **cfg):
    alloc = PageAllocator(num_pages, page_size)
    base = dict(max_num_seqs=16, max_prefill_chunk=8, decode_multistep=8)
    base.update(cfg)
    s = Scheduler(alloc, SchedulerConfig(**base))
    s.max_context_hint = 512
    return s


def _resolve(sched, plan, finished=None):
    """What the engine loop does with a plan's result, counts only: the
    accounting, one token for every row that sampled (a block: as many
    as the device would write), and the end of a row at its budget."""
    def accept(seq):
        seq.tokens.append(9)
        seq.generated.append(9)
        if len(seq.generated) >= seq.request.stop_conditions.max_tokens:
            sched.finish(seq)
            if finished is not None:
                finished.append(seq)

    if isinstance(plan, MultiStepBatch):
        adv = [min(plan.width, b) if q.phase is Phase.RUNNING else 0
               for q, b in zip(plan.seqs, plan.budgets)]
        sched.on_multistep_done(plan, adv)
        for q, a in zip(plan.seqs, adv):
            for _ in range(a):
                accept(q)
        sched.commit_block(plan)
        return
    sched.on_step_done(plan)
    if isinstance(plan, (PrefillBatch, MixedStepBatch)):
        rows = [c.seq for c in plan.chunks if c.is_last]
        rows += getattr(plan, "decode_seqs", [])
    else:
        rows = plan.seqs
    for q in rows:
        if q.phase is Phase.RUNNING:
            accept(q)


def _next_plan(sched):
    """The loop's planning, without its chained blocks: schedule, and
    upgrade a pure-decode plan to a fused block."""
    plan = sched.schedule()
    if isinstance(plan, DecodeBatch):
        plan = sched.plan_multistep(plan) or plan
    return plan


def _closed_loop(sched, clients, steps, prompt, max_tokens, seed=0):
    """``clients`` callers, each sending its next prompt when the last is
    answered. Returns the plans in order, as comparable tuples."""
    rng = np.random.default_rng(seed)
    n = [0]

    def send():
        n[0] += 1
        lo, hi = prompt
        out = (max_tokens if isinstance(max_tokens, int)
               else int(rng.integers(max_tokens[0], max_tokens[1] + 1)))
        sched.add_request(make_req(
            rng.integers(1, 400, int(rng.integers(lo, hi + 1))).tolist(),
            f"r{n[0]}", max_tokens=out, ignore_eos=True))

    for _ in range(clients):
        send()
    log, blocks = [], []
    for _ in range(steps):
        plan = _next_plan(sched)
        assert plan is not None
        ids = [q.request.request_id for q in plan.seqs]
        log.append((type(plan).__name__, getattr(plan, "width", 0),
                    tuple(c.length for c in getattr(plan, "chunks", ())),
                    tuple(ids)))
        if isinstance(plan, MultiStepBatch):
            blocks.append(plan)
        done = []
        _resolve(sched, plan, done)
        for _ in done:
            send()
    return log, blocks


class TestAdmissionRuns:
    def _with_a_running_row(self, **cfg):
        sched = _sched(**cfg)
        sched.add_request(make_req(range(1, 6), "a", max_tokens=64))
        _resolve(sched, sched.schedule())       # the prompt, alone
        _resolve(sched, sched.schedule())       # the decode half
        return sched, sched.active["a"]

    @pytest.mark.parametrize("ended_by,cfg,waiting,kinds", [
        # one prompt of three chunks and nobody behind it: no queue
        # stands, and its chunks alternate with the rows' decode half
        # as they always did
        ("queue", {}, [20], "MD"),
        # a standing queue, rows and pages to spare: the pass takes the
        # prompts it may (3 x 6 tokens), two full steps compute 16 of
        # them, and 2 tokens are not worth a third
        ("partial", dict(max_prefill_seqs=3), [6] * 10, "MMD"),
        # two rows free: both prompts are computed in three full steps
        ("rows", dict(max_num_seqs=3, max_prefill_seqs=3), [12] * 6, "MMMD"),
        # 13 usable pages: beside what "a" and the first prompt will ask
        # for through their next block, the second prompt does not fit
        ("pages", dict(num_pages=14, max_prefill_seqs=3), [16] * 4, "MMD"),
    ])
    def test_what_ends_a_run(self, ended_by, cfg, waiting, kinds):
        sched, a = self._with_a_running_row(**cfg)
        for i, n in enumerate(waiting):
            # unique prompts: no prefix cache shortens a chunk
            sched.add_request(make_req(range(100 * i + 100, 100 * i + 100 + n),
                                       f"w{i}", max_tokens=64))
        assert sum(sched.admission_runs.values()) == 0
        seen = ""
        for _ in kinds:
            plan = sched.schedule()
            seen += {MixedStepBatch: "M", DecodeBatch: "D"}[type(plan)]
            n0, g0 = a.num_computed, len(a.generated)
            if isinstance(plan, MixedStepBatch):
                # every step of a run takes every decode row one token on
                assert a in plan.decode_seqs
                # ... and carries the whole budget
                assert sum(c.length for c in plan.chunks) == 8
            _resolve(sched, plan)
            assert (a.num_computed, len(a.generated)) == (n0 + 1, g0 + 1)
        assert seen == kinds
        want = dict.fromkeys(("queue", "rows", "pages", "partial"), 0)
        want[ended_by] = 1
        assert sched.admission_runs == want
        assert sched.admission_run_steps == kinds.count("M")
        assert sched.num_preemptions == 0

    def test_pool_bound_closed_loop_never_preempts_or_narrows(self):
        # 32 callers on 16 rows and a pool that holds fewer than 16 of
        # them to the end of their 24 tokens: the pool bounds the batch, and
        # because admission leaves what the rows are sure to ask for,
        # no row is ever evicted and every block runs at full width
        sched = _sched(num_pages=161, max_prefill_chunk=64)
        log, blocks = _closed_loop(sched, clients=32, steps=1500,
                                   prompt=(8, 32), max_tokens=24)
        assert sched.num_preemptions == 0
        assert sched.multistep_fallbacks.get("pages", 0) == 0
        assert len(blocks) > 300
        for b in blocks:
            most = min(8, max(b.budgets))
            assert b.width == 1 << (most.bit_length() - 1)
        # (the first prompts go alone: no decode row yet)
        assert {k for k, *_ in log[1:]} == {"MixedStepBatch",
                                            "MultiStepBatch"}
        runs = sched.admission_runs
        assert runs["pages"] > runs["partial"] > runs["rows"] == 0
        assert runs["queue"] == 0
        # some runs are longer than one step
        assert sched.admission_run_steps > 1.2 * sum(runs.values())
        assert max(len(b.seqs) for b in blocks) < 16

    def test_as_many_callers_as_rows_plans_as_before(self):
        # the reasoning cell's shape, ramp included (16 callers at once,
        # a pass of 8, two or three prompts a step): no queue stands,
        # every run is one step long, and the plans are the strict
        # alternation's, plan for plan
        class Alternating(Scheduler):
            def schedule(self):
                self._run_steps = 0     # every mixed step ends its run
                return super().schedule()

        logs = []
        for cls in (Scheduler, Alternating):
            sched = cls(PageAllocator(2049, 4), SchedulerConfig(
                max_num_seqs=16, max_prefill_chunk=32, decode_multistep=4))
            sched.max_context_hint = 512
            log, _ = _closed_loop(sched, clients=16, steps=600,
                                  prompt=(8, 16), max_tokens=(24, 56),
                                  seed=3)
            logs.append(log)
            if cls is Scheduler:
                runs = sched.admission_runs
                # (the run under way when the loop stops is not counted)
                assert 20 < sched.admission_run_steps <= sum(runs.values()) + 1
                assert runs["rows"] == runs["pages"] == 0
                assert runs["partial"] <= 3 < runs["queue"]     # the ramp's
        assert logs[0] == logs[1]

    def test_a_lone_prompt_is_admitted_whatever_it_will_ask_for(self):
        # 3 usable pages: the prompt fits, its next block does not; with
        # no row to protect it runs as far as it gets
        sched = _sched(num_pages=4)
        sched.add_request(make_req(range(1, 6), "a", max_tokens=32))
        assert isinstance(sched.schedule(), PrefillBatch)


# -- metrics surface ------------------------------------------------------


class TestMetricsSurface:
    async def test_engine_dispatch_stats_carry_mixed_and_fallbacks(self):
        from dynamo_tpu.worker.metrics import engine_dispatch_stats
        # penalty_window=0 disables the device-resident penalty path so
        # the penalized row still refuses fusion — this test is about the
        # fallback *counter* surface, not the fused penalty path
        eng = tiny_engine(mixed_batch=True, penalty_window=0)
        try:
            started = asyncio.Event()

            async def leader():
                async for out in eng.generate(
                        make_req([1, 2, 3], "a", max_tokens=20,
                                 samp=SamplingOptions(
                                     temperature=0.0,
                                     presence_penalty=0.5))):
                    started.set()

            async def follower():
                await started.wait()
                await collect(eng, make_req([4, 5, 6], "b", max_tokens=6))

            await asyncio.gather(leader(), follower())
            stats = engine_dispatch_stats(eng)
            assert stats["mixed_dispatches"] == eng.mixed_steps
            assert stats["mixed_dispatches"] > 0
            # the penalized row refused fusion with a recorded reason
            assert stats["multistep_fallbacks"].get("penalties", 0) >= 1
        finally:
            await eng.stop()

    def test_worker_registry_renders_fallback_family(self):
        from prometheus_client import CollectorRegistry

        from dynamo_tpu.worker.metrics import WorkerMetrics
        wm = WorkerMetrics(CollectorRegistry())
        wm.engine.attach(lambda: {
            "decode_dispatches": 5, "mixed_dispatches": 2,
            "multistep_fallbacks": {"penalties": 3}})
        families = {f.name: f for f in wm.registry.collect()}
        assert "dynamo_worker_mixed_dispatches" in families
        fb = families["dynamo_worker_multistep_fallback"]
        by_reason = {s.labels["reason"]: s.value for s in fb.samples
                     if s.name.endswith("_total")}
        assert by_reason["penalties"] == 3.0
        # pre-seeded labels show at zero before any refusal; "mesh" is no
        # longer a reason at all — sharded engines fuse (PR 10)
        assert by_reason["waiters"] == 0.0 and by_reason["multihost"] == 0.0
        assert "mesh" not in by_reason

    def test_worker_registry_renders_admission_runs(self):
        from prometheus_client import CollectorRegistry

        from dynamo_tpu.worker.metrics import (WorkerMetrics,
                                               engine_dispatch_stats)
        wm = WorkerMetrics(CollectorRegistry())
        from dynamo_tpu.engine.scheduler import RUN_ENDS
        assert type(wm.engine).RUN_ENDS == RUN_ENDS

        def value(name, **labels):
            return wm.registry.get_sample_value(name, labels or None)

        # on the scrape at 0 before an engine is attached, every label
        for ended_by in ("queue", "rows", "pages", "partial"):
            assert value("dynamo_worker_sched_admission_runs_total",
                         ended_by=ended_by) == 0.0
        assert value("dynamo_worker_sched_admission_run_steps_total") == 0.0
        assert value("dynamo_worker_preemptions_total") == 0.0

        class Eng:
            scheduler = _sched()
        Eng.scheduler.admission_runs["pages"] = 3
        Eng.scheduler.admission_run_steps = 7
        Eng.scheduler.num_preemptions = 2
        wm.engine.attach(lambda: engine_dispatch_stats(Eng))
        assert value("dynamo_worker_sched_admission_runs_total",
                     ended_by="pages") == 3.0
        assert value("dynamo_worker_sched_admission_runs_total",
                     ended_by="queue") == 0.0
        assert value("dynamo_worker_sched_admission_run_steps_total") == 7.0
        assert value("dynamo_worker_preemptions_total") == 2.0


# -- engine-internal caches ----------------------------------------------


class TestTableCache:
    def test_device_table_reused_until_pages_change(self):
        eng = tiny_engine()
        from dynamo_tpu.engine.scheduler import Sequence
        seqs = [Sequence(make_req([1, 2, 3], f"s{i}"), page_size=4)
                for i in range(2)]
        for i, s in enumerate(seqs):
            s.page_ids = [i + 1]
            s.pages_changed()
        t1, d1 = eng._table_arrays(seqs, 2)
        t2, d2 = eng._table_arrays(seqs, 2)
        assert t1 is t2 and d1 is d2           # no rebuild, no re-upload
        seqs[0].page_ids.append(5)
        seqs[0].pages_changed()
        t3, d3 = eng._table_arrays(seqs, 2)
        assert d3 is not d1
        assert list(t3[0][:2]) == [1, 5]       # stale row rewritten
        assert list(t3[1][:1]) == [2]
        # the previously returned host table was not mutated in place
        assert list(t1[0][:2]) == [1, 0]


# -- mocker ---------------------------------------------------------------


class TestMockerMixed:
    async def test_mocker_mixed_parity_and_hooks(self):
        from dynamo_tpu.mocker.engine import MockEngineArgs, MockerEngine

        async def run(mixed):
            eng = MockerEngine(MockEngineArgs(
                speedup_ratio=200.0, mixed_batch=mixed))
            try:
                started = asyncio.Event()

                async def leader():
                    frames = []
                    async for out in eng.generate(
                            make_req([1, 2, 3], "k0", max_tokens=16)):
                        frames.append(out)
                        started.set()
                    return frames

                async def follower(i):
                    await started.wait()
                    return await collect(
                        eng, make_req(list(range(1, 40)), f"k{i}",
                                      max_tokens=6))

                results = await asyncio.gather(leader(), follower(1),
                                               follower(2))
                return ([toks_of(f) for f in results], eng.mixed_steps)
            finally:
                await eng.stop()

        mixed_toks, mixed_steps = await run(True)
        legacy_toks, legacy_steps = await run(False)
        assert mixed_steps > 0 and legacy_steps == 0
        assert mixed_toks == legacy_toks
        assert [len(t) for t in mixed_toks] == [16, 6, 6]


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
