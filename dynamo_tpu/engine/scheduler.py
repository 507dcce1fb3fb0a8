"""Continuous-batching scheduler for the TPU serving engine.

Request lifecycle (capability parity with the reference's engine-internal
schedulers — vLLM/SGLang on CUDA, and the rust mocker's chunked scheduler
``lib/llm/src/mocker/scheduler.rs:249-520`` — re-designed for a jit-compiled
engine):

  WAITING --admit (prefix-match + allocate pages)--> PREFILL
  PREFILL --chunked prefill steps--> RUNNING (first token sampled)
  RUNNING --decode steps, page-by-page growth--> FINISHED
  RUNNING --page pressure--> PREEMPTED (pages released) --> WAITING (re-admit,
           prefix cache usually revives the computed prefix)

The scheduler is pure host-side bookkeeping: it never touches device arrays.
Each call to :meth:`schedule` returns ONE step plan — a prefill batch
(up to ``max_prefill_seqs`` sequences sharing the ``max_prefill_chunk`` token
budget, one [B, S] step), a decode batch over all running sequences, or —
with ``mixed_batch`` on (the default) — a :class:`MixedStepBatch` packing
the prefill chunks AND the decode rows into that same [B, S] step (each
decode row is a ragged length-1 chunk) — and the engine turns the plan
into padded/bucketed device arrays. Mixed steps alternate with pure decode
plans (the half the engine fuses into multi-step blocks); with
``mixed_batch`` off, prefill and decode alternate when both are runnable,
bounded by the ``decode_progress_every`` guarantee.

Token accounting: ``num_computed`` counts positions whose KV is written to the
cache. A decode step feeds the single newest token (position ``len-1``),
samples the next, appends it. A prefill chunk feeds prompt positions
``[num_computed, num_computed+chunk)``; the final chunk's logits produce the
first generated token. Pages whose every position is computed are committed to
the allocator under their chained block hash (``block_size == page_size``),
which both enables prefix reuse and emits the router-facing ``stored`` events.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Deque, Dict, List, Optional, Union

import numpy as np

from dynamo_tpu.engine.pages import (OutOfPages, PageAllocator,
                                     PrefixMatch)
from dynamo_tpu.engine.spec import propose_ngram
from dynamo_tpu.protocols.common import PreprocessedRequest
from dynamo_tpu.protocols.events import (
    ForwardPassMetrics,
    KvStats,
    SpecDecodeStats,
    WorkerStats,
)
from dynamo_tpu.tokens import TokenBlockSequence


class Phase(Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    RUNNING = "running"
    FINISHED = "finished"


class Sequence:
    """Host-side state of one in-flight request."""

    __slots__ = ("request", "tokens", "page_ids", "committed_pages",
                 "num_computed", "cached_tokens", "num_prompt", "generated",
                 "phase", "cancelled", "arrival", "salt_hash",
                 "enqueued_unix", "admitted_unix", "timings_sent",
                 "decode_steps", "decode_dispatches", "table_version",
                 "multistep_fallbacks", "compile_ms", "compile_events",
                 "block_state", "gen_passes", "gen_blocks", "state_slot")

    def __init__(self, request: PreprocessedRequest, page_size: int,
                 salt_hash: int = 0):
        self.request = request
        self.salt_hash = salt_hash
        self.tokens = TokenBlockSequence(request.token_ids,
                                         block_size=page_size,
                                         salt_hash=salt_hash)
        self.num_prompt = len(request.token_ids)
        self.page_ids: List[int] = []
        self.committed_pages = 0
        self.num_computed = 0
        self.cached_tokens = 0
        self.generated: List[int] = []
        self.phase = Phase.WAITING
        self.cancelled = False
        self.arrival = time.monotonic()
        # wall-clock stage boundaries for the tracing layer (utils/tracing):
        # queue = enqueued -> first admission, prefill = admission -> first
        # emitted frame; the engine loop ships them on the first frame
        self.enqueued_unix = time.time()
        self.admitted_unix: Optional[float] = None
        self.timings_sent = False
        # decode-stage accounting for the trace layer: tokens produced by
        # decode-family steps and the number of jitted dispatches that
        # produced them (a fused multi-step block is ONE dispatch) — shipped
        # on the final frame so the decode span carries steps/dispatches
        self.decode_steps = 0
        self.decode_dispatches = 0
        # bumped whenever ``page_ids`` changes (allocation, growth, adopt,
        # preemption, release): the engine's device-resident page-table
        # cache keys on it instead of hashing/rebuilding the padded table
        # host-side every step
        self.table_version = 0
        # fused-decode refusals that touched this sequence (the trace
        # layer ships the count as a decode-span attr)
        self.multistep_fallbacks = 0
        # jit compiles this sequence waited behind (fresh-bucket first
        # calls, engine/steptrace.py): shipped on the first frame that
        # follows (or the final frame for post-first-token compiles) so
        # the request trace carries an xla_compile event
        self.compile_ms = 0.0
        self.compile_events = 0
        # generation by diffusion over blocks: the block being denoised
        # (engine/loop.py ``BlockState``; None = all masks past the
        # prompt's tail), and the forward passes and committed blocks
        # this request cost (the decode span's ``passes`` / ``blocks``)
        self.block_state = None
        self.gen_passes = 0
        self.gen_blocks = 0
        # the slot of the recurrent-state pool this request's linear
        # layers read and write while it is admitted (0: none - slot 0 is
        # no request's; a family without such layers never has one)
        self.state_slot = 0

    def pages_changed(self) -> None:
        self.table_version += 1

    def __len__(self) -> int:
        return len(self.tokens)


@dataclass
class PrefillChunk:
    seq: Sequence
    start: int      # first position fed this step (== seq.num_computed)
    length: int     # real tokens in the chunk
    is_last: bool   # final chunk => sample the first generated token


@dataclass
class PrefillBatch:
    """One prefill step advancing several sequences at once ([B, S] on
    device, one row per chunk). Concurrent arrivals share a step instead of
    serializing, so decode cadence stays bounded under bursts — the role of
    the reference mocker's token-budget chunked scheduler
    (``lib/llm/src/mocker/scheduler.rs:249-520``).

    ``ring=True`` marks a sequence-parallel long-prompt step: one chunk
    covering the WHOLE prompt, executed via ring attention over the ``sp``
    mesh axis (``parallel/ring_prefill.py``) instead of chunked paged
    prefill. Only emitted when the engine enabled it (sp mesh present)."""

    chunks: List[PrefillChunk]
    ring: bool = False

    @property
    def seqs(self) -> List[Sequence]:
        return [c.seq for c in self.chunks]


@dataclass
class DecodeBatch:
    seqs: List[Sequence]


@dataclass
class SpecDecodeBatch:
    """One speculative verify step over the running batch ([B, K+1] on
    device): row i feeds its last context token plus ``drafts[i]`` and the
    device verifies the drafts by exact rejection sampling
    (``ops/sampling.spec_verify``). Emitted instead of a DecodeBatch when
    speculation is enabled, every row is spec-eligible, and at least one
    row produced a real n-gram draft (rows without a match carry padding
    drafts — the step shape is uniform and their acceptance just stops
    early)."""

    seqs: List[Sequence]
    drafts: np.ndarray          # [len(seqs), K] int32
    has_draft: List[bool] = field(default_factory=list)  # real match per row


@dataclass
class MultiStepBatch:
    """One FUSED decode dispatch: ``width`` decode steps for every row run
    inside a single jitted program (``JaxEngine._multistep_impl``'s
    ``lax.scan``) with on-device sampling and stop detection — one Python
    round trip, one dispatch, one device->host fetch for ``width`` tokens.

    ``start_lens[i]`` is row i's effective token count at BLOCK START
    (``len(seq)`` plus the tokens of any still-in-flight previous block the
    host has not appended yet): the block feeds the row's last token at
    position ``start_lens[i] - 1`` and writes KV for positions
    ``start_lens[i]-1 .. start_lens[i]+width-2``. Pages covering every
    written position are allocated AT PLAN TIME, so the fused program never
    needs mid-block page allocation.

    ``budgets[i]``/``min_gates[i]`` are the remaining max-token budget and
    the outstanding ``min_tokens`` requirement at block start — the device
    stop check consumes them (rows past their stop are masked to no-ops so
    finished sequences stop writing KV). ``chained`` marks a block whose
    first input token/position/liveness come from the device instead of
    host arrays, and ``behind`` says from what: ``"block"``, the previous
    block's carry, or ``"mixed"``, the packed output of the
    prefill-carrying step still in flight — then ``src_rows[i]`` is the
    row of that output that holds row i's first token."""

    seqs: List[Sequence]
    width: int
    chained: bool = False
    start_lens: List[int] = field(default_factory=list)
    budgets: List[int] = field(default_factory=list)
    min_gates: List[int] = field(default_factory=list)
    behind: str = ""
    src_rows: List[int] = field(default_factory=list)

    # mirrors the other plan kinds' diagnostic slot (set by the engine)
    _step_id: Optional[int] = None


@dataclass
class GenPassBatch(MultiStepBatch):
    """One fused dispatch of ``width`` forward PASSES of generation by
    diffusion over blocks: every live row runs its current block of
    ``gen_block`` positions through the model ``width`` times, each row
    in its own phase. A pass over a block that still holds masks reveals
    some of them (``ops/sampling.reveal``); a pass over a block without
    masks is its COMMITTING pass: its keys and values are the final
    ones, the row moves on to the next block and the host emits the
    block's tokens. It is a ``MultiStepBatch`` to the loop (one dispatch,
    one fetch, chained through a device carry), with the tokens of a
    pass counted per row, 0 to ``gen_block``.

    ``start_lens[i]`` is row i's first uncommitted position
    (``num_computed``, a block boundary), ``tails[i]`` the prompt tokens
    that lie in that block (``len(seq) - num_computed``: they are known
    from the start and never emitted), ``budgets[i]`` the tokens the row
    may still emit. ``inflight`` counts the passes of chained dispatches
    the host has not processed yet: pages are grown for every block the
    device may reach meanwhile."""

    tails: List[int] = field(default_factory=list)
    inflight: int = 0


@dataclass
class MixedStepBatch:
    """ONE token-budgeted dispatch advancing prefill chunks AND decode
    rows together — continuous batching at real occupancy instead of the
    strict prefill-XOR-decode alternation (the Ragged Paged Attention
    batch shape, PAPERS.md).

    Rows 0..len(chunks)-1 are prefill chunks (the ``_prefill_plan``
    packing, same token budget); the remaining rows are RUNNING sequences
    each feeding their newest token at position ``len-1`` — a decode row
    is just a ragged chunk of length 1 (``start == num_computed``,
    ``is_last``), so the engine's [B, S] step program serves the whole
    batch: per-row ``new_lens`` carries the raggedness, each row samples
    at its last real token, and decode rows' sampling (seeds included:
    they key on token position) matches the plain decode step exactly.

    ``behind == "mixed"`` marks a step planned while the mixed step in
    front of it is still in flight (``Scheduler.plan_behind``): its
    decode rows feed the token that step samples, which the host has not
    seen - each at position ``len`` instead of ``len - 1`` - and
    ``src_rows[j]`` is the row of that step's packed output that holds
    decode row j's token.
    """

    chunks: List[PrefillChunk]
    decode_seqs: List[Sequence] = field(default_factory=list)
    behind: str = ""
    src_rows: List[int] = field(default_factory=list)

    _step_id: Optional[int] = None

    @property
    def seqs(self) -> List[Sequence]:
        return [c.seq for c in self.chunks] + list(self.decode_seqs)


StepPlan = Union[PrefillBatch, DecodeBatch, SpecDecodeBatch, MultiStepBatch,
                 GenPassBatch, MixedStepBatch]

# the kinds of step the device can run a program behind, reading its
# first tokens from the step's on-device output: a decode step (the next
# decode step, row for row), a fused block (the next block, from its
# carry) and a mixed step (the next mixed step of its admission run, or
# the block that ends the run). An engine names the ones it has the
# programs for (``EngineLoop.chain_kinds``)
CHAIN_KINDS = ("decode", "block", "mixed")


@dataclass
class InFlight:
    """A dispatched step the host has not accounted for, as the planner
    sees it (``Scheduler.plan_behind``): what the step will have done to
    each row it carries by the time the plan behind it runs. The
    scheduler reads every length, budget, page count and phase through it
    (``_out``, ``_len``, ``_computed``, ``_phase``); with nothing in
    flight they read the host's state as it stands.

    ``out`` is how far a row the step samples for is ahead of what the
    host holds: 1 token behind a decode or a mixed step, ``width`` behind
    a block, the passes in flight behind a pass dispatch. ``src`` maps
    such a row (by ``id``) to its row of the step's output: the step's
    rows in the step's order; of a mixed step its packed output's, chunk
    rows then decode rows - the decode rows, and every prompt whose LAST
    chunk rides (a ``prefill_only`` one ends at its first token and is
    not among them). ``rode`` maps a prompt to its chunk that rides."""

    plan: StepPlan
    kind: str           # one of CHAIN_KINDS
    out: int
    src: Dict[int, int]
    rode: Dict[int, PrefillChunk] = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    max_num_seqs: int = 64           # concurrent running+prefill sequences
    max_prefill_chunk: int = 512     # prompt-token budget per prefill step
    max_prefill_seqs: int = 8        # max sequences sharing one prefill step
    watermark: float = 0.01          # keep this fraction of pages free at admit
    # slots of the recurrent-state pool (a family with linear-attention
    # layers: an admitted sequence owns its pages AND one slot, given at
    # admission, taken back at ``finish`` and at preemption; with slots
    # the prefix cache is off - no page is committed, claimed or adopted,
    # because a prefix's pages without the state that matches them are a
    # wrong answer). 0: every other family, nothing changes
    state_slots: int = 0
    # what the slots hold (``ModelConfig.slot_kind``): the reason the
    # refused prefix lookups are counted under
    slot_kind: str = "recurrent_state"
    max_queue: int = 4096
    # prompts longer than this (and with no resident prefix) prefill in ONE
    # sequence-parallel ring step instead of chunks; None disables (set by
    # the engine only when an sp mesh exists)
    ring_threshold: Optional[int] = None
    # cap on concurrently-admitted ring-eligible sequences: ring steps run
    # one at a time, so each extra admission pins its full prompt's pages
    # idle across many steps — a burst of long prompts could otherwise
    # starve decode growth and trigger preemption storms (ADVICE r2)
    max_ring_seqs: int = 2
    # speculative decoding (engine/spec.py): drafts per verify step
    # (0 = off) and the n-gram match sizes for the prompt-lookup proposer
    spec_tokens: int = 0
    spec_ngram_max: int = 4
    spec_ngram_min: int = 2
    # with speculation on, plain decode steps may still CHAIN (pipelined
    # decode) when no draft matched — but a chain never consults the
    # proposer, so it is broken after this many consecutive chained steps
    # to give fresh context a chance to draft. 0 disables chaining while
    # speculation is on.
    spec_chain_break: int = 8
    # fused decode: max decode steps per jitted dispatch (DYN_DECODE_MULTISTEP
    # resolved by the engine; <=1 disables the fused path). The planner may
    # narrow the width per batch — see plan_multistep.
    decode_multistep: int = 1
    # rows with detokenizer-level stop STRINGS cap the fuse width here: the
    # host only learns of a string match after detokenizing, so a wide block
    # can overshoot the stop by up to width-1 tokens per in-flight block.
    # Small lookback bounds that waste while still amortizing the dispatch.
    stop_str_lookback: int = 2
    # mixed prefill+decode dispatch (DYN_MIXED_BATCH): pack decode rows
    # into every prefill step as length-1 ragged chunks AND lift the fused
    # multi-step gate so blocks keep running while arrivals onboard
    # (plan_multistep no longer refuses on waiters/prefills; chained
    # blocks still break at boundaries so admission proceeds). False
    # restores the strict prefill-XOR-decode alternation and the PR 8
    # "no waiters/prefills" fuse gate.
    mixed_batch: bool = True
    # decode-progress guarantee under sustained arrivals: while the
    # waiting queue never drains, prefill-only steps may run at most
    # K-1 in a row before a step that advances decode rows is forced
    # (DYN_DECODE_PROGRESS). With mixed batching on, decode rows ride
    # every prefill step and the guarantee is trivially met; it binds on
    # the legacy alternation path, where bursts may prefer prefill for
    # TTFT. 0 disables the guarantee (strict alternation).
    decode_progress_every: int = 2
    # device-side penalty ring buffer width per row (tokens tracked for
    # repetition/presence/frequency penalties inside a fused block).
    # 0 = no device window: penalized rows refuse fusion ("penalties"
    # reason), as before. The serving engine sets this from its own
    # config; the raw Scheduler default keeps host-only behavior.
    penalty_window: int = 0
    # set by the engine: returns True when a guided row's grammar has a
    # device transition table (engine/guided.build_guided_table) so the
    # row can ride the fused block. None = no device lowering available:
    # guided rows refuse fusion ("guided" reason, as before); a False
    # return means the grammar's table exceeded the byte cap and only
    # that batch falls back, under the "guided_table" reason.
    guided_fuse_check: Optional[Callable] = None
    # generation by diffusion over blocks (``ModelConfig.gen_block``; 1 =
    # a causal model, every path as it was): prompts are prefilled in
    # whole blocks and cut on block boundaries, running rows advance by
    # fused pass dispatches (``GenPassBatch``) and wait through an
    # admission step instead of riding it (``gen_rows_waited``)
    gen_block: int = 1


# what bounds a run of consecutive prefill-carrying (mixed) steps: what
# its admission pass left waiting, and why. Nothing ("queue"); or requests
# the row limit kept out ("rows"), or the page pool ("pages"), or neither:
# the pass took the prompts it may (``max_prefill_seqs``) and what is left
# of them in the end does not fill a step ("partial")
RUN_ENDS = ("queue", "rows", "pages", "partial")

# why nothing was chained behind a prefill-carrying (mixed) step in
# flight (``Scheduler.plan_behind``): the rows of what follows cannot be
# told before the step's result ("rows": one is cancelled, or runs outside
# the step), a row's device penalty window or guided automaton state is
# built on the host from tokens that include the one in flight ("pcarry"),
# the run goes on with a step that does not chain ("run": a prompt for the
# ring is next), no row is left, or none with two tokens to go, so that
# the host's plan would admit or decode step by step ("budget"), or the
# pool lacks the rows' next pages ("pages": the plan behind a step never
# preempts and never admits)
CHAIN_REFUSALS = ("run", "rows", "pcarry", "budget", "pages")


def _penalized(so) -> bool:
    """Does the row keep a device penalty window (penalties or a bias)?"""
    return bool(so.frequency_penalty or so.presence_penalty or so.logit_bias
                or (so.repetition_penalty is not None
                    and so.repetition_penalty > 0
                    and so.repetition_penalty != 1.0))


def _constrained(seq) -> bool:
    """Does the row's sampling read per-token state beside its tokens: a
    penalty window, a guided automaton?"""
    so = seq.request.sampling_options
    return bool(so.guided or _penalized(so))


class Scheduler:
    """Chunked-prefill continuous batching over a :class:`PageAllocator`."""

    def __init__(self, allocator: PageAllocator, config: SchedulerConfig):
        self.alloc = allocator
        self.cfg = config
        self.page_size = allocator.page_size
        self.waiting: Deque[Sequence] = deque()
        self.active: Dict[str, Sequence] = {}  # request_id -> seq (prefill+running)
        self._prefer_prefill = True
        # the dispatched step the plan under way is made behind
        # (``plan_behind``); None while the host plans from its own state
        self._flight: Optional[InFlight] = None
        self.num_preemptions = 0
        # set by the engine loop: the context ceiling of the
        # deterministic end-of-stream checks (``_out_of_budget``)
        self.max_context_hint: Optional[int] = None
        # engine-dp rank advertised in load metrics (reference
        # WorkerStats.data_parallel_rank, kv_router/protocols.rs:52);
        # set by the worker when serving one rank of a dp group
        self.dp_rank: Optional[int] = None
        # cancelled sequences reaped outside an engine step; the engine drains
        # this to emit their CANCELLED frames (otherwise the caller's stream
        # would never terminate)
        self.reaped: List[Sequence] = []
        # speculative-decode acceptance counters (reference surface:
        # SpecDecodeStats in the metrics plane, protocols/events.py)
        self.spec_stats = SpecDecodeStats()
        # consecutive chained decode steps since the last schedule() (the
        # spec_chain_break counter)
        self._chain_run = 0
        # blocks adopted mid-prefill from the prefix cache (injected by the
        # KVBM prefetch scheduler or a concurrent request after THIS
        # sequence was admitted) instead of being recomputed
        self.adopted_blocks = 0
        # why the fused multi-step path was refused, by reason (waiters,
        # prefill, penalties, penalty_window, guided, guided_table, spec,
        # budget, pages, multihost): the worker metrics layer surfaces these as
        # dynamo_worker_multistep_fallback_total{reason=...} so the
        # "fallback-reason near zero" roadmap criterion is measurable
        self.multistep_fallbacks: Dict[str, int] = {}
        # most recent fallback reason, consumed by the engine loop so the
        # demoted dispatch's StepRecord carries WHY it left the fast path
        self.last_fallback = ""
        # consecutive scheduled steps that advanced NO decode row (the
        # decode-progress guarantee counter)
        self._steps_since_decode = 0
        # mixed-dispatch diagnostics (the engine also counts dispatches)
        self.mixed_plans = 0
        # block diffusion: running rows that waited through an admission
        # step (a prefill step carries no row in mid-block)
        self.gen_rows_waited = 0
        # admission runs: consecutive prefill-carrying (mixed) steps, by
        # what ended each (``RUN_ENDS``), and the steps they held; the
        # ratio is a run's mean length (dynamo_worker_sched_admission_*)
        self.admission_runs: Dict[str, int] = dict.fromkeys(RUN_ENDS, 0)
        self.admission_run_steps = 0
        self._run_steps = 0     # mixed steps planned in the run under way
        # why the last admission pass stopped (one of RUN_ENDS), and the
        # pass of the run under way
        self._admit_stop = self._run_stop = "queue"
        # fused blocks whose first tokens came from the device, by what
        # they were chained behind, the mixed steps whose decode rows'
        # tokens did, and the chains behind a mixed step that were
        # refused, by reason (``CHAIN_REFUSALS``)
        # (dynamo_worker_multistep_chained_total{behind},
        # dynamo_worker_mixed_chained_total{behind},
        # dynamo_worker_multistep_chain_refused_total{reason})
        self.chained_blocks: Dict[str, int] = {"block": 0, "mixed": 0}
        self.chained_steps: Dict[str, int] = {"mixed": 0}
        self.chain_refusals: Dict[str, int] = dict.fromkeys(
            CHAIN_REFUSALS, 0)
        # free slots of the recurrent-state pool, low numbers first (slot
        # 0 is never handed out), and the prefix lookups that were not
        # made because a hit could not be used, by reason
        # (dynamo_worker_prefix_reuse_refused_total{reason})
        self._free_slots: List[int] = list(range(config.state_slots, 0, -1))
        self.prefix_reuse_refused: Dict[str, int] = {config.slot_kind: 0}

    def record_chain_refusal(self, reason: str, seqs=()) -> None:
        """Count one chain behind a mixed step that was not taken (what
        follows the step is planned from host state, as it always was);
        ``seqs`` is ``record_fallback``'s, unused: no row leaves the
        fused path here."""
        self.chain_refusals[reason] = self.chain_refusals.get(reason, 0) + 1

    def _refuse(self, reason: str) -> None:
        """Nothing is chained behind the step in flight. Behind a mixed
        step that is counted by its reason; behind a decode step or a
        block it is how every chain ends."""
        if self._flight.kind == "mixed":
            self.record_chain_refusal(reason)

    def record_fallback(self, reason: str, seqs=()) -> None:
        """Count one fused-path refusal; also stamp the sequences it
        touched so the trace layer can attribute it."""
        self.multistep_fallbacks[reason] = (
            self.multistep_fallbacks.get(reason, 0) + 1)
        self.last_fallback = reason
        for seq in seqs:
            seq.multistep_fallbacks += 1

    def drain_reaped(self) -> List[Sequence]:
        out, self.reaped = self.reaped, []
        return out

    # -- intake ------------------------------------------------------------

    def add_request(self, request: PreprocessedRequest) -> Sequence:
        if len(self.waiting) >= self.cfg.max_queue:
            raise RuntimeError("scheduler queue full")
        seq = Sequence(request, self.page_size)
        self.waiting.append(seq)
        return seq

    def cancel(self, request_id: str) -> None:
        seq = self.active.get(request_id)
        if seq is not None:
            seq.cancelled = True
            return
        for seq in self.waiting:
            if seq.request.request_id == request_id:
                seq.cancelled = True
                self.waiting.remove(seq)
                self.reaped.append(seq)
                return

    # -- admission ---------------------------------------------------------

    def _watermark_pages(self) -> int:
        return max(1, int(self.alloc.num_pages * self.cfg.watermark))

    def _ahead(self, seq: Sequence) -> int:
        """Pages ``seq`` will ask for: through the prefill-carrying step
        that takes it along and the fused block behind it (the step feeds
        position ``len - 1``, the block writes ``decode_multistep``
        positions from ``len``), and beyond that as far as it is sure to
        go: to ``min_tokens``, or to the end of its budget where nothing
        but the budget can end it (``ignore_eos`` and no stop set)."""
        sc = seq.request.stop_conditions
        done = len(seq.generated)
        left = self._max_new(seq)
        left = (1 << 30) if left is None else left - done
        if self.max_context_hint is not None:
            left = min(left, self.max_context_hint - len(seq))
        sure = (sc.min_tokens or 0) - done
        if sc.ignore_eos and not sc.stop and not sc.stop_token_ids:
            sure = left
        return self._pages_needed(
            len(seq) + max(sure, min(left, self.cfg.decode_multistep), 0))

    def _block_reserve(self) -> int:
        """Pages the admitted rows do not hold yet and will ask for
        (``_ahead``). Admission leaves them in the pool: a prompt that
        takes them is paid for by a narrower block (a program nobody has
        called), single steps or a preemption whose prompt is then
        computed twice."""
        return sum(max(0, self._ahead(s) - len(s.page_ids))
                   for s in self.active.values())

    def _try_admit(self, reserve: int) -> Optional[Sequence]:
        """Admit the head of the queue if a row is free and the pool holds
        its prompt, what it will ask for (``_ahead``) and ``reserve``:
        what the rows admitted before it will."""
        while self.waiting and self.waiting[0].cancelled:
            self.reaped.append(self.waiting.popleft())
        if not self.waiting:
            self._admit_stop = "queue"
            return None
        recurrent = self.cfg.state_slots > 0
        if len(self.active) >= self.cfg.max_num_seqs or (
                recurrent and not self._free_slots):
            self._admit_stop = "rows"
            return None
        seq = self.waiting[0]
        hashes = seq.tokens.block_hashes()
        # Prefix-cache hit: claim resident pages, but always leave >=1 token
        # to compute so the final-chunk logits exist. (For a preempted
        # sequence len(seq) includes generated tokens; the revive covers them
        # too since its full pages were committed before release.)
        # (a row with a recurrent state claims nothing: the whole prompt,
        # or the whole of a preempted row, is computed from token 0)
        match = PrefixMatch() if recurrent else self.alloc.match_prefix(
            hashes)
        # (block diffusion: the prompt's whole blocks are all there is to
        # prefill, and no logits are taken from them)
        cached = min(match.num_pages * self.page_size,
                     len(seq) - 1 if self.cfg.gen_block <= 1
                     else self._prefill_target(seq))
        full_cached_pages = cached // self.page_size
        if full_cached_pages < match.num_pages:
            self.alloc.release(match.page_ids[full_cached_pages:])
            match.page_ids = match.page_ids[:full_cached_pages]
        cached = full_cached_pages * self.page_size
        need = self._pages_needed(len(seq)) - len(match.page_ids)
        spare = self.alloc.num_free - self._watermark_pages()
        # beside other rows the pool has to hold what they and this one
        # will ask for; alone, a prompt that fits runs as far as it gets
        if (need > spare or self.active and self._ahead(seq)
                - len(match.page_ids) > spare - reserve):
            self.alloc.release(match.page_ids)
            self._admit_stop = "pages"
            return None
        try:
            fresh = self.alloc.allocate(need) if need else []
        except OutOfPages:
            self.alloc.release(match.page_ids)
            self._admit_stop = "pages"
            return None
        if recurrent:
            self.prefix_reuse_refused[self.cfg.slot_kind] += 1
            seq.state_slot = self._free_slots.pop()
        else:
            self.alloc.count_lookup(hits=full_cached_pages,
                                    misses=len(hashes) - full_cached_pages)
        self.waiting.popleft()
        seq.page_ids = match.page_ids + fresh
        seq.pages_changed()
        seq.committed_pages = len(match.page_ids)
        seq.num_computed = cached
        if seq.admitted_unix is None:  # keep the FIRST admission (a
            seq.admitted_unix = time.time()  # preemption revive re-admits)
        if not seq.generated:  # first admission: report the prefix hit
            seq.cached_tokens = cached
        seq.phase = Phase.PREFILL
        self._skip_empty_prefill(seq)
        self.active[seq.request.request_id] = seq
        return seq

    def _prefill_target(self, seq: Sequence) -> int:
        """Positions a prefill computes: every token of a causal row; of
        a block-diffusion row the whole blocks (the tail rides the first
        generated block: its block's other positions are still masks)."""
        B = self.cfg.gen_block
        return len(seq) if B <= 1 else len(seq) // B * B

    def _skip_empty_prefill(self, seq: Sequence) -> None:
        """A block-diffusion row whose whole blocks are all cached (or
        whose prompt is shorter than a block) has nothing to prefill."""
        if (self.cfg.gen_block > 1 and seq.phase is Phase.PREFILL
                and seq.num_computed >= self._prefill_target(seq)):
            seq.phase = Phase.RUNNING

    def _pages_needed(self, num_tokens: int) -> int:
        # positions [0, num_tokens-1] must be addressable
        return (num_tokens + self.page_size - 1) // self.page_size

    # -- the step in flight ------------------------------------------------

    def _out(self, seq: Sequence) -> int:
        """Tokens (passes, of a pass dispatch) the step in flight puts
        the row ahead of what the host holds."""
        f = self._flight
        return f.out if f is not None and id(seq) in f.src else 0

    def _len(self, seq: Sequence) -> int:
        """The row's tokens with those in flight counted."""
        return len(seq) if self._flight is None else len(seq) + self._out(seq)

    def _computed(self, seq: Sequence) -> int:
        """The prompt positions computed once the chunk that rides the
        step in flight is."""
        f = self._flight
        chunk = f.rode.get(id(seq)) if f is not None and f.rode else None
        return seq.num_computed + (chunk.length if chunk is not None else 0)

    def _phase(self, seq: Sequence) -> Phase:
        """The row's phase once the step in flight has resolved, as far
        as the host can tell before it has: a prompt whose last chunk
        rides a mixed step decodes (a ``prefill_only`` one has ended),
        and a row the ONE token in flight is sure to end has ended. A
        row that ends inside a block in flight ends on the device: the
        plan behind the block keeps it, as a dead row."""
        f = self._flight
        phase = seq.phase
        if f is None or f.kind == "block":
            return phase
        if phase is Phase.PREFILL:
            chunk = f.rode.get(id(seq))
            if chunk is None or not chunk.is_last:
                return phase
            phase = Phase.RUNNING if id(seq) in f.src else Phase.FINISHED
        if (phase is Phase.RUNNING and id(seq) in f.src
                and self._out_of_budget(seq, f.out)):
            return Phase.FINISHED
        return phase

    def _out_of_budget(self, seq: Sequence, ahead: int = 0) -> bool:
        """Has the row, ``ahead`` tokens past what the host holds, spent
        its budget or reached the context ceiling (the rules of
        ``_accept_token`` that need no look at the token)?"""
        max_new = self._max_new(seq)
        return ((max_new is not None
                 and len(seq.generated) + ahead >= max_new)
                or (self.max_context_hint is not None
                    and len(seq) + ahead >= self.max_context_hint))

    # -- per-step bookkeeping ---------------------------------------------

    def _commit_full_pages(self, seq: Sequence) -> None:
        if self.cfg.state_slots:
            return      # nothing is published: no request could use it
        full = seq.num_computed // self.page_size
        blocks = seq.tokens.blocks
        for i in range(seq.committed_pages, min(full, len(seq.page_ids))):
            b = blocks[i]
            self.alloc.commit(seq.page_ids[i], b.block_hash, b.local_hash,
                              b.parent_hash if b.position > 0 else None)
        seq.committed_pages = max(seq.committed_pages, full)

    def finish(self, seq: Sequence) -> None:
        """Release a sequence's resources (idempotent)."""
        if seq.phase == Phase.FINISHED:
            return
        self._commit_full_pages(seq)
        self.alloc.release(seq.page_ids)
        seq.page_ids = []
        self._release_slot(seq)
        seq.pages_changed()
        seq.phase = Phase.FINISHED
        self.active.pop(seq.request.request_id, None)

    def _release_slot(self, seq: Sequence) -> None:
        """Take back the sequence's slot of the state pool. The slot is
        not cleared: the next owner's first chunk starts at position 0,
        which starts from zeros whatever the slot holds."""
        if seq.state_slot:
            self._free_slots.append(seq.state_slot)
            seq.state_slot = 0

    def _preempt_one(self) -> bool:
        """Evict the newest running sequence back to the waiting queue."""
        victims = [s for s in self.active.values() if s.phase == Phase.RUNNING]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.arrival)
        self._commit_full_pages(victim)
        self.alloc.release(victim.page_ids)
        victim.page_ids = []
        self._release_slot(victim)
        victim.pages_changed()
        victim.committed_pages = 0
        victim.num_computed = 0
        victim.block_state = None   # it resumes at a block boundary
        victim.phase = Phase.WAITING
        self.active.pop(victim.request.request_id)
        self.waiting.appendleft(victim)
        self.num_preemptions += 1
        return True

    def _short(self, seq: Sequence) -> int:
        """Pages the row lacks for the position it feeds next (``len -
        1``; behind a step in flight the position of the token in
        flight, ``len``)."""
        return max(0, self._pages_needed(self._len(seq)) - len(seq.page_ids))

    def _grow_for_decode(self, seq: Sequence) -> bool:
        """Ensure the page for the position the row feeds next exists;
        may preempt others."""
        need = self._short(seq)
        while need > 0:
            try:
                seq.page_ids.extend(self.alloc.allocate(need))
                seq.pages_changed()
                return True
            except OutOfPages:
                if not self._preempt_one() or seq.phase != Phase.RUNNING:
                    return False
        return True

    def _adopt_resident(self, seq: Sequence) -> int:
        """Mid-prefill prefix adoption: swap upcoming fresh pages for blocks
        that became resident AFTER this sequence was admitted.

        Admission prefix-matches once; blocks injected later (the KVBM
        prefetch scheduler streaming tier promotions ahead of the chunked
        prefill cursor, a disagg pull, or a concurrent request committing
        the same prefix) would be recomputed without this. At each prefill
        planning pass, walk the chain from the cursor: while the next
        block's hash is resident, claim the resident page, release the
        fresh page allocated for that position, and advance
        ``num_computed`` past it — the prefill chunk then starts where
        residency ends. Committed pages are immutable, so sharing one with
        its owner is the ordinary prefix-cache aliasing.

        Only runs at page-aligned cursors (a partially computed page can't
        be spliced) and always leaves >=1 token to compute so the final
        chunk's logits exist (the admission rule)."""
        if seq.num_computed % self.page_size:
            return 0
        blocks = seq.tokens.blocks
        limit = min((len(seq) - 1) // self.page_size, len(seq.page_ids))
        i = seq.num_computed // self.page_size
        adopted = 0
        while i < limit and i < len(blocks):
            page = self.alloc._by_hash.get(blocks[i].block_hash)
            if page is None or page == seq.page_ids[i]:
                break
            self.alloc.incref(page)
            old = seq.page_ids[i]
            seq.page_ids[i] = page
            seq.pages_changed()
            self.alloc.release([old])  # fresh + uncommitted: frees
            seq.num_computed += self.page_size
            seq.committed_pages = max(seq.committed_pages, i + 1)
            adopted += 1
            i += 1
        if adopted:
            self.adopted_blocks += adopted
            if not seq.generated:  # still reporting the prefix hit
                seq.cached_tokens += adopted * self.page_size
        return adopted

    # -- the step ----------------------------------------------------------

    def _prefill_plan(self, admit: bool = True) -> Optional[PrefillBatch]:
        """Admit waiting sequences (bounded by slots, pages, and batch
        width; none with ``admit`` off), then pack up to
        ``max_prefill_seqs`` chunks into one step under the
        ``max_prefill_chunk`` token budget, oldest first. Behind a step
        in flight every prompt goes on from where the chunk that rides
        the step leaves it."""
        # adopt blocks that became resident since admission (prefetch or
        # disagg injects, concurrent requests committing a shared prefix)
        # so each chunk starts where residency ends (not behind a step in
        # flight, under whose chunk the cursor would move: such a block is
        # computed once more, or adopted the next time the host plans)
        for s in self.active.values() if self._flight is None else ():
            if s.phase == Phase.PREFILL:
                self._adopt_resident(s)
                self._skip_empty_prefill(s)
        rt = self.cfg.ring_threshold

        def ring_eligible(s: Sequence) -> bool:
            # a resident prefix composes with the ring (cached pages are
            # merged via blockwise partials) as long as it is page-aligned
            # (prefix-cache hits always are — admission truncates to full
            # pages); the REMAINING tokens must justify a ring step
            return (rt is not None
                    and self._computed(s) % self.page_size == 0
                    and len(s) - self._computed(s) > rt)

        # cap admission at the batch width so admitted pages don't sit idle
        # across many steps waiting for a row; ring candidates run alone and
        # are held out of packing, so they don't consume a row — but their
        # admissions are capped separately (max_ring_seqs): each one pins
        # its whole prompt's pages until its single ring step runs
        n_prefill = sum(1 for s in self.active.values()
                        if s.phase == Phase.PREFILL and not ring_eligible(s))
        n_ring = sum(1 for s in self.active.values()
                     if s.phase == Phase.PREFILL and ring_eligible(s))
        if admit:
            # why the pass stops: until ``_try_admit`` refuses for another
            # reason, by its own cap (or a ring prompt held back)
            self._admit_stop = "partial"
        reserve = self._block_reserve() if admit else 0
        while admit and n_prefill < self.cfg.max_prefill_seqs:
            while self.waiting and self.waiting[0].cancelled:
                self.reaped.append(self.waiting.popleft())
            if rt is not None and self.waiting and n_ring >= self.cfg.max_ring_seqs:
                head = self.waiting[0]
                cached = (self.alloc.peek_prefix(head.tokens.block_hashes())
                          * self.page_size)
                if len(head) - cached > rt:
                    # head would take the ring path (its REMAINING tokens
                    # after any prefix hit exceed the threshold); hold it —
                    # FIFO order forbids skipping ahead to shorter prompts
                    break
            seq = self._try_admit(reserve)
            if seq is None:
                break
            reserve += max(0, self._ahead(seq) - len(seq.page_ids))
            if ring_eligible(seq):
                n_ring += 1
            else:
                n_prefill += 1
        prefilling = sorted(
            (s for s in self.active.values()
             if self._phase(s) == Phase.PREFILL),
            key=lambda s: s.arrival)
        if not prefilling:
            return None
        # Long prompts take the sequence-parallel ring path: the remaining
        # tokens in ONE step, alone (compute already split sp ways). A
        # page-aligned resident prefix rides along — the ring merges cached
        # pages via blockwise online-softmax partials (ring_prefill.py).
        # Oldest-first still governs: a ring step runs only when its sequence
        # is the oldest prefilling one; until then ring candidates are held
        # OUT of chunk packing (a single chunk would spoil eligibility), so
        # neither path can starve the other.
        if ring_eligible(prefilling[0]):
            seq = prefilling[0]
            return PrefillBatch(ring=True, chunks=[PrefillChunk(
                seq=seq, start=self._computed(seq),
                length=len(seq) - self._computed(seq), is_last=True)])
        budget = self.cfg.max_prefill_chunk
        chunks: List[PrefillChunk] = []
        packable = [s for s in prefilling if not ring_eligible(s)]
        for seq in packable[:self.cfg.max_prefill_seqs]:
            if budget <= 0:
                break
            # len(seq), not num_prompt: a revived preempted sequence must
            # also re-prefill the tokens it had generated before eviction
            start = self._computed(seq)
            remaining = self._prefill_target(seq) - start
            length = min(remaining, budget)
            if length < remaining:
                # a chunk ends on a block boundary (any position is one
                # for a causal row)
                length -= length % self.cfg.gen_block
                if length <= 0:
                    break
            chunks.append(PrefillChunk(seq=seq, start=start, length=length,
                                       is_last=(length == remaining)))
            budget -= length
        return PrefillBatch(chunks=chunks) if chunks else None

    def _grow_ready(self, decodable: List[Sequence]) -> List[Sequence]:
        """Grow pages for the decode rows (may preempt newest RUNNING
        sequences); returns the rows that survived with pages in place,
        by arrival. Behind a step in flight nothing is preempted: every
        row's page is there, or no row is returned and no page taken."""
        rows = sorted(decodable, key=lambda s: s.arrival)
        if self._flight is not None and sum(
                map(self._short, rows)) > self.alloc.num_free:
            return []
        ready: List[Sequence] = []
        for seq in rows:
            if self._phase(seq) != Phase.RUNNING:
                continue  # preempted by an earlier grow
            if self._grow_for_decode(seq):
                ready.append(seq)
        return [s for s in ready if self._phase(s) == Phase.RUNNING]

    def schedule(self) -> Optional[StepPlan]:
        """Pick the next engine step from the host's state, or None if
        there is nothing to run (``_next_plan`` says what runs when). A
        pure-decode plan the loop upgrades to a fused block
        (``plan_multistep``); while a step it dispatched is in flight it
        asks ``plan_behind`` first, and comes here when that refuses and
        the step has resolved."""
        return self._plan(None)

    def plan_behind(self, prev: StepPlan,
                    kinds=CHAIN_KINDS) -> Optional[StepPlan]:
        """Plan what the device runs BEHIND ``prev``, which is dispatched
        and not yet accounted for (its result is still on the device),
        or None: the loop then resolves ``prev`` and plans from host
        state. ``kinds`` are the ``CHAIN_KINDS`` the engine has programs
        for.

        The plan is the one ``schedule()`` would return once ``prev`` had
        resolved, made by the same function (``_next_plan``) over what
        the host holds plus what ``prev`` will have done (``InFlight``):
        behind a decode step the next decode step, behind a fused block
        the next block, behind a mixed step the next mixed step of its
        admission run or, behind the run's last, the block. ``behind``
        on a mixed step or a block says what it was chained behind, and
        ``src_rows`` where each of its rows finds its newest token in a
        mixed step's packed output. The scheduler's books stand where
        ``schedule()`` would have left them; after a refusal NOTHING has
        changed (but pages a block that was then refused took for a
        narrower width, which its rows need next anyway), and behind a
        mixed step the refusal is counted (``CHAIN_REFUSALS``)."""
        flight = self._in_flight(prev, kinds)
        return self._plan(flight) if flight is not None else None

    def chains_behind(self, step: MixedStepBatch, kinds=CHAIN_KINDS) -> bool:
        """May something be chained behind the mixed ``step``
        (``plan_behind`` will say what)? Asked BEFORE a step planned
        from host state is dispatched: one that chains returns at its
        enqueue, one that does not is resolved inside its dispatch, as it
        always was. The answer is ``plan_behind``'s first test
        (``_rows_known``), and a refusal is counted there."""
        self._flight = self._in_flight(step, kinds)
        try:
            return self._flight is not None and self._rows_known()
        finally:
            self._flight = None

    def _plan(self, flight: Optional[InFlight]) -> Optional[StepPlan]:
        self._flight = flight
        try:
            plan = (self._next_plan()
                    if flight is None or self._rows_known() else None)
        finally:
            self._flight = None
        if (self._run_steps and not isinstance(plan, MixedStepBatch)
                and (plan is not None or flight is None)):
            self._end_run()
        return plan

    def _end_run(self) -> None:
        """The run of mixed steps is over: count it by what bounded it."""
        self.admission_runs[self._run_stop] += 1
        self._run_steps = 0

    def _in_flight(self, prev: StepPlan, kinds) -> Optional[InFlight]:
        """The planner's view of ``prev``, or None where nothing is
        chained behind a step of its kind: the engine lacks the program,
        no block follows a mixed step (``decode_multistep`` < 2: a mixed
        step is only planned for a causal model without drafts), or
        speculation is due (chains never consult the draft proposer:
        they break every ``spec_chain_break`` steps so that repetitive
        context gets its verify steps)."""
        rode: Dict[int, PrefillChunk] = {}
        if isinstance(prev, MultiStepBatch):
            kind, out = "block", prev.width + getattr(prev, "inflight", 0)
        elif isinstance(prev, MixedStepBatch):
            kind, out = "mixed", 1
        elif isinstance(prev, DecodeBatch):
            kind, out = "decode", 1
        else:
            return None
        if kind not in kinds:
            return None
        if kind == "mixed":
            if self.cfg.decode_multistep < 2:
                return None
            rode = {id(c.seq): c for c in prev.chunks}
            src = {id(c.seq): i for i, c in enumerate(prev.chunks)
                   if c.is_last and not c.seq.request.prefill_only}
            src.update((id(s), len(prev.chunks) + j)
                       for j, s in enumerate(prev.decode_seqs))
        else:
            src = {id(s): i for i, s in enumerate(prev.seqs)}
        if (kind == "decode" and self.cfg.spec_tokens > 0
                and self._chain_run >= self.cfg.spec_chain_break):
            return None
        return InFlight(prev, kind, out, src, rode)

    def _rows_known(self) -> bool:
        """Can the rows of what follows the step in flight be told
        before its result? Not where a row is cancelled or runs outside
        a mixed step (the host's plan would drop the one and take the
        other along), and not where a row of a one-token step keeps
        per-token state the HOST builds - a penalty window, a guided
        automaton: both would lack the token in flight (a block carries
        them on the device; seeds alone are fine, their keys fold the
        token's position)."""
        f = self._flight
        if (any(s.cancelled for s in (*f.plan.seqs, *self.active.values()))
                or f.kind == "mixed" and any(
                    s.phase is Phase.RUNNING and id(s) not in f.src
                    for s in self.active.values())):
            self._refuse("rows")
            return False
        if f.kind != "block" and any(_constrained(s) for s in f.plan.seqs):
            self._refuse("pcarry")
            return False
        return True

    def _next_plan(self) -> Optional[StepPlan]:
        """What runs next: the one statement of it, for the host's own
        plan and for the plan behind a step in flight.

        With ``mixed_batch`` on (the default), prefill steps carry the
        decode rows along as length-1 ragged chunks (MixedStepBatch). A
        run of them starts with an admission pass: the prompts that
        wait, as far as rows, ``max_prefill_seqs`` and the pool allow
        (``_try_admit`` leaves in the pool what the admitted rows will
        ask for, ``_block_reserve``). After a mixed step comes another
        one while a queue stands and what the pass admitted still fills
        a whole step; every step of a run takes every decode row one
        token on. Then comes the pure-decode plan (a fused block, once
        upgraded), and completions free rows and pages for the next run.
        Where no queue stands behind a mixed step the run is one step
        long and the plans alternate mixed / pure-decode. With
        ``mixed_batch`` off, the legacy prefill-XOR-decode alternation
        applies, except that a deep waiting queue may take up to
        ``decode_progress_every - 1`` consecutive prefill steps (burst
        TTFT) before a decode step is forced: the decode-progress
        guarantee that bounds decode tail latency under sustained
        arrivals.

        Behind a step in flight the plan differs in this and nothing
        else: it never admits (where the host's plan would, it is
        refused: an admission needs the completions the host has not
        seen), never preempts and never adopts from the prefix cache;
        a row the token in flight is sure to end is gone (``_phase``);
        its rows are those the device can find in the step's output
        (``_rows_behind``); and the plan says where (``src_rows``)."""
        f = self._flight
        if f is None:
            self._chain_run = 0
            # drop cancelled active sequences
            for seq in [s for s in self.active.values() if s.cancelled]:
                self.finish(seq)
                self.reaped.append(seq)

        decodable = [s for s in self.active.values()
                     if self._phase(s) == Phase.RUNNING]

        K = self.cfg.decode_progress_every
        force_decode = bool(decodable and K > 0
                            and self._steps_since_decode >= K - 1)
        # a run of mixed steps is under way, and it goes on while a queue
        # stands (more requests wait than the next pass could admit: a
        # burst that one pass absorbs is served as it always was) and what
        # the run's first step admitted still fills a whole step. Only
        # that first step admits, ``max_prefill_seqs`` prompts at most: a
        # pool filled in one long run empties in one (rows admitted
        # together end together), and then neither the pool nor the
        # window of a measurement sees a steady state
        go_on = bool(self._run_steps and decodable
                     and len(self.waiting) >= self.cfg.max_prefill_seqs)
        if not force_decode and (self._prefer_prefill or not decodable
                                 or go_on):
            if f is not None and not go_on and (self.waiting
                                                or not decodable):
                # the host's plan admits, or has no row left to decode
                return self._refuse("budget")
            batch = self._prefill_plan(admit=not go_on and f is None)
            if (go_on and batch is not None
                    and sum(c.length for c in batch.chunks)
                    < self.cfg.max_prefill_chunk):
                # a part-filled step costs every decode row a long token
                # for few prompt tokens, in a program (``T`` follows the
                # tokens) the steady state does not call; what is left
                # rides the next run's first step, as it always has
                batch = None
            if f is not None and batch is not None and (
                    batch.ring or f.kind != "mixed"):
                # a step for the ring; prompt chunks behind a decode step
                # or a block: the device chains neither
                return self._refuse("run")
            if batch is not None:
                if self.cfg.gen_block > 1:
                    # rows in mid-block wait through an admission step
                    self.gen_rows_waited += len(decodable)
                elif (self.cfg.mixed_batch and not batch.ring
                        and self.cfg.spec_tokens == 0 and decodable):
                    ready = self._grow_ready(decodable)
                    if f is not None and not ready:
                        return self._refuse("pages")
                    # re-filter: growth may have preempted a planned chunk's
                    # sequence back to WAITING — drop its chunk
                    chunks = [c for c in batch.chunks
                              if c.seq.phase is Phase.PREFILL]
                    if ready and chunks:
                        self._prefer_prefill = False
                        self._steps_since_decode = 0
                        self.mixed_plans += 1
                        if not go_on:
                            # a run starts: what its pass left waiting,
                            # and why, names it
                            self._run_stop = (self._admit_stop
                                              if self.waiting else "queue")
                        self._run_steps += 1
                        self.admission_run_steps += 1
                        step = MixedStepBatch(chunks=chunks,
                                              decode_seqs=ready)
                        if f is not None:
                            step.behind = f.kind
                            step.src_rows = [f.src[id(s)] for s in ready]
                            self.chained_steps[f.kind] += 1
                        return step
                    if not chunks and not ready:
                        return None
                    if not chunks:
                        batch = None  # fall through to the decode plan
                    else:
                        batch = PrefillBatch(chunks=chunks)
                if batch is not None:
                    # legacy (or decode-less) prefill step; under a deep
                    # waiting queue keep preferring prefill up to the
                    # decode-progress bound
                    # (block diffusion: running rows wait through an
                    # admission step instead of riding it, so a wave of
                    # prompts is prefilled in consecutive steps, as far
                    # as the guarantee allows, and its rows then run
                    # their passes together)
                    more = self.waiting or (
                        self.cfg.gen_block > 1 and any(
                            s.phase is Phase.PREFILL
                            for s in self.active.values()))
                    self._prefer_prefill = bool(
                        more and K > 0
                        and self._steps_since_decode + 1 < K - 1)
                    if decodable:
                        self._steps_since_decode += 1
                    return batch
        plan = (self._decode_plan(decodable) if f is None
                else self._decode_behind(decodable))
        if plan is not None or f is None:
            self._prefer_prefill = True
        if plan is not None:
            self._steps_since_decode = 0
        return plan

    def _decode_plan(self, decodable: List[Sequence]) -> Optional[StepPlan]:
        """The host's pure-decode plan (a verify step where drafts
        match)."""
        if self.cfg.gen_block > 1:
            # an admission may have put a row straight to RUNNING (its
            # prompt's whole blocks were all cached: nothing to prefill)
            decodable = [s for s in self.active.values()
                         if s.phase == Phase.RUNNING]
        ready = self._grow_ready(decodable)
        if not ready:
            return None
        if self.cfg.spec_tokens > 0:
            spec = self._spec_plan(ready)
            if spec is not None:
                return spec
        return DecodeBatch(seqs=ready)

    def _decode_behind(self, decodable: List[Sequence]) -> Optional[StepPlan]:
        """The pure-decode plan behind the step in flight, as the device
        runs it: behind a decode step the next one, its pages grown one
        position ahead; behind a block or a mixed step the fused block,
        which the loop does not have to upgrade."""
        f = self._flight
        rows = self._rows_behind(decodable)
        if rows is None:
            return None
        if f.kind == "decode":
            if not self._grow_ready(rows):
                return None
            self._chain_run += 1
            return DecodeBatch(seqs=rows)
        plan = (self._plan_passes(rows) if self.cfg.gen_block > 1
                else self._plan_block(rows))
        if plan is not None and f.kind == "mixed":
            plan.src_rows = [f.src[id(s)] for s in rows]
        return plan

    def _rows_behind(self, decodable: List[Sequence]
                     ) -> Optional[List[Sequence]]:
        """Which rows the pure-decode plan behind the step in flight
        holds, and in which order: those the device can find in the
        step's output.

        Behind a mixed step a row is found by ``src_rows``: the rows are
        the host's (``decodable``, by arrival), and a block takes along
        the rows the token in flight is sure to end, dead from its start
        as the device will have them. Behind a decode step or a block
        row i reads row i: the step's rows in the step's order, and that
        only where its live rows are the rows the host would plan. A
        decode step's rows all have to live (a row that is sure to end,
        or any other change of the row set, breaks the chain). A block
        keeps a row that ended because its budget ran out (``_spent``:
        dead in the device's carry too) as a dead row while more than
        half of its rows live - the end of one stream does not stall the
        others - and breaks at a row the host alone ended: the device
        has that one alive."""
        f = self._flight
        if f.kind == "mixed":
            return sorted((s for s in self.active.values()
                           if id(s) in f.src), key=lambda s: s.arrival)
        rows = list(f.plan.seqs)
        live = [s for s in rows if s.phase is Phase.RUNNING]
        if live != sorted(decodable, key=lambda s: s.arrival):
            return None
        if f.kind == "decode":
            return rows if len(live) == len(rows) else None
        if 2 * len(live) <= len(rows) or not all(
                self._spent(s) for s in rows if s.phase is not Phase.RUNNING):
            return None
        return rows

    # -- speculative decoding ----------------------------------------------

    @staticmethod
    def _spec_eligible(seq: Sequence) -> bool:
        """Rows whose sampling the verify step reproduces exactly.

        Penalties / logit_bias mutate logits from host bookkeeping that
        goes stale within a multi-token step; per-request seeds key their
        randomness on a single token position. Any such row sends the
        whole batch down the plain decode path. Top-logprobs requests ARE eligible (the verify
        step packs per-position alternatives), and so are GUIDED rows —
        the host walks the automaton along the known draft path and ships
        one allow-mask per chunk slot (JaxEngine._guided_spec_masks), so
        structured outputs keep their exactness under speculation."""
        so = seq.request.sampling_options
        return not (_penalized(so) or so.seed is not None or so.min_p)

    def _spec_plan(self, ready: List[Sequence]) -> Optional[SpecDecodeBatch]:
        """Try to upgrade this decode step to a [B, K+1] verify step."""
        K = self.cfg.spec_tokens
        if not all(self._spec_eligible(s) for s in ready):
            return None
        # context-ceiling guard: the verify step feeds
        # positions len .. len+K-1 and needs pages/table slots for len+K
        # tokens — a row within K of max_context would overrun the static
        # page-table width (and the positions themselves). Those rows are
        # about to finish; the plain decode step handles them.
        if self.max_context_hint is not None and any(
                len(s) + K >= self.max_context_hint for s in ready):
            return None
        drafts = np.zeros((len(ready), K), np.int32)
        has = [False] * len(ready)
        for i, seq in enumerate(ready):
            toks = seq.tokens.tokens()  # one O(context) pass per row
            d = propose_ngram(toks, K, max_n=self.cfg.spec_ngram_max,
                              min_n=self.cfg.spec_ngram_min)
            if d is not None:
                drafts[i] = d
                has[i] = True
            else:
                # no match: pad with the last context token — the row still
                # gets its guaranteed one token from slot 0, and rejection
                # costs nothing the step isn't already spending
                drafts[i] = toks[-1]
        if not any(has):
            return None
        # grow pages for the +K lookahead (positions len .. len+K-1). No
        # preemption on this path — evicting a row already planned into
        # this very batch would corrupt it; on pressure we just fall back
        # to the plain decode step, which needs no extra pages. Pages
        # allocated before the failure stay with their sequences (they are
        # the very next pages those rows will use anyway).
        for seq in ready:
            need = self._pages_needed(len(seq) + K) - len(seq.page_ids)
            if need > 0:
                try:
                    seq.page_ids.extend(self.alloc.allocate(need))
                    seq.pages_changed()
                except OutOfPages:
                    return None
        return SpecDecodeBatch(seqs=list(ready), drafts=drafts, has_draft=has)

    def on_spec_done(self, plan: SpecDecodeBatch, advances: List[int],
                     accepted: Optional[List[int]] = None) -> None:
        """Advance accounting after a verify step.

        ``advances[i]`` = 1 (the fed context token's KV at slot 0) + the
        number of drafts row i actually APPENDED (accepted, then possibly
        truncated by a stop). Slots past the advance hold rejected drafts'
        KV — never committed (num_computed stops short), overwritten by the
        next step that reaches those positions, and masked from attention
        by true context length in between.

        Advances accounting ONLY — page commits wait for
        :meth:`commit_spec` AFTER the engine appended the accepted tokens:
        committing here would index token blocks that do not exist yet
        (``num_computed`` crosses a page boundary whose tokens are still
        in the candidate list)."""
        for seq, adv in zip(plan.seqs, advances):
            seq.num_computed += adv
        K = self.cfg.spec_tokens
        self.spec_stats.num_spec_tokens = K
        self.spec_stats.num_drafts += sum(1 for h in plan.has_draft if h)
        self.spec_stats.num_draft_tokens += K * sum(
            1 for h in plan.has_draft if h)
        # acceptance counts what the DEVICE accepted (draft quality), not
        # what survived stop truncation / cancellation — an operator tuning
        # K against the acceptance rate should not be steered by
        # short-completion workloads
        acc = accepted if accepted is not None else [
            max(0, a - 1) for a in advances]
        self.spec_stats.num_accepted_tokens += sum(
            a for a, h in zip(acc, plan.has_draft) if h)

    def commit_spec(self, plan: SpecDecodeBatch) -> None:
        """Commit full pages once the verify step's tokens are appended
        (rows the appends finished are no-ops: ``finish`` already
        committed and released their pages)."""
        for seq in plan.seqs:
            self._commit_full_pages(seq)

    # -- fused multi-step decode --------------------------------------------

    def _fuse_gate(self, seq: Sequence):
        """Admit one row to the fused block, or name the refusal.

        Returns ``(reason, width_cap)``: ``reason`` is a fallback-reason
        string when the row cannot ride a block (None when it can), and
        ``width_cap`` bounds the block width for rows whose device-side
        penalty ring buffer could overflow mid-block.

        Penalized / biased rows ride the block via the device penalty
        window (``cfg.penalty_window`` slots per row): the fresh-block
        carry seeds the window with the row's bias ids and distinct
        generated tokens, and each scanned step may insert at most one
        NEW distinct token — so a block of width w is exact iff
        ``distinct + inflight + w <= W`` (``inflight`` = device-sampled
        tokens a chained block hasn't fetched yet, each conservatively a
        new distinct insert). Guided rows ride iff the engine lowered
        their grammar to a device transition table
        (``cfg.guided_fuse_check``); an oversized grammar refuses as
        ``guided_table`` (per-batch, not per-deployment). Seeds and
        ``min_p`` remain always eligible: both are static per request
        and ship to the device (seeded draws key on token position, not
        step)."""
        so = seq.request.sampling_options
        cap = 1 << 20
        if _penalized(so):
            W = self.cfg.penalty_window
            if W <= 0:
                return "penalties", cap
            distinct = set(so.logit_bias or ()) | set(seq.generated)
            if (seq.request.resumed_tokens or 0) > 0:
                # migration resume: the trailing resumed_tokens of the
                # "prompt" are really prior-hop generations and count
                # toward the window (JaxEngine._penalty_row)
                toks = seq.tokens.tokens()
                n_prompt = seq.num_prompt - min(
                    seq.request.resumed_tokens, seq.num_prompt)
                distinct |= set(toks[n_prompt:seq.num_prompt])
            cap = W - len(distinct) - self._out(seq)
            if cap < 2:
                return "penalty_window", cap
        if so.guided:
            if self.cfg.guided_fuse_check is None:
                return "guided", cap
            if not self.cfg.guided_fuse_check(seq):
                return "guided_table", cap
        return None, cap

    def _grow_for_block(self, seqs: List[Sequence], start_lens: List[int],
                        writes: List[int]) -> bool:
        """Allocate every page the block will write (row i writes
        ``writes[i]`` positions from ``start_lens[i] - 1``; a row that is
        dead from block start writes none) up front. No preemption on this
        path — the caller narrows the width instead; pages allocated
        before a failure stay with their sequences (they are the very next
        pages those rows use anyway, as ``_spec_plan``)."""
        for seq, sl, n in zip(seqs, start_lens, writes):
            if n <= 0:
                continue
            need = self._pages_needed(sl + n - 1) - len(seq.page_ids)
            if need > 0:
                try:
                    seq.page_ids.extend(self.alloc.allocate(need))
                    seq.pages_changed()
                except OutOfPages:
                    return False
        return True

    def _max_new(self, seq: Sequence) -> Optional[int]:
        sc = seq.request.stop_conditions
        if sc.max_tokens is not None:
            return sc.max_tokens
        return (self.max_context_hint - seq.num_prompt
                if self.max_context_hint else None)

    def _spent(self, seq: Sequence) -> bool:
        """Did this finished row end because its token budget ran out? Then
        the device's budget carry ran out at the same token (one rule on
        both sides), and a chained block may keep the row as a dead one.
        Rows the host alone ended (cancelled, stop strings, errors) are
        alive on the device and never ride; constrained rows keep
        per-request device state that ``release_request`` drops."""
        return not _constrained(seq) and self._out_of_budget(seq)

    def _plan_block(self, seqs: List[Sequence]) -> Optional[MultiStepBatch]:
        """Compute the fuse width for one block over ``seqs`` and allocate
        its pages, or None to fall back to the per-step path. Behind a
        step in flight the block is chained (``MultiStepBatch.behind``
        names the step's kind): every row starts ``_out`` tokens past
        what the host holds, counted in budgets, ``min_tokens`` gates,
        widths and the pages grown up front. Behind a mixed step a
        refusal is a chain not taken (``record_chain_refusal``), not a
        fallback: the block is planned again from host state.

        The width is the configured cap (``decode_multistep``), narrowed
        to what the row with the MOST tokens left can still use
        (max_tokens / max_context: a batch that ends in <2 steps isn't
        worth a block) and, for rows with detokenizer-level stop strings,
        to the stop-string lookback; then rounded DOWN to a power of two
        (bounded compile count), then narrowed further if page pressure
        refuses the up-front allocation — so the fused program never
        needs mid-block page allocation. A row with fewer tokens left
        than the width does NOT narrow it: the device stops the row at
        its budget and masks it for the rest of the block, so a stream
        that ends takes no other row through narrower, unchained blocks.
        In a chained block a row that ends inside the block still in
        flight, or has ended (``_rows_behind``), rides as a dead row: the
        device has it dead from block start. Penalized / biased
        rows additionally cap the width by their remaining device
        penalty-window capacity (``_fuse_gate``); spec-decode mode and
        rows the gate cannot admit (no penalty window configured,
        grammar without a device table) refuse entirely.
        """
        cap = self.cfg.decode_multistep
        if cap < 2:
            return None
        behind = self._flight.kind if self._flight is not None else ""
        start_lens = [self._len(s) for s in seqs]
        refuse = (self.record_chain_refusal if behind == "mixed"
                  else self.record_fallback)
        if self.cfg.spec_tokens > 0:
            refuse("spec", seqs)
            return None
        w, most = cap, 0
        budgets: List[int] = []
        min_gates: List[int] = []
        for seq, sl in zip(seqs, start_lens):
            if self._phase(seq) is not Phase.RUNNING:
                budgets.append(0)       # a dead row
                min_gates.append(0)
                continue
            reason, row_cap = self._fuse_gate(seq)
            if reason is not None:
                refuse(reason, seqs)
                return None
            w = min(w, row_cap)
            sc = seq.request.stop_conditions
            gen_eff = len(seq.generated) + self._out(seq)
            max_new = self._max_new(seq)
            rem = (max_new - gen_eff) if max_new is not None else 1 << 20
            if self.max_context_hint is not None:
                rem = min(rem, self.max_context_hint - sl)
            rem = max(rem, 0)   # chained: it ends inside the block in flight
            most = max(most, rem)
            if sc.stop and rem:
                w = min(w, max(1, self.cfg.stop_str_lookback))
            budgets.append(min(rem, 1 << 20))  # int32-safe device budget
            min_gates.append(max(0, (sc.min_tokens or 0) - gen_eff))
        if most < 2:
            refuse("budget", seqs)
            return None
        w = min(w, most)
        w = 1 << (w.bit_length() - 1)
        while w >= 2 and not self._grow_for_block(
                seqs, start_lens, [min(w, b) for b in budgets]):
            w //= 2
        if w < 2:
            refuse("pages", seqs)
            return None
        if behind:
            self.chained_blocks[behind] += 1
        return MultiStepBatch(seqs=list(seqs), width=w, chained=bool(behind),
                              start_lens=start_lens, budgets=budgets,
                              min_gates=min_gates, behind=behind)

    def plan_multistep(self, batch: DecodeBatch) -> Optional[MultiStepBatch]:
        """Try to upgrade a planned decode step into a fused block.

        With ``mixed_batch`` on (the default), the PR 8 "no waiters /
        prefills" gate is LIFTED: arrivals onboard through the mixed
        steps that alternate with the fused blocks, so fusing while they
        wait no longer head-of-line blocks admission for more than one
        block (a chain of blocks still breaks there, ``_next_plan``: the
        host's plan admits). With it off, the legacy gate applies and the
        refusal is recorded as a fallback reason."""
        if self.cfg.gen_block > 1:
            return self._plan_passes(batch.seqs)
        if not self.cfg.mixed_batch:
            if self.waiting:
                self.record_fallback("waiters", batch.seqs)
                return None
            if any(s.phase is Phase.PREFILL for s in self.active.values()):
                self.record_fallback("prefill", batch.seqs)
                return None
        return self._plan_block(batch.seqs)

    def _gen_budget(self, seq: Sequence) -> int:
        """Tokens a block-diffusion row may still emit (``max_tokens``
        and the context limit; the host's ``_accept_token`` applies the
        same two)."""
        rem = 1 << 20
        max_new = self._max_new(seq)
        if max_new is not None:
            rem = min(rem, max_new - len(seq.generated))
        if self.max_context_hint is not None:
            rem = min(rem, self.max_context_hint - len(seq))
        return max(rem, 0)

    def _plan_passes(self, seqs: List[Sequence]) -> Optional[GenPassBatch]:
        """One fused dispatch of ``decode_multistep`` passes over every
        row's current block (``GenPassBatch``). Behind a dispatch in
        flight it is CHAINED: the rows' block state is that dispatch's
        device carry, and the host's view of each row lags by the passes
        in flight (``InFlight.out``). Pages are grown for every block a row can
        reach by the end of this dispatch: a block takes at least two
        passes (one that reveals, one that commits), and no row goes
        past the block its budget ends in. A fresh plan preempts the
        newest rows when the pool is short (they resume at a block
        boundary); a chained one is refused instead, and the chain
        breaks."""
        B = self.cfg.gen_block
        w = max(1, self.cfg.decode_multistep)
        chained = self._flight is not None
        inflight = self._flight.out if chained else 0
        rows: List[Sequence] = []
        for seq in (seqs if chained
                    else sorted(seqs, key=lambda s: s.arrival)):
            if seq.phase is not Phase.RUNNING:
                if chained:
                    rows.append(seq)        # dead in the device carry too
                continue
            budget = self._gen_budget(seq)
            tail = len(seq) - seq.num_computed
            last = seq.num_computed + (tail + budget + B - 1) // B * B
            reach = seq.num_computed + B * ((inflight + w + 1) // 2 + 1)
            want = self._pages_needed(min(last, reach))
            while len(seq.page_ids) < want:
                try:
                    seq.page_ids.extend(
                        self.alloc.allocate(want - len(seq.page_ids)))
                    seq.pages_changed()
                except OutOfPages:
                    if chained:
                        return None
                    if (not self._preempt_one()
                            or seq.phase is not Phase.RUNNING):
                        break
            if seq.phase is Phase.RUNNING and len(seq.page_ids) >= want:
                rows.append(seq)
        # a fresh plan's later growth may have preempted an earlier row
        rows = [s for s in rows if chained or s.phase is Phase.RUNNING]
        if not any(s.phase is Phase.RUNNING for s in rows):
            return None
        live = [s.phase is Phase.RUNNING for s in rows]
        return GenPassBatch(
            seqs=rows, width=w, chained=chained, inflight=inflight,
            start_lens=[s.num_computed for s in rows],
            tails=[len(s) - s.num_computed if a else 0
                   for s, a in zip(rows, live)],
            budgets=[self._gen_budget(s) if a else 0
                     for s, a in zip(rows, live)],
            min_gates=[0] * len(rows))

    def on_multistep_done(self, plan: MultiStepBatch,
                          advances: List[int]) -> None:
        """Advance accounting after a fused block resolved host-side.

        ``advances[i]`` = KV positions the block actually wrote for row i
        (== tokens appended): the device masks rows to no-ops after their
        stop, and the host re-derives the same stop point from the same
        rules. Slots past the advance hold dead-row KV — never committed,
        overwritten by the next step that reaches those positions, masked
        from attention by true context length in between (the ``on_spec_
        done`` safety argument). Commits wait for :meth:`commit_block`
        AFTER the engine appended the tokens (token blocks must exist)."""
        for seq, adv in zip(plan.seqs, advances):
            if adv:
                seq.num_computed += adv

    def commit_block(self, plan: MultiStepBatch) -> None:
        """Commit full pages once the block's tokens are appended (rows
        that finished are no-ops: ``finish`` already released them)."""
        for seq in plan.seqs:
            self._commit_full_pages(seq)

    def on_step_done(self, plan: StepPlan) -> None:
        """Advance accounting after the engine ran the planned step."""
        if isinstance(plan, (PrefillBatch, MixedStepBatch)):
            for chunk in plan.chunks:
                seq = chunk.seq
                if seq.phase is Phase.FINISHED:
                    # cancelled, and ended when the step in front of this
                    # (chained) one resolved
                    continue
                seq.num_computed += chunk.length
                if chunk.is_last:
                    seq.phase = Phase.RUNNING
                self._commit_full_pages(seq)
            for seq in getattr(plan, "decode_seqs", ()):
                seq.num_computed += 1
                self._commit_full_pages(seq)
        else:
            for seq in plan.seqs:
                seq.num_computed += 1
                self._commit_full_pages(seq)

    # -- metrics -----------------------------------------------------------

    def metrics(self) -> ForwardPassMetrics:
        total = self.alloc.num_pages - 1
        hits = self.alloc.hits
        lookups = hits + self.alloc.misses
        return ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_active_slots=len(self.active),
                request_total_slots=self.cfg.max_num_seqs,
                num_requests_waiting=len(self.waiting),
                data_parallel_rank=self.dp_rank,
            ),
            kv_stats=KvStats(
                kv_active_blocks=total - self.alloc.num_free,
                kv_total_blocks=total,
                gpu_cache_usage_perc=self.alloc.usage(),
                gpu_prefix_cache_hit_rate=(hits / lookups) if lookups else 0.0,
            ),
            spec_decode_stats=(self.spec_stats
                               if self.cfg.spec_tokens > 0 else None),
        )


__all__ = ["Scheduler", "SchedulerConfig", "Sequence", "Phase",
           "PrefillChunk", "PrefillBatch", "DecodeBatch", "SpecDecodeBatch",
           "MultiStepBatch", "MixedStepBatch", "InFlight", "CHAIN_KINDS"]
