"""Engine step flight recorder: a bounded, preallocated per-process ring
of StepRecords stamped by the engine loop around every dispatch family
(prefill / decode / chained / multistep / mixed / spec / gather).

The request-level flight recorder (utils/tracing.py) answers "what
happened to THIS request"; this module answers "what was the engine
doing" — per-dispatch kind, fused width, batch occupancy vs padding
waste, queue depth and page-pool pressure at plan time, plan/dispatch/
fetch/process wall time on the host, the program's DEVICE time as the
host can see it (``device_ms``: from the later of this dispatch's enqueue
and the previous result's arrival, to this result's arrival), and the
step GAP since the previous dispatch (host overhead and exclusive-window
stalls made visible). XLA compiles detected on a fresh jit bucket land
here too, so a mid-run compile is attributable instead of masquerading
as a throughput regression.

The loop's phases (``plan``/``dispatch``/``fetch``/``process``/``idle``/
``blocked``) are stamped by one helper, ``StepRecorder.phase``: it takes
the host-clock stamps the ring keeps AND opens a
``jax.profiler.TraceAnnotation`` named ``loop.<phase>`` with the
dispatch's ``seq``, so any profile of the process carries the same
phases on the device trace's clock (a TraceMe that checks one flag when
no profile runs).

A ``dispatch`` phase is taken apart into six stages the same way, on both
clocks: ``handover`` (the loop's thread -> the first instruction on the
worker thread) and ``resume`` (the call's return -> the loop's thread
back in the coroutine) from the stamps ``Phase.in_thread`` takes anyway;
``assemble`` (plan -> host arrays), ``upload`` (host arrays -> device
arrays), ``enqueue`` (the jitted call until it returns its futures) and
``wait`` (synchronous kinds: the call's return -> the result on the
host) marked by the engine, inside the call, with ``stage(name)``: a
``perf_counter`` pair summed per stage and a ``dispatch.<stage>``
annotation with the dispatch's ``seq`` and ``kind``.

Design constraints, in order:

* The hot path must cost <2% tok/s on fused decode (bench-proven).
  ``record()`` mutates a PREALLOCATED slot in place under one lock —
  no dict building, no prometheus client calls, no allocation beyond
  the occasional fallback string. Aggregates (per-kind duration /
  occupancy / step-gap histograms, compile counters, pool gauges) are
  plain fixed-bucket arrays updated inline; the worker /metrics
  collector renders them at scrape time.
* Bounded memory: the ring holds ``DYN_STEPTRACE_RING`` records
  (default 2048) and overwrites oldest-first. ``snapshot()`` paginates
  newest-first for ``GET /v1/steptrace``."""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import logging
import os
import sys
import threading
import time
from bisect import bisect_left
from typing import Any, Dict, List, Optional

from dynamo_tpu.utils import aio

__all__ = [
    "StepRecord", "StepRecorder", "Phase", "STAGES", "stage",
    "get_step_recorder", "set_step_recorder",
]

logger = logging.getLogger(__name__)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# fixed histogram bounds (seconds / ratio); cumulative rendering happens
# at scrape time so observe() is a bisect + two adds
_DUR_BOUNDS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
               0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
_GAP_BOUNDS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
               0.025, 0.05, 0.1, 0.25, 1.0)
_OCC_BOUNDS = (0.1, 0.25, 0.5, 0.625, 0.75, 0.875, 0.95, 1.0)
_STAGE_BOUNDS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                 0.01, 0.025, 0.05, 0.1, 0.25, 1.0, 2.5)

# the host's side of one dispatch, in the order it passes through them
STAGES = ("handover", "assemble", "upload", "enqueue", "wait", "resume")
_NO_STAGES = (0.0,) * len(STAGES)


class _Hist:
    """Fixed-bucket histogram: observe() is O(log buckets), no alloc."""

    __slots__ = ("bounds", "counts", "count", "sum")

    def __init__(self, bounds):
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # last slot = +Inf
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        self.counts[bisect_left(self.bounds, v)] += 1
        self.count += 1
        self.sum += v

    def cumulative(self) -> List[tuple]:
        """[(le_label, cumulative_count)] incl +Inf — prometheus shape."""
        out, acc = [], 0
        for b, c in zip(self.bounds, self.counts):
            acc += c
            out.append((str(b), acc))
        out.append(("+Inf", acc + self.counts[-1]))
        return out


# the grouped expert layer's counts a ring record carries, in the order the
# engine hands them over (``models/moe.grouped_experts``'s ``aux`` keys)
MOE_COUNTS = ("moe_experts_touched", "moe_assignments",
              "moe_held_assignments", "moe_zero_assignments")


class StepRecord:
    """One engine dispatch. Slots + in-place reuse keep the ring
    allocation-free in steady state; ``seq`` is the monotonic dispatch
    index (survives ring wrap, anchors pagination)."""

    FIELDS = ("seq", "t_unix", "kind", "program", "width", "rows", "batch",
              "decode_kernel_rows", "tokens_real", "tokens_padded",
              "queue_depth", "running", "pool_free", "pool_pinned",
              "plan_ms", "dispatch_ms",
              "fetch_ms", "process_ms", "unpack_ms", "device_ms",
              "ready_unix", "gap_ms", "compile_ms", "fallback", "chained",
              "chained_behind", "experts_touched", "moe_assignments",
              "moe_held_assignments", "moe_zero_assignments", "passes",
              "row_passes", "revealed", "commits", "handover_ms",
              "assemble_ms", "upload_ms",
              "enqueue_ms", "resume_ms", "fetch_resume_ms",
              "state_rows", "gdn_tokens", "gdn_step_rows", "score_pairs",
              "selected_keys", "state_bytes")
    # _enqueue: perf_counter at the start of the enqueue, kept until the
    # result arrives and device_ms can be taken; _experts: the dispatch's
    # expert-layer counts (MOE_COUNTS) while they are still device
    # scalars; neither is exported
    __slots__ = FIELDS + ("_enqueue", "_experts")

    def __init__(self) -> None:
        self.seq = -1
        self.t_unix = 0.0
        self.kind = ""
        self.program = ""
        self.width = 0
        self.rows = 0
        self.batch = 0
        # rows of a token-packed step that the decode kernel attended (its
        # trailing one-token rows; 0 for every other program, and where a
        # model's visibility block keeps them with the ragged kernel)
        self.decode_kernel_rows = 0
        self.tokens_real = 0
        self.tokens_padded = 0
        self.queue_depth = 0
        self.running = 0
        self.pool_free = 0
        self.pool_pinned = 0
        self.plan_ms = 0.0
        self.dispatch_ms = 0.0
        self.fetch_ms = 0.0
        self.process_ms = 0.0
        self.unpack_ms = 0.0
        self.device_ms = 0.0
        self.ready_unix = 0.0
        self.gap_ms = 0.0
        self.compile_ms = 0.0
        self.fallback = ""
        self.chained = False
        # a fused decode block whose first tokens came from the device:
        # what it was chained behind, "block" (the previous block's
        # carry) or "mixed" (the packed output of the prefill-carrying
        # step in front of it); "" on every other record
        self.chained_behind = ""
        # experts the dispatch read, summed over its expert layers and
        # steps (MoE families' grouped layer; 0 elsewhere)
        self.experts_touched = 0
        # every pick of the dispatch's valid tokens, and of those the
        # picks computed here (an expert this worker holds) and the picks
        # of zero-compute experts (the token itself, no matmul); what is
        # left was an expert held elsewhere
        self.moe_assignments = 0
        self.moe_held_assignments = 0
        self.moe_zero_assignments = 0
        # a dispatch of generation by diffusion over blocks (0 elsewhere):
        # forward passes it scanned, passes summed over the rows alive at
        # each (a pass serves every live row), positions those passes
        # revealed, and blocks they committed
        self.passes = 0
        self.row_passes = 0
        self.revealed = 0
        self.commits = 0
        # a dispatch of a model with linear-attention layers (0
        # elsewhere): rows whose recurrent state it read and wrote, tokens
        # that went through the chunk form of the rule (rows of several
        # tokens), row-steps through the one-token form (a layer each),
        # and the (query, key) pairs one full-attention layer scored: a
        # new token at position p sees p + 1 keys
        self.state_rows = 0
        self.gdn_tokens = 0
        self.gdn_step_rows = 0
        self.score_pairs = 0
        # a dispatch of a model whose full-attention layers attend a
        # learned selection (``index_topk``; 0 elsewhere): the keys ONE
        # such layer's queries attended - min(index_topk, p + 1) of the
        # p + 1 a token at position p can see (``score_pairs``: what its
        # indexer scored); ``state_rows`` are then the window slots read
        self.selected_keys = 0
        # bytes of recurrent state the dispatch read and wrote: every
        # linear layer's slot once in and once out for each row-step (a
        # prompt chunk's row once, a fused block's rows once a step), so a
        # reader needs no model arithmetic for it (0 elsewhere)
        self.state_bytes = 0
        # the dispatch phase by stage (``dispatch_ms`` less these five is
        # the ``wait`` for the result, of a synchronous kind), and how
        # long the fetched result waited for the loop's thread
        self.handover_ms = 0.0
        self.assemble_ms = 0.0
        self.upload_ms = 0.0
        self.enqueue_ms = 0.0
        self.resume_ms = 0.0
        self.fetch_resume_ms = 0.0
        self._enqueue = 0.0
        self._experts = None

    def to_dict(self) -> Dict[str, Any]:
        return {s: getattr(self, s) for s in self.FIELDS}


_annotation = None


def _trace_annotation(name: str, seq: int, kind: str):
    """``jax.profiler.TraceAnnotation(name, seq=, kind=)`` in a process
    that has imported jax; a null context in one that has not (the
    mocker), where no profile can run either."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return contextlib.nullcontext()
        _annotation = jax.profiler.TraceAnnotation
    return _annotation(name, seq=seq, kind=kind)


class Phase:
    """One phase of the step loop for one dispatch, on both clocks: the
    host-clock stamps the ring keeps (``t0``/``t1``, ``ms``) and a
    ``loop.<name>`` annotation in whatever profile is running. A ``with``
    block for the phases the loop runs itself; ``in_thread`` for the two
    it hands to a worker thread. There the loop's side (the hand-over to
    the thread and back included: what ``ms`` measures) is annotated on
    the loop's thread, and the call itself once more, under the same name
    and ``seq``, on the thread that does the work: ``called`` is its
    first instruction there, ``ready``/``ready_unix`` are taken the
    moment the call returns, before the event loop gets round to
    resuming. While the call runs the phase is the thread's current one,
    which is where ``stage`` adds up what the engine marks inside it."""

    __slots__ = ("_recorder", "name", "seq", "kind", "t0", "t1", "called",
                 "ready", "ready_unix", "assemble_s", "upload_s",
                 "enqueue_s", "wait_s", "_open", "_ann")

    def __init__(self, recorder: "StepRecorder", name: str, seq: int,
                 kind: str) -> None:
        self._recorder = recorder
        self.name = name
        self.seq = seq
        self.kind = kind
        self.t0 = self.t1 = self.called = self.ready = 0.0
        self.ready_unix = 0.0
        self.assemble_s = self.upload_s = self.enqueue_s = 0.0
        self.wait_s = 0.0
        self._open = False

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0

    @property
    def handover_ms(self) -> float:
        """The loop's thread -> the call's first instruction."""
        return max(0.0, self.called - self.t0) * 1000.0

    @property
    def resume_ms(self) -> float:
        """The call's return -> the loop's thread back in the coroutine."""
        return max(0.0, self.t1 - self.ready) * 1000.0

    def stage_seconds(self) -> tuple:
        """A finished threaded phase in seconds, in ``STAGES``' order."""
        return (max(0.0, self.called - self.t0), self.assemble_s,
                self.upload_s, self.enqueue_s, self.wait_s,
                max(0.0, self.t1 - self.ready))

    def _annotation(self):
        return _trace_annotation("loop." + self.name, self.seq, self.kind)

    def __enter__(self) -> "Phase":
        self._ann = self._annotation()
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        waits = self._recorder.loop_wait_s
        if self.name in waits:
            waits[self.name] += self.t1 - self.t0
        if self.ready:
            self._say_if_late()

    def _call(self, fn, args):
        self.called = time.perf_counter()
        _current.phase = self
        try:
            with self._annotation():
                out = fn(*args)
                self.ready = time.perf_counter()
                self.ready_unix = time.time()
        finally:
            _current.phase = None
        return out

    async def in_thread(self, fn, *args, head_start: float = 0.0):
        """``head_start`` seconds the loop's thread first WAITS for the
        call (a dispatch that only enqueues a program returns in a few ms)
        before it goes on to other work: otherwise the frames the loop has
        just put out for its streams are serialised on this thread at the
        same moment, the two take turns at the interpreter lock, and the
        device waits for its next program twice as long. A call that
        outlasts the head start (a first call compiles) is awaited as
        without one."""
        with self:
            if head_start <= 0.0:
                return await asyncio.to_thread(self._call, fn, args)
            returned = threading.Event()

            def call():
                try:
                    return self._call(fn, args)
                finally:
                    returned.set()
            # submitted here and now (``to_thread`` would start it only
            # when this coroutine next yields)
            pending = asyncio.get_running_loop().run_in_executor(
                None, contextvars.copy_context().run, call)
            returned.wait(head_start)
            return await pending

    def _say_if_late(self) -> None:
        """One line in the process's log where ready work waited as long
        for a thread (``handover``) or for the event loop (``resume``) as
        the loop's own heartbeat finds worth one (``aio.LAG_WARN_S``): an
        untraced run keeps its logs, so a silence in the token stream can
        be laid beside them."""
        for what, ms in (("handover", self.handover_ms),
                         ("resume", self.resume_ms)):
            if ms > aio.LAG_WARN_S * 1000.0:
                logger.warning(
                    "step loop late: %s of seq %d (loop.%s, kind %s) took "
                    "%.1f ms", what, self.seq, self.name, self.kind or "-",
                    ms)


# the threaded phase whose call runs on this thread, if any
_current = threading.local()
_NO_STAGE = contextlib.nullcontext()


class _Stage:
    """One opening of a stage inside a dispatch call."""

    __slots__ = ("_phase", "_name", "_ann", "_t0")

    def __init__(self, phase: Phase, name: str) -> None:
        self._phase = phase
        self._name = name

    def __enter__(self) -> None:
        ph = self._phase
        ph._open = True
        self._ann = _trace_annotation("dispatch." + self._name, ph.seq,
                                      ph.kind)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        ph, attr = self._phase, self._name + "_s"
        setattr(ph, attr, getattr(ph, attr) + dt)
        ph._open = False


def stage(name: str):
    """Mark one stage (``assemble``, ``upload``, ``enqueue`` or ``wait``)
    of the dispatch whose call runs on this thread: ``with
    stage("upload"): ...``. It may open any number of times in one
    dispatch and the phase keeps the sum. A null context where no phase is
    current (priming, tests, ``bench.py``) and inside a stage that is
    already open, whose time it stays."""
    ph = getattr(_current, "phase", None)
    if ph is None or ph._open:
        return _NO_STAGE
    return _Stage(ph, name)


class StepRecorder:
    """Process-wide step ring + inline fleet aggregates.

    The loop calls ``record()`` once per dispatch (cheap), then patches
    host-side costs in as they become known: ``note_ready()`` when the
    result is on the host, ``note_unpack()`` when the overlapped
    fetch+process completes, ``note_compile()`` when the
    engine reports a fresh-jit-bucket compile attributed to that
    dispatch. Aggregate reads (``aggregates()``/``snapshot()``) take the
    same lock — scrape-time only, never on the hot path.
    """

    def __init__(self, capacity: Optional[int] = None) -> None:
        if capacity is None:
            capacity = _env_int("DYN_STEPTRACE_RING", 2048)
        self.capacity = max(1, capacity)
        self._ring = [StepRecord() for _ in range(self.capacity)]
        self._n = 0                      # dispatches ever recorded
        self._lock = threading.Lock()
        # fleet aggregates (rendered by worker/metrics.StepTraceCollector)
        self._dur: Dict[str, _Hist] = {}
        self._occ: Dict[str, _Hist] = {}
        self._gap = _Hist(_GAP_BOUNDS)
        # a dispatch phase's stages, each over the dispatches it opened in
        self._stage = {name: _Hist(_STAGE_BOUNDS) for name in STAGES}
        self.compile_events: Dict[str, int] = {}
        self.compile_seconds: Dict[str, float] = {}
        self.pool_free = 0
        self.pool_pinned = 0
        # perf_counter when the newest result arrived: where the next
        # program's device time starts if it was enqueued before that
        self._last_ready = 0.0
        # seconds the loop spent waiting, process-wide: ``idle`` for a
        # request, ``blocked`` on a full cache (written by the loop's
        # thread alone, through ``phase``)
        self.loop_wait_s: Dict[str, float] = {"idle": 0.0, "blocked": 0.0}

    # -- hot path ----------------------------------------------------------

    def phase(self, name: str, seq: int, kind: str = "") -> Phase:
        """The stamping helper for one loop phase (see ``Phase``)."""
        return Phase(self, name, seq, kind)

    def record(self, kind: str, *, program: str = "", width: int = 0,
               rows: int = 0,
               batch: int = 0, tokens_real: int = 0, tokens_padded: int = 0,
               queue_depth: int = 0, running: int = 0, pool_free: int = 0,
               pool_pinned: int = 0, plan_ms: float = 0.0,
               dispatch_ms: float = 0.0, gap_ms: float = 0.0,
               fallback: str = "", chained: bool = False,
               chained_behind: str = "", enqueue: float = 0.0,
               experts: Any = None,
               decode_kernel_rows: int = 0,
               state: tuple = (0, 0, 0, 0, 0, 0),
               phase: Optional[Phase] = None) -> StepRecord:
        """Stamp one dispatch; returns the live ring slot (later patched
        by note_ready/note_unpack/note_compile).
        ``enqueue`` is the perf_counter at the start of the enqueue;
        ``experts`` its expert layers' counts (``MOE_COUNTS`` order),
        device scalars that ``note_ready`` reads in one transfer once the
        result is on the host;
        ``phase`` the finished dispatch phase, for its stages."""
        now = time.time()
        with self._lock:
            rec = self._ring[self._n % self.capacity]
            self._n += 1
            rec.seq = self._n - 1
            rec.t_unix = now
            rec.kind = kind
            rec.program = program
            rec.width = width
            rec.rows = rows
            rec.batch = batch
            rec.decode_kernel_rows = decode_kernel_rows
            rec.tokens_real = tokens_real
            rec.tokens_padded = tokens_padded
            rec.queue_depth = queue_depth
            rec.running = running
            rec.pool_free = pool_free
            rec.pool_pinned = pool_pinned
            rec.plan_ms = plan_ms
            rec.dispatch_ms = dispatch_ms
            rec.fetch_ms = 0.0
            rec.process_ms = 0.0
            rec.unpack_ms = 0.0
            rec.device_ms = 0.0
            rec.ready_unix = 0.0
            rec.gap_ms = gap_ms
            rec.compile_ms = 0.0
            rec.fallback = fallback
            rec.chained = chained
            rec.chained_behind = chained_behind
            rec.experts_touched = rec.moe_assignments = 0
            rec.moe_held_assignments = rec.moe_zero_assignments = 0
            rec.passes = rec.row_passes = rec.revealed = rec.commits = 0
            (rec.state_rows, rec.gdn_tokens, rec.gdn_step_rows,
             rec.score_pairs, rec.selected_keys, rec.state_bytes) = state
            rec._enqueue = enqueue
            rec._experts = experts
            rec.fetch_resume_ms = 0.0
            stages = _NO_STAGES if phase is None else phase.stage_seconds()
            # (spelled out: a generator here costs the hot path 5 us)
            rec.handover_ms = stages[0] * 1000.0
            rec.assemble_ms = stages[1] * 1000.0
            rec.upload_ms = stages[2] * 1000.0
            rec.enqueue_ms = stages[3] * 1000.0
            rec.resume_ms = stages[5] * 1000.0
            if phase is not None:
                for name, seconds in zip(STAGES, stages):
                    if seconds > 0.0:
                        self._stage[name].observe(seconds)
            if tokens_padded > 0:
                o = self._occ.get(kind)
                if o is None:
                    o = self._occ[kind] = _Hist(_OCC_BOUNDS)
                o.observe(min(1.0, tokens_real / tokens_padded))
            if gap_ms > 0.0:
                self._gap.observe(gap_ms / 1000.0)
            self.pool_free = pool_free
            self.pool_pinned = pool_pinned
            return rec

    def note_ready(self, rec: Optional[StepRecord], ready: float,
                   ready_unix: float) -> None:
        """The dispatch's result is on the host (``ready``: perf_counter,
        ``ready_unix``: wall clock). The device runs programs in the
        order they were enqueued, so this one had the device from the
        later of its own enqueue and the previous result's arrival:
        ``device_ms`` is that span, for synchronous and asynchronous
        kinds alike, and under the chained overlap (N+1 enqueued before
        N is fetched) two programs never count the same time. It is the
        host's estimate: the enqueue's own host work and the copy back
        are inside it. The per-kind duration histogram observes it."""
        if rec is None:
            return
        with self._lock:
            device_s = max(0.0, ready - max(rec._enqueue, self._last_ready))
            self._last_ready = ready
            rec.ready_unix = ready_unix
            rec.device_ms = device_s * 1000.0
            h = self._dur.get(rec.kind)
            if h is None:
                h = self._dur[rec.kind] = _Hist(_DUR_BOUNDS)
            h.observe(device_s)
        if rec._experts is not None:
            # the program has run, so the scalars are there to be read
            import jax
            counts, rec._experts = jax.device_get(rec._experts), None
            (rec.experts_touched, rec.moe_assignments,
             rec.moe_held_assignments, rec.moe_zero_assignments) = (
                int(c) for c in counts)

    def note_unpack(self, rec: Optional[StepRecord], fetch_ms: float,
                    process_ms: float, fetch_resume_ms: float = 0.0) -> None:
        """Patch the host's time after the dispatch into its record:
        blocked in the fetch (waiting for the device and the copy back;
        ``fetch_resume_ms`` of it the result lay on the host until the
        loop's thread came round), and in its own unpack-and-emit work;
        ``unpack_ms`` is their sum (known only when the overlapped fetch
        completes, often after the NEXT dispatch has been stamped)."""
        if rec is None:
            return
        with self._lock:
            rec.fetch_ms = fetch_ms
            rec.process_ms = process_ms
            rec.unpack_ms = fetch_ms + process_ms
            rec.fetch_resume_ms = fetch_resume_ms

    def note_passes(self, rec: Optional[StepRecord], passes: int,
                    row_passes: int, revealed: int, commits: int,
                    positions: int) -> None:
        """What a pass dispatch did, known once its result is unpacked:
        rows die inside a dispatch, so ``tokens_real`` - the positions
        it computed, ``row_passes`` times the block length - is known
        only now too."""
        if rec is None:
            return
        with self._lock:
            rec.passes, rec.row_passes = passes, row_passes
            rec.revealed, rec.commits = revealed, commits
            rec.tokens_real = positions

    def note_compile(self, kind: str, seconds: float,
                     rec: Optional[StepRecord] = None) -> None:
        """Count a first-call compile on a fresh (kind, shape) jit
        bucket; attributes it to ``rec`` when the dispatch is known."""
        with self._lock:
            self.compile_events[kind] = self.compile_events.get(kind, 0) + 1
            self.compile_seconds[kind] = (
                self.compile_seconds.get(kind, 0.0) + seconds)
            if rec is not None:
                rec.compile_ms += seconds * 1000.0

    # -- read side (scrape / HTTP) -----------------------------------------

    @property
    def total(self) -> int:
        return self._n

    def snapshot(self, limit: int = 100, offset: int = 0) -> Dict[str, Any]:
        """Newest-first page of records for ``GET /v1/steptrace``."""
        limit = max(0, limit)
        offset = max(0, offset)
        with self._lock:
            live = min(self._n, self.capacity)
            recs = []
            for i in range(offset, min(offset + limit, live)):
                # i newest-first -> ring index
                rec = self._ring[(self._n - 1 - i) % self.capacity]
                recs.append(rec.to_dict())
            return {"total": self._n, "capacity": self.capacity,
                    "count": len(recs), "offset": offset, "records": recs}

    def aggregates(self) -> Dict[str, Any]:
        """Plain-data aggregate snapshot for the metrics collector."""
        with self._lock:
            return {
                "duration": {k: (h.cumulative(), h.sum, h.count)
                             for k, h in self._dur.items()},
                "occupancy": {k: (h.cumulative(), h.sum, h.count)
                              for k, h in self._occ.items()},
                "gap": (self._gap.cumulative(), self._gap.sum,
                        self._gap.count),
                "stage": {k: (h.cumulative(), h.sum, h.count)
                          for k, h in self._stage.items()},
                "compile_events": dict(self.compile_events),
                "compile_seconds": dict(self.compile_seconds),
                "pool_free": self.pool_free,
                "pool_pinned": self.pool_pinned,
                "loop_wait_s": dict(self.loop_wait_s),
            }


_recorder: Optional[StepRecorder] = None
_recorder_lock = threading.Lock()


def get_step_recorder() -> StepRecorder:
    """Process-wide recorder (the ``get_tracer`` pattern): every engine
    in the process stamps the same ring, the system server exports it."""
    global _recorder
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = StepRecorder()
    return _recorder


def set_step_recorder(recorder: StepRecorder) -> StepRecorder:
    """Swap the process recorder (tests / re-reading env knobs)."""
    global _recorder
    _recorder = recorder
    return recorder
