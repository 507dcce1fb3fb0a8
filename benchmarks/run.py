#!/usr/bin/env python3
"""Run one benchmark cell once through the served path and print its line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``benchmarks/configs/``), a traffic mix (``benchmarks/traffic/``) and the
cell's own file (``benchmarks/cells/``: layout, rate or clients). The run
starts a coordinator, the layout's workers and a frontend as separate
processes, sends the traffic over HTTP, and prints one JSON object as the
last line of its standard output. This process never imports jax.

The timeline of a run: spawn -> workers ready -> warm-up segments of the
cell's own traffic (another seed) until one whole segment adds no compile
event -> lead-in segments (the window's seed; their sources still ask
questions inside the window) -> the measured window of ``--seconds`` -> up
to 30 s for its requests to finish -> the correctness probes -> stop.
A closed loop has no lead-in: its warm-up ends behind a count of answered
requests and a burst of tokens (``drive_closed``).
``setup_s`` runs from spawn to the start of the window. With ``--trace 1``
the step ring is paged, request traces are exported and the worker's
launcher takes a profiler trace of a few seconds in the middle of the
window; the line then carries the cell's per-layer metrics instead of its
end-to-end ones.

``--tiny`` (toy widths on the CPU backend, for the tests) prints
``device.platform: "cpu"``; without it a machine with no TPU ends the run
non-zero and prints no line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import breakdown  # noqa: E402
import correctness  # noqa: E402
import loadgen  # noqa: E402
import modeldir  # noqa: E402
import served  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402
from served import Failed  # noqa: E402

TRACE_SLICE_S = 8.0      # profiler slice in the middle of the window
MAX_WARM_SEGMENTS = 40   # unless the cell's file says otherwise
SETTLED_QUEUE = 4        # waiting requests a settled system may show
COMPILED_S = 4.0         # mean seconds a first call takes only if it compiles
COLD_CLEAN = 3           # segments in a row without a compile that end a
#                          cold run's warm-up


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def compile_events(stack) -> tuple:
    """(first calls of fresh step programs so far, the seconds they took),
    summed over workers. A first call served from the compile cache takes
    about a second; one that compiles takes 13-18 s."""
    events, seconds = 0, 0.0
    for w in stack.workers:
        try:
            with urllib.request.urlopen(w.system_url + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
        except OSError:
            raise Failed(f"{w.name} /metrics does not answer")
        for line in text.splitlines():
            if line.startswith("dynamo_worker_compile_events_total"):
                events += int(float(line.rpartition(" ")[2]))
            elif line.startswith("dynamo_worker_compile_seconds_total"):
                seconds += float(line.rpartition(" ")[2])
    return events, seconds


def compiled(before: tuple, now: tuple) -> bool:
    """Did a segment's first calls compile, and not just load? Then the
    run is the first of its kind in this checkout, and warm-up goes on
    until a segment only loads: what it compiles now, no later run will."""
    events, seconds = now[0] - before[0], now[1] - before[1]
    return events > 0 and seconds / events > COMPILED_S


def queue_depth(stack) -> int:
    """Requests waiting for admission at the newest dispatch, summed over
    workers."""
    total = 0
    for w in stack.workers:
        body = served.get_json(w.system_url + "/v1/steptrace?limit=1") or {}
        for rec in body.get("records", []):
            total += rec["queue_depth"]
    return total


class RingPager:
    """Pages each worker's step ring while the run goes on (``--trace 1``):
    the ring overwrites oldest-first, a run outlasts it."""

    def __init__(self, stack):
        self.stack = stack
        self.records = [dict() for _ in stack.workers]   # seq -> record

    def poll(self) -> None:
        for i, w in enumerate(self.stack.workers):
            head = served.get_json(w.system_url + "/v1/steptrace?limit=1")
            if not head:
                continue
            have = self.records[i]
            new = head["total"] - (max(have) + 1 if have else 0)
            # re-read the newest few: unpack_ms lands after the next record
            body = served.get_json(
                f"{w.system_url}/v1/steptrace?limit={min(new + 8, 16384)}",
                timeout=30) if new > 0 else None
            for rec in (body or {}).get("records", []):
                have[rec["seq"]] = rec

    def all(self) -> list:
        return [[have[k] for k in sorted(have)] for have in self.records]


class Run:
    """What one run knows; per-layer metric readers get this object."""

    def __init__(self, args, bench: dict):
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise Failed(f"no workload {args.workload!r} in BENCHMARK.json")
        self.args = args
        self.bench = bench
        self.workload = cells[args.workload]
        self.config = modeldir.load_config(self.workload["config"], args.tiny)
        self.mix = traffic.load_mix(self.workload["traffic"])
        self.cell = traffic.load_cell(args.workload)
        if args.tiny:
            self.mix = {**self.mix, **self.mix.get("tiny", {})}
            self.cell = {**self.cell, **self.cell.get("tiny", {})}
        if args.rate is not None:
            key = "clients" if self.mix["loop"] == "closed" else "rate_per_s"
            self.cell = {**self.cell, key: args.rate}
        with open(os.path.join(HERE, "layouts",
                               f"{self.cell['layout']}.json")) as f:
            self.layout = json.load(f)
        if self.layout["chips"] != self.workload["chips"]:
            raise Failed(f"layout {self.cell['layout']} is for "
                         f"{self.layout['chips']} chips, the cell asks for "
                         f"{self.workload['chips']}")
        wargs = self.config["bench"]["worker_args"]

        def flag(name: str, default: int) -> int:
            return (int(wargs[wargs.index(name) + 1]) if name in wargs
                    else default)
        # the worker's own defaults where the configuration names none
        self.num_pages = flag("--num-pages", 2048)
        self.page_size = flag("--page-size", 16)
        self.platform = "cpu" if args.tiny else "tpu"
        self.traced = bool(args.trace)
        tag = args.workload + ("-tiny" if args.tiny else "")
        self.run_dir = os.path.join(HERE, ".runs", tag)
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        self.cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(HERE, ".cache", "jax"))
        self.gen = traffic.Generator(self.mix, self.cell,
                                     self.config["hf"]["vocab_size"],
                                     args.seed)
        # filled as the run goes
        self.stack = None
        self.requests: list = []      # measured requests
        self.t0_unix = 0.0            # window start, wall clock
        self.seconds = float(args.seconds)
        self.setup_s = 0.0
        self.window_tokens = 0
        self.compiles_in_setup = 0
        self.compiles_in_window = 0
        self.ring: list = []          # per worker, records in seq order
        self.device_traces: list = []  # per worker, xplane reduction
        self.trace_marks: list = []
        self.devices: list = []
        self.probe_result: dict = {}
        self.warm_inflight = 0
        self.in_flight: dict = {}      # closed loop: client -> its request

    # ------------------------------------------------------------- the run

    def execute(self) -> dict:
        t_spawn = time.monotonic()
        model_dir = modeldir.write_model_dir(
            os.path.join(self.run_dir, "model"), self.config["hf"])
        b = self.config["bench"]
        self.stack = served.Stack(
            self.run_dir, self.layout, model_dir, b["served_name"],
            ["--dtype", b["dtype"]] + b["worker_args"], self.platform,
            self.cache_dir, self.traced, b.get("worker_env", {}))
        try:
            self.stack.start()
            say(f"served path up after {time.monotonic() - t_spawn:.1f}s: "
                + ", ".join(f"{w.name} {w.health.get('device_kind')!r} "
                            f"attn_impl={w.health.get('attn_impl')}"
                            for w in self.stack.workers))
            asyncio.run(self.drive(t_spawn))
            self.devices = [self.stack.ask_worker(w, "device", {}, 30)
                            for w in self.stack.workers]
        finally:
            self.stack.stop()
        self.check_device()
        self.read_exports()
        # without the probes (a builder's flag) nothing decides ``correct``
        correct = correctness.judge(self) if self.args.probes else None
        line = self.result(correct)
        self.keep(line)
        return line

    def keep(self, line: dict) -> None:
        """What a person reading one run wants beside its line, left in the
        run directory: every measured request, and of a traced run the
        ring and the trace's reduction."""
        with open(os.path.join(self.run_dir, "run.json"), "w") as f:
            json.dump({
                "line": line, "setup_s": self.setup_s,
                "t0_unix": self.t0_unix, "seconds": self.seconds,
                "compiles_in_setup": self.compiles_in_setup,
                "requests": [
                    {"source": r.source, "turn": r.turn, "due": r.due,
                     "sent": r.sent, "first": r.first, "last": r.last,
                     "prompt": len(r.prompt), "max_tokens": r.max_tokens,
                     "tokens": r.tokens, "ok": r.ok, "error": r.error}
                    for r in self.requests],
                "ring": self.ring, "device_traces": self.device_traces}, f)

    async def drive(self, t_spawn: float) -> None:
        gen, stack = self.gen, self.stack
        L = gen.segment_s
        pager = RingPager(stack) if self.traced else None
        loop = asyncio.get_running_loop()
        async with loadgen.Client(stack.base_url,
                                  self.config["bench"]["served_name"]) as c:
            c.t0 = time.monotonic()
            if gen.closed:
                await self.drive_closed(c, t_spawn, pager)
            else:
                # warm-up: segments of the cell's own traffic with other
                # tokens, back to back: at least ``warm_segments`` of them,
                # and on until one leaves no queue behind (a run that
                # compiles takes longer to settle). A fresh process makes a
                # first call of every step program it meets, about a second
                # each from the compile cache, and the engine has some
                # fifty: warm-up meets most, the window may meet a few more
                # (``step.compiles_in_window``)
                seg, before = 0, await loop.run_in_executor(
                    None, compile_events, stack)
                clean, cold = 0, False
                while True:
                    for r in gen.segment(seg, warm=True):
                        r.due += seg * L
                        c.launch(self.send_warm(c, r))
                    seg += 1
                    await asyncio.sleep(max(0.0, seg * L - c.now()))
                    stack.check_alive()
                    now = await loop.run_in_executor(
                        None, compile_events, stack)
                    waiting = await loop.run_in_executor(
                        None, queue_depth, stack)
                    say(f"warm-up segment {seg}: {now[0] - before[0]} first "
                        f"calls in {now[1] - before[1]:.1f}s, {waiting} "
                        f"waiting, {self.warm_inflight} in flight")
                    clean = 0 if compiled(before, now) else clean + 1
                    cold = cold or not clean
                    # a run that compiled repeats the schedule until
                    # COLD_CLEAN segments in a row only load: each repeat
                    # meets the batch shapes a little differently, and what
                    # compiles now no later run has to
                    settled = (waiting <= SETTLED_QUEUE
                               and clean >= (COLD_CLEAN if cold else 1))
                    if (seg >= self.cell["warm_segments"] and settled) \
                            or seg >= self.cell.get("max_warm_segments",
                                                    MAX_WARM_SEGMENTS):
                        break
                    before = now
                lead = gen.lead_segments()
                start = (seg + lead) * L          # window start, timeline
                n_win = -(-int(self.seconds * 1000) // int(L * 1000))
                for j in range(-lead, n_win):
                    for r in gen.segment(j, warm=False):
                        r.due += start + j * L
                        if r.due >= start + self.seconds:
                            continue
                        r.measured = r.due >= start
                        if r.measured:
                            self.requests.append(r)
                        c.launch(c.send_at(r))
                await asyncio.sleep(max(0.0, start - c.now()))
                await self.window(c, start, t_spawn, pager)
            self.window_tokens = c.window_tokens
            # what warm-up and lead-in still have in flight is dropped, so
            # the probes meet an idle system and the same batch in every run
            await c.cancel_all()
            if self.args.probes:
                await correctness.send_probes(self, c)
        if pager:
            pager.poll()
            self.ring = pager.all()

    async def send_warm(self, c, r) -> None:
        """A warm-up request, dropped when ``warm_inflight_cap`` are in
        flight already: while programs compile nothing is answered, and an
        open loop would pile up a backlog that outlasts the warm-up."""
        delay = r.due - c.now()
        if delay > 0:
            await asyncio.sleep(delay)
        if self.warm_inflight >= self.cell["warm_inflight_cap"]:
            return
        self.warm_inflight += 1
        try:
            await c.send(r)
        finally:
            self.warm_inflight -= 1

    async def drive_closed(self, c, t_spawn: float, pager) -> None:
        """``clients`` callers, each sending its next request when the last
        one is answered; warm-up requests until told otherwise, then the
        window's.

        A closed loop is one process from the first request on: rows fill
        up over some cycles of the step loop, programs for ever larger
        batches are called for the first time, and only then does every
        cycle look like the last. The window has to lie behind all that,
        and at the same place in every run, because the system streams in
        bursts (one per fused decode block: a twelfth of a 50 s window
        each at PR 23) and a window that starts a little earlier or later
        holds one burst more or fewer. So warm-up ends (1) after the
        segments any cell warms up for, (2) when ``warm_requests`` requests
        have been answered - a count, not a time: callers start in the
        order of their numbers and each always asks for the same lengths,
        so the count names one state of the loop - and (3) ``quiet_s``
        after the burst of tokens that carried that answer ended."""
        gen, loop, cell = self.gen, asyncio.get_running_loop(), self.cell
        state = {"warm": True, "stop": False, "answered": 0}
        streams = {True: {}, False: {}}

        def take(client: int, k: int, warm: bool):
            segs = streams[warm]
            if k not in segs:
                segs[k] = gen.segment(k, warm=warm)
            return segs[k][client]

        async def caller(client: int):
            # in the order of their numbers: who is admitted when decides
            # every later batch
            await asyncio.sleep(client * cell.get("stagger_s", 0.0))
            k_warm = k_win = 0
            while not state["stop"]:
                if state["warm"]:
                    r = take(client, k_warm, True)
                    k_warm += 1
                else:
                    r = take(client, k_win, False)
                    k_win += 1
                    r.measured = True
                    self.requests.append(r)
                r.due = c.now()
                self.in_flight[client] = r
                await c.send(r)
                state["answered"] += 1

        for i in range(gen.per_segment):
            c.launch(caller(i))
        before = await loop.run_in_executor(None, compile_events, self.stack)
        clean, cold = 0, False
        max_segments = cell.get("max_warm_segments", MAX_WARM_SEGMENTS)
        for seg in range(1, max_segments + 1):
            c.longest_silence = 0.0
            await asyncio.sleep(gen.segment_s)
            self.stack.check_alive()
            now = await loop.run_in_executor(None, compile_events, self.stack)
            say(f"warm-up segment {seg}: {now[0] - before[0]} first calls in "
                f"{now[1] - before[1]:.1f}s, {state['answered']} answered")
            clean = 0 if compiled(before, now) else clean + 1
            cold = cold or not clean
            if seg >= cell["warm_segments"] and clean >= (
                    COLD_CLEAN if cold else 1):
                break
            before = now
        give_up = c.now() + max_segments * gen.segment_s
        while state["answered"] < cell.get("warm_requests", 0) \
                and c.now() < give_up:
            await asyncio.sleep(0.005)
        if state["answered"] < cell.get("warm_requests", 0):
            say("gave up waiting for the answers that end warm-up")
        self.stack.check_alive()
        quiet = cell.get("quiet_s", 0.0)
        if quiet and c.longest_silence >= quiet:
            # tokens come in bursts with silences longer than ``quiet_s``
            # between them: start behind one, not inside one
            armed = c.now()
            give_up = armed + 2 * c.longest_silence + quiet
            while not c.settled(armed, quiet) and c.now() < give_up:
                await asyncio.sleep(0.005)
        say(f"warm-up ends with {state['answered']} answered, "
            f"{c.now() - c.last_token:.2f}s after the newest token; longest "
            f"silence since the last segment {c.longest_silence:.2f}s")
        state["warm"] = False
        # what the callers have in flight now is the window's work too
        self.requests.extend(self.in_flight.values())
        await self.window(c, c.now(), t_spawn, pager)
        state["stop"] = True

    async def window(self, c, start: float, t_spawn: float, pager) -> None:
        """The measured window ``[start, start + seconds)`` on the client's
        timeline, which has just begun."""
        loop = asyncio.get_running_loop()
        self.setup_s = (c.t0 + start) - t_spawn
        self.t0_unix = time.time() - (c.now() - start)
        c.window = (start, start + self.seconds)
        c.window_tokens = 0
        at_start = await loop.run_in_executor(None, compile_events,
                                              self.stack)
        self.compiles_in_setup = at_start[0]
        say(f"window starts: set-up {self.setup_s:.1f}s, {at_start[0]} "
            f"first calls of step programs so far ({at_start[1]:.0f}s)")
        end = start + self.seconds
        trace_at = start + max(0.0, (self.seconds - TRACE_SLICE_S) / 2)
        slice_s = min(TRACE_SLICE_S, self.seconds)
        traced = False
        while c.now() < end:
            if self.traced and not traced and c.now() >= trace_at:
                traced = True
                for w in self.stack.workers:
                    # the answer is collected after the window
                    self.stack.request(w, "trace", {"seconds": slice_s})
            if pager:
                await loop.run_in_executor(None, pager.poll)
            self.stack.check_alive()
            await asyncio.sleep(min(2.0, max(0.0, end - c.now())))
        at_end = await loop.run_in_executor(None, compile_events, self.stack)
        self.compiles_in_window = at_end[0] - at_start[0]
        # a closed loop is judged on the tokens streamed inside the window:
        # a request still streaming at its end has not failed
        if self.gen.closed:
            for r in self.requests:
                r.ok = r.ok or not r.error
        else:
            await c.wait_done(self.requests, end + loadgen.DRAIN_S)
        if self.traced:
            for w in self.stack.workers:
                mark = self.stack.answer(w, "trace", 120)
                self.trace_marks.append(mark)

    # ------------------------------------------------------- after the run

    def check_device(self) -> None:
        kinds = {d["kind"] for d in self.devices}
        count = sum(d["count"] for d in self.devices)
        if any(d["platform"] != self.platform for d in self.devices):
            raise Failed(f"workers ran on {self.devices}, wanted "
                         f"{self.platform}")
        if len(kinds) != 1:
            raise Failed(f"workers on different devices: {kinds}")
        if not self.args.tiny and count != self.workload["chips"]:
            raise Failed(f"the cell asks for {self.workload['chips']} chips, "
                         f"the workers hold {count}")

    def read_exports(self) -> None:
        if not self.traced:
            return
        for w, mark in zip(self.stack.workers, self.trace_marks):
            out = subprocess.run(
                [sys.executable, os.path.join(HERE, "xplane.py"),
                 mark["dir"]], capture_output=True, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
            if out.returncode != 0:
                raise Failed(f"xplane reduction failed for {w.name}:\n"
                             + out.stderr[-2000:])
            red = json.loads(out.stdout.strip().splitlines()[-1])
            red["mark"] = mark
            self.device_traces.append(red)

    def result(self, correct) -> dict:
        done = [r for r in self.requests if r.ok]
        failed = len(self.requests) - len(done)
        for r in self.requests:
            if not r.ok:
                say(f"failed request {r.source}.{r.turn}: {r.error}")
        got = sorted((r.due, r.first - r.due) for r in done if r.first >= 0)
        if got:
            # a queue that grows shows as the later half waiting longer
            half = len(got) // 2
            say(f"{len(done)} of {len(self.requests)} requests completed; "
                "mean wait for the first token, earlier half "
                f"{sum(w for _d, w in got[:half or 1]) / (half or 1):.2f}s, "
                f"later half "
                f"{sum(w for _d, w in got[half:]) / len(got[half:]):.2f}s; "
                f"{self.window_tokens / self.seconds:.1f} tokens/s streamed "
                "in the window")
        wanted = listed(self.bench,
                        "per_layer" if self.traced else "end_to_end",
                        self.args.workload)
        metrics = {}
        if self.traced:
            for name, m in wanted.items():
                value = reader(name).compute(self)
                if value is not None:
                    metrics[name] = {"value": value, "unit": m["unit"]}
        else:
            values = self.end_to_end(done)
            for name, m in wanted.items():
                metrics[name] = {"value": values[name](), "unit": m["unit"]}
        device = {"platform": self.devices[0]["platform"],
                  "kind": self.devices[0]["kind"],
                  "count": sum(d["count"] for d in self.devices),
                  "memory_peak_bytes": max(d["memory_peak_bytes"]
                                           for d in self.devices)}
        line = {"correct": correct if correct is None else bool(correct),
                "attempted": len(self.requests),
                "failed": failed, "metrics": metrics, "device": device,
                "compiles_in_window": self.compiles_in_window}
        if self.traced and self.device_traces:
            n = len(self.device_traces)
            device["busy_s"] = sum(t["busy_s"] for t in self.device_traces) / n
            device["window_s"] = sum(t["window_s"]
                                     for t in self.device_traces) / n
            line["breakdown"] = breakdown.build(self)
        # what was compared, each number beside its limit: last in the line
        line["probes"] = self.probe_result
        return line

    def end_to_end(self, done: list) -> dict:
        """Each end-to-end metric as a function, computed only where the
        cell reports it. A time to first token runs from when the request
        was due."""
        streamed = [r for r in done if r.first >= 0]

        def mean(values):
            if not values:
                raise Failed("no measured request completed")
            return sum(values) / len(values)
        return {
            "setup_s": lambda: self.setup_s,
            # from when a request was due to its last token, over every
            # completed request of the window: the one latency that a dozen
            # requests give steadily (PERF.md, PR 23)
            "answer_mean_ms": lambda: mean([(r.last - r.due) * 1000.0
                                            for r in streamed]),
            "out_tok_per_s": lambda: self.window_tokens / self.seconds,
        }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--rate", type=float, default=None,
                   help="another arrival rate (sources/s; in a closed loop "
                        "the number of clients) than the cell's file fixes: "
                        "the knob of the sweep that found that rate, "
                        "never passed by the driver")
    p.add_argument("--probes", type=int, choices=[0, 1], default=1,
                   help="0 leaves out the correctness probes and prints "
                        "correct: null: for a builder's sets of runs that "
                        "ask only how far a metric spreads, never passed "
                        "by the driver")
    p.add_argument("--tiny", action="store_true",
                   help="toy widths on the CPU backend: tests this "
                        "harness, never a measurement")
    args = p.parse_args()
    # first, so that the benchmark alone, without the program, fails
    if not os.path.isdir(os.path.join(REPO, "dynamo_tpu", "worker")):
        say("the program (dynamo_tpu/) is not in this checkout")
        return 1
    try:
        run = Run(args, load_benchmark())
        line = run.execute()
    except Failed as e:
        say(f"FAILED: {e}")
        return 1
    probes = line["probes"]
    if probes:
        # ... and last on standard error
        say(f"correct {line['correct']}: served_vs_reference_max_nats "
            f"{probes['served_vs_reference_max_nats']} (limit "
            f"{probes['reference_tol']}), served_vs_reference_mean_nats "
            f"{probes['served_vs_reference_mean_nats']} (limit "
            f"{probes['reference_mean_tol']}), cold_vs_cached_max_nats "
            f"{probes['cold_vs_cached_max_nats']} (limit "
            f"{probes['repeat_tol']}), {probes['logprobs_compared']} "
            "log-probabilities compared")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
