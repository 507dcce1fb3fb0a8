"""CPU tests of what PR 24 added to the benchmark: the reduction that lays
the step loop's ``loop.*`` annotations over the device's idle time
(``benchmarks/hostspans.py``), and the six per-layer readers of the ring's
device time, the profile's annotations, the queue spans and the ``startup``
trace - each on recorded data, and each on what a program without them
leaves behind (nothing to read: no value, no error)."""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import hostspans  # noqa: E402
from layer_metrics import reader  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes():
    """A small recorded trace with a host plane that carries the loop's
    annotations: a mixed step (seq 10) and a fused block (seq 11) on one
    device, window 1 s. Idle: [0.30, 0.40] between the two programs,
    0.5 ms inside the block, [0.95, 1.0] after it. The threaded phases
    (dispatch, fetch) are annotated twice under one name: on the loop's
    thread, and a little narrower on the worker thread."""
    with open(os.path.join(HERE, "recorded_loop_trace.json")) as f:
        return json.load(f)


def _without_annotations(planes):
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [
            e for e in ln["events"] if not e[0].startswith("loop.")]}
        for ln in p["lines"]]} for p in planes]


# ---------------------------------------------------------------- hostspans


def test_idle_time_is_cut_by_the_annotation_that_was_open():
    red = hostspans.reduce(_planes())
    assert red["window_s"] == pytest.approx(1.0)
    assert red["idle_s"] == pytest.approx(0.1505)
    by = red["idle_by_phase"]
    # the sync dispatch's tail + the fused block's enqueue (the two
    # annotations of one phase count once: their union); the unpack of
    # both; the planning; the fetch that waited over three holes; the wait
    # for a request; 2 ms between two phases that nothing covered; and the
    # 5 ms behind the last annotation the trace holds
    assert by["loop.dispatch"] == pytest.approx(0.046)
    assert by["loop.process"] == pytest.approx(0.036)
    assert by["loop.plan"] == pytest.approx(0.016)
    assert by["loop.fetch"] == pytest.approx(0.0205)
    assert by["loop.idle"] == pytest.approx(0.025)
    assert by[hostspans.NONE] == pytest.approx(0.002)
    assert by[hostspans.EDGE] == pytest.approx(0.005)
    assert sum(by.values()) == pytest.approx(red["idle_s"])
    assert red["overlap_s"] == 0.0
    assert hostspans.host_share(red) == pytest.approx(9.8)


def test_long_gaps_phases_and_seqs():
    red = hostspans.reduce(_planes())
    # only the holes of a millisecond and more, each split by what was open
    assert [round(g[1], 4) for g in red["long_gaps"]] == [0.1, 0.05]
    first, second = red["long_gaps"]
    assert first[2] == pytest.approx({
        "loop.dispatch": 0.046, "loop.process": 0.028, "loop.plan": 0.016,
        "loop.fetch": 0.008, hostspans.NONE: 0.002})
    assert second[2][hostspans.EDGE] == pytest.approx(0.005)
    assert hostspans.NONE not in second[2]
    assert sum(second[2].values()) == pytest.approx(0.05)
    # annotations are cut to the window and merged by name; the ring
    # numbers join the ring
    assert red["phases"]["loop.dispatch"] == [4, pytest.approx(0.346)]
    assert red["phases"]["loop.fetch"] == [2, pytest.approx(0.57)]
    assert red["seqs"] == [10, 11, 12]


def test_a_trace_without_annotations_reads_as_nothing():
    red = hostspans.reduce(_without_annotations(_planes()))
    assert red["phases"] == {} and red["idle_s"] == pytest.approx(0.1505)
    assert red["idle_by_phase"] == {hostspans.NONE: pytest.approx(0.1505)}
    assert hostspans.host_share(red) is None
    assert hostspans.host_share({"phases": {}}) is None


# ------------------------------------------------------------------ readers


def _record(seq, t_unix, kind, **kw):
    base = {"seq": seq, "t_unix": t_unix, "kind": kind, "width": 0,
            "compile_ms": 0.0}
    base.update(kw)
    return base


def _run(tmp_path, ring, traces=()):
    if traces is not None:
        with open(tmp_path / "worker0.traces.jsonl", "w") as f:
            for t in traces:
                f.write(json.dumps(t) + "\n")
    return types.SimpleNamespace(
        ring=[ring], t0_unix=100.0, seconds=50.0, run_dir=str(tmp_path),
        layout={"workers": [{}]}, device_traces=[])


RING = [
    # set-up: two first calls before the window
    _record(0, 40.0, "prefill", device_ms=9000.0, compile_ms=8000.0),
    _record(1, 60.0, "multistep", width=8, device_ms=5000.0,
            compile_ms=2500.0),
    # the window
    _record(2, 101.0, "mixed", device_ms=1500.0),
    _record(3, 103.0, "multistep", width=8, device_ms=2560.0),
    _record(4, 105.0, "mixed", device_ms=1600.0),
    _record(5, 107.0, "multistep", width=8, device_ms=2640.0),
    _record(6, 109.0, "decode", device_ms=300.0),
    _record(7, 111.0, "prefill", device_ms=900.0),
    # behind the window
    _record(8, 151.0, "multistep", width=8, device_ms=80000.0),
]
OLD_RING = [{k: v for k, v in r.items() if k != "device_ms"} for r in RING]


def test_device_time_per_step_from_the_ring(tmp_path):
    run = _run(tmp_path, RING)
    # per step: 320, 330 (the blocks of 8) and 300 (the single step)
    assert reader("step.decode_device_ms").compute(run) == \
        pytest.approx(320.0)
    # mixed 1500, 1600 and the prefill 900
    assert reader("step.mixed_device_ms").compute(run) == \
        pytest.approx(1500.0)
    old = _run(tmp_path, OLD_RING)
    assert reader("step.decode_device_ms").compute(old) is None
    assert reader("step.mixed_device_ms").compute(old) is None
    assert reader("step.mixed_device_ms").compute(_run(tmp_path, [])) is None


def test_first_calls_before_the_window(tmp_path):
    assert reader("setup.first_calls_s").compute(_run(tmp_path, RING)) == \
        pytest.approx(10.5)
    # the field is older than this PR: an older program's ring reads too
    assert reader("setup.first_calls_s").compute(
        _run(tmp_path, OLD_RING)) == pytest.approx(10.5)
    empty = _run(tmp_path, [])
    empty.ring = []                    # an untraced run pages no ring
    assert reader("setup.first_calls_s").compute(empty) is None


def _span(name, seconds):
    return {"name": name, "duration_s": seconds}


TRACES = [
    {"name": "startup", "start_unix": 20.0, "duration_s": 17.5, "spans": [
        _span("startup", 17.5), _span("startup.imports", 6.0),
        _span("startup.weights", 5.0), _span("startup.engine", 4.0),
        _span("startup.prime", 0.5), _span("startup.register", 1.5)]},
    # ended before the window, or ends behind it: not counted
    {"name": "worker.generate", "start_unix": 90.0, "duration_s": 9.0,
     "spans": [_span("queue", 100.0), _span("prefill", 1.0)]},
    {"name": "worker.generate", "start_unix": 140.0, "duration_s": 30.0,
     "spans": [_span("queue", 100.0), _span("decode", 1.0)]},
    # started long before the window and ended inside it: counted
    {"name": "worker.generate", "start_unix": 60.0, "duration_s": 50.0,
     "spans": [_span("queue", 40.0), _span("prefill", 2.0),
               _span("decode", 8.0)]},
    {"name": "worker.generate", "start_unix": 110.0, "duration_s": 12.0,
     "spans": [_span("worker.generate", 12.0), _span("queue", 6.0),
               _span("prefill", 2.0), _span("decode", 4.0)]},
    {"name": "worker.generate", "start_unix": 120.0, "duration_s": 8.0,
     "spans": [_span("queue", 3.0), _span("prefill", 1.0),
               _span("decode", 4.0)]},
]


def test_queue_wait_share_and_worker_ready(tmp_path):
    run = _run(tmp_path, RING, TRACES)
    assert reader("sched.queue_wait_share").compute(run) == \
        pytest.approx(100.0 * 49.0 / 70.0)
    assert reader("setup.worker_ready_s").compute(run) == \
        pytest.approx(17.5)
    # an older program: request traces, no startup trace; or no export
    old = _run(tmp_path, RING, TRACES[1:])
    assert reader("setup.worker_ready_s").compute(old) is None
    assert reader("sched.queue_wait_share").compute(old) == \
        pytest.approx(70.0)
    os.remove(tmp_path / "worker0.traces.jsonl")
    assert reader("sched.queue_wait_share").compute(old) is None
    assert reader("setup.worker_ready_s").compute(old) is None


@pytest.mark.parametrize("annotated", [True, False])
def test_idle_behind_host_share_reads_the_profile(tmp_path, monkeypatch,
                                                  annotated):
    """The reader runs ``hostspans.py`` on the trace directory in a child
    (the parent never imports jax); here the child is replaced by the
    reduction of the recorded planes."""
    planes = _planes() if annotated else _without_annotations(_planes())
    mod = reader("loop.idle_behind_host_share")
    seen = []

    def child(argv, **kw):
        seen.append(argv)
        assert kw["env"]["JAX_PLATFORMS"] == "cpu"
        return types.SimpleNamespace(
            returncode=0, stdout="noise\n" + json.dumps(
                hostspans.reduce(planes)) + "\n", stderr="")
    monkeypatch.setattr(mod.subprocess, "run", child)
    run = _run(tmp_path, RING)
    assert mod.compute(run) is None          # an untraced run: no profile
    run.device_traces = [{"mark": {"dir": "/somewhere/trace"}}]
    value = mod.compute(run)
    assert seen[0][1:] == [hostspans.__file__, "/somewhere/trace"]
    with open(tmp_path / "loop_phases.worker0.json") as f:
        table = json.load(f)
    assert table["idle_s"] == pytest.approx(0.1505)
    if annotated:
        assert value == pytest.approx(9.8)
        assert table["idle_by_phase"]["loop.fetch"] == pytest.approx(0.0205)
    else:
        assert value is None


def test_hostspans_reads_a_real_profile(tmp_path):
    """``read_planes`` on a profile jax writes here: the annotation's
    ``seq`` and ``kind`` come back with its name."""
    import subprocess
    script = (
        "import jax, jax.numpy as jnp, sys\n"
        "f = jax.jit(lambda x: x @ x)\n"
        "x = jnp.ones((64, 64)); f(x).block_until_ready()\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "with jax.profiler.TraceAnnotation('bench_slice'):\n"
        "    with jax.profiler.TraceAnnotation('loop.dispatch', seq=41,"
        " kind='mixed'):\n"
        "        f(x).block_until_ready()\n"
        "jax.profiler.stop_trace()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    out = subprocess.run([sys.executable, hostspans.__file__, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    red = json.loads(out.stdout.strip().splitlines()[-1])
    assert red["phases"]["loop.dispatch"][0] == 1
    assert red["seqs"] == [41]
    assert red["window_s"] > 0
