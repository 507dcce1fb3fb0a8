"""CPU tests of what PR 39 added to the benchmark: the reduction that takes
the ``loop.dispatch`` and ``loop.fetch`` phases apart by the twins of each
annotation and the ``dispatch.<stage>`` annotations inside the call
(``benchmarks/dispatchspans.py``), and the three per-layer readers of it -
each on a recorded trace of its own, on what a program without the stages
leaves behind (nothing to read: no value, no error), and once on a profile
jax writes here around the program's own stamping helper."""

import copy
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import dispatchspans  # noqa: E402
import hostspans  # noqa: E402
from layer_metrics import reader  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
QUANTITIES = ("assemble", "enqueue", "handover")
CELLS = {"batch": "qwen3-4b.batch", "reason": "joyai-llm-flash.reason",
         "blockgen": "sdar-30b-a3b-chat.blockgen"}
# the device's idle seconds by hand (the recorded trace's docstring)
BY_HAND = {"assemble": 2.4, "enqueue": 2.1, "handover": 1.4}


def _planes():
    """A synchronous token-packed step (seq 20, ``mixed``) and the
    unchained fused block behind it (seq 21, ``multistep``) on one device,
    window [1.0, 2.0] s. The device runs [1.030, 1.320] and [1.394, 1.950]
    but for 0.5 ms at 1.700: it idles 30 ms before the step's program
    starts, 74 ms between the two programs, and 50 ms behind the block.
    Loop's thread (``MainThread``): plan 20 [1.000, 1.010], dispatch 20
    [1.010, 1.330], process [1.330, 1.350], plan 21 [1.350, 1.360],
    dispatch 21 [1.360, 1.400], fetch 21 [1.400, 1.960], process [1.960,
    1.970], idle. Worker thread (``asyncio_0``): dispatch 20 [1.012,
    1.326] with assemble [1.013, 1.023], upload [1.023, 1.027], enqueue
    [1.028, 1.032], wait [1.032, 1.325]; dispatch 21 [1.363, 1.396] with
    assemble [1.364, 1.370] and [1.373, 1.381] (the table, then the
    sampling arrays), upload [1.370, 1.373] and [1.381, 1.389], enqueue
    [1.390, 1.395]; fetch 21 [1.402, 1.955]."""
    with open(os.path.join(HERE, "recorded_dispatch_trace.json")) as f:
        return json.load(f)


def _without_stages(planes):
    """The same profile from a program that has the twins and no stages."""
    planes = copy.deepcopy(planes)
    for p in planes:
        for ln in p["lines"]:
            ln.pop("dispatch", None)
            ln["events"] = [e for e in ln["events"]
                            if not e[0].startswith("dispatch.")]
    return planes


# ------------------------------------------------------------ dispatchspans


@pytest.mark.parametrize("which", QUANTITIES)
def test_each_share_by_hand(which):
    red = dispatchspans.reduce(_planes())
    assert red["window_s"] == pytest.approx(1.0)
    assert dispatchspans.share_of(red, which) == pytest.approx(
        BY_HAND[which])


@pytest.mark.parametrize("phase", dispatchspans.PHASES)
def test_the_parts_add_up_to_hostspans_idle_under_the_phase(phase):
    """Both cut the same gaps of the same planes: the parts of a phase,
    ``other`` included, partition the union of its two annotations."""
    planes = _planes()
    red = dispatchspans.reduce(planes)
    whole = hostspans.reduce(planes)
    table = red["phases"][phase]
    assert set(table["idle_by_part"]) <= set(dispatchspans.PARTS)
    assert sum(table["idle_by_part"].values()) == pytest.approx(
        whole["idle_by_phase"][phase])
    assert table["idle_s"] == pytest.approx(whole["idle_by_phase"][phase])
    # and again kind by kind
    assert sum(v for by in table["idle_by_kind"].values()
               for v in by.values()) == pytest.approx(table["idle_s"])
    assert red["idle_s"] == pytest.approx(whole["idle_s"])
    assert red["overlap_s"] == 0.0


def test_the_table_by_part_and_by_kind():
    red = dispatchspans.reduce(_planes())
    dispatch = red["phases"]["loop.dispatch"]
    assert dispatch["idle_by_part"] == pytest.approx({
        "handover": 0.005, "assemble": 0.024, "upload": 0.015,
        "enqueue": 0.006, "wait": 0.005, "resume": 0.004, "other": 0.005})
    # the step's program started 2 ms before its enqueue returned, and
    # the device idled 5 ms of the wait (the copy back)
    assert dispatch["idle_by_kind"]["mixed"] == pytest.approx({
        "handover": 0.002, "assemble": 0.010, "upload": 0.004,
        "enqueue": 0.002, "wait": 0.005, "resume": 0.004, "other": 0.003})
    # an asynchronous kind has no wait, and its resume found the device
    # at work
    assert dispatch["idle_by_kind"]["multistep"] == pytest.approx({
        "handover": 0.003, "assemble": 0.014, "upload": 0.011,
        "enqueue": 0.004, "other": 0.002})
    # a fetch has no stages: the hole inside the block and the copy back
    # are its call's own, the 5 ms after it the event loop's
    assert red["phases"]["loop.fetch"]["idle_by_part"] == pytest.approx({
        "other": 0.0055, "resume": 0.005})
    # annotations counted and seconds open; a stage opens more than once
    assert red["stages"] == {
        "assemble": [3, pytest.approx(0.024)],
        "upload": [3, pytest.approx(0.015)],
        "enqueue": [2, pytest.approx(0.009)],
        "wait": [1, pytest.approx(0.293)]}
    assert red["enqueues"] == {"0": 0, "1": 2, "more": 0}
    assert red["seqs"] == [20, 21]


def test_a_trace_without_stages_reads_as_nothing():
    planes = _without_stages(_planes())
    red = dispatchspans.reduce(planes)
    assert red == {"stages": {}}
    for which in QUANTITIES:
        assert dispatchspans.share_of(red, which) is None
    # the older reader still reads the same planes as before
    assert hostspans.reduce(planes)["idle_by_phase"][
        "loop.dispatch"] == pytest.approx(0.064)


def test_a_call_whose_loop_side_was_lost_at_the_profiles_edge():
    """An annotation in flight when the profile starts is lost whole: the
    worker thread's twin alone still has its stages, and nothing is
    called hand-over or resume."""
    planes = _planes()
    for ln in planes[0]["lines"]:
        if ln["name"] == "MainThread":
            ln["loop"] = [e for e in ln["loop"]
                          if not (e[0] == "loop.dispatch" and e[3] == 20)]
    by = dispatchspans.reduce(planes)["phases"]["loop.dispatch"][
        "idle_by_kind"]["mixed"]
    assert by == pytest.approx({
        "assemble": 0.010, "upload": 0.004, "enqueue": 0.002,
        "wait": 0.005, "other": 0.003})


def test_overlapping_stages_count_once():
    planes = _planes()
    for ln in planes[0]["lines"]:
        if ln["name"] == "asyncio_0":
            # an upload reported inside the first assemble of seq 20
            ln["dispatch"].append(
                ["dispatch.upload", 1_015_000_000, 4_000_000, 20, "mixed"])
    planes_red = dispatchspans.reduce(planes)
    by = planes_red["phases"]["loop.dispatch"]["idle_by_kind"]["mixed"]
    assert by["assemble"] == pytest.approx(0.010)
    assert by["upload"] == pytest.approx(0.004)
    assert planes_red["phases"]["loop.dispatch"]["idle_s"] == pytest.approx(
        0.064)


# ------------------------------------------------------------------ readers


def _run(tmp_path, traces=()):
    return types.SimpleNamespace(run_dir=str(tmp_path),
                                 device_traces=list(traces))


@pytest.mark.parametrize("staged", [True, False])
def test_the_readers_run_one_child_and_leave_the_table(tmp_path,
                                                       monkeypatch, staged):
    """The first reader of a run runs ``dispatchspans.py`` on the trace
    directory in a child (the parent never imports jax) and leaves the
    table in the run directory; the others find it there. Here the child
    is replaced by the reduction of the recorded planes."""
    planes = _planes() if staged else _without_stages(_planes())
    seen = []

    def child(argv, **kw):
        seen.append(argv)
        assert kw["env"]["JAX_PLATFORMS"] == "cpu"
        return types.SimpleNamespace(
            returncode=0, stdout="noise\n" + json.dumps(
                dispatchspans.reduce(planes)) + "\n", stderr="")
    monkeypatch.setattr(dispatchspans.subprocess, "run", child)
    for which in QUANTITIES:        # an untraced run: no profile
        assert reader(f"loop.idle_in_{which}_share").compute(
            _run(tmp_path)) is None
    assert not seen
    run = _run(tmp_path, [{"mark": {"dir": "/somewhere/trace"}}])
    for which in QUANTITIES:
        value = reader(f"loop.idle_in_{which}_share").compute(run)
        if staged:
            assert value == pytest.approx(BY_HAND[which])
        else:
            assert value is None
    assert [a[1:] for a in seen] == [
        [dispatchspans.__file__, "/somewhere/trace"]]
    with open(tmp_path / "dispatch_phases.worker0.json") as f:
        table = json.load(f)
    assert bool(table["stages"]) is staged


def test_a_child_that_fails_gives_no_value(tmp_path, monkeypatch):
    monkeypatch.setattr(
        dispatchspans.subprocess, "run",
        lambda argv, **kw: types.SimpleNamespace(
            returncode=1, stdout="", stderr="no .xplane.pb"))
    run = _run(tmp_path, [{"mark": {"dir": "/somewhere/trace"}}])
    assert reader("loop.idle_in_assemble_share").compute(run) is None
    assert not os.path.exists(tmp_path / "dispatch_phases.worker0.json")


@pytest.mark.parametrize("which", QUANTITIES)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_stem_is_listed_once_and_lists_the_cell(tmp_path, which, cell):
    name = f"loop.idle_in_{which}_share"
    with open(tmp_path / "dispatch_phases.worker0.json", "w") as f:
        json.dump(dispatchspans.reduce(_planes()), f)
    run = _run(tmp_path, [{"mark": {"dir": "/nowhere"}}])
    assert reader(name).compute(run) == pytest.approx(BY_HAND[which])
    # and the benchmark lists it once, under its own name, in the three
    # cells that came with it, as a share of the step loop's layer that
    # moves the tokens per second
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"].startswith(name)]
    same_layer = next(m for m in bench["per_layer"]
                      if m["name"] == "loop.idle_behind_host_share")
    assert entry == dict(same_layer, name=name,
                         workloads=list(CELLS.values()))
    assert CELLS[cell] in entry["workloads"]


def test_dispatchspans_reads_a_real_profile(tmp_path):
    """A profile jax writes here around the program's own helper: a
    threaded ``dispatch`` phase whose call marks its stages. Their ``seq``
    comes back, the twins pair, and the dispatch has its one enqueue."""
    script = (
        "import asyncio, sys, time\n"
        "import jax, jax.numpy as jnp\n"
        "from dynamo_tpu.engine.steptrace import StepRecorder, stage\n"
        "f = jax.jit(lambda x: x @ x)\n"
        "x = jnp.ones((64, 64)); f(x).block_until_ready()\n"
        "def call():\n"
        "    with stage('assemble'):\n"
        "        time.sleep(0.002)\n"
        "    with stage('upload'):\n"
        "        y = jnp.asarray(x)\n"
        "    with stage('enqueue'):\n"
        "        out = f(y)\n"
        "    with stage('wait'):\n"
        "        out.block_until_ready()\n"
        "async def main():\n"
        "    rec = StepRecorder(capacity=8)\n"
        "    with rec.phase('plan', 41):\n"
        "        pass\n"
        "    ph = rec.phase('dispatch', 41, 'mixed')\n"
        "    await ph.in_thread(call)\n"
        "    r = rec.record('mixed', dispatch_ms=ph.ms, phase=ph)\n"
        "    assert r.assemble_ms >= 2.0 and r.enqueue_ms > 0.0\n"
        "jax.profiler.start_trace(sys.argv[1])\n"
        "with jax.profiler.TraceAnnotation('bench_slice'):\n"
        "    asyncio.run(main())\n"
        "jax.profiler.stop_trace()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    out = subprocess.run(
        [sys.executable, dispatchspans.__file__, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    red = json.loads(out.stdout.strip().splitlines()[-1])
    assert red["seqs"] == [41]
    assert {k: v[0] for k, v in red["stages"].items()} == {
        "assemble": 1, "upload": 1, "enqueue": 1, "wait": 1}
    assert red["stages"]["assemble"][1] >= 0.002
    assert red["enqueues"] == {"0": 0, "1": 1, "more": 0}
    assert red["window_s"] > 0
    for which in QUANTITIES:
        assert dispatchspans.share_of(red, which) is not None
