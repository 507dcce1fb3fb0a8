"""CPU tests of what PR 53 added to the benchmark: the reduction of a device
trace by the program's stages (``benchmarks/scopespans.py``) on a recorded
trace - plain data WITH event metadata, a dozen operations over two
dispatches, one of them the compiler's own, one a loop - and the seven
``stage.*_time_share`` readers of it through a real ``.xplane.pb``."""

import copy
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import scopespans  # noqa: E402
import xplane  # noqa: E402
from layer_metrics import reader  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the program's table as a worker ships it, as far as the trace needs it
STAGES = ("mixer_in:layer.attn_in,layer.gdn_in;"
          "cache_write:layer.kv_write,layer.gdn_in/conv_write;"
          "mixer:layer.attn,layer.gdn;mixer_out:layer.attn_out;"
          "ffn:layer.moe,layer.moe/route,layer.moe/experts;"
          "around_layers:embed,logits,sample,sample/top_candidates,"
          "step.chain")
# device seconds by hand (the docstring of ``_planes``)
BY_HAND = {"mixer_in": 0.04, "cache_write": 0.06, "mixer": 0.40,
           "mixer_out": 0.03, "ffn": 0.18, "around_layers": 0.15,
           "unnamed": 0.05}
BUSY = 0.91


def _planes():
    """One device, window [1.0, 2.0] s. A packed step
    (``jit__packed_step_impl``, [0.950, 1.500]): embed [0.950, 1.050] (half
    of it before the window), a ``while`` [1.050, 1.450] around
    ``layer.attn_in`` 40 ms, ``layer.kv_write`` 10, the ``ragged_mixed``
    kernel 100, ``layer.attn_out`` 30, the ``moe_grouped`` kernel under
    ``layer.moe/experts`` 160 and ``layer.moe/route`` 20 - the loop's first
    10 ms and last 30 under none of its operations - then ``logits`` 40 and
    a ``copy`` of a weight stack with no ``tf_op`` 10. A decode block
    (``jit__unknown``, [1.550, 1.950]): ``sample/top_candidates`` 50, the
    ``gdn_step`` kernel 300, ``layer.gdn_in/conv_write`` 50. And the
    hand-over's gather under ``step.chain`` [1.960, 1.970], in no recorded
    module. Busy 0.5 + 0.4 + 0.01 s."""
    with open(os.path.join(HERE, "recorded_stage_trace.json")) as f:
        return json.load(f)


def _table():
    return scopespans.reduce(_planes(), scopespans.parse_stages(STAGES))


def test_the_seven_groups_partition_the_busy_time():
    red = _table()
    assert red["busy_s"] == pytest.approx(BUSY)
    assert red["busy_s"] == pytest.approx(
        xplane.reduce(_planes())["busy_s"])
    for group, seconds in BY_HAND.items():
        assert red["groups"][group] == pytest.approx(seconds), group
        assert scopespans.share_of(red, group) == pytest.approx(
            100.0 * seconds / BUSY), group
    assert sum(red["shares"].values()) == pytest.approx(100.0)
    assert red["partition_error"] < 1e-9
    assert set(red["shares"]) == set(scopespans.GROUPS) | {"unnamed"}


def test_a_loop_is_not_counted_and_its_holes_are_unnamed():
    red = _table()
    assert not any("while" in st for st in red["stages"])
    assert sum(d["seconds"] for d in red["stages"].values()) \
        == pytest.approx(BUSY - 0.04)
    assert red["between_ops_s"] == pytest.approx(0.04)
    assert red["unnamed"][0][:2] == [scopespans.BETWEEN,
                                     pytest.approx(0.04)]


def test_an_operation_half_outside_the_slice_is_cut():
    red = _table()
    assert red["stages"]["embed"]["seconds"] == pytest.approx(0.05)
    assert red["modules"]["jit__packed_step_impl"]["calls"] == 1


def test_a_stage_is_the_longest_registered_path_through_the_tf_op():
    red = _table()
    stages = red["stages"]
    # a child across ``jit(...)`` and the kernel's own names; a trailing
    # colon; transforms between the program's name and the stage
    assert stages["layer.moe/experts"]["seconds"] == pytest.approx(0.16)
    assert stages["layer.moe/route"]["seconds"] == pytest.approx(0.02)
    assert stages["sample/top_candidates"]["group"] == "around_layers"
    assert stages["layer.gdn_in/conv_write"]["group"] == "cache_write"
    assert stages["layer.gdn"]["categories"] == {
        "custom-call": pytest.approx(0.3)}
    table = scopespans.parse_stages(STAGES)
    assert scopespans.stage_of("jit(f)/while/body/add", table) is None
    assert scopespans.stage_of(
        "jit(f)/layer.attn/index/score/dot_general",
        {"layer.attn": "mixer", "layer.attn/index/score": "mixer"}) \
        == "layer.attn/index/score"
    assert scopespans.stage_of(
        "jit(f)/layer.attn/index/iota",
        {"layer.attn": "mixer", "layer.attn/index/score": "mixer"}) \
        == "layer.attn"


def test_the_table_is_by_dispatch_with_milliseconds_a_call():
    mods = _table()["modules"]
    assert list(mods) == ["jit__packed_step_impl", "jit__unknown", "other"]
    packed = mods["jit__packed_step_impl"]
    assert packed["seconds"] == pytest.approx(0.46)
    assert packed["stages"]["layer.attn"]["ms_per_call"] \
        == pytest.approx(100.0)
    assert mods["jit__unknown"]["stages"]["layer.gdn"]["seconds"] \
        == pytest.approx(0.3)
    assert mods["other"]["stages"] == {
        "step.chain": {"seconds": pytest.approx(0.01),
                       "ms_per_call": None}}


def test_a_trace_without_metadata_gives_nothing():
    bare = copy.deepcopy(_planes())
    for plane in bare:
        plane.pop("metadata", None)
        for ln in plane["lines"]:
            ln.pop("meta", None)
    table = scopespans.parse_stages(STAGES)
    assert scopespans.reduce(bare, table) is None
    # metadata entries that name no scope at all (the CPU backend's)
    unscoped = copy.deepcopy(_planes())
    for md in unscoped[1]["metadata"].values():
        md["tf_op"] = ""
    assert scopespans.reduce(unscoped, table) is None
    assert scopespans.share_of(None, "mixer") is None


def test_shares_that_do_not_add_up_are_not_reported():
    red = _table()
    red["partition_error"] = 0.6
    assert scopespans.share_of(red, "mixer") is None


# ---- the readers, through a real .xplane.pb --------------------------------

def _write_xplane(planes, path):
    """The recorded planes as the protobuf a profiler writes."""
    space = scopespans._messages()()
    stats = {"tf_op": (1, "tf_op"), "hlo_category": (2, "category"),
             "source": (3, "source")}
    for plane in planes:
        xp = space.planes.add(name=plane["name"])
        for name, (sid, _key) in stats.items():
            entry = xp.stat_metadata.add(key=sid)
            entry.value.id, entry.value.name = sid, name
        ids = {}        # event name -> metadata id
        for ln in plane["lines"]:
            line = xp.lines.add(name=ln["name"], timestamp_ns=0)
            # a recorded id where the line has them, else one a name
            given = ln.get("meta") or [None] * len(ln["events"])
            for (name, start, dur), mid in zip(ln["events"], given):
                ids[name] = mid = mid or ids.get(name) or 1000 + len(ids)
                line.events.add(metadata_id=mid, offset_ps=start * 1000,
                                duration_ps=dur * 1000)
        for name, mid in ids.items():
            entry = xp.event_metadata.add(key=mid)
            entry.value.id, entry.value.name = mid, name
            md = plane.get("metadata", {}).get(str(mid), {})
            for sid, key in stats.values():
                if md.get(key):
                    entry.value.stats.add(metadata_id=sid,
                                          str_value=md[key])
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def _run(tmp_path, stages=STAGES):
    trace = tmp_path / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    _write_xplane(_planes(), str(trace / "host.xplane.pb"))
    spans = [{"name": "startup.engine",
              "attrs": {"prefill.form": "packed",
                        **({"stages": stages} if stages else {})}}]
    with open(tmp_path / "worker0.traces.jsonl", "w") as f:
        f.write(json.dumps({"name": "startup", "spans": spans}) + "\n")
    return types.SimpleNamespace(
        run_dir=str(tmp_path),
        device_traces=[{"busy_s": BUSY,
                        "mark": {"dir": str(tmp_path / "trace")}}])


def test_the_protobuf_reader_reads_what_was_recorded(tmp_path):
    run = _run(tmp_path)
    planes = scopespans.read_planes(run.device_traces[0]["mark"]["dir"])
    want = _planes()
    assert [p["name"] for p in planes] == [p["name"] for p in want]
    dev = planes[1]
    assert [[list(e) for e in ln["events"]] for ln in dev["lines"]] \
        == [ln["events"] for ln in want[1]["lines"]]
    assert dev["metadata"][5] == want[1]["metadata"]["5"]
    assert dev["metadata"][10]["tf_op"] == ""
    assert scopespans.reduce(planes, scopespans.parse_stages(STAGES))[
        "groups"] == pytest.approx(BY_HAND)


@pytest.mark.parametrize("group", sorted(BY_HAND))
def test_each_reader_reports_its_groups_share(tmp_path, group):
    run = _run(tmp_path)
    value = reader(f"stage.{group}_time_share").compute(run)
    assert value == pytest.approx(100.0 * BY_HAND[group] / BUSY)
    with open(tmp_path / "stage_times.worker0.json") as f:
        table = json.load(f)
    # the whole table is left beside the run, the unnamed operation with
    # the line of the program it came from
    copies = [u for u in table["unnamed"] if u[0].startswith("%copy.653")]
    assert copies and copies[0][4] == "dynamo_tpu/models/dots3.py:612"
    assert table["reduce_s"] < 60.0
    # ... and the later readers of the same run find it there
    os.remove(tmp_path / "worker0.traces.jsonl")
    assert reader("stage.mixer_time_share").compute(run) \
        == pytest.approx(100.0 * BY_HAND["mixer"] / BUSY)


def test_an_older_program_ships_no_table_and_the_readers_say_nothing(
        tmp_path):
    run = _run(tmp_path, stages=None)
    assert reader("stage.unnamed_time_share").compute(run) is None
    assert not os.path.exists(tmp_path / "stage_times.worker0.json")


def test_the_operators_tool_prints_the_same_table(tmp_path):
    """``tools/xplane_scopes.py --by-stage``: the same reduction against
    the program's own table."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import xplane_scopes
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
    run = _run(tmp_path)
    text = "\n".join(xplane_scopes.by_stage(
        run.device_traces[0]["mark"]["dir"], top=3))
    assert "busy 0.9100 s of a window of 1.0000 s" in text
    assert "   0.4000 s  43.96 %  mixer" in text
    assert "jit__packed_step_impl: 1 dispatches" in text
    assert "100.000 ms a dispatch  layer.attn" in text
    assert "%copy.653 copy bf16[2,3,1024,20480]{2,3,1,0}  [data formatting]" \
        "  (dynamo_tpu/models/dots3.py:612)" in text
