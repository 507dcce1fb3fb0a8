"""The ``joyai-llm-flash.reason`` cell: its files carry the parameters the
cell was defined with, its byte and operation counts are those of the
shapes, its readers read what the program writes and return nothing where a
program or a device does not write it, and a traced ``--tiny`` run goes
end to end through the served path with the expert layer's counts in the
ring."""

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

import modeldir  # noqa: E402
import moe_cost  # noqa: E402
import peaks  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CELL = "joyai-llm-flash.reason"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# as ``benchmarks/run.py`` selects them: an entry without a list is every
# cell's
METRICS = list(listed(BENCHMARK, "per_layer", CELL))
# what the cell brought of its own (ISSUE 34, 36, 39), by name
OWN = ["moe.experts_touched_share.reason", "kernel.moe_time_share.reason",
       "kernel.moe_roofline_share.reason", "kernel.mla_time_share.reason",
       "step.decode_hbm_share.reason", "step.mfu.reason",
       "loop.idle_in_assemble_share", "loop.idle_in_enqueue_share",
       "loop.idle_in_handover_share"]


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("reason")
    bench = modeldir.load_config("joyai-llm-flash")["bench"]
    rows = int(bench["worker_args"][bench["worker_args"].index(
        "--max-num-seqs") + 1])
    assert mix["loop"] == "closed" and cell["clients"] == rows == 16
    assert bench["worker_args"] == ["--max-num-seqs", "16", "--num-pages",
                                    "4096", "--attn-impl", "pallas",
                                    "--decode-multistep", "4"]
    assert "0.34 %" in bench["why_decode_multistep"]    # forced, and says so
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 512,
                                       "hi": 1536}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert (cell["segment_s"], cell["warm_segments"]) == (10, 2)
    assert cell["quiet_s"] == 0.02 and cell["stagger_s"] == 0.01
    assert cell["warm_requests"] >= 40
    gen = traffic.Generator(mix, cell, 129280, 3_000_000_019)
    seg = gen.segment(0, warm=False)
    outs = sorted(r.max_tokens for r in seg)
    assert len(seg) == 16 and len(set(outs)) == 16      # no wave of ends
    assert sum(outs) / 16 == 1024 and outs[0] >= 512 and outs[-1] <= 1536
    assert all(128 <= len(r.prompt) <= 512 for r in seg)
    assert max(len(r.prompt) + r.max_tokens for r in seg) <= 2048


def test_only_this_pr_lists_the_cell_and_no_metric_is_left_without_a_list():
    """The cell's own metrics are listed, and an entry that has a list
    lists cells that exist (one without is every cell's)."""
    assert set(OWN) <= set(METRICS)
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["per_layer"]:
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
    moved = {m["moves"] for m in BENCHMARK["per_layer"]
             if m["name"] in METRICS}
    assert moved == {"out_tok_per_s", "setup_s"}


def test_counts_from_shapes():
    hf = modeldir.load_config("joyai-llm-flash")["hf"]
    assert moe_cost.expert_params(hf) == 3 * 2048 * 768
    assert moe_cost.expert_slots(hf) == 4 * 256
    assert moe_cost.attention_params(hf) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048)
    # 16 rows x 8 choices touch 39 % of a layer's experts in expectation:
    # the step reads 4.7 GB where every expert would be 10.8 GB
    touched = 4 * 256 * (1 - (255 / 256) ** 128)
    step = moe_cost.decode_step_bytes(hf, "bfloat16", touched, 16 * 1024)
    assert 4.5e9 < step < 4.9e9
    assert 10.6e9 < moe_cost.decode_step_bytes(
        hf, "bfloat16", 4 * 256, 16 * 1024) < 10.9e9
    flops, nbytes = moe_cost.grouped_cost(hf, "bfloat16", touched, 4 * 128)
    assert flops == 2 * 4 * 128 * 3 * 2048 * 768
    assert nbytes / 819e9 > 100 * flops / 197e12        # bytes bound it


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config("joyai-llm-flash")
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 4096, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


def _record(**kw):
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 8, "rows": 16,
           "batch": 16, "running": 16, "pool_free": 4096 - 1024,
           "tokens_real": 128, "tokens_padded": 128,
           "device_ms": 64.0, "experts_touched": 8 * 400}
    rec.update(kw)
    return rec


def test_readers_read_the_ring_and_the_trace():
    ring = [_record(), _record(t_unix=120.0),
            _record(kind="mixed", width=0, experts_touched=900,
                    tokens_real=330, tokens_padded=16 * 512,
                    device_ms=40.0),
            _record(t_unix=10.0, experts_touched=5)]      # before the window
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.20,
             "ops": [["%moe_grouped.3 custom-call f32[2176,2048]{1,0} "
                      "[mosaic]", 0.10, 64],
                     ["%moe_grouped.7 custom-call f32[98304,2048]{1,0} "
                      "[mosaic]", 0.01, 4],
                     ["%mla_decode.1 custom-call f32[16,32,512]{2,1,0} "
                      "[mosaic]", 0.004, 80],
                     ["%mla_prefill.2 custom-call f32[16,512,32,512] "
                      "[mosaic]", 0.002, 5],
                     ["%fusion.9 fusion bf16[16,2048]", 0.05, 900]]}
    run = _run_stub(ring, [trace])
    share = reader("moe.experts_touched_share.reason").compute(run)
    assert share == pytest.approx(100 * (2 * 3200 + 900) / (17 * 1024))
    assert reader("kernel.moe_time_share.reason").compute(run) == \
        pytest.approx(55.0)
    assert reader("kernel.mla_time_share.reason").compute(run) == \
        pytest.approx(3.0)
    # two blocks of 8 steps touched 6,400 experts of 9.4 MB in 0.10 s
    roof = reader("kernel.moe_roofline_share.reason").compute(run)
    nbytes = 6400 * 3 * 2048 * 768 * 2 + 2 * 8 * 4 * 128 * 2048 * 6
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.10)
    assert 0 < roof <= 100
    hbm = reader("step.decode_hbm_share.reason").compute(run)
    hf = run.config["hf"]
    per_block = (8 * moe_cost.decode_step_bytes(hf, "bfloat16", 0, 16384)
                 + 3200 * moe_cost.expert_params(hf) * 2)
    assert hbm == pytest.approx(100 * 2 * per_block / 819e9 / 0.128)
    assert 0 < hbm <= 100


def test_the_whole_steps_share_of_the_peak_stands_beside_the_rooflines():
    """``step.mfu``: two FLOPs for every parameter a real token of the window
    meets (8 of 256 experts, not all), the projection for the tokens a decode
    dispatch samples, over the peak and the dispatches' device time."""
    hf = modeldir.load_config("joyai-llm-flash")["hf"]
    layers, head = peaks.active_params(hf)
    assert layers == (5 * moe_cost.attention_params(hf) + 3 * 2048 * 7168
                      + 4 * (2048 * 256 + 9 * 3 * 2048 * 768))
    assert head == 129280 * 2048
    qwen = modeldir.load_config("qwen3-4b")["hf"]
    assert sum(peaks.active_params(qwen)) * 2 == peaks.weight_bytes(
        qwen, "bfloat16")                  # dense: every matrix, every token
    ring = [_record(), _record(t_unix=120.0),
            _record(kind="mixed", width=0, tokens_real=330,
                    tokens_padded=16 * 512, device_ms=40.0),
            _record(t_unix=10.0)]                         # before the window
    for cell in ("reason", "batch"):
        mfu = reader(f"step.mfu.{cell}").compute(_run_stub(ring))
        assert mfu == pytest.approx(
            100 * (2 * layers * 586 + 2 * head * 256) / 197e12 / 0.168)
    assert 0 < mfu < 100
    assert reader("step.mfu.reason").compute(
        _run_stub(ring, platform="cpu")) is None
    assert reader("step.mfu.reason").compute(_run_stub([])) is None
    assert [m["moves"] for m in BENCHMARK["per_layer"]
            if m["name"].startswith("step.mfu.")] == ["out_tok_per_s"] * 2


def test_kernel_readers_survive_a_token_packed_step():
    """The slice above, its admission computed as a token-packed step of 256
    slots (a short prompt and 15 decode rows: 2,048 assignments, 6,144 rows
    at the kernel's tile, where a decode step's call has 2,176) by a packed
    latent-attention kernel: both readers still return a value, and the
    value they return on the padded slice."""
    assert moe_cost.grouped_rows(
        modeldir.load_config("joyai-llm-flash")["hf"], 16 * 8) == 2176
    ops = [["%moe_grouped.3 custom-call f32[2176,2048]{1,0} [mosaic]",
            0.10, 64],
           ["%mla_decode.1 custom-call f32[16,32,512]{2,1,0} [mosaic]",
            0.004, 80],
           ["%fusion.9 fusion bf16[16,2048]", 0.05, 900]]
    mark = {"start_unix": 105.0, "stop_unix": 125.0}
    padded = {"mark": mark, "busy_s": 0.20, "ops": ops + [
        ["%moe_grouped.7 custom-call f32[98304,2048]{1,0} [mosaic]",
         0.01, 4],
        ["%mla_prefill.2 custom-call f32[16,512,32,512] [mosaic]",
         0.002, 5]]}
    packed = {"mark": mark, "busy_s": 0.20, "ops": ops + [
        ["%moe_grouped.7 custom-call f32[6144,2048]{1,0} [mosaic]",
         0.01, 4],
        ["%mla_ragged.2 custom-call f32[256,32,512] [mosaic]", 0.002, 5]]}
    blocks = [_record(), _record(t_unix=120.0)]
    readings = []
    for trace, step in (
            (padded, _record(kind="mixed", width=0, experts_touched=900,
                             tokens_real=330, tokens_padded=16 * 512,
                             device_ms=40.0)),
            (packed, _record(kind="mixed", width=0, experts_touched=900,
                             tokens_real=143, tokens_padded=256,
                             device_ms=12.0))):
        run = _run_stub(blocks + [step], [trace])
        readings.append((
            reader("kernel.moe_roofline_share.reason").compute(run),
            reader("kernel.mla_time_share.reason").compute(run)))
    nbytes = 6400 * 3 * 2048 * 768 * 2 + 2 * 8 * 4 * 128 * 2048 * 6
    assert readings[0] == readings[1] == (
        pytest.approx(100 * nbytes / 819e9 / 0.10), pytest.approx(3.0))
    # a prefill-carrying step of as many slots as a decode step has rows
    # could not be told from one: nothing, rather than a share of both
    same = _run_stub(blocks + [_record(kind="prefill", width=0,
                                       tokens_padded=16)], [packed])
    assert reader("kernel.moe_roofline_share.reason").compute(same) is None


@pytest.mark.parametrize("metric", [
    "moe.experts_touched_share.reason", "kernel.moe_time_share.reason",
    "kernel.moe_roofline_share.reason", "kernel.mla_time_share.reason",
    "step.decode_hbm_share.reason"])
def test_readers_return_nothing_from_a_program_without_the_layer(metric):
    """The parent commit has no ``experts_touched`` in its ring and no
    ``moe_grouped`` in its trace: the line leaves the metric out."""
    old = _record()
    del old["experts_touched"]
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[16,2048]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None


def test_a_traced_tiny_run_reports_the_expert_layers_counts():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2500000007", "--seconds", "4", "--trace", "1", "--tiny"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if k != "JAX_COMPILATION_CACHE_DIR"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) <= set(METRICS)
    # 4 rows x top-2 of 8 experts: most of them, not all, every step
    touched = line["metrics"]["moe.experts_touched_share.reason"]["value"]
    assert 25.0 < touched <= 100.0
    assert "step.decode_device_ms" in line["metrics"]
    with open(os.path.join(BENCH, ".runs", CELL + "-tiny", "run.json")) as f:
        ring = json.load(f)["ring"][0]
    assert any(r["experts_touched"] for r in ring if r["kind"] == "multistep")
    assert all(r["experts_touched"] <= 2 * 8 * max(1, r["width"])
               for r in ring)
