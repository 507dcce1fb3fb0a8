"""The ``sdar-30b-a3b-chat.blockgen`` cell: its files carry the parameters
the cell was defined with, its byte and operation counts are those of the
shapes, its readers read what the program writes and return nothing where a
program or a device does not write it, and a ``--tiny`` run goes end to end
through the served path and is ``correct`` by the configuration's own rule -
and not by that rule read one pass off. No test here waits for a profiler
file: the tiny runs are untraced, the readers run on a recorded ring."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import blockgen_cost  # noqa: E402
import modeldir  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG, CELL = "sdar-30b-a3b-chat", "sdar-30b-a3b-chat.blockgen"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# as ``benchmarks/run.py`` selects them: an entry without a list is every
# cell's
METRICS = list(listed(BENCHMARK, "per_layer", CELL))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    hf = modeldir.load_config(CONFIG)["hf"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    assert hf["num_hidden_layers"] == 7 and hf["model_type"] == "sdar_moe"
    assert (hf["hidden_size"], hf["num_attention_heads"],
            hf["num_key_value_heads"], hf["head_dim"]) == (2048, 32, 4, 128)
    assert (hf["num_experts"], hf["num_experts_per_tok"],
            hf["moe_intermediate_size"], hf["vocab_size"]) == (
        128, 8, 768, 151936)
    assert (hf["block_size"], hf["mask_token_id"]) == (4, 151669)
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert {k: v for k, v in row["config"].items()
                if hf.get(k, "absent") != v} == {"num_hidden_layers": 48}


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("blockgen")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = bench["worker_args"]
    assert args[:10] == ["--max-num-seqs", "32", "--num-pages", "2048",
                         "--attn-impl", "pallas", "--denoising-steps", "2",
                         "--confidence-threshold", "0.9"]
    assert args[10] == "--decode-multistep" and args[12:] == [
        "--min-decode-bucket", "32", "--min-prefill-bucket", "1024",
        "--min-prefill-seqs-bucket", "8"]
    assert mix["loop"] == "closed" and cell["clients"] == 32
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert mix["output"]["tokens"] == {"dist": "const", "value": 256}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert (cell["layout"], cell["segment_s"], cell["warm_segments"]) == (
        "one-chip", 10, 2)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] <= 0.075
    # whole generations of 32 answers
    assert cell["warm_requests"] >= 128 and cell["warm_requests"] % 32 == 0
    assert bench["probe"] == {"extra": {}, "carry": ["reveal_pass"]}
    assert bench["reference"] == "sdar" and bench["dtype"] == "bfloat16"
    assert bench["probe_lengths"] == [47, 301, 1100, 1902]
    assert sorted(n % 4 for n in bench["probe_lengths"]) == [0, 1, 2, 3]
    assert bench["worker_env"] == {"DYN_LEASE_TTL": "60",
                                   "DYN_DECODE_PROGRESS": "16"}
    assert 0 < bench["reference_mean_tol"]["bfloat16"] < 0.3
    gen = traffic.Generator(mix, cell, 151936, 3_000_000_019)
    seg = gen.segment(0, warm=False)
    assert len(seg) == 32 and {r.max_tokens for r in seg} == {256}
    assert all(128 <= len(r.prompt) <= 512 for r in seg)
    # 32 rows of at most 768 tokens fit the pool
    assert 32 * (512 + 256) <= 2048 * 16


def test_only_this_pr_lists_the_cell_and_every_new_metric_has_its_list():
    """At least the cell's own stems: what carries its name lists it alone,
    and the quantities every cell reports reach it under their own."""
    ours = [m for m in BENCHMARK["per_layer"]
            if m["name"].endswith(".blockgen")]
    assert {m["name"] for m in ours} >= {
        "gen.tokens_per_pass.blockgen", "gen.commit_pass_share.blockgen",
        "step.decode_hbm_share.blockgen", "step.pass_mfu.blockgen",
        "moe.experts_touched_share.blockgen",
        "kernel.moe_time_share.blockgen",
        "kernel.moe_roofline_share.blockgen",
        "kernel.attn_time_share.blockgen"}
    assert all(m["workloads"] == [CELL] for m in ours)
    assert {m["name"] for m in ours} <= set(METRICS)
    assert set(METRICS) >= {
        "loop.host_gap_share", "loop.idle_behind_host_share",
        "sched.queue_wait_share", "step.decode_device_ms",
        "step.mixed_device_ms", "step.prefill_occupancy",
        "step.compiles_in_window", "loop.idle_in_assemble_share",
        "loop.idle_in_enqueue_share", "loop.idle_in_handover_share"}
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert {by_name[n]["moves"] for n in METRICS} == {"out_tok_per_s",
                                                      "setup_s"}
    assert {n for n in METRICS if by_name[n]["moves"] == "setup_s"} == {
        "setup.worker_ready_s", "setup.first_calls_s"}
    workload = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert workload["chips"] == 1 and workload["traffic"] == "blockgen"
    for name in METRICS:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", f"{name}.py")), name


def test_counts_from_shapes():
    hf = modeldir.load_config(CONFIG)["hf"]
    assert blockgen_cost.expert_params(hf) == 3 * 2048 * 768
    assert blockgen_cost.attention_params(hf) == (
        2 * 2048 * 4096 + 2 * 2048 * 512)
    assert blockgen_cost.expert_slots(hf) == 7 * 128
    layers, head = blockgen_cost.active_params(hf)
    assert layers == 7 * (18874368 + 2048 * 128 + 8 * 4718592)
    assert head == 151936 * 2048
    # a layer 623.1 M parameters, the whole cut 4.984 B (ISSUE 37)
    whole = (7 * (18874368 + 262144 + 128 * 4718592) + 2 * head)
    assert 4.98e9 < whole < 4.99e9
    assert blockgen_cost.grouped_rows(hf, 32 * 4 * 8) == 3072
    assert blockgen_cost.grouped_rows(hf, 1024 * 8) == (64 + 128) * 128
    # a pass over 32 rows reads every expert: 8.7 GB of layers, 0.6 of head
    fixed = blockgen_cost.pass_bytes(hf, "bfloat16", 32 * 512)
    experts = 7 * 128 * blockgen_cost.expert_bytes(hf, "bfloat16")
    assert 9.3e9 < fixed + experts < 9.6e9
    flops, nbytes = blockgen_cost.grouped_cost(hf, "bfloat16", 7 * 128,
                                               7 * 1024)
    assert flops == 2 * 7 * 1024 * 3 * 2048 * 768
    assert nbytes / 819e9 > 10 * flops / 197e12         # bytes bound it
    assert blockgen_cost.kv_bytes_per_token(hf, "bfloat16") == 14336


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 2048, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


def _passes(**kw):
    """One dispatch of three passes over 32 rows, as the ring records it."""
    rec = {"t_unix": 110.0, "kind": "multistep", "program": "passes3[32,4]",
           "width": 3, "rows": 32, "batch": 32, "running": 32,
           "pool_free": 2048 - 1024, "tokens_real": 384,
           "tokens_padded": 384, "device_ms": 45.0,
           "experts_touched": 3 * 7 * 128, "passes": 3, "row_passes": 96,
           "revealed": 128, "commits": 32, "gap_ms": 1.0, "unpack_ms": 0.5,
           "compile_ms": 0.0}
    rec.update(kw)
    return rec


def _prefill(**kw):
    return _passes(kind="prefill", program="packed[1024,8]", width=0,
                   rows=8, batch=8, tokens_real=1000, tokens_padded=1024,
                   device_ms=20.0, experts_touched=7 * 128, passes=0,
                   row_passes=0, revealed=0, commits=0, **kw)


def test_readers_read_the_ring_and_the_trace():
    ring = [_passes(), _passes(t_unix=120.0, row_passes=90, revealed=118,
                               commits=31, tokens_real=360),
            _prefill(t_unix=115.0), _passes(t_unix=10.0)]   # before the window
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.20,
             "ops": [["%moe_grouped.3 custom-call f32[3072,2048]{1,0} "
                      "[mosaic]", 0.09, 42],
                     ["%moe_grouped.7 custom-call f32[24576,2048]{1,0} "
                      "[mosaic]", 0.01, 7],
                     ["%paged_prefill.1 custom-call bf16[32,4,32,128] "
                      "[mosaic]", 0.004, 42],
                     ["%ragged_mixed.2 custom-call bf16[1024,32,128] "
                      "[mosaic]", 0.002, 7],
                     ["%fusion.9 fusion bf16[128,2048]", 0.05, 900]]}
    run = _run_stub(ring, [trace])
    hf = run.config["hf"]
    assert reader("gen.tokens_per_pass.blockgen").compute(run) == \
        pytest.approx(246 / 186)
    assert reader("gen.commit_pass_share.blockgen").compute(run) == \
        pytest.approx(100 * 63 / 186)
    assert reader("step.decode_device_ms").compute(run) == 15.0
    assert reader("step.mixed_device_ms").compute(run) == 20.0
    assert reader("step.prefill_occupancy").compute(run) == \
        pytest.approx(100 * 1000 / 1024)
    assert reader("moe.experts_touched_share.blockgen").compute(run) == \
        pytest.approx(100.0)
    assert reader("kernel.moe_time_share.blockgen").compute(run) == \
        pytest.approx(50.0)
    assert reader("kernel.attn_time_share.blockgen").compute(run) == \
        pytest.approx(3.0)
    layers, head = blockgen_cost.active_params(hf)
    mfu = reader("step.pass_mfu.blockgen").compute(run)
    assert mfu == pytest.approx(
        100 * (2 * layers * (384 + 360 + 1000) + 2 * head * (384 + 360))
        / 197e12 / 0.110)
    assert 0 < mfu < 100
    hbm = reader("step.decode_hbm_share.blockgen").compute(run)
    per = (3 * blockgen_cost.pass_bytes(hf, "bfloat16", 1024 * 16)
           + 3 * 7 * 128 * blockgen_cost.expert_bytes(hf, "bfloat16"))
    assert hbm == pytest.approx(100 * 2 * per / 819e9 / 0.090)
    assert 0 < hbm <= 100
    # the passes' calls by their row count (3,072), not the prefill's
    roof = reader("kernel.moe_roofline_share.blockgen").compute(run)
    nbytes = (2 * 3 * 7 * 128 * 3 * 2048 * 768 * 2
              + 186 * 4 * 8 * 7 * 2048 * 6)
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.09)
    assert 0 < roof <= 100
    # the generic readers under the cell's name read the same ring
    assert reader("loop.host_gap_share").compute(run) is not None
    assert reader("step.compiles_in_window").compute is not None


@pytest.mark.parametrize("metric", [
    "gen.tokens_per_pass.blockgen", "gen.commit_pass_share.blockgen",
    "step.pass_mfu.blockgen", "step.decode_hbm_share.blockgen",
    "moe.experts_touched_share.blockgen",
    "kernel.moe_roofline_share.blockgen", "kernel.moe_time_share.blockgen",
    "kernel.attn_time_share.blockgen"])
def test_readers_return_nothing_from_a_program_without_the_passes(metric):
    """The parent commit's ring has no ``row_passes`` (and it cannot serve
    this configuration at all): the line leaves the metric out."""
    old = {k: v for k, v in _passes().items()
           if k not in ("passes", "row_passes", "revealed", "commits",
                        "experts_touched")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[16,2048]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None
    assert reader(metric).compute(
        _run_stub([old], [trace], platform="cpu")) is None


def _tiny_run(cwd: str):
    out = subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "4",
         "--trace", "0", "--tiny"],
        cwd=cwd, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if k != "JAX_COMPILATION_CACHE_DIR"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_tiny_run_is_correct_by_the_configurations_own_rule():
    line = _tiny_run(REPO)
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_per_s", "setup_s"}
    probes = line["probes"]
    assert probes["logprobs_compared"] == 768
    assert probes["probe_lengths"] == [11, 41, 72, 150]
    # float32 at toy widths: summation order against the limit of 2e-3
    assert probes["served_vs_reference_max_nats"] < 1e-4, probes
    assert probes["cold_vs_cached_max_nats"] < 1e-4, probes
    # the child scored by what each token carried: the reveal passes are in
    # the reference cache's keys, and both branches of the rule ran
    with open(os.path.join(BENCH, ".cache", "reference",
                           f"{CONFIG}-tiny.json")) as f:
        assert len(json.load(f)) >= 4


def test_the_rule_read_one_pass_off_is_not_correct(tmp_path):
    """The same run against a copy of the reference whose ``score`` replays
    every token one pass late: the block it then feeds holds tokens the
    serving pass had not seen (the token itself among them), and the run is
    not correct."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    path = root / "benchmarks/reference/sdar.py"
    src = path.read_text()
    # the block as it stood AFTER the pass that revealed the token
    shifted = src.replace("reveal_pass[at - P] < p)",
                          "reveal_pass[at - P] <= p)")
    assert shifted != src
    path.write_text(shifted)
    line = _tiny_run(str(root))
    assert line["correct"] is False and line["failed"] == 0, line
    assert line["probes"]["served_vs_reference_max_nats"] > 0.01
