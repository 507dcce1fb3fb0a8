"""The ``longcat-flash-omni.turns`` cell: its configuration is the catalog's
row cut in three named keys, its files carry the parameters the cell was
defined with, ``longcat_cost`` counts from shapes, its readers read what the
program writes and return nothing where a program does not write it, and a
``--tiny`` run goes end to end through the served path - correct as the
program stands, not correct with a fault planted in a copy of it."""

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

import longcat_cost  # noqa: E402
import modeldir  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG, CELL = "longcat-flash-omni", "longcat-flash-omni.turns"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# the metrics ISSUE 41 names for the cell (a later PR may list it under more)
NEW = ["step.rank_mfu", "step.decode_hbm_share", "kernel.moe_roofline_share",
       "kernel.moe_time_share", "kernel.mla_time_share",
       "moe.experts_touched_share", "moe.zero_pick_share",
       "moe.held_pick_share", "step.decode_device_ms",
       "step.mixed_device_ms", "step.prefill_occupancy",
       "step.compiles_in_window", "loop.host_gap_share",
       "loop.idle_behind_host_share", "sched.queue_wait_share",
       "setup.worker_ready_s", "setup.first_calls_s"]



def _args(bench):
    a = bench["worker_args"]
    return {a[i]: a[i + 1] for i in range(0, len(a), 2)}


def test_the_configuration_is_the_catalogs_row_cut_in_three_keys():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert sorted(bench["reduced"]) == sorted(entry["reduced"])
    assert bench["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                  "vocab_size": 131072}
    assert (raw["num_layers"], raw["n_routed_experts"],
            raw["vocab_size"]) == (4, 16, 16384)
    assert (raw["ep_size"], raw["ep_rank"]) == (32, 0)   # 16 x 32 = 512
    assert "32 chips share each layer" in bench["deployment"]
    assert bench["reference"] == "longcat" and "probe" not in bench
    for key in ("left_out", "assumed", "memory", "reference_mean_tol",
                "why_reference_mean_tol", "tiny"):
        assert bench[key], key
    assert sum("as remembered from the release" in a
               for a in bench["assumed"]) >= 5
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        changed = {k for k, v in row["config"].items() if raw.get(k) != v}
        assert changed == set(entry["reduced"])          # every width as is
        for key in changed:
            assert bench["published"][key] == row["config"][key]


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("turns")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = _args(bench)
    rows = int(args["--max-num-seqs"])
    assert mix["loop"] == "closed" and cell["clients"] == rows == 128
    assert args["--attn-impl"] == "pallas" and args["--num-pages"] == "8192"
    # a burst every <= 75 ms: the fused block's width is measured, and said
    assert int(args["--decode-multistep"]) in (2, 3)
    assert "ms" in bench["why_worker_args"]
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 256,
                                       "hi": 768}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert mix["lifetime_s"] == 0 and mix["who"] and mix["tiny"]
    assert (cell["layout"], cell["segment_s"], cell["warm_segments"]) == (
        "one-chip", 10, 2)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] < 0.075
    assert cell["warm_requests"] >= 128 and cell["why_the_start"]
    assert cell["tiny"]["clients"] <= 8
    gen = traffic.Generator(mix, cell, 16384, 4_100_000_011)
    seg = gen.segment(0, warm=False)
    outs = sorted(r.max_tokens for r in seg)
    assert len(seg) == 128 and 256 <= outs[0] and outs[-1] <= 768
    assert 510 <= sum(outs) / 128 <= 514
    assert all(128 <= len(r.prompt) <= 512 for r in seg)
    assert all(0 <= t < 16384 for r in seg for t in r.prompt)   # the slice
    assert max(len(r.prompt) + r.max_tokens for r in seg) <= 1280
    # every row's whole answer has its pages
    assert sum(-(-(len(r.prompt) + r.max_tokens) // 16)
               for r in seg) <= 8192
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "turns"
    assert "32x" in entry["why"]


def test_the_benchmark_lists_the_metrics_the_issue_names():
    # as the harness selects them; a quantity every cell reports is under
    # the stem's name, the cell's own under the cell's
    mine = listed(BENCHMARK, "per_layer", CELL)
    for stem in NEW:
        m = mine.get(f"{stem}.turns") or mine[stem]
        assert m["moves"] == ("setup_s" if stem.startswith("setup.")
                              else "out_tok_per_s")
        assert callable(reader(m["name"]).compute)


def test_counts_from_shapes():
    hf = modeldir.load_config(CONFIG)["hf"]
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 8192 * 6144)
    assert longcat_cost.attention_params(hf) == attn
    assert longcat_cost.expert_params(hf) == 3 * 6144 * 2048
    assert longcat_cost.router_width(hf) == 768
    assert longcat_cost.fixed_params(hf) == 4 * (
        2 * attn + 2 * 3 * 6144 * 12288 + 6144 * 768)
    assert longcat_cost.expert_slots(hf) == 4 * 16
    assert longcat_cost.kv_bytes_per_token(hf, "bfloat16") == 16384
    # ISSUE 41's arithmetic: 5.17 B parameters held here
    held = (longcat_cost.fixed_params(hf) + 64 * longcat_cost.expert_params(
        hf) + 2 * longcat_cost.head_params(hf))
    assert 5.172e9 < held < 5.173e9
    # a decode step at 128 rows: 5.3 GB outside the experts and ~1.2 of cache
    step = longcat_cost.decode_step_bytes(hf, "bfloat16", 128 * 576)
    assert 6.4e9 < step < 6.6e9
    # identity picks and picks held elsewhere are zero FLOPs
    assert longcat_cost.step_flops(hf, 128, 0, 0) == \
        2.0 * 128 * longcat_cost.fixed_params(hf)
    assert (longcat_cost.step_flops(hf, 128, 32, 128)
            - longcat_cost.step_flops(hf, 128, 0, 0)) == 2.0 * (
        32 * 3 * 6144 * 2048 + 128 * 16384 * 6144)
    assert longcat_cost.grouped_rows(hf, 128) == 1792
    assert longcat_cost.grouped_rows(hf, 512) == 8192
    flops, nbytes = longcat_cost.grouped_cost(hf, "bfloat16", 55, 128)
    assert flops == 2 * 128 * 3 * 6144 * 2048
    assert nbytes / 819e9 > 100 * flops / 197e12        # bytes bound it


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 8192, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


def _record(**kw):
    """A fused block of two decode steps at 126 rows, as the chip wrote
    them (my chip run, PR 41, call L41A)."""
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 2, "rows": 126,
           "batch": 128, "running": 128, "pool_free": 8192 - 4224,
           "tokens_real": 252, "tokens_padded": 256, "device_ms": 48.0,
           "experts_touched": 108, "moe_assignments": 12096,
           "moe_held_assignments": 230, "moe_zero_assignments": 4092}
    rec.update(kw)
    return rec


MIXED = dict(kind="mixed", width=0, rows=127, batch=1, tokens_real=510,
             tokens_padded=512, device_ms=60.0, experts_touched=55,
             moe_assignments=24480, moe_held_assignments=505,
             moe_zero_assignments=8348)
TRACE = {"mark": {"start_unix": 105.0, "stop_unix": 125.0}, "busy_s": 0.20,
         "ops": [["%moe_grouped.12 custom-call f32[1792,6144]{1,0} [mosaic]",
                  0.030, 16],
                 ["%moe_grouped.13 custom-call f32[8192,6144]{1,0} [mosaic]",
                  0.010, 4],
                 ["%mla_decode.24 custom-call f32[128,64,512] [mosaic]",
                  0.012, 32],
                 ["%mla_ragged.12 custom-call f32[512,64,512] [mosaic]",
                  0.008, 8],
                 ["%fusion.9 fusion bf16[128,12288]", 0.05, 900]]}


def test_readers_read_the_ring_and_the_trace():
    ring = [_record(), _record(t_unix=120.0), _record(**MIXED),
            _record(t_unix=10.0, experts_touched=5)]      # before the window
    run = _run_stub(ring, [TRACE])
    hf = run.config["hf"]
    assert reader("moe.experts_touched_share.turns").compute(run) == \
        pytest.approx(100 * (2 * 108 + 55) / (5 * 64))
    picks = 2 * 12096 + 24480
    assert reader("moe.zero_pick_share.turns").compute(run) == \
        pytest.approx(100 * (2 * 4092 + 8348) / picks)
    assert reader("moe.held_pick_share.turns").compute(run) == \
        pytest.approx(100 * (2 * 230 + 505) / picks)
    assert reader("kernel.moe_time_share.turns").compute(run) == \
        pytest.approx(20.0)
    assert reader("kernel.mla_time_share.turns").compute(run) == \
        pytest.approx(10.0)
    # the decode blocks' calls alone: 216 experts of 75.5 MB in 0.030 s
    roof = reader("kernel.moe_roofline_share.turns").compute(run)
    nbytes = 216 * 3 * 6144 * 2048 * 2 + 460 * 6144 * 6
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.030)
    assert 0 < roof <= 100
    hbm = reader("step.decode_hbm_share.turns").compute(run)
    ctx = 4224 * 16 / 128 * 126
    need = 2 * (2 * longcat_cost.decode_step_bytes(hf, "bfloat16", ctx)
                + 108 * longcat_cost.expert_bytes(hf, "bfloat16"))
    assert hbm == pytest.approx(100 * need / 819e9 / 0.096)
    assert 0 < hbm <= 100
    mfu = reader("step.rank_mfu.turns").compute(run)
    flops = (2 * longcat_cost.step_flops(hf, 252, 230, 252)
             + longcat_cost.step_flops(hf, 510, 505, 0))
    assert mfu == pytest.approx(100 * flops / 197e12 / 0.156)
    assert 0 < mfu <= 100
    assert reader("step.decode_device_ms").compute(run) == 24.0
    assert reader("step.mixed_device_ms").compute(run) == 60.0
    for name in ("step.decode_hbm_share.turns", "step.rank_mfu.turns"):
        assert reader(name).compute(_run_stub(ring, platform="cpu")) is None


@pytest.mark.parametrize("metric", [
    "moe.experts_touched_share.turns", "moe.zero_pick_share.turns",
    "moe.held_pick_share.turns", "kernel.moe_time_share.turns",
    "kernel.moe_roofline_share.turns", "kernel.mla_time_share.turns",
    "step.decode_hbm_share.turns", "step.rank_mfu.turns"])
def test_readers_return_nothing_from_a_program_without_the_counters(metric):
    """A program whose expert layer cannot be told which experts it holds
    (the parent commit, had it run) writes none of the four counts and no
    kernel of these names: the line leaves the metric out, nothing
    raises."""
    old = {k: v for k, v in _record().items()
           if k != "experts_touched" and not k.startswith("moe_")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[128,12288]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None


def _tiny(root, seed, traced):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "4",
         "--trace", str(traced), "--tiny"],
        cwd=root, capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_traced_tiny_run_with_a_planted_fault_is_not_correct(tmp_path):
    """A copy of the program whose expert branch holds the experts one
    further on than the file says (one of the faults ISSUE 41 plants on the
    chip): the served path runs, its ring and its readers count the three
    kinds of pick, and the comparison with the reference says no. (The
    program as it stands runs ``correct`` under this cell's name in
    ``test_benchmarks_e2e.py``, traced and untraced.)"""
    root = tmp_path / "repo"
    root.mkdir()
    ignore = shutil.ignore_patterns(".runs", ".cache", "__pycache__")
    shutil.copytree(BENCH, root / "benchmarks", ignore=ignore)
    shutil.copytree(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu",
                    ignore=ignore)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    family = root / "dynamo_tpu" / "models" / "longcat.py"
    text = family.read_text()
    held = "first_expert=cfg.expert_offset,"
    assert text.count(held) == 1
    family.write_text(text.replace(held,
                                   "first_expert=cfg.expert_offset + 1,"))
    line = _tiny(str(root), 4_100_000_043, traced=1)
    assert line["failed"] == 0 and line["correct"] is False, line
    probes = line["probes"]
    assert probes["served_vs_reference_max_nats"] > probes["reference_tol"]
    metrics = line["metrics"]
    # 3 picks of 24 outputs: 8 identity, 4 held here (a rank of 4)
    assert 15.0 < metrics["moe.zero_pick_share.turns"]["value"] < 55.0
    assert 4.0 < metrics["moe.held_pick_share.turns"]["value"] < 35.0
    assert 0.0 < metrics["moe.experts_touched_share.turns"]["value"] <= 100.0
    assert "step.decode_device_ms" in metrics
    with open(root / "benchmarks" / ".runs" / (CELL + "-tiny")
              / "run.json") as f:
        ring = json.load(f)["ring"][0]
    busy = [r for r in ring if r["moe_assignments"]]
    assert busy and all(
        r["moe_held_assignments"] + r["moe_zero_assignments"]
        <= r["moe_assignments"] for r in busy)
    # the touched count's range is the experts HELD: 2 layers x 4 a step
    assert all(r["experts_touched"] <= 2 * 4 * max(1, r["width"])
               for r in ring)
