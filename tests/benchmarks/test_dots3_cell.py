"""The ``dots3-note-prev.longctx`` cell: its configuration is the catalog's
row cut in the keys it names, its files carry the parameters ISSUE 47
defined it with (or the fallback it provides, said in the file),
``dots3_cost`` counts what the issue counted by hand at the published
widths, and each of its readers reads what the program writes - and
returns nothing where a program does not write it. No count of the cells or
metrics ``BENCHMARK.json`` holds is pinned here. (The cell's two ``--tiny``
runs through the served path are ``test_benchmarks_e2e.py``'s, under the
cell's name.)"""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import dots3_cost  # noqa: E402
import modeldir  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG = "dots3-note-prev"
CELL = CONFIG + ".longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
# the metrics this cell came with (a later PR may list it under more)
NEW = ["attn.selected_share", "cache.bytes_per_live_token",
       "kernel.sparse_attn_time_share", "kernel.sparse_attn_roofline_share",
       "kernel.window_attn_time_share", "kernel.window_attn_roofline_share",
       "step.rank_mfu", "step.decode_hbm_share",
       "step.decode_device_ms", "step.mixed_device_ms",
       "step.prefill_occupancy", "step.compiles_in_window",
       "kernel.moe_time_share", "kernel.moe_roofline_share",
       "moe.experts_touched_share", "moe.held_pick_share",
       "loop.host_gap_share", "loop.idle_behind_host_share",
       "sched.queue_wait_share", "setup.worker_ready_s",
       "setup.first_calls_s"]



def _args(bench):
    a = bench["worker_args"]
    return {a[i]: a[i + 1] for i in range(0, len(a), 2)}


def test_the_configuration_is_the_catalogs_row_cut_in_the_named_keys():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert sorted(bench["reduced"]) == sorted(REDUCED)
    assert bench["published"] == {"num_hidden_layers": 46,
                                  "n_routed_experts": 256,
                                  "vocab_size": 152064}
    assert (raw["num_hidden_layers"], raw["n_routed_experts"],
            raw["vocab_size"]) == (9, 8, 19008)
    assert raw["layer_types"] == (
        ["full_attention"] * 2 + ["sliding_attention"] * 3
        + ["full_attention"] + ["sliding_attention"] * 3)
    assert (raw["ep_size"], raw["ep_rank"]) == (32, 0)   # 8 x 32 = 256
    assert "32 chips" in bench["deployment"]
    assert "3.09 B" in bench["deployment"]
    assert bench["reference"] == "dots3" and "probe" not in bench
    for key in ("left_out", "assumed", "memory", "reference_mean_tol",
                "why_reference_mean_tol", "why_worker_args", "tiny"):
        assert bench[key] and "TBD" not in json.dumps(bench[key]), key
    for what in ("vision", "multi-token-prediction", "Hadamard", "FP8"):
        assert what in bench["left_out"], what
    for what in ("indexer", "rotary", "gate", "window", "QUERY_GAIN"):
        assert any(what in a for a in bench["assumed"]), what
    # the probes: one under the window, one between window and selection,
    # three past the selection of which one past 6,144
    probes = bench["probe_lengths"]
    assert min(probes) < 513 and any(513 <= n <= 2048 for n in probes)
    assert sum(n > 2048 for n in probes) >= 3 and max(probes) >= 6144
    assert max(probes) + 16 <= int(_args(bench)["--max-context"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        changed = {k for k, v in row["config"].items() if raw.get(k) != v}
        assert changed == set(REDUCED)                   # every width as is
        assert raw["layer_types"] == row["config"]["layer_types"][:9]


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("longctx")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = _args(bench)
    rows = int(args["--max-num-seqs"])
    assert mix["loop"] == "closed" and cell["clients"] == rows
    # 32 rows, or the issue's fallback of 24 with its reason in the file
    assert rows in (32, 24) and int(args["--state-slots"]) == rows
    if rows == 24:
        assert "24" in cell["why"] and "fallback" in cell["why"]
    assert args["--attn-impl"] == "pallas"
    chunk = int(args["--max-prefill-chunk"])
    assert chunk in (1024, 512, 256) and "ms" in bench["why_worker_args"]
    cap = -(-(chunk + rows) // 128) * 128
    assert int(args["--min-prefill-bucket"]) == cap
    assert int(args["--min-prefill-seqs-bucket"]) == rows
    assert int(args["--min-decode-bucket"]) == rows
    assert int(args["--max-context"]) == 25600
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 8192,
                                     "hi": 24576}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 256,
                                       "hi": 768}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert mix["lifetime_s"] == 0 and mix["who"] and mix["tiny"]
    assert (cell["layout"], cell["segment_s"]) == ("one-chip", 10)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] < 0.2
    assert cell["warm_requests"] >= rows and cell["why_the_start"]
    assert "TBD" not in cell["why"] + cell["why_the_start"]
    assert cell["tiny"]["clients"] <= 8
    gen = traffic.Generator(mix, cell, 19008, 4_100_000_011)
    seg = gen.segment(0, warm=False)
    outs = sorted(r.max_tokens for r in seg)
    assert len(seg) == rows and 256 <= outs[0] and outs[-1] <= 768
    assert all(8192 <= len(r.prompt) <= 24576 for r in seg)
    assert all(0 <= t < 19008 for r in seg for t in r.prompt[:64])
    longest = max(len(r.prompt) + r.max_tokens for r in seg)
    assert longest <= 25344 <= int(args["--max-context"])
    # every row at its longest has its pages: no preemption in a window
    assert rows * -(-25344 // 16) <= int(args["--num-pages"]) - 1
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "longctx"
    assert f"{rows} clients" in entry["why"]


def test_the_benchmark_lists_the_cells_metrics_each_with_its_reader():
    # as the harness selects them; a quantity every cell reports is under
    # the stem's name, the cell's own under the cell's
    mine = listed(BENCHMARK, "per_layer", CELL)
    for stem in NEW:
        m = mine.get(f"{stem}.longctx") or mine[stem]
        assert m["moves"] == ("setup_s" if stem.startswith("setup.")
                              else "out_tok_per_s")
        assert callable(reader(m["name"]).compute)
        if "roofline" in stem or "mfu" in stem:
            assert m["unit"] == "%"


def test_counts_from_shapes_are_the_issues_hand_counts():
    hf = modeldir.load_config(CONFIG)["hf"]
    assert dots3_cost.kinds(hf) == (3, 6)
    # the issue's table: 144.05 M a full block, 90.83 M a window block
    assert dots3_cost.attention_params(hf, False) == 144_048_128
    assert dots3_cost.attention_params(hf, True) == 90_832_896
    assert dots3_cost.expert_params(hf) == 23_592_960
    assert dots3_cost.router_width(hf) == 256
    assert 3 * 5120 * 13824 == 212_336_640
    assert dots3_cost.expert_slots(hf) == 8 * 8
    assert 2 * dots3_cost.head_params(hf) == 194_641_920
    # 3.09 B parameters, 6.19 GB (the vectors of the norms left out)
    assert round(dots3_cost.total_params(hf) / 1e9, 2) == 3.09
    assert round(dots3_cost.total_params(hf) * 2 / 1e9, 2) == 6.19
    # a page holds 2 x 512 + 128 values a token a full layer
    assert dots3_cost.page_bytes_per_token(hf, "bfloat16") == 3 * 1152 * 2
    assert dots3_cost.ring_positions(hf, 512) == 1024
    assert dots3_cost.window_bytes_per_sequence(hf, "bfloat16", 512) == (
        6 * 1024 * 2 * 1024 * 2)
    # the mechanisms, from the mathematics
    flops, nbytes = dots3_cost.index_cost(hf, "bfloat16", 1000, 100)
    assert flops == 1000 * 64 * 258 and nbytes == 100 * 256 + 4000
    flops, nbytes = dots3_cost.sparse_attn_cost(hf, "bfloat16", 2048)
    assert flops == 2048 * 128 * 2 * 1088 and nbytes == 2048 * 576 * 2
    flops, nbytes = dots3_cost.window_attn_cost(hf, "bfloat16", 513)
    assert flops == 513 * 64 * 2 * 2112 and nbytes == 513 * 1088 * 2
    # a chunk's 512 queries read the chunk and the window before it once
    assert dots3_cost.chunk_window_keys(hf, 512) == 1024
    assert dots3_cost.window_attn_cost(hf, "bfloat16", 512 * 513, 1024) == (
        512 * 513 * 64 * 2 * 2112, 1024 * 1088 * 2)
    assert dots3_cost.window_pairs(0, 10, 513) == 55
    assert dots3_cost.window_pairs(1000, 10, 513) == 5130
    assert dots3_cost.window_pairs(510, 5, 513) == 511 + 512 + 3 * 513
    # a prompt token at a context of 8 k: the indexer, the selection-sized
    # attention and the window are about half of it (the issue: 5.8 GFLOP)
    base = dots3_cost.step_flops(hf, 1, 0.25 * 8, 0, 0, 0, 0)
    whole = dots3_cost.step_flops(hf, 1, 0.25 * 8, 0, 8192, 2048, 513)
    assert 5.0e9 < whole < 6.5e9 and 0.4 < (whole - base) / whole < 0.6
    step = dots3_cost.decode_step_bytes(hf, "bfloat16", 24, 24 * 16000)
    fixed = dots3_cost.fixed_params(hf)
    assert step == ((fixed + 19008 * 5120) * 2
                    + 3 * (24 * 16000 * 256 + 24 * 2048 * 1152)
                    + 6 * 24 * 513 * 2176)


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 38400, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


def _record(**kw):
    """A fused block of two decode steps at 20 rows of 16 k tokens."""
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 2, "rows": 20,
           "batch": 24, "running": 24, "pool_free": 38400 - 24000,
           "tokens_real": 40, "tokens_padded": 48, "device_ms": 80.0,
           "experts_touched": 60, "moe_assignments": 2560,
           "moe_held_assignments": 80, "moe_zero_assignments": 0,
           "state_rows": 20, "gdn_tokens": 0, "gdn_step_rows": 0,
           "score_pairs": 40 * 16000, "selected_keys": 40 * 2048}
    rec.update(kw)
    return rec


# a packed step: one prompt chunk of 512 tokens at 8 k and 20 one-token rows
MIXED = dict(kind="mixed", width=0, rows=21, batch=1, tokens_real=532,
             tokens_padded=640, device_ms=150.0, experts_touched=64,
             moe_assignments=34048, moe_held_assignments=1064,
             state_rows=21, score_pairs=512 * 8448 + 20 * 16000,
             selected_keys=532 * 2048)
TRACE = {"mark": {"start_unix": 105.0, "stop_unix": 125.0}, "busy_s": 0.40,
         "ops": [["%mla_selected.9 custom-call f32[640,128,512] [mosaic]",
                  0.080, 3],
                 ["%mla_window.16 custom-call f32[640,64,1024] [mosaic]",
                  0.020, 6],
                 ["%moe_grouped.12 custom-call f32[6144,5120]{1,0} [mosaic]",
                  0.020, 24],
                 # sorts, which no kernel reader counts: the expert layer's
                 # of a step's picks
                 ["%sort.7 s32[5120]{0}", 0.010, 24],
                 ["%fusion.9 fusion bf16[640,5120]", 0.05, 900]]}


def test_readers_read_the_ring_and_the_trace():
    ring = [_record(), _record(t_unix=120.0), _record(**MIXED),
            _record(t_unix=10.0, experts_touched=5)]      # before the window
    run = _run_stub(ring, [TRACE])
    hf = run.config["hf"]
    assert reader("kernel.sparse_attn_time_share.longctx").compute(run) == \
        pytest.approx(20.0)
    assert reader("kernel.window_attn_time_share.longctx").compute(run) == \
        pytest.approx(5.0)
    assert reader("kernel.moe_time_share.longctx").compute(run) == \
        pytest.approx(5.0)
    # the chunk's 512 tokens' selected rows in three layers against 80 ms
    chunk = 512 / 532
    flops, nbytes = dots3_cost.sparse_attn_cost(hf, "bfloat16",
                                                 532 * 2048 * chunk)
    roof = reader("kernel.sparse_attn_roofline_share.longctx").compute(run)
    assert roof == pytest.approx(
        100 * 3 * max(flops / 197e12, nbytes / 819e9) / 0.080)
    flops, nbytes = dots3_cost.window_attn_cost(
        hf, "bfloat16", 532 * 513 * chunk, 512 + 512)
    assert flops / 197e12 > nbytes / 819e9      # a chunk: its FLOPs bound it
    wroof = reader("kernel.window_attn_roofline_share.longctx").compute(run)
    assert wroof == pytest.approx(
        100 * 6 * max(flops / 197e12, nbytes / 819e9) / 0.020)
    moe_roof = reader("kernel.moe_roofline_share.longctx").compute(run)
    nbytes = 184 * 23_592_960 * 2 + 1224 * 5120 * 6
    assert moe_roof == pytest.approx(100 * nbytes / 819e9 / 0.020)
    for share in (roof, wroof, moe_roof):
        assert 0 < share <= 100
    assert reader("attn.selected_share.longctx").compute(run) == \
        pytest.approx(100 * (2 * 40 + 532) * 2048
                      / (2 * 40 * 16000 + 512 * 8448 + 20 * 16000))
    per_token = 3 * 1152 * 2 + 24 * 6 * 1024 * 4096 / (24000 * 16)
    assert reader("cache.bytes_per_live_token.longctx").compute(run) == \
        pytest.approx(per_token)
    assert reader("moe.experts_touched_share.longctx").compute(run) == \
        pytest.approx(100 * (2 * 60 + 64) / (5 * 64))
    assert reader("moe.held_pick_share.longctx").compute(run) == \
        pytest.approx(100 * (2 * 80 + 1064) / (2 * 2560 + 34048))
    hbm = reader("step.decode_hbm_share.longctx").compute(run)
    ctx = 24000 * 16 / 24 * 20
    need = 2 * (2 * dots3_cost.decode_step_bytes(hf, "bfloat16", 20, ctx)
                + 60 * dots3_cost.expert_bytes(hf, "bfloat16"))
    assert hbm == pytest.approx(100 * need / 819e9 / 0.160)
    assert 0 < hbm <= 100
    mfu = reader("step.rank_mfu.longctx").compute(run)
    flops = (2 * dots3_cost.step_flops(hf, 40, 80, 40, 40 * 16000,
                                       40 * 2048, 40 * 513)
             + dots3_cost.step_flops(
                 hf, 532, 1064, 0, MIXED["score_pairs"], 532 * 2048,
                 532 * 513))
    assert mfu == pytest.approx(100 * flops / 197e12 / 0.310)
    assert 0 < mfu <= 100
    assert reader("step.decode_device_ms").compute(run) == 40.0
    assert reader("step.mixed_device_ms").compute(run) == 150.0
    assert reader("step.prefill_occupancy").compute(run) == \
        pytest.approx(100 * 532 / 640)
    for name in ("step.decode_hbm_share.longctx", "step.rank_mfu.longctx"):
        assert reader(name).compute(_run_stub(ring, platform="cpu")) is None


@pytest.mark.parametrize("metric", [
    "kernel.sparse_attn_time_share.longctx",
    "kernel.sparse_attn_roofline_share.longctx",
    "kernel.window_attn_time_share.longctx",
    "kernel.window_attn_roofline_share.longctx",
    "kernel.moe_time_share.longctx",
    "kernel.moe_roofline_share.longctx", "attn.selected_share.longctx",
    "cache.bytes_per_live_token.longctx",
    "moe.experts_touched_share.longctx", "moe.held_pick_share.longctx",
    "step.decode_hbm_share.longctx", "step.rank_mfu.longctx"])
def test_readers_return_nothing_from_a_program_without_the_counters(metric):
    """A program that does not know the family (the parent commit, had it
    run) writes none of the ring's counts and no kernel of these names:
    the line leaves the metric out, nothing raises."""
    old = {k: v for k, v in _record().items()
           if k not in ("experts_touched", "state_rows", "gdn_tokens",
                        "gdn_step_rows", "score_pairs", "selected_keys")
           and not k.startswith("moe_")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[128,12288]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None
