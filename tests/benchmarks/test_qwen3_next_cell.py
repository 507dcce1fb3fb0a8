"""The ``qwen3-next-80b-a3b-instruct.longdoc`` cell: its configuration is
the catalog's row cut in two named keys, its files carry the parameters
ISSUE 43 defined it with, ``gdn_cost`` counts what the issue counted by
hand at the published widths, and each of its readers reads what the
program writes - and returns nothing where a program does not write it.
(The cell's two ``--tiny`` runs through the served path are
``test_benchmarks_e2e.py``'s, under the cell's name.)"""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import gdn_cost  # noqa: E402
import modeldir  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG = "qwen3-next-80b-a3b-instruct"
CELL = CONFIG + ".longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# the metrics ISSUE 43 names for the cell (a later PR may list it under more)
NEW = ["kernel.gdn_time_share", "kernel.gdn_roofline_share",
       "kernel.gdn_step_roofline_share", "step.rank_mfu",
       "step.decode_hbm_share", "step.decode_device_ms",
       "step.mixed_device_ms", "step.prefill_occupancy",
       "step.compiles_in_window", "kernel.attn_time_share",
       "kernel.moe_time_share", "kernel.moe_roofline_share",
       "moe.experts_touched_share", "moe.held_pick_share",
       "loop.host_gap_share", "loop.idle_behind_host_share",
       "sched.queue_wait_share", "setup.worker_ready_s",
       "setup.first_calls_s"]



def _args(bench):
    a = bench["worker_args"]
    return {a[i]: a[i + 1] for i in range(0, len(a), 2)}


def test_the_configuration_is_the_catalogs_row_cut_in_two_keys():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert sorted(bench["reduced"]) == sorted(entry["reduced"])
    assert bench["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 512}
    assert (raw["num_hidden_layers"], raw["num_experts"],
            raw["vocab_size"]) == (8, 128, 151936)       # the vocabulary whole
    assert (raw["ep_size"], raw["ep_rank"]) == (4, 0)    # 128 x 4 = 512
    assert "four chips" in bench["deployment"]
    assert "4,133,998,720" in bench["deployment"]
    assert bench["reference"] == "qwen3_next" and "probe" not in bench
    for key in ("left_out", "assumed", "memory", "reference_mean_tol",
                "why_reference_mean_tol", "why_worker_args", "tiny"):
        assert bench[key] and "TO BE MEASURED" not in json.dumps(
            bench[key]), key
    assert "multi-token-prediction" in bench["left_out"]
    assert any("decay" in a and "0.9999" in a for a in bench["assumed"])
    # the probes reach the cell's sizes and straddle the chunk boundaries
    chunk = int(_args(bench)["--max-prefill-chunk"])
    probes = bench["probe_lengths"]
    assert min(probes) < 64 and max(probes) >= 11000
    assert any(0 < n % chunk <= 64 and n > chunk for n in probes)
    assert max(probes) + 16 <= int(_args(bench)["--max-context"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        changed = {k for k, v in row["config"].items() if raw.get(k) != v}
        assert changed == set(entry["reduced"])          # every width as is
        for key in changed:
            assert bench["published"][key] == row["config"][key]


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("longdoc")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = _args(bench)
    rows = int(args["--max-num-seqs"])
    assert mix["loop"] == "closed" and cell["clients"] == rows
    assert rows in (64, 48) and int(args["--state-slots"]) == rows
    assert args["--attn-impl"] == "pallas"
    # a prompt is computed in chunks of at most 2,048 tokens (1,024 where
    # a step at 2,048 takes over 75 ms on the chip, said in the why)
    chunk = int(args["--max-prefill-chunk"])
    assert chunk in (1024, 2048)
    if chunk == 1024:
        assert "75 ms" in bench["why_worker_args"]
    # the window's step programs are pinned to two
    cap = -(-(chunk + rows) // 128) * 128
    assert int(args["--min-prefill-bucket"]) == cap
    assert int(args["--min-prefill-seqs-bucket"]) == rows
    assert int(args["--min-decode-bucket"]) == rows
    assert "ms" in bench["why_worker_args"]
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 2048,
                                     "hi": 12288}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 128,
                                       "hi": 384}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert mix["lifetime_s"] == 0 and mix["who"] and mix["tiny"]
    assert (cell["layout"], cell["segment_s"], cell["warm_segments"]) == (
        "one-chip", 10, 2)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] < 0.075
    assert cell["warm_requests"] >= rows and cell["why_the_start"]
    assert "TO BE MEASURED" not in cell["why"] + cell["why_the_start"]
    assert cell["tiny"]["clients"] <= 8
    gen = traffic.Generator(mix, cell, 151936, 4_100_000_011)
    seg = gen.segment(0, warm=False)
    outs = sorted(r.max_tokens for r in seg)
    assert len(seg) == rows and 128 <= outs[0] and outs[-1] <= 384
    assert all(2048 <= len(r.prompt) <= 12288 for r in seg)
    assert 7000 <= sum(len(r.prompt) for r in seg) / rows <= 7340
    longest = max(len(r.prompt) + r.max_tokens for r in seg)
    assert longest <= 12672 <= int(args["--max-context"])
    # every row at its longest has its pages: no preemption in a window
    assert rows * -(-12672 // 16) <= int(args["--num-pages"]) - 1
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "longdoc"
    assert "prefix cache off" in entry["why"]


def test_the_benchmark_lists_the_metrics_the_issue_names():
    # as the harness selects them; a quantity every cell reports is under
    # the stem's name, the cell's own under the cell's
    mine = listed(BENCHMARK, "per_layer", CELL)
    for stem in NEW:
        m = mine.get(f"{stem}.longdoc") or mine[stem]
        assert m["moves"] == ("setup_s" if stem.startswith("setup.")
                              else "out_tok_per_s")
        assert callable(reader(m["name"]).compute)
    for stem in ("kernel.gdn_roofline_share", "kernel.gdn_step_roofline_share",
                 "kernel.moe_roofline_share", "step.rank_mfu"):
        assert mine[f"{stem}.longdoc"]["unit"] == "%"


def test_counts_from_shapes_are_the_issues_hand_counts():
    hf = modeldir.load_config(CONFIG)["hf"]
    assert gdn_cost.conv_channels(hf) == 8192
    assert gdn_cost.gdn_mixer_params(hf) == 33_718_464
    assert gdn_cost.full_mixer_params(hf) == 27_263_488
    assert gdn_cost.ffn_fixed_params(hf) == 4_200_448
    assert gdn_cost.expert_params(hf) == 3_145_728
    assert gdn_cost.experts_held(hf) * gdn_cost.expert_params(hf) == \
        402_653_184
    assert (gdn_cost.linear_layers(hf), gdn_cost.full_layers(hf)) == (6, 2)
    assert (6 * gdn_cost.layer_params(hf, "gdn")
            + 2 * gdn_cost.layer_params(hf, "full")) == 3_511_666_816
    assert 2 * gdn_cost.head_params(hf) == 622_329_856
    assert gdn_cost.total_params(hf) == 4_133_998_720
    assert gdn_cost.router_width(hf) == 512
    assert gdn_cost.expert_slots(hf) == 8 * 128
    # what a request carries between steps, a linear layer
    assert gdn_cost.state_bytes(hf) == 2_097_152
    assert gdn_cost.conv_state_bytes(hf, "bfloat16") == 49_152
    # 2 KB a token for a whole period of four layers: 4 KB over this cut
    assert gdn_cost.kv_bytes_per_token(hf, "bfloat16") == 4096
    # the rule, from the rule: 7 Dk Dv a token a head
    assert gdn_cost.rule_flops_per_token(hf) == 7 * 128 * 128 * 32
    assert gdn_cost.rule_token_bytes(hf, "bfloat16") == (
        8192 * 2 + 2 * 32 * 4 + 4096 * 4)
    flops, nbytes = gdn_cost.rule_cost(hf, "bfloat16", 2048, 2)
    assert flops == 2048 * 3_670_016
    assert nbytes == 2048 * 33_024 + 2 * 2 * 2_097_152
    assert nbytes / 819e9 > flops / 197e12              # bytes bound it
    flops, nbytes = gdn_cost.rule_cost(hf, "bfloat16", 64, 64)
    assert nbytes / 819e9 > 50 * flops / 197e12         # the states do
    # a decode step at 64 rows and 8 k tokens of context each
    step = gdn_cost.decode_step_bytes(hf, "bfloat16", 64, 64 * 8192)
    fixed = gdn_cost.fixed_params(hf)
    assert fixed == 6 * 33_718_464 + 2 * 27_263_488 + 8 * 4_200_448
    assert step == ((fixed + 311_164_928) * 2 + 64 * 8192 * 4096
                    + 64 * 6 * 2 * (2_097_152 + 49_152))
    # the whole step: parameters met, picks computed here, the rule, the
    # scores of the two full layers, the head
    assert gdn_cost.step_flops(hf, 100, 0, 0, 0) == (
        2.0 * 100 * fixed + 100 * 6 * 3_670_016)
    assert (gdn_cost.step_flops(hf, 100, 250, 64, 1000)
            - gdn_cost.step_flops(hf, 100, 0, 0, 0)) == (
        2.0 * (250 * 3_145_728 + 64 * 311_164_928)
        + 4.0 * 1000 * 16 * 256 * 2)
    # a prompt token against the mean ~4,200 keys: three quarters of a
    # GFLOP (the issue reckoned 0.87), a sixth of it the attention scores
    per_token = gdn_cost.step_flops(hf, 1, 2.5, 0, 4193) / 1e9
    assert 0.7 < per_token < 0.9
    assert 0.1 < gdn_cost.score_flops(hf, 4193) / 1e9 / per_token < 0.25
    flops, nbytes = gdn_cost.grouped_cost(hf, "bfloat16", 90, 160)
    assert flops == 2 * 160 * 3_145_728
    assert nbytes / 819e9 > 100 * flops / 197e12        # bytes bound it


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 51200, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


def _record(**kw):
    """A fused block of two decode steps at 60 rows."""
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 2, "rows": 60,
           "batch": 64, "running": 64, "pool_free": 51200 - 32000,
           "tokens_real": 120, "tokens_padded": 128, "device_ms": 40.0,
           "experts_touched": 1400, "moe_assignments": 9600,
           "moe_held_assignments": 2400, "moe_zero_assignments": 0,
           "state_rows": 60, "gdn_tokens": 0, "gdn_step_rows": 120,
           "score_pairs": 120 * 8000}
    rec.update(kw)
    return rec


# a packed step: one prompt chunk of 1,000 tokens, the tail of another of
# 24, and 60 one-token rows
MIXED = dict(kind="mixed", width=0, rows=62, batch=1, tokens_real=1084,
             tokens_padded=1152, device_ms=55.0, experts_touched=1024,
             moe_assignments=86720, moe_held_assignments=21700,
             state_rows=62, gdn_tokens=1024, gdn_step_rows=60,
             score_pairs=1024 * 5000 + 60 * 8000)
TRACE = {"mark": {"start_unix": 105.0, "stop_unix": 125.0}, "busy_s": 0.20,
         "ops": [["%gdn_chunk.10 custom-call f32[98,32,64,128] [mosaic]",
                  0.012, 6],
                 ["%gdn_step.10 custom-call f32[64,32,128] [mosaic]",
                  0.012, 30],
                 ["%moe_grouped.12 custom-call f32[2688,2048]{1,0} [mosaic]",
                  0.030, 32],
                 ["%moe_grouped.13 custom-call f32[38144,2048]{1,0} [mosaic]",
                  0.010, 8],
                 ["%ragged_mixed.16 custom-call bf16[1152,16,256] [mosaic]",
                  0.006, 2],
                 ["%paged_decode.16 custom-call bf16[64,16,256] [mosaic]",
                  0.004, 10],
                 ["%fusion.9 fusion bf16[1152,12288]", 0.05, 900]]}


def test_readers_read_the_ring_and_the_trace():
    ring = [_record(), _record(t_unix=120.0), _record(**MIXED),
            _record(t_unix=10.0, experts_touched=5)]      # before the window
    run = _run_stub(ring, [TRACE])
    hf = run.config["hf"]
    assert reader("kernel.gdn_time_share.longdoc").compute(run) == \
        pytest.approx(12.0)
    assert reader("kernel.attn_time_share.longdoc").compute(run) == \
        pytest.approx(5.0)
    assert reader("kernel.moe_time_share.longdoc").compute(run) == \
        pytest.approx(20.0)
    # the chunk form: 1,024 tokens of 2 rows through six layers in 12 ms
    _f, nbytes = gdn_cost.rule_cost(hf, "bfloat16", 6 * 1024, 6 * 2)
    roof = reader("kernel.gdn_roofline_share.longdoc").compute(run)
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.012)
    # the step form: 2 x 120 + 60 row-steps, each a state in and out
    _f, nbytes = gdn_cost.rule_cost(hf, "bfloat16", 6 * 300, 6 * 300)
    step_roof = reader("kernel.gdn_step_roofline_share.longdoc").compute(run)
    assert step_roof == pytest.approx(100 * nbytes / 819e9 / 0.012)
    # every grouped call of the slice: 2 x 1,400 + 1,024 experts' weights
    moe_roof = reader("kernel.moe_roofline_share.longdoc").compute(run)
    nbytes = 3824 * 3_145_728 * 2 + 26500 * 2048 * 6
    assert moe_roof == pytest.approx(100 * nbytes / 819e9 / 0.040)
    for share in (roof, step_roof, moe_roof):
        assert 0 < share <= 100
    assert reader("moe.experts_touched_share.longdoc").compute(run) == \
        pytest.approx(100 * (2 * 1400 + 1024) / (5 * 1024))
    assert reader("moe.held_pick_share.longdoc").compute(run) == \
        pytest.approx(100 * (2 * 2400 + 21700) / (2 * 9600 + 86720))
    hbm = reader("step.decode_hbm_share.longdoc").compute(run)
    ctx = 32000 * 16 / 64 * 60
    need = 2 * (2 * gdn_cost.decode_step_bytes(hf, "bfloat16", 60, ctx)
                + 1400 * gdn_cost.expert_bytes(hf, "bfloat16"))
    assert hbm == pytest.approx(100 * need / 819e9 / 0.080)
    assert 0 < hbm <= 100
    mfu = reader("step.rank_mfu.longdoc").compute(run)
    flops = (2 * gdn_cost.step_flops(hf, 120, 2400, 120, 120 * 8000)
             + gdn_cost.step_flops(hf, 1084, 21700, 0,
                                   1024 * 5000 + 60 * 8000))
    assert mfu == pytest.approx(100 * flops / 197e12 / 0.135)
    assert 0 < mfu <= 100
    assert reader("step.decode_device_ms").compute(run) == 20.0
    assert reader("step.mixed_device_ms").compute(run) == 55.0
    assert reader("step.prefill_occupancy").compute(run) == \
        pytest.approx(100 * 1084 / 1152)
    for name in ("step.decode_hbm_share.longdoc", "step.rank_mfu.longdoc"):
        assert reader(name).compute(_run_stub(ring, platform="cpu")) is None


@pytest.mark.parametrize("metric", [
    "kernel.gdn_time_share.longdoc", "kernel.gdn_roofline_share.longdoc",
    "kernel.gdn_step_roofline_share.longdoc", "kernel.attn_time_share.longdoc",
    "kernel.moe_time_share.longdoc", "kernel.moe_roofline_share.longdoc",
    "moe.experts_touched_share.longdoc", "moe.held_pick_share.longdoc",
    "step.decode_hbm_share.longdoc", "step.rank_mfu.longdoc"])
def test_readers_return_nothing_from_a_program_without_the_counters(metric):
    """A program that does not know the family (the parent commit, had it
    run) writes none of the ring's state counts and no kernel of these
    names: the line leaves the metric out, nothing raises."""
    old = {k: v for k, v in _record().items()
           if k not in ("experts_touched", "state_rows", "gdn_tokens",
                        "gdn_step_rows", "score_pairs")
           and not k.startswith("moe_")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[128,12288]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None
