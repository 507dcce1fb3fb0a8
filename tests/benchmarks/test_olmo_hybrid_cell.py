"""The ``olmo-hybrid-7b.crowd`` cell: its configuration is the catalog's row
cut in two named keys, its files carry the parameters ISSUE 51 defined it
with, ``olmo_hybrid_cost`` counts what the issue counted by hand at the
published widths, and each of its readers reads what the program writes -
and returns nothing where a program does not write it. (The cell's two
``--tiny`` runs through the served path are ``test_benchmarks_e2e.py``'s,
under the cell's name.)"""

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
import olmo_hybrid_cost as cost  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG = "olmo-hybrid-7b"
CELL = CONFIG + ".crowd"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# the metrics ISSUE 51 names for the cell
NEW = ["step.rank_mfu", "step.decode_hbm_share", "step.decode_device_ms",
       "step.mixed_device_ms", "step.prefill_occupancy",
       "step.compiles_in_window", "kernel.gdn_time_share",
       "kernel.gdn_step_roofline_share", "kernel.gdn_roofline_share",
       "kernel.attn_time_share", "kernel.attn_decode_roofline_share",
       "cache.state_share", "loop.host_gap_share",
       "loop.idle_behind_host_share", "sched.queue_wait_share",
       "setup.worker_ready_s", "setup.first_calls_s"]



def _args(bench):
    a = bench["worker_args"]
    return {a[i]: a[i + 1] for i in range(0, len(a), 2)}


def test_the_configuration_is_the_catalogs_row_cut_in_two_keys():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert sorted(bench["reduced"]) == sorted(entry["reduced"])
    assert bench["published"]["num_hidden_layers"] == 32
    assert bench["published"]["layer_types"][:16] == raw["layer_types"]
    assert raw["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 4
    assert (raw["num_hidden_layers"], raw["vocab_size"], raw["hidden_size"],
            raw["num_key_value_heads"]) == (16, 100352, 3840, 30)
    assert raw["rope_parameters"] == {"rope_theta": None}
    assert raw["linear_allow_neg_eigval"] is True
    assert "stage 0" in bench["deployment"]
    assert "4,100,788,944" in bench["deployment"]
    assert bench["reference"] == "olmo_hybrid" and "probe" not in bench
    for key in ("left_out", "assumed", "memory", "reference_mean_tol",
                "why_reference_mean_tol", "why_worker_args", "tiny"):
        assert bench[key] and "TO BE MEASURED" not in json.dumps(
            bench[key]), key
    said = " ".join(bench["assumed"])
    for what in ("block order", "whole", "rotary", "beta", "embedding",
                 "token"):
        assert what in said, what
    # the probes cross a chunk boundary twice and stay inside the context
    chunk = int(_args(bench)["--max-prefill-chunk"])
    probes = bench["probe_lengths"]
    assert probes == [48, 300, 700, 1250]
    assert sum(n > chunk for n in probes) == 2 and max(probes) > 2 * chunk
    assert max(probes) + 16 <= int(_args(bench)["--max-context"])
    # a tiny preset that does not tile either, at a group of one
    tiny = bench["tiny"]["config"]
    assert tiny["linear_num_value_heads"] % 2 and tiny[
        "linear_key_head_dim"] % 128 and tiny["linear_value_head_dim"] % 128
    assert tiny["num_attention_heads"] == tiny["num_key_value_heads"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        changed = {k for k, v in row["config"].items() if raw.get(k) != v}
        assert changed == set(entry["reduced"])          # every width as is
        for key in changed:
            assert bench["published"][key] == row["config"][key]


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("crowd")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = _args(bench)
    rows = int(args["--max-num-seqs"])
    assert mix["loop"] == "closed" and cell["clients"] == rows
    assert rows in (48, 40) and int(args["--state-slots"]) == rows
    assert int(args["--num-pages"]) == {48: 4096, 40: 3328}[rows]
    assert args["--attn-impl"] == "pallas"
    assert int(args["--max-context"]) == 1280
    chunk = int(args["--max-prefill-chunk"])
    assert chunk == 512
    # the window's step programs are pinned to two
    cap = -(-(chunk + rows) // 128) * 128
    assert int(args["--min-prefill-bucket"]) == cap
    assert int(args["--min-prefill-seqs-bucket"]) == rows
    assert int(args["--min-decode-bucket"]) == rows
    assert "75 ms" in bench["why_worker_args"]
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 128,
                                     "hi": 512}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 256,
                                       "hi": 768}
    assert "pool" not in mix and "own_prefix" not in mix       # unique
    assert mix["lifetime_s"] == 0 and mix["who"] and mix["tiny"]
    assert (cell["layout"], cell["segment_s"], cell["warm_segments"]) == (
        "one-chip", 10, 2)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] < 0.075
    assert cell["warm_requests"] >= rows and cell["why_the_start"]
    assert "TO BE MEASURED" not in cell["why"] + cell["why_the_start"]
    assert cell["tiny"]["clients"] <= 8
    gen = traffic.Generator(mix, cell, 100352, 4_100_000_011)
    seg = gen.segment(0, warm=False)
    outs = sorted(r.max_tokens for r in seg)
    assert len(seg) == rows and 256 <= outs[0] and outs[-1] <= 768
    assert all(128 <= len(r.prompt) <= 512 for r in seg)
    longest = max(len(r.prompt) + r.max_tokens for r in seg)
    assert longest <= 1280 <= int(args["--max-context"])
    # every row at its longest has its pages: no preemption in a window
    assert rows * -(-1280 // 16) <= int(args["--num-pages"]) - 1
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "crowd"
    assert len(entry["why"]) <= 200 and str(rows) in entry["why"]
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 0
    assert len(BENCHMARK["workloads"]) == 7


def test_the_benchmark_lists_the_metrics_the_issue_names():
    # as the harness selects them; a quantity every cell reports is under
    # the stem's name, the cell's own under the cell's
    mine = listed(BENCHMARK, "per_layer", CELL)
    for stem in NEW:
        m = mine.get(f"{stem}.crowd") or mine[stem]
        assert m["moves"] == ("setup_s" if stem.startswith("setup.")
                              else "out_tok_per_s")
        assert callable(reader(m["name"]).compute)
        assert len(m["name"]) <= 64
    for stem in ("kernel.gdn_roofline_share", "kernel.gdn_step_roofline_share",
                 "kernel.attn_decode_roofline_share", "step.rank_mfu",
                 "step.decode_hbm_share", "cache.state_share"):
        assert mine[f"{stem}.crowd"]["unit"] == "%"
    # every metric of the cell has a reader file of its own name
    for name in mine:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", f"{name}.py")), name


def test_counts_from_shapes_are_the_issues_hand_counts():
    hf = modeldir.load_config(CONFIG)["hf"]
    assert gdn_cost.conv_channels(hf) == 11_520
    assert cost.gdn_mixer_params(hf) == 88_750_332
    assert cost.full_mixer_params(hf) == 58_982_400 + 7_680
    assert cost.ffn_params(hf) == 126_812_160
    assert cost.layer_params(hf, "gdn") == 215_570_172
    assert cost.layer_params(hf, "full") == 185_809_920
    assert (cost.linear_layers(hf), cost.full_layers(hf)) == (12, 4)
    assert 3 * cost.layer_params(hf, "gdn") + cost.layer_params(
        hf, "full") == 832_520_436                      # a period
    assert 2 * cost.head_params(hf) == 770_703_360
    assert cost.total_params(hf) == 4_100_788_944
    # what a request carries between steps
    assert gdn_cost.state_bytes(hf) == 30 * 96 * 192 * 4 == 2_211_840
    assert cost.sequence_state_bytes(hf) == 26_542_080
    assert 12 * gdn_cost.conv_state_bytes(hf, "bfloat16") == 829_440
    assert cost.kv_bytes_per_token(hf, "bfloat16") == 61_440
    assert cost.attn_pair_bytes(hf, "bfloat16") == 15_360
    # the rule, from the rule: 7 Dk Dv a token a head, never a padded tile
    assert gdn_cost.rule_flops_per_token(hf) == 7 * 96 * 192 * 30
    flops, nbytes = cost.rule_cost(hf, "bfloat16", 48, 48)
    assert flops == 48 * 7 * 96 * 192 * 30
    assert nbytes == 48 * (11_520 * 2 + 2 * 30 * 4 + 5_760 * 4) \
        + 48 * 2 * 2_211_840
    assert nbytes / 819e9 > 50 * flops / 197e12         # the states do
    # a decode step at 48 rows and 580 tokens of context each: the issue's
    # 7.43 GB of weights + 2.55 GB of states + 1.7 GB of keys and values
    moved = 48 * 2 * 26_542_080
    step = cost.decode_step_bytes(hf, "bfloat16", moved, 48 * 580)
    fixed = cost.fixed_params(hf)
    assert fixed == 4 * 832_520_436
    assert step == (fixed + 385_351_680) * 2 + moved + 48 * 580 * 61_440
    assert 7.42e9 < (fixed + 385_351_680) * 2 < 7.44e9
    assert 2.54e9 < moved < 2.56e9 and 11.6e9 < step < 11.8e9
    # the whole step: parameters met, the rule, the four layers' scores
    assert cost.step_flops(hf, 100, 0, 0) == (
        2.0 * 100 * fixed + 100 * 12 * 7 * 96 * 192 * 30)
    assert (cost.step_flops(hf, 100, 48, 1000)
            - cost.step_flops(hf, 100, 0, 0)) == (
        2.0 * 48 * 385_351_680 + 4.0 * 1000 * 30 * 128 * 4)


def _run_stub(ring, traces=(), platform="tpu"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 4096, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    return run


ROW = 2 * 26_542_080        # what one row's step moves of state


def _record(**kw):
    """A fused block of two decode steps at 46 rows."""
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 2, "rows": 46,
           "batch": 48, "running": 48, "pool_free": 4096 - 2000,
           "tokens_real": 92, "tokens_padded": 96, "device_ms": 52.0,
           "decode_kernel_rows": 0, "state_rows": 46, "gdn_tokens": 0,
           "gdn_step_rows": 92, "score_pairs": 92 * 600,
           "state_bytes": 92 * ROW}
    rec.update(kw)
    return rec


# a packed step: a prompt of 400 tokens, the tail of another of 24, and 46
# one-token rows
MIXED = dict(kind="mixed", width=0, rows=48, batch=1, tokens_real=470,
             tokens_padded=640, device_ms=50.0, decode_kernel_rows=46,
             state_rows=48, gdn_tokens=424, gdn_step_rows=46,
             score_pairs=400 * 401 // 2 + 24 * 500 + 46 * 600,
             state_bytes=48 * ROW)
TRACE = {"mark": {"start_unix": 105.0, "stop_unix": 125.0}, "busy_s": 0.20,
         "ops": [["%gdn_chunk.10 custom-call f32[57,30,64,192] [mosaic]",
                  0.002, 12],
                 ["%gdn_step.10 custom-call f32[48,5,6,192] [mosaic]",
                  0.040, 60],
                 ["%ragged_mixed.16 custom-call bf16[640,30,128] [mosaic]",
                  0.004, 4],
                 ["%paged_decode.16 custom-call bf16[48,30,128] [mosaic]",
                  0.016, 20],
                 ["%fusion.9 fusion bf16[640,11008]", 0.05, 900]]}


def test_readers_read_the_ring_and_the_trace():
    ring = [_record(), _record(t_unix=120.0), _record(**MIXED),
            _record(t_unix=10.0, state_bytes=5)]          # before the window
    run = _run_stub(ring, [TRACE])
    hf = run.config["hf"]
    assert reader("kernel.gdn_time_share.crowd").compute(run) == \
        pytest.approx(21.0)
    assert reader("kernel.attn_time_share.crowd").compute(run) == \
        pytest.approx(10.0)
    # the chunk form: 424 tokens of 2 rows through twelve layers in 2 ms
    _f, nbytes = cost.rule_cost(hf, "bfloat16", 12 * 424, 12 * 2)
    roof = reader("kernel.gdn_roofline_share.crowd").compute(run)
    assert roof == pytest.approx(100 * nbytes / 819e9 / 0.002)
    # the step form: 2 x 92 + 46 row-steps, each a state in and out
    _f, nbytes = cost.rule_cost(hf, "bfloat16", 12 * 230, 12 * 230)
    step_roof = reader("kernel.gdn_step_roofline_share.crowd").compute(run)
    assert step_roof == pytest.approx(100 * nbytes / 819e9 / 0.040)
    # paged_decode: the blocks' pairs and the packed step's 46 rows at the
    # mean context of a running row, a key and a value of 30 heads a pair
    pairs = 2 * 92 * 600 + 46 * (2000 * 16 / 48)
    attn_roof = reader("kernel.attn_decode_roofline_share.crowd").compute(run)
    assert attn_roof == pytest.approx(
        100 * pairs * 4 * 15_360 / 819e9 / 0.016)
    for share in (roof, step_roof, attn_roof):
        assert 0 < share <= 100
    state, paged = 48 * 26_542_080, 2000 * 16 * 61_440
    assert reader("cache.state_share.crowd").compute(run) == pytest.approx(
        100 * state / (state + paged))
    hbm = reader("step.decode_hbm_share.crowd").compute(run)
    ctx = 2000 * 16 / 48 * 46
    need = 2 * (2 * cost.decode_step_bytes(hf, "bfloat16", 0, ctx)
                + 92 * ROW)
    assert hbm == pytest.approx(100 * need / 819e9 / 0.104)
    assert 0 < hbm <= 100
    mfu = reader("step.rank_mfu.crowd").compute(run)
    flops = (2 * cost.step_flops(hf, 92, 92, 92 * 600)
             + cost.step_flops(hf, 470, 0, MIXED["score_pairs"]))
    assert mfu == pytest.approx(100 * flops / 197e12 / 0.154)
    assert 0 < mfu <= 100
    assert reader("step.decode_device_ms").compute(run) == 26.0
    assert reader("step.mixed_device_ms").compute(run) == 50.0
    assert reader("step.prefill_occupancy").compute(run) == \
        pytest.approx(100 * 470 / 640)
    for name in ("step.decode_hbm_share.crowd", "step.rank_mfu.crowd"):
        assert reader(name).compute(_run_stub(ring, platform="cpu")) is None


@pytest.mark.parametrize("metric", [
    "kernel.gdn_time_share.crowd", "kernel.gdn_roofline_share.crowd",
    "kernel.gdn_step_roofline_share.crowd", "kernel.attn_time_share.crowd",
    "kernel.attn_decode_roofline_share.crowd", "cache.state_share.crowd",
    "step.decode_hbm_share.crowd", "step.rank_mfu.crowd"])
def test_readers_return_nothing_from_a_program_without_the_counters(metric):
    """A program that does not know the family (the parent commit, had it
    run) writes none of the ring's state counts and no kernel of these
    names: the line leaves the metric out, nothing raises."""
    old = {k: v for k, v in _record().items()
           if k not in ("state_rows", "gdn_tokens", "gdn_step_rows",
                        "score_pairs", "state_bytes")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[48,11008]",
                                     0.05, 900]]}
    assert reader(metric).compute(_run_stub([old], [trace])) is None
    assert reader(metric).compute(_run_stub([], [])) is None
    # PR 49's program on its own linear model: the rule's counts and no
    # ``state_bytes`` - the step's share of the memory roofline stays out
    if metric == "step.decode_hbm_share.crowd":
        pr49 = {k: v for k, v in _record().items() if k != "state_bytes"}
        assert reader(metric).compute(_run_stub([pr49], [trace])) is None
