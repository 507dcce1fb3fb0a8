"""The ``keye-vl-2.0-30b-a3b.askmany`` cell: its configuration is the
catalog's row cut in the one key it names, its files carry the parameters
ISSUE 56 defined it with, ``keye_cost`` counts what the issue counted by
hand at the published widths, and each of its readers reads what the
program writes - and returns nothing where a program does not write it (the
parent commit, which the driver runs with these files laid over it). No
count of the cells or metrics ``BENCHMARK.json`` holds is pinned here. (The
cell's two ``--tiny`` runs through the served path are
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

import keye_cost  # noqa: E402
import modeldir  # noqa: E402
import traffic  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

CONFIG = "keye-vl-2.0-30b-a3b"
CELL = CONFIG + ".askmany"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
# the entries this cell came with, each listing it alone
OWN = ["kernel.index_time_share.askmany",
       "kernel.index_roofline_share.askmany",
       "kernel.sparse_attn_time_share.askmany",
       "kernel.sparse_attn_roofline_share.askmany",
       "attn.selected_share.askmany", "cache.bytes_per_live_token.askmany",
       "kernel.moe_time_share.askmany", "kernel.moe_roofline_share.askmany",
       "moe.experts_touched_share.askmany", "step.decode_hbm_share.askmany",
       "step.stage_mfu.askmany", "sched.cached_prompt_share"]


def _args(bench):
    a = bench["worker_args"]
    return {a[i]: a[i + 1] for i in range(0, len(a), 2)}


def test_the_configuration_is_the_catalogs_row_cut_in_the_named_key():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert list(bench["reduced"]) == ["num_hidden_layers"]
    assert bench["published"] == {"num_hidden_layers": 48}
    assert raw["num_hidden_layers"] in (6, 5)
    if raw["num_hidden_layers"] == 5:
        assert "5" in bench["reduced"]["num_hidden_layers"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        assert row["name"] == "Keye-VL-2.0-30B-A3B"
        for key, value in row["config"].items():
            if key != "num_hidden_layers":
                assert raw[key] == value, key
    # the keys the parent's loader never looked at stand as published
    assert raw["model_type"] == "KeyeVL2"
    assert raw["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert raw["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert bench["reference"] == "keye" and "probe" not in bench
    assert bench["dtype"] == "bfloat16"
    for key in ("deployment", "left_out", "assumed", "memory",
                "reference_mean_tol", "why_reference_mean_tol",
                "why_worker_args", "tiny"):
        assert bench[key] and "TBD" not in json.dumps(bench[key]), key
    assert "8 chips" in bench["deployment"] or "8 pipeline" in \
        bench["deployment"]
    assert "4.375 B" in bench["deployment"]
    for what in ("vision", "Hadamard", "FP8"):
        assert what in bench["left_out"], what
    assumed = " ".join(bench["assumed"])
    for what in ("q/k norm", "LayerNorm", "rotary", "mrope_section",
                 "q_chunk_size", "seeded"):
        assert what in assumed, what
    assert "peak_bytes_in_use" in bench["memory"]
    # probes on both sides of the selection, the longest over 12,000
    lengths = bench["probe_lengths"]
    assert min(lengths) < 2048 < max(lengths) and max(lengths) >= 12000
    tiny = bench["tiny"]["config"]
    assert tiny["sa_config"]["topk"] == 24
    assert min(bench["tiny"]["probe_lengths"]) < 24 < max(
        bench["tiny"]["probe_lengths"])
    assert tiny["sa_config"]["indexer_num_kv_heads"] == 1


def test_the_cells_files_carry_the_parameters_it_was_defined_with():
    cell, mix = traffic.load_cell(CELL), traffic.load_mix("askmany")
    bench = modeldir.load_config(CONFIG)["bench"]
    args = _args(bench)
    rows = int(args["--max-num-seqs"])
    assert mix["loop"] == "closed" and cell["clients"] == rows
    # 48 rows, or the issue's fallback of 32 with its reason in the file
    assert rows in (48, 32)
    if rows == 32:
        assert "32" in cell["why"] and "fallback" in cell["why"]
    assert "--state-slots" not in args           # the family keeps no slot
    assert args["--attn-impl"] == "pallas"
    assert int(args["--num-pages"]) == 16384
    assert int(args["--max-context"]) == 25600
    chunk = int(args["--max-prefill-chunk"])
    cap = -(-(chunk + rows) // 128) * 128
    assert int(args["--min-prefill-bucket"]) == cap
    assert int(args["--min-prefill-seqs-bucket"]) == rows
    assert int(args["--min-decode-bucket"]) == rows
    assert mix["pool"] == {"size": 8, "zipf": 1.0, "tokens": {
        "dist": "const", "value": 24576}}
    assert mix["tail"]["tokens"] == {"dist": "uniform", "lo": 64,
                                     "hi": 256}
    assert mix["output"]["tokens"] == {"dist": "uniform", "lo": 128,
                                       "hi": 384}
    assert "own_prefix" not in mix and "requests_per_source" not in mix
    assert mix["lifetime_s"] == 0 and mix["who"] and mix["tiny"]
    assert (cell["layout"], cell["segment_s"]) == ("one-chip", 10)
    assert cell["stagger_s"] == 0.01 and 0 < cell["quiet_s"] < 0.2
    assert cell["warm_requests"] >= rows and cell["why_the_start"]
    assert "TBD" not in cell["why"] + cell["why_the_start"]
    assert cell["tiny"]["clients"] <= 8
    gen = traffic.Generator(mix, cell, 151936, 4_100_000_011)
    seg = gen.segment(0, warm=False)
    warm = gen.segment(0, warm=True)
    assert len(seg) == rows
    outs = sorted(r.max_tokens for r in seg)
    assert 128 <= outs[0] and outs[-1] <= 384
    # a prompt is one of the 8 documents - whole pages of 16, so the tail
    # starts on a page - and a tail of its own; warm-up and window share
    # the documents, the tails are unique
    docs = {tuple(r.prompt[:24576]) for r in seg}
    assert 1 < len(docs) <= 8 and 24576 % 16 == 0
    assert {tuple(r.prompt[:24576]) for r in warm} <= docs | {
        tuple(p) for p in gen.pool}
    assert docs <= {tuple(p) for p in gen.pool}
    tails = [tuple(r.prompt[24576:]) for r in seg + warm]
    assert all(64 <= len(t) <= 256 for t in tails)
    assert len(set(tails)) == len(tails)
    longest = max(len(r.prompt) + r.max_tokens for r in seg)
    assert longest <= 25216 <= int(args["--max-context"])
    # the documents resident and every row's own pages beside them
    own = rows * -(-(256 + 384 + 15) // 16)
    assert 8 * 1536 + own <= int(args["--num-pages"]) - 1
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "askmany"
    assert f"{rows} clients" in entry["why"] and len(entry["why"]) <= 200


def test_the_benchmark_lists_the_cells_metrics_each_with_its_reader():
    mine = listed(BENCHMARK, "per_layer", CELL)
    for name in OWN:
        m = mine[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_per_s"
        assert callable(reader(name).compute)
        if "roofline" in name or "mfu" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
    # what every cell reports came without an edit
    for name in ("stage.mixer_time_share", "stage.unnamed_time_share",
                 "step.decode_device_ms", "loop.host_gap_share",
                 "setup.worker_ready_s"):
        assert name in mine and "workloads" not in mine[name]
    e2e = listed(BENCHMARK, "end_to_end", CELL)
    assert set(e2e) == {"out_tok_per_s", "setup_s"}


def test_counts_from_shapes_are_the_issues_hand_counts():
    hf = modeldir.load_config(CONFIG)["hf"]
    hf = dict(hf, num_hidden_layers=6)
    # the issue's table: attention 18.87 M, indexer 2.26 M, router 0.26 M,
    # 128 experts of 4.72 M: a layer 625.4 M
    assert keye_cost.attention_params(hf) == 18_874_368
    assert keye_cost.indexer_params(hf) == 2048 * (1024 + 64 + 16) \
        == 2_260_992
    assert keye_cost.expert_params(hf) == 4_718_592
    layer = (18_874_368 + 2_260_992 + 2048 * 128 + 128 * 4_718_592)
    assert round(layer / 1e6, 1) == 625.4
    assert keye_cost.expert_slots(hf) == 6 * 128
    assert 2 * keye_cost.head_params(hf) == 622_329_856
    # 4.375 B parameters, 8.75 GB (the vectors of the norms left out)
    assert keye_cost.total_params(hf) == 6 * layer + 622_329_856
    assert round(keye_cost.total_params(hf) / 1e9, 3) == 4.375
    assert round(keye_cost.total_params(hf) * 2 / 1e9, 2) == 8.75
    # the fallback of 5 layers: 7.50 GB
    five = dict(hf, num_hidden_layers=5)
    assert round(keye_cost.total_params(five) * 2 / 1e9, 2) == 7.50
    # the cache: 13,056 B a token; 16,384 pages of 16 are 3.42 GB
    assert keye_cost.cache_bytes_per_token(hf, "bfloat16") == 13056
    assert round(16384 * 16 * 13056 / 1e9, 2) == 3.42
    # the mechanisms, from the mathematics
    flops, nbytes = keye_cost.index_cost(hf, "bfloat16", 1000, 100)
    assert flops == 1000 * 16 * 130 and nbytes == 100 * 128 + 4000
    flops, nbytes = keye_cost.sparse_attn_cost(hf, "bfloat16", 2048)
    assert flops == 2048 * 32 * 4 * 128 and nbytes == 2048 * 2048
    # the issue's step: 48 rows of 24.7 k - the masked form streams 14.6 GB
    # of keys and values and 0.9 GB of index keys, the selection needs 1.2
    rows, ctx = 48, 24700
    assert round(rows * ctx * 2048 * 6 / 1e9, 1) == 14.6
    assert round(rows * ctx * 128 * 6 / 1e9, 1) == 0.9
    assert round(6 * keye_cost.sparse_attn_cost(
        hf, "bfloat16", rows * 2048)[1] / 1e9, 1) == 1.2
    step = keye_cost.decode_step_bytes(hf, "bfloat16", rows, rows * ctx)
    assert step == ((keye_cost.fixed_params(hf) + 151936 * 2048) * 2
                    + 6 * (rows * ctx * 128 + rows * 2048 * 2048))
    active, head = keye_cost.active_params(hf)
    assert active == keye_cost.fixed_params(hf) + 6 * 8 * 4_718_592
    assert head == 151936 * 2048
    # the keys a record's rows hold between them
    assert keye_cost.record_row_keys(
        {"kind": "multistep", "score_pairs": 96 * 24000}) == 96 * 24000
    assert keye_cost.record_row_keys(
        {"kind": "mixed", "score_pairs": 1000, "rows": 5,
         "tokens_real": 100}) == 50.0


def _run_stub(ring, traces=(), platform="tpu", run_dir="/nonexistent"):
    run = types.SimpleNamespace()
    run.config = modeldir.load_config(CONFIG)
    run.config["hf"]["num_hidden_layers"] = 6
    run.ring, run.device_traces = [ring], list(traces)
    run.t0_unix, run.seconds = 100.0, 50.0
    run.num_pages, run.page_size, run.platform = 16384, 16, platform
    run.devices = [{"kind": "TPU v5 lite"}]
    run.run_dir = run_dir
    run.layout = {"workers": [{}]}
    return run


def _record(**kw):
    """A fused block of two decode steps at 48 rows of 24,700 tokens, the
    eight documents and the rows' own pages in use."""
    rec = {"t_unix": 110.0, "kind": "multistep", "width": 2, "rows": 48,
           "batch": 48, "running": 48, "pool_free": 16384 - 14000,
           "tokens_real": 96, "tokens_padded": 96, "device_ms": 100.0,
           "experts_touched": 2 * 6 * 120, "moe_assignments": 96 * 8 * 6,
           "moe_held_assignments": 96 * 8 * 6, "moe_zero_assignments": 0,
           "state_rows": 0, "gdn_tokens": 0, "gdn_step_rows": 0,
           "score_pairs": 96 * 24700, "selected_keys": 96 * 2048}
    rec.update(kw)
    return rec


# a packed step: a tail of 160 tokens at 24,576 and 47 one-token rows
MIXED = dict(kind="mixed", width=0, rows=48, batch=1, tokens_real=207,
             tokens_padded=640, device_ms=120.0, experts_touched=6 * 128,
             moe_assignments=207 * 8 * 6, moe_held_assignments=207 * 8 * 6,
             score_pairs=160 * 24656 + 47 * 24700,
             selected_keys=207 * 2048)
TRACE = {"mark": {"start_unix": 105.0, "stop_unix": 125.0}, "busy_s": 0.40,
         "ops": [["%selected_rows.9 custom-call bf16[48,32,128] [mosaic]",
                  0.120, 18],
                 ["%selected_chunks.4 custom-call bf16[640,32,128] [mosaic]",
                  0.020, 6],
                 ["%moe_grouped.12 custom-call f32[2432,2048]{1,0} [mosaic]",
                  0.060, 36],
                 ["%fusion.9 fusion bf16[640,2048]", 0.05, 900]]}
STAGES = {"busy_s": 0.40, "shares": {"mixer": 70.0}, "partition_error": 0.0,
          "stages": {"layer.attn/index/score": {"seconds": 0.06},
                     "layer.attn/index/topk": {"seconds": 0.04},
                     "layer.attn/sparse": {"seconds": 0.14},
                     "layer.moe/experts": {"seconds": 0.06}}}


def test_readers_read_the_ring_the_trace_and_the_stages(tmp_path):
    with open(tmp_path / "stage_times.worker0.json", "w") as f:
        json.dump(STAGES, f)
    ring = [_record(), _record(t_unix=120.0), _record(**MIXED),
            _record(t_unix=10.0, experts_touched=5)]      # before the window
    run = _run_stub(ring, [TRACE], run_dir=str(tmp_path))
    hf = run.config["hf"]
    assert reader("kernel.sparse_attn_time_share.askmany").compute(run) == \
        pytest.approx(35.0)
    assert reader("kernel.moe_time_share.askmany").compute(run) == \
        pytest.approx(15.0)
    assert reader("kernel.index_time_share.askmany").compute(run) == \
        pytest.approx(25.0)
    # the slice's three records: the selected tokens of every query in six
    # layers against the two kernels' 140 ms
    picked = (2 * 96 + 207) * 2048
    flops, nbytes = keye_cost.sparse_attn_cost(hf, "bfloat16", picked)
    assert nbytes / 819e9 > flops / 197e12       # the read bounds it
    roof = reader("kernel.sparse_attn_roofline_share.askmany").compute(run)
    assert roof == pytest.approx(100 * 6 * nbytes / 819e9 / 0.140)
    pairs = 2 * 96 * 24700 + MIXED["score_pairs"]
    keys = 2 * 96 * 24700 + MIXED["score_pairs"] * 48 / 207
    flops, nbytes = keye_cost.index_cost(hf, "bfloat16", pairs, keys)
    iroof = reader("kernel.index_roofline_share.askmany").compute(run)
    assert iroof == pytest.approx(
        100 * 6 * max(flops / 197e12, nbytes / 819e9) / 0.100)
    touched = 2 * 2 * 6 * 120 + 6 * 128
    picks = (2 * 96 + 207) * 8 * 6
    nbytes = touched * 4_718_592 * 2 + picks * 2048 * 6
    moe_roof = reader("kernel.moe_roofline_share.askmany").compute(run)
    assert moe_roof == pytest.approx(100 * nbytes / 819e9 / 0.060)
    for share in (roof, iroof, moe_roof):
        assert 0 < share <= 100
    assert reader("attn.selected_share.askmany").compute(run) == \
        pytest.approx(100 * picked / pairs)
    # 14,000 pages hold the context of 48 rows: a fifth of what an
    # unshared context costs
    live = reader("cache.bytes_per_live_token.askmany").compute(run)
    assert live == pytest.approx(13056 * 14000 * 16 / (48 * 24700))
    assert live < 13056 / 4
    assert reader("moe.experts_touched_share.askmany").compute(run) == \
        pytest.approx(100 * touched / (5 * 6 * 128))
    hbm = reader("step.decode_hbm_share.askmany").compute(run)
    need = 2 * (2 * keye_cost.decode_step_bytes(hf, "bfloat16", 48,
                                                48 * 24700)
                + 2 * 6 * 120 * keye_cost.expert_bytes(hf, "bfloat16"))
    assert hbm == pytest.approx(100 * need / 819e9 / 0.200)
    assert 0 < hbm <= 100
    mfu = reader("step.stage_mfu.askmany").compute(run)
    active, head = keye_cost.active_params(hf)
    flops = (2 * active * (2 * 96 + 207) + 2 * head * 2 * 96
             + 6 * (keye_cost.index_cost(hf, "bfloat16", pairs, 0)[0]
                    + keye_cost.sparse_attn_cost(hf, "bfloat16",
                                                 picked)[0]))
    assert mfu == pytest.approx(100 * flops / 197e12 / 0.320)
    assert 0 < mfu <= 100
    for name in ("step.decode_hbm_share.askmany", "step.stage_mfu.askmany"):
        assert reader(name).compute(_run_stub(ring, platform="cpu")) is None


def test_the_cached_share_is_read_off_the_workers_spans(tmp_path):
    """``sched.cached_prompt_share``, the reader that was there: a hit that
    brought 24,576 of a prompt's 24,736 tokens reads 99.4 %."""
    record = {"start_unix": 110.0, "spans": [
        {"name": "worker.generate", "attrs": {"prompt_tokens": 24736}},
        {"name": "prefill", "attrs": {"cached_tokens": 24576}}]}
    with open(tmp_path / "worker0.traces.jsonl", "w") as f:
        f.write(json.dumps(record) + "\n")
        f.write(json.dumps(dict(record, start_unix=10.0)) + "\n")
    run = _run_stub([], run_dir=str(tmp_path))
    assert reader("sched.cached_prompt_share").compute(run) == \
        pytest.approx(100 * 24576 / 24736)


# (``step.stage_mfu.askmany`` needs none of them: the tokens and the device time
# of any program's ring give it the matrix multiplications' share)
@pytest.mark.parametrize("metric", [n for n in OWN if n not in (
    "sched.cached_prompt_share", "step.stage_mfu.askmany")])
def test_readers_return_nothing_from_a_program_without_the_counters(
        metric, tmp_path):
    """A program that does not know the family (the parent commit, which
    serves the file as a dense model or refuses it) writes none of the
    ring's counts, traces under none of the indexer's stages and calls no
    kernel of these names: the line leaves the metric out, nothing
    raises."""
    old = {k: v for k, v in _record().items()
           if k not in ("experts_touched", "state_rows", "gdn_tokens",
                        "gdn_step_rows", "score_pairs", "selected_keys")
           and not k.startswith("moe_")}
    trace = {"mark": {"start_unix": 105.0, "stop_unix": 125.0,
                      "dir": str(tmp_path)},
             "busy_s": 0.2, "ops": [["%fusion.9 fusion bf16[128,12288]",
                                     0.05, 900]]}
    with open(tmp_path / "stage_times.worker0.json", "w") as f:
        json.dump({"busy_s": 0.2, "shares": {}, "partition_error": 0.0,
                   "stages": {"layer.attn": {"seconds": 0.1}}}, f)
    assert reader(metric).compute(
        _run_stub([old], [trace], run_dir=str(tmp_path))) is None
    assert reader(metric).compute(_run_stub([], [])) is None
