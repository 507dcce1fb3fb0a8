"""CPU tests of the benchmark's own code (``benchmarks/``): its data files,
generator, trace reduction, references and, end to end at toy widths, each
traffic mix's cell. No libtpu at import; the end-to-end cases start the
served path as processes on the CPU backend (``--tiny``)."""

import json
import os
import re
import statistics
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import breakdown  # noqa: E402
import modeldir  # noqa: E402
import peaks  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402
from layer_metrics import listed, reader  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# every cell and configuration the benchmark has files for, listed in
# BENCHMARK.json or kept for a later PR (PERF.md, Open questions)
BUILT_CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "cells")))
BUILT_CONFIGS = sorted(f[:-5] for f in os.listdir(
    os.path.join(BENCH, "configs")))


def _has_own_rule(config: str) -> bool:
    """Told from the file's data: a configuration whose served tokens are
    scored by a rule of its reference module's own has a ``probe`` block."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        return "probe" in json.load(f)["benchmark"]


# the configurations the next-token rule scores (``reference/score.py``)
DEFAULT_RULE_CONFIGS = [c for c in BUILT_CONFIGS if not _has_own_rule(c)]
# what ``reduced`` may never name: a hidden, intermediate, latent, state or
# projection size, a head size, an expansion factor, the experts a token
# picks. A vocabulary or a number of experts that one chip holds a slice of
# is no width (the model-configs guide, section 4)
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_size"
                   r"|state_size|expand|experts_per_tok|top_?k")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ------------------------------------------------------------ BENCHMARK.json


def test_benchmark_json_obeys_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # the metric it moves is reported in every cell where it is
        cells = set(m.get("workloads", CELLS))
        moved = e2e[m["moves"]]
        assert cells <= set(moved.get("workloads", CELLS)), m["name"]
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    configs = {c["name"] for c in b["configs"]}
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        # every cell reports set-up, another end-to-end metric and a
        # per-layer metric
        mine = [m for m in b["end_to_end"]
                if w["name"] in m.get("workloads", CELLS)]
        assert len(mine) >= 2
        assert any(w["name"] in m.get("workloads", CELLS)
                   for m in b["per_layer"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("config", [c["name"] for c in BENCHMARK["configs"]])
def test_configuration_file_is_the_published_config(config):
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config)
    with open(os.path.join(REPO, entry["file"])) as f:
        raw = json.load(f)
    bench = raw.pop("benchmark")
    assert bench["source"] == entry["source"]
    assert sorted(bench["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert not WIDTH.search(key), f"{key} is a width"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(x) for x in f]
        row = [r for r in rows if r["source_url"] == entry["source"]]
        for key, value in (row[0]["config"] if row else {}).items():
            if key not in entry["reduced"]:
                assert raw[key] == value, key


@pytest.mark.parametrize("metric", sorted(
    f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
    if f.endswith(".py") and not f.startswith("_")))
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(reader(metric).compute)


# every (entry, cell) pair a traced run prints, as ``benchmarks/run.py``
# selects them (``layer_metrics.listed``)
PAIRS = [(name, cell) for cell in CELLS
         for name in listed(BENCHMARK, "per_layer", cell)]


@pytest.mark.parametrize("metric, cell", PAIRS)
def test_every_pair_of_metric_and_cell_has_a_reader(metric, cell):
    """The reader file is there under the entry's own name, and the cell
    reports the end-to-end metric the entry says it moves."""
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       f"{metric}.py"))
    assert callable(reader(metric).compute)
    entry = listed(BENCHMARK, "per_layer", cell)[metric]
    assert entry["moves"] in listed(BENCHMARK, "end_to_end", cell)


# The pairs at PR 55, written out: the 128 of PR 54's list under the names
# they have since (a quantity every cell reports has one name, the cell is
# in ``workloads``), less ``kernel.topk_time_share`` on ``longctx`` (no
# step program holds the sort it read), plus the seven stages in the seven
# cells. 16 x 7 + 3 x 3 + 55 = 176.
EVERY_CELL_PR55 = [
    "loop.host_gap_share", "loop.idle_behind_host_share",
    "sched.queue_wait_share", "setup.worker_ready_s", "setup.first_calls_s",
    "step.decode_device_ms", "step.mixed_device_ms",
    "step.prefill_occupancy", "step.compiles_in_window",
    "stage.mixer_in_time_share", "stage.cache_write_time_share",
    "stage.mixer_time_share", "stage.mixer_out_time_share",
    "stage.ffn_time_share", "stage.around_layers_time_share",
    "stage.unnamed_time_share"]
IDLE_IN_PR55 = ["loop.idle_in_assemble_share", "loop.idle_in_enqueue_share",
                "loop.idle_in_handover_share"]
OWN_PR55 = {
    "qwen3-4b.batch": IDLE_IN_PR55 + [
        "step.decode_hbm_share.batch", "step.mfu.batch",
        "kernel.attn_time_share.batch"],
    "joyai-llm-flash.reason": IDLE_IN_PR55 + [
        "moe.experts_touched_share.reason", "kernel.moe_time_share.reason",
        "kernel.moe_roofline_share.reason", "kernel.mla_time_share.reason",
        "step.decode_hbm_share.reason", "step.mfu.reason"],
    "sdar-30b-a3b-chat.blockgen": IDLE_IN_PR55 + [
        "gen.tokens_per_pass.blockgen", "gen.commit_pass_share.blockgen",
        "step.decode_hbm_share.blockgen", "step.pass_mfu.blockgen",
        "moe.experts_touched_share.blockgen",
        "kernel.moe_time_share.blockgen",
        "kernel.moe_roofline_share.blockgen",
        "kernel.attn_time_share.blockgen"],
    "longcat-flash-omni.turns": [
        "step.rank_mfu.turns", "step.decode_hbm_share.turns",
        "kernel.moe_roofline_share.turns", "kernel.moe_time_share.turns",
        "kernel.mla_time_share.turns", "moe.experts_touched_share.turns",
        "moe.zero_pick_share.turns", "moe.held_pick_share.turns"],
    "qwen3-next-80b-a3b-instruct.longdoc": [
        "kernel.gdn_time_share.longdoc",
        "kernel.gdn_roofline_share.longdoc",
        "kernel.gdn_step_roofline_share.longdoc", "step.rank_mfu.longdoc",
        "step.decode_hbm_share.longdoc", "kernel.attn_time_share.longdoc",
        "kernel.moe_time_share.longdoc",
        "kernel.moe_roofline_share.longdoc",
        "moe.experts_touched_share.longdoc", "moe.held_pick_share.longdoc"],
    "dots3-note-prev.longctx": [
        "attn.selected_share.longctx", "cache.bytes_per_live_token.longctx",
        "step.rank_mfu.longctx", "step.decode_hbm_share.longctx",
        "kernel.moe_time_share.longctx",
        "kernel.moe_roofline_share.longctx",
        "moe.experts_touched_share.longctx", "moe.held_pick_share.longctx",
        "kernel.sparse_attn_time_share.longctx",
        "kernel.sparse_attn_roofline_share.longctx",
        "kernel.window_attn_time_share.longctx",
        "kernel.window_attn_roofline_share.longctx"],
    "olmo-hybrid-7b.crowd": [
        "step.rank_mfu.crowd", "step.decode_hbm_share.crowd",
        "kernel.gdn_time_share.crowd",
        "kernel.gdn_step_roofline_share.crowd",
        "kernel.gdn_roofline_share.crowd", "kernel.attn_time_share.crowd",
        "kernel.attn_decode_roofline_share.crowd",
        "cache.state_share.crowd"]}


def test_no_cell_has_lost_a_metric_it_printed_at_pr_55():
    """A later collapse of the list may not drop a cell's metric unseen:
    every pair of PR 55 is still a pair (a later PR adds cells and
    entries, so more is fine), a name is listed once, and the metric whose
    sort no step program holds stays out."""
    held = {(name, cell) for cell, own in OWN_PR55.items()
            for name in EVERY_CELL_PR55 + own}
    assert len(held) == 176 and set(OWN_PR55) <= set(CELLS)
    assert held <= set(PAIRS), sorted(held - set(PAIRS))
    assert len(PAIRS) == len(set(PAIRS))
    # what every cell reports has no list to edit when a cell is added
    by_name = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert not any("workloads" in by_name[n] for n in EVERY_CELL_PR55)
    assert not any(n.startswith("kernel.topk_time_share")
                   for n, _cell in PAIRS)


# ----------------------------------------------------------------- traffic


def _gen(cell_name, seed, tiny=False):
    # a cell is named <configuration>.<traffic mix>
    mix = traffic.load_mix(cell_name.rsplit(".", 1)[1])
    cell = traffic.load_cell(cell_name)
    if tiny:
        mix, cell = {**mix, **mix["tiny"]}, {**cell, **cell["tiny"]}
    return traffic.Generator(mix, cell, 50000, seed), mix, cell


@pytest.mark.parametrize("cell", BUILT_CELLS)
def test_generator_is_deterministic_in_the_seed(cell):
    a, _, _ = _gen(cell, 2_200_000_011)
    b, _, _ = _gen(cell, 2_200_000_011)
    c, _, _ = _gen(cell, 2_200_000_012)
    sa, sb, sc = a.segment(0, False), b.segment(0, False), c.segment(0, False)
    assert [(r.due, r.prompt, r.max_tokens) for r in sa] == \
        [(r.due, r.prompt, r.max_tokens) for r in sb]
    assert [r.prompt for r in sa] != [r.prompt for r in sc]
    # warm-up differs from the window it precedes
    assert [r.prompt[-8:] for r in a.segment(0, True)[:3]] != \
        [r.prompt[-8:] for r in sa[:3]]


@pytest.mark.parametrize("cell", BUILT_CELLS)
def test_every_seed_and_segment_offers_the_same_schedule(cell):
    a, _, _ = _gen(cell, 1)
    b, _, _ = _gen(cell, 3_000_000_019)
    shapes = [[(round(r.due, 9), len(r.prompt), r.max_tokens)
               for r in g.segment(j, warm)]
              for g in (a, b) for j in (0, 1) for warm in (False, True)]
    assert all(s == shapes[0] for s in shapes[1:])
    # and the tokens are the seed's, the segment's and the phase's own
    assert a.segment(0, False)[0].prompt[-8:] != \
        b.segment(0, False)[0].prompt[-8:]
    assert a.segment(0, False)[0].prompt[-8:] != \
        a.segment(1, False)[0].prompt[-8:]


def test_chat_lengths_and_sharing_are_as_stated():
    mix = traffic.load_mix("chat")
    cell = {"segment_s": 100, "rate_per_s": 4.0}
    g = traffic.Generator(mix, cell, 50000, 7)
    reqs = g.segment(0, False)
    assert len(reqs) == 400
    tails = [len(r.prompt) - 256 for r in reqs]
    assert min(tails) >= 64 and max(tails) <= 3072
    assert 350 <= statistics.median(tails) <= 420          # median 384
    outs = [r.max_tokens for r in reqs]
    stated = mix["output"]["tokens"]
    assert stated["lo"] <= min(outs) and max(outs) <= stated["hi"]
    # the clip at ``hi`` takes a little off a geometric mean
    assert 0.85 * stated["mean"] <= statistics.mean(outs) <= \
        1.1 * stated["mean"]
    # 8 system prompts, Zipf 1.2: the first takes 1/2.33 = 43 % of requests
    heads = {}
    for r in reqs:
        heads[tuple(r.prompt[:256])] = heads.get(tuple(r.prompt[:256]), 0) + 1
    assert len(heads) == 8
    assert 0.40 <= max(heads.values()) / 400 <= 0.46
    # arrivals fill the segment and are bursty like a Poisson process
    dues = [r.due for r in reqs]
    assert 0 <= min(dues) and max(dues) < 100
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    assert 0.8 <= statistics.pstdev(gaps) / statistics.mean(gaps) <= 1.2


def test_docqa_documents_are_asked_again_with_the_document_resent():
    mix = traffic.load_mix("docqa")
    g = traffic.Generator(mix, {"segment_s": 100, "rate_per_s": 1.0},
                          50000, 11)
    reqs = g.segment(0, False)
    by_doc = {}
    for r in reqs:
        by_doc.setdefault(r.source, []).append(r)
    assert len(by_doc) == 100
    for turns in by_doc.values():
        turns.sort(key=lambda r: r.turn)
        assert 3 <= len(turns) <= 5
        doc = len(turns[0].prompt) - 128
        shared = os.path.commonprefix([t.prompt for t in turns])
        assert 2048 <= len(shared) <= 6144 and len(shared) >= doc
        for a, b in zip(turns, turns[1:]):
            assert 2.0 <= b.due - a.due <= 6.0
        for t in turns:
            assert 32 <= len(t.prompt) - len(shared) + 1 <= 129
            assert mix["output"]["tokens"]["lo"] <= t.max_tokens <= \
                mix["output"]["tokens"]["hi"]
    assert g.lead_segments() == 1          # 24 s of life in 100 s segments


def test_closed_loop_segment_is_one_request_per_client():
    g, mix, cell = _gen("qwen3-4b.batch", 5)
    reqs = g.segment(0, False)
    assert g.closed and len(reqs) == cell["clients"]
    assert all(r.max_tokens == mix["output"]["tokens"]["value"]
               for r in reqs)
    assert all(128 <= len(r.prompt) <= 512 for r in reqs)


@pytest.mark.parametrize("last_token, now, armed, want", [
    (10.0, 10.3, 9.0, False),    # the burst is still fresh
    (10.0, 10.7, 9.0, True),     # quiet for quiet_s behind a burst
    (8.6, 9.5, 9.0, False),      # that burst ended before the arming
    (8.8, 9.5, 9.0, True),       # ... unless only just: it carried the answer
    (-1.0, 5.0, 0.0, False),     # nothing has streamed yet
])
def test_a_closed_loop_window_starts_behind_a_burst(last_token, now, armed,
                                                     want):
    import loadgen
    c = loadgen.Client("http://127.0.0.1:1", "m")
    c.last_token, c.now = last_token, lambda: now
    assert c.settled(armed, 0.6) is want


def test_the_closed_loop_cell_names_where_its_window_starts():
    cell = traffic.load_cell("qwen3-4b.batch")
    # behind the ramp by a count of answers, not by a time (PERF.md 2)
    assert cell["warm_requests"] > 0 and cell["quiet_s"] > 0
    assert cell["stagger_s"] * cell["clients"] < cell["segment_s"]


def test_quantiles_clip_and_zipf_rounds_to_n():
    d = {"dist": "lognormal", "median": 100, "sigma": 2.0, "lo": 10,
         "hi": 500}
    assert traffic.quantile(d, 0.001) == 10
    assert traffic.quantile(d, 0.999) == 500
    assert traffic.quantile(d, 0.5) == pytest.approx(100)
    import random
    assert len(traffic.zipf_choices(8, 1.2, 37, random.Random(0))) == 37


# ------------------------------------------------------- the trace reduction


def _planes():
    """A small recorded trace: two dispatches on one device, the launcher's
    slice annotation on a host thread, times in ns."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "recorded_trace.json")) as f:
        return json.load(f)


def test_xplane_reduction_on_a_recorded_trace():
    red = xplane.reduce(_planes())
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(1.0)
    # ops: [0.10,0.30] [0.25,0.40] overlap -> 0.30; [0.60,0.90] -> 0.30;
    # the op that starts before the slice is cut to it: [0.0,0.05] -> 0.05
    assert red["busy_s"] == pytest.approx(0.65)
    assert red["ops"][0][0] == "%fusion.7 fusion bf16[8,128]{1,0}" and \
        red["ops"][0][1] == pytest.approx(0.30)
    # the loop that spans its body's operations is busy time, not an
    # operation; the Pallas kernel is marked
    assert not any("while" in o[0] for o in red["ops"])
    assert [o[0] for o in red["ops"] if o[0].endswith("[mosaic]")] == \
        ["%_paged_decode.8 custom-call bf16[32,32,128]{2,1,0} [mosaic]"]
    gaps = sorted((round(s, 3), round(d, 3)) for _k, s, d in red["gaps"])
    assert gaps == [(0.05, 0.05), (0.4, 0.2), (0.9, 0.1)]
    assert [m[0] for m in red["modules"]] == ["jit_multistep", "jit_mixed"]


def test_merge_and_missing_device_plane():
    assert xplane.merge([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    with pytest.raises(ValueError):
        xplane.reduce([{"name": "/host:CPU", "lines": []}])


def test_idle_gaps_are_attributed_from_the_ring():
    base = {"queue_depth": 1, "running": 4, "rows": 4, "unpack_ms": 0.0}
    recs = [
        dict(base, t_unix=100.10, dispatch_ms=50.0, gap_ms=0.0, plan_ms=1.0,
             kind="mixed", unpack_ms=20.0),
        dict(base, t_unix=100.30, dispatch_ms=100.0, gap_ms=100.0,
             plan_ms=10.0, kind="multistep"),
    ]
    ends = [r["t_unix"] for r in recs]
    # second record: host gap [100.10, 100.20], dispatch [100.20, 100.30]
    assert breakdown.attribute(100.11, recs, ends) == "unpack"
    assert breakdown.attribute(100.15, recs, ends) == \
        "host between dispatches"
    assert breakdown.attribute(100.195, recs, ends) == "plan"
    assert breakdown.attribute(100.25, recs, ends) == "in dispatch multistep"
    assert breakdown.attribute(100.02, recs, ends) == "waiting for a request"
    assert breakdown.attribute(101.0, recs, ends) == "unattributed"


def test_peaks_and_bytes_from_shapes():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")
    qwen = modeldir.load_config("qwen3-4b")["hf"]
    # 36 x (2560x4096 + 2x2560x1024 + 4096x2560 + 3x2560x9728) + 151936x2560
    assert peaks.weight_bytes(qwen, "bfloat16") == 2 * (
        36 * (2560 * 4096 * 2 + 2 * 2560 * 1024 + 3 * 2560 * 9728)
        + 151936 * 2560)
    assert peaks.kv_bytes_per_token(qwen, "bfloat16") == 144 * 1024
    ds = modeldir.load_config("dsv2lite")["hf"]
    assert peaks.kv_bytes_per_token(ds, "bfloat16") == 14 * 1024
    assert 7.5e9 < peaks.weight_bytes(ds, "bfloat16") < 8.1e9


# ------------------------------------------------------------ the references


def _program_logprobs(hf, tokens):
    """The program's own no-cache scoring path on the same weights."""
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf(hf, dtype="float32")
    fam = get_family(cfg)
    params = fam.init_params(cfg, jax.random.PRNGKey(0))
    T = len(tokens)
    ps, P = 4, -(-T // 4) + 1
    pages = fam.make_pages(cfg, P + 1, ps)
    table = jnp.arange(1, P + 1, dtype=jnp.int32)[None, :]
    toks = jnp.asarray(tokens, jnp.int32)[None, :]
    pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    out = []
    # prefill t tokens, read the logits at the last: position by position
    for t in (T // 2, T):
        res = fam.forward(params, cfg, toks[:, :t], pos[:, :t], pages, table,
                          jnp.asarray([t], jnp.int32),
                          jnp.asarray([t], jnp.int32))
        logits = res[0]
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
    return params, out


def _load(name, path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_shipped_configurations_are_scored_by_the_next_token_rule():
    """The seam's default: the configurations without a ``probe`` block (so
    the cache keys and the request bodies are the parent's) are scored by
    the next-token rule - the seven of PR 55 at the least, a later one's
    too - and none of the three reference modules the seam shipped with
    exports a ``score``; ``sdar-30b-a3b-chat`` alone has a rule of its
    own. The agreement test below holds a case for each."""
    assert set(DEFAULT_RULE_CONFIGS) >= {
        "dots3-note-prev", "dsv2lite", "joyai-llm-flash",
        "longcat-flash-omni", "olmo-hybrid-7b", "qwen3-4b",
        "qwen3-next-80b-a3b-instruct"}
    assert "sdar-30b-a3b-chat" not in DEFAULT_RULE_CONFIGS
    for family in ("llama", "deepseek", "joyai"):
        with open(os.path.join(BENCH, "reference", family + ".py")) as f:
            assert not re.search(r"^(def score\b|score\s*=)", f.read(), re.M)
    assert {modeldir.load_config(c)["bench"]["reference"]
            for c in DEFAULT_RULE_CONFIGS} >= {"llama", "deepseek", "joyai"}


@pytest.mark.parametrize("config", DEFAULT_RULE_CONFIGS)
def test_the_default_rule_is_the_next_token_rule(config):
    """``next_token_rule``, fed a toy sequence as the child feeds it, returns
    for continuation token j the reference's row at position
    ``len(prompt) - 1 + j`` of one clean pass over prompt + continuation:
    what the child wrote before the rule had a name."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dynamo_tpu.models import get_family
    from dynamo_tpu.models.config import ModelConfig

    c = modeldir.load_config(config, tiny=True)
    hf = c["hf"]
    ref = _load("ref_rule_" + config.replace("-", "_"), os.path.join(
        BENCH, "reference", c["bench"]["reference"] + ".py"))
    score_py = _load("reference_score", os.path.join(BENCH, "reference",
                                                     "score.py"))
    cfg = ModelConfig.from_hf(hf, dtype="float32")
    params = get_family(cfg).init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(5).integers(0, hf["vocab_size"],
                                               size=29).tolist()
    prompt, cont = tokens[:21], tokens[21:]
    layer_fns = {kind: jax.jit(lambda w, h, fn=fn: fn(hf, w, h))
                 for kind, fn in ref.LAYER_FNS.items()}
    with jax.default_matmul_precision("highest"):
        got = score_py.next_token_rule(ref, hf, params)(
            hf, params, layer_fns, prompt, cont, {})
        h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for kind, stack, n in ref.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                h = ref.LAYER_FNS[kind](hf, w, h)
        want = jax.nn.log_softmax(ref.head(hf, params, h), axis=-1)
    assert got.shape == (len(cont), hf["vocab_size"])
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[20:28]),
                               atol=1e-5, rtol=0)
    # one position off is another answer: the rows are told apart
    assert float(jnp.max(jnp.abs(got - want[21:29]))) > 1e-2


@pytest.mark.parametrize("config", DEFAULT_RULE_CONFIGS)
def test_reference_agrees_with_the_programs_family_at_toy_size(config):
    import importlib.util
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = modeldir.load_config(config, tiny=True)
    hf = c["hf"]
    spec = importlib.util.spec_from_file_location(
        "ref_" + config.replace("-", "_"),
        os.path.join(BENCH, "reference", c["bench"]["reference"] + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    tokens = np.random.default_rng(3).integers(0, hf["vocab_size"],
                                               size=37).tolist()
    params, served = _program_logprobs(hf, tokens)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for kind, stack, n in ref.layers(params):
            for i in range(n):
                w = jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                h = ref.LAYER_FNS[kind](hf, w, h)
        logp = jax.nn.log_softmax(ref.head(hf, params, h), axis=-1)
    T = len(tokens)
    for t, got in zip((T // 2, T), served):
        np.testing.assert_allclose(np.asarray(logp[t - 1]), np.asarray(got),
                                   atol=2e-4, rtol=0)
    # and it is a real check: dropping a layer moves log-probabilities
    kind, stack, n = ref.layers(params)[-1]
    w = jax.tree_util.tree_map(lambda a: a[n - 1], stack)
    assert float(jnp.max(jnp.abs(ref.LAYER_FNS[kind](hf, w, h) - h))) > 1e-2


# ------------------------------------------------- whose rule decides correct


def _chunk(ids, lps, **more):
    return dict({"tokens": [f"<{i}>" for i in ids], "token_logprobs": lps,
                 "top_logprobs": [{f"<{i}>": v} for i, v in zip(ids, lps)]},
                **more)


def test_served_keeps_what_the_configuration_carries_per_token():
    import correctness
    from served import Failed
    ids = list(range(100, 116))
    chunks = [_chunk(ids[:10], [-1.0] * 10, text_offset=list(range(10)),
                     revealed_in=[0] * 10),
              _chunk(ids[10:], [-2.0] * 6, text_offset=list(range(10, 16)),
                     revealed_in=[1] * 6)]
    plain = correctness._served(chunks)
    assert plain["ids"] == ids and plain["carried"] == {}
    got = correctness._served(chunks, ["revealed_in"])
    assert (got["ids"], got["lps"], got["top"]) == (
        plain["ids"], plain["lps"], plain["top"])
    assert got["carried"] == {"revealed_in": [0] * 10 + [1] * 6}
    # one entry for each token of its chunk, or the probe has failed
    chunks[1]["revealed_in"] = [1] * 5
    with pytest.raises(Failed, match="revealed_in"):
        correctness._served(chunks, ["revealed_in"])
    with pytest.raises(Failed, match="no_such_key"):
        correctness._served(chunks, ["no_such_key"])


def test_a_configuration_without_the_block_keeps_the_parents_cache_keys():
    import hashlib
    import numpy as np
    import correctness
    tokens = [5, 151935, 0, 77]
    parents = hashlib.sha256(np.asarray(tokens, np.int64).tobytes()
                             ).hexdigest()
    assert correctness._key(tokens) == parents
    assert correctness._key(tokens, {}) == parents
    carried = correctness._key(tokens, {"revealed_in": [0, 0, 1, 1]})
    assert carried != parents
    assert carried != correctness._key(tokens, {"revealed_in": [0, 1, 1, 1]})
    assert carried == correctness._key(tokens, {"revealed_in": [0, 0, 1, 1]})


def test_the_probes_ask_what_the_configurations_file_says():
    import asyncio
    import types
    import correctness

    class Client:
        def __init__(self):
            self.extras = []

        def now(self):
            return 0.0

        async def send(self, r, extra=None):
            self.extras.append(extra)
            r.ok = True
            return [_chunk(list(range(16)), [-1.0] * 16,
                           text_offset=list(range(16)))]

    config = modeldir.load_config("qwen3-4b", tiny=True)
    run = types.SimpleNamespace(config=config)
    client = Client()
    asyncio.run(correctness.send_probes(run, client))
    assert client.extras == [{"logprobs": 5}] * 8        # the parent's body
    assert all(p[w]["carried"] == {} for p in run.probes
               for w in ("cold", "cached"))
    config["bench"]["probe"] = {"extra": {"nvext": {"passes": 3},
                                          "logprobs": 1},
                                "carry": ["text_offset"]}
    client = Client()
    asyncio.run(correctness.send_probes(run, client))
    assert client.extras == [{"nvext": {"passes": 3}, "logprobs": 5}] * 8
    assert run.probes[0]["cold"]["carried"] == {
        "text_offset": list(range(16))}


def _judged(monkeypatch, cold_carried, cached_carried, cached_lps):
    """``judge`` on one stub probe whose reference agrees with the cold
    pass to the digit; returns (correct, the result block, the sequences the
    reference was asked for)."""
    import types
    import correctness
    ids = list(range(200, 216))
    cold_lps = [-1.0 - 0.01 * i for i in range(16)]

    def served(lps, carried):
        return {"ids": ids, "lps": lps, "top": [{} for _ in ids],
                "carried": carried}
    run = types.SimpleNamespace(
        config={"bench": {"dtype": "float32"}},
        probes=[{"prompt": [1, 2, 3], "cold": served(cold_lps, cold_carried),
                 "cached": served(cached_lps, cached_carried)}])
    asked = []

    def scores(_run, sequences):
        asked.extend(sequences)
        out = {}
        for p, c, k in sequences:
            lps = cold_lps if k == cold_carried else cached_lps
            out[correctness._key(p + c, k)] = [
                {str(i): v} for i, v in zip(c, lps)]
        return out
    monkeypatch.setattr(correctness, "reference_scores", scores)
    return correctness.judge(run), run.probe_result, asked


def test_judge_compares_cold_and_cached_only_while_they_carried_the_same(
        monkeypatch):
    cold_lps = [-1.0 - 0.01 * i for i in range(16)]
    # position 9 on, the cached pass says something else (0.5 nats off)
    moved = cold_lps[:9] + [v - 0.5 for v in cold_lps[9:]]
    # nothing carried: the same tokens are the same context, and 0.5 fails
    ok, result, asked = _judged(monkeypatch, {}, {}, moved)
    assert not ok and result["cold_vs_cached_max_nats"] == pytest.approx(0.5)
    assert len(asked) == 1               # one sequence, scored once
    # the same values carried: nothing changes
    same = {"revealed_in": [0] * 8 + [1] * 8}
    ok, result, asked = _judged(monkeypatch, same, dict(same), moved)
    assert not ok and result["cold_vs_cached_max_nats"] == pytest.approx(0.5)
    assert len(asked) == 1
    # the cached pass revealed position 9 in another pass: from there on
    # the two conditioned on different inputs, and the comparison ends
    other = {"revealed_in": [0] * 8 + [1] + [2] * 7}
    ok, result, asked = _judged(monkeypatch, same, other, moved)
    assert ok and result["cold_vs_cached_max_nats"] == 0.0
    assert result["served_vs_reference_max_nats"] == 0.0
    assert len(asked) == 2               # each scored under what it carried
    assert result["logprobs_compared"] == 32


def test_judge_holds_the_mean_gap_to_the_configurations_own_limit(
        monkeypatch):
    """Every served log-probability 0.05 nats off its reference: the widest
    gap is far inside bfloat16's 0.3, and only a configuration that states
    a limit for the mean (between a clean run's and the int8 control's,
    PERF.md PR 36) sees it."""
    import types
    import correctness
    ids = list(range(300, 316))
    served = {"ids": ids, "lps": [-1.0] * 16, "top": [{} for _ in ids],
              "carried": {}}
    monkeypatch.setattr(
        correctness, "reference_scores", lambda _run, seqs: {
            correctness._key(p + c, k): [{str(i): -1.05} for i in c]
            for p, c, k in seqs})

    def judged(bench):
        run = types.SimpleNamespace(config={"bench": bench}, probes=[
            {"prompt": [7, 8], "cold": served, "cached": served}])
        return correctness.judge(run), run.probe_result
    ok, result = judged({"dtype": "bfloat16"})
    assert ok and result["reference_mean_tol"] is None
    assert result["served_vs_reference_mean_nats"] == pytest.approx(0.05)
    ok, result = judged({"dtype": "bfloat16",
                         "reference_mean_tol": {"bfloat16": 0.03}})
    assert not ok and result["reference_mean_tol"] == 0.03
    assert result["served_vs_reference_max_nats"] == pytest.approx(0.05)
    ok, _ = judged({"dtype": "bfloat16",
                    "reference_mean_tol": {"bfloat16": 0.06}})
    assert ok
    # the limit is the stated dtype's: the tiny float32 overlay has none
    for config in ("qwen3-4b", "joyai-llm-flash"):
        stated = modeldir.load_config(config)["bench"]
        assert 0 < stated["reference_mean_tol"]["bfloat16"] < 0.1
        tiny = modeldir.load_config(config, tiny=True)["bench"]
        assert tiny["dtype"] not in tiny["reference_mean_tol"]
