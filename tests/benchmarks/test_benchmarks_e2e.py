"""End-to-end CPU tests of the benchmark: each traffic mix's cell at toy
widths through the served path (``--tiny``: coordinator, worker and frontend
as processes on the CPU backend), the contract's last line checked; what a
run does without a TPU or without the program; a later PR's cell and
metric added with files alone; and a later PR's configuration whose served
tokens its own reference module scores, added with files alone."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)

import traffic  # noqa: E402
from layer_metrics import listed  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _run(args, cwd=REPO, env=None):
    e = dict(os.environ if env is None else env)
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py")] + args,
        cwd=cwd, env=e, capture_output=True, text=True, timeout=900)


def _check_line(out, traced, cell):
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0, out.stderr[-2000:]
    assert line["device"]["platform"] == "cpu"
    for key in ("kind", "count", "memory_peak_bytes"):
        assert key in line["device"]
    declared = listed(BENCHMARK, "per_layer" if traced else "end_to_end",
                      cell)
    assert set(line["metrics"]) <= set(declared)
    for name, m in line["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], (int, float))
    if traced:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
        assert line["metrics"], "a traced run reports a per-layer metric"
    else:
        assert set(line["metrics"]) == set(declared)
        assert all(m["value"] > 0 for m in line["metrics"].values())
    return line


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [0, 1])
def test_tiny_cell_runs_end_to_end_on_the_cpu(cell, traced):
    out = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "4",
                "--trace", str(traced), "--tiny"])
    _check_line(out, traced, cell)


@pytest.mark.parametrize("cell", ["qwen3-4b.chat", "dsv2lite.docqa"])
def test_a_built_cell_kept_for_a_later_pr_still_runs(cell, tmp_path):
    """The two open-loop cells are built and were run on the chip, and wait
    under Open questions in PERF.md: listed in a copy of BENCHMARK.json as a
    later PR would list them, each runs end to end at toy widths."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu")
    b = copy.deepcopy(BENCHMARK)
    config, mix = cell.rsplit(".", 1)
    b["workloads"].append({"name": cell, "config": config, "traffic": mix,
                           "chips": 1, "why": "kept for a later PR"})
    b["end_to_end"].append({
        "name": "answer_mean_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    out = _run(["--workload", cell, "--seed", "11", "--seconds", "4",
                "--trace", "0", "--tiny"], cwd=str(root))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["answer_mean_ms"]["value"] > 0


def test_a_run_without_a_tpu_fails_and_prints_no_line():
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                "--trace", "0"],
               env=dict(os.environ, JAX_PLATFORMS="", TPU_SKIP_MDS_QUERY="1"))
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_the_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                "--trace", "0", "--tiny"], cwd=str(tmp_path))
    assert out.returncode != 0 and not out.stdout.strip()


def test_a_later_pr_adds_a_cell_and_a_metric_with_files_alone(tmp_path):
    """A temp copy of the repo's benchmark gains a traffic mix, a cell, an
    end-to-end-neutral per-layer metric and their BENCHMARK.json entries -
    new files only, no edit to a file that was there - and runs."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu")
    b = copy.deepcopy(BENCHMARK)
    b["workloads"].append({
        "name": "qwen3-4b.dummy", "config": "qwen3-4b", "traffic": "dummy",
        "chips": 1, "why": "a test's cell: short unique prompts"})
    for m in b["end_to_end"] + b["per_layer"]:
        m.setdefault("workloads", list(CELLS))
    b["end_to_end"].append({
        "name": "answer_mean_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["qwen3-4b.dummy"]})
    next(m for m in b["end_to_end"] if m["name"] == "setup_s")[
        "workloads"].append("qwen3-4b.dummy")
    b["per_layer"].append({
        "name": "dummy.requests_ok", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "load generator (benchmarks/"
        "loadgen.py)", "moves": "answer_mean_ms",
        "workloads": ["qwen3-4b.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    chat = traffic.load_mix("chat")
    (root / "benchmarks/traffic/dummy.json").write_text(json.dumps(
        {"loop": "open", "tail": chat["tiny"]["tail"],
         "output": chat["tiny"]["output"], "lifetime_s": 0, "tiny": {}}))
    cell = traffic.load_cell("qwen3-4b.chat")
    (root / "benchmarks/cells/qwen3-4b.dummy.json").write_text(
        json.dumps(cell))
    (root / "benchmarks/layer_metrics/dummy.requests_ok.py").write_text(
        "def compute(run):\n"
        "    return sum(r.ok for r in run.requests)\n")
    out = _run(["--workload", "qwen3-4b.dummy", "--seed", "9", "--seconds",
                "3", "--trace", "1", "--tiny"], cwd=str(root))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"]["dummy.requests_ok"]["value"] == \
        line["attempted"] > 0


OWN_RULE = '''"""A scratch configuration's rule, in its own words: the
model reads prompt and continuation once, clean and causal, and the token
at position t is scored by the logits that came out at position t - 1. It
wants ``text_offset`` carried: one entry a served token, each the characters
streamed before it."""

import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "ownrule_llama", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "llama.py"))
_llama = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_llama)
LAYER_FNS, layers, head = _llama.LAYER_FNS, _llama.layers, _llama.head
READ_AT = -1          # the position before the token's own


def score(hf, params, layer_fns, prompt, continuation, carried):
    offsets = carried["text_offset"]
    assert len(offsets) == len(continuation), (offsets, continuation)
    assert offsets[0] == 0 and all(
        b - a >= len(f"<{t}>")
        for a, b, t in zip(offsets, offsets[1:], continuation)), offsets
    tokens = jnp.asarray(prompt + continuation, jnp.int32)
    h = params["embed"][tokens].astype(jnp.float32)
    for kind, stack, n in layers(params):
        for i in range(n):
            w = jax.tree_util.tree_map(
                lambda a, i=i: a[i].astype(jnp.float32), stack)
            h = layer_fns[kind](w, h)
    logp = jax.nn.log_softmax(head(hf, params, h), axis=-1)
    at = [len(prompt) + j + READ_AT for j in range(len(continuation))]
    return logp[jnp.asarray(at)]
'''


@pytest.mark.parametrize("read_at, correct", [(-1, True), (0, False)])
def test_a_later_pr_adds_a_configuration_with_a_rule_of_its_own(
        tmp_path, read_at, correct):
    """A temp copy of the repo's benchmark gains a configuration whose file
    carries a key of the streamed ``logprobs`` per token (``probe.carry``), a
    reference module that exports ``score``, a cell and their BENCHMARK.json
    entries - new files only - and the tiny run is correct. The same with a
    rule that reads one position off is not: the seam cannot pass a wrong
    rule."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu")
    before = {str(p.relative_to(root)): p.read_bytes()
              for p in (root / "benchmarks").rglob("*") if p.is_file()}
    with open(os.path.join(BENCH, "configs", "qwen3-4b.json")) as f:
        config = json.load(f)
    config["benchmark"].update(
        reference="ownrule", served_name="toy-own-rule",
        probe={"extra": {"echo": False}, "carry": ["text_offset"]})
    (root / "benchmarks/configs/toy-own-rule.json").write_text(
        json.dumps(config))
    (root / "benchmarks/reference/ownrule.py").write_text(
        OWN_RULE.replace("READ_AT = -1", f"READ_AT = {read_at}"))
    shutil.copy(os.path.join(BENCH, "cells", "qwen3-4b.batch.json"),
                root / "benchmarks/cells/toy-own-rule.batch.json")
    b = copy.deepcopy(BENCHMARK)
    b["configs"].append({
        "name": "toy-own-rule", "source": "a test's configuration",
        "file": "benchmarks/configs/toy-own-rule.json", "reduced": [],
        "why": "served tokens scored by its reference module's own rule"})
    b["workloads"].append({
        "name": "toy-own-rule.batch", "config": "toy-own-rule",
        "traffic": "batch", "chips": 1, "why": "a test's cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    out = _run(["--workload", "toy-own-rule.batch", "--seed", "2400000011",
                "--seconds", "3", "--trace", "0", "--tiny"], cwd=str(root))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct and line["failed"] == 0, line
    assert line["probes"]["logprobs_compared"] == 768
    # float32 at toy widths: rounding against a wrong row's 0.05 nats
    worst = line["probes"]["served_vs_reference_max_nats"]
    assert (worst <= 2e-3) if correct else (worst > 0.02), line["probes"]
    # the child ran the module's rule and kept its scores under keys that
    # hold what was carried; no file that was there was touched
    with open(root / "benchmarks/.cache/reference/toy-own-rule-tiny.json"
              ) as f:
        assert len(json.load(f)) >= 4
    assert {k: (root / k).read_bytes() for k in before} == before


def test_the_control_in_the_next_precision_down_is_not_correct(tmp_path):
    """The control of ``correct``, at a size a test can hold: the program's
    own lower-precision path in the program's place. The tiny configuration
    states float32 (reference and limit, 2e-3 nats); served in bfloat16, the
    nearest precision below, the same run is not correct."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.
                    ignore_patterns(".runs", ".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "dynamo_tpu"), root / "dynamo_tpu")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    path = root / "benchmarks/configs/qwen3-4b.json"
    config = json.loads(path.read_text())
    # the worker takes the last --dtype it is given
    config["benchmark"]["tiny"]["worker_args"] += ["--dtype", "bfloat16"]
    path.write_text(json.dumps(config))
    out = _run(["--workload", "qwen3-4b.batch", "--seed", "2600000017",
                "--seconds", "3", "--trace", "0", "--tiny"], cwd=str(root))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0, line
    probes = line["probes"]
    assert probes["reference_tol"] == 2e-3
    # the clean run reads 2e-6 (summation order): the limit lies between
    assert probes["served_vs_reference_max_nats"] > 2e-3, probes
    # each number compared stands beside its limit, last on standard error
    assert "served_vs_reference_max_nats" in out.stderr.splitlines()[-1]
    assert list(line)[-1] == "probes"


def test_the_reference_side_control_reads_a_gap_at_toy_size():
    """``reference/control.py``, the builder's tool that puts the reference
    in the next precision down in the program's place: at toy size it runs,
    names what it compared, and reads a gap far above a clean run's 2e-6."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference", "control.py"),
         "qwen3-4b", "--tiny", "--seeds", "1"], cwd=REPO,
        env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert (line["stated"], line["control"]) == ("float32", "bfloat16")
    assert line["limit"] == 2e-3 and len(line["readings"]) == 1
    assert line["readings"][0] > 1e-4
    assert line["control_fails"] is (line["readings"][0] > 2e-3)
