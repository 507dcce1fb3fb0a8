"""End-to-end CPU tests of the benchmark: each traffic mix's cell at toy
widths through the served path (``--tiny``: coordinator, worker and frontend
as processes on the CPU backend), the contract's last line checked; what a
run does without a TPU or without the program; and a later PR's cell and
metric added with files alone."""

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
    group = BENCHMARK["per_layer" if traced else "end_to_end"]
    declared = {m["name"]: m for m in group
                if cell in m.get("workloads", [cell])}
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
