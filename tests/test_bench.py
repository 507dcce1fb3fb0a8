"""bench.py: one process runs the legs it is asked for on the device it
finds. ``--tiny`` is the explicit toy-model run the suite uses to drive
every default leg on the CPU; without it the run needs a TPU and must exit
non-zero here.
"""

import json
import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def test_full_size_run_refuses_the_cpu():
    r = subprocess.run([sys.executable, BENCH, "--legs", "engine"],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == b""      # no result line
    assert b"need a TPU" in r.stderr


def test_single_process_tiny_chain():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # short long-context leg so the smoke chain stays inside its budget
    # (the default 4k/16k/32k curve is the real bench's)
    env["BENCH_LONGCTX"] = "4096,8192"
    # short fleet phases so the supervisor leg (a ~30s trace at the real
    # bench's defaults) stays inside the smoke chain's budget
    env["BENCH_FLEET_PHASES"] = "2rps:4s,10rps:8s,2rps:5s"
    # short routing leg (fewer requests per A/B side, milder stall) so the
    # cost-vs-RR comparison stays inside the smoke chain's budget
    env["BENCH_ROUTING_REQS"] = "16"
    env["BENCH_ROUTING_STALL"] = "0.25,0.4"
    # short steptrace leg (fewer generated tokens)
    env["BENCH_STEPTRACE_GEN"] = "24"
    # short shared-prefix leg (fewer requests/groups, shorter prefixes)
    # so the three-arm hot/cold-on/cold-off comparison stays inside the
    # smoke chain's budget
    env["BENCH_SHARED_REQS"] = "6"
    env["BENCH_SHARED_GROUPS"] = "2"
    env["BENCH_SHARED_BLOCKS"] = "24"
    r = subprocess.run([sys.executable, BENCH, "--tiny"],
                       env=env, capture_output=True, timeout=380)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    line = r.stdout.decode().strip().splitlines()[-1]
    result = json.loads(line)
    stderr = r.stderr.decode()
    # every step program primed before the measurement, in this process
    for prog in ("prefill", "decode", "chained", "multistep"):
        assert f'"program": "{prog}"' in stderr, stderr[-2000:]
    assert '"stage": "measured"' in stderr
    assert result["value"] > 0
    # the run names the device it ran on, and claims no roofline share
    # for a CPU run
    assert result["platform"] == "cpu"
    assert result["device_kind"] and result["device_count"] >= 1
    assert result["tiny"] is True
    assert result["vs_baseline"] is None
    # decode dispatch fusion: the width, the fused run's dispatches per
    # token (must beat one-dispatch-per-token), and the same-run
    # fused-vs-per-step A/B all land in the result JSON
    assert result["decode_multistep"] >= 2
    assert 0 < result["decode_dispatches_per_token"] < 1.0
    ab = result["decode_ab"]
    assert ab["fused_tok_s"] > 0 and ab["perstep_tok_s"] > 0
    assert ab["fused_speedup"] > 0
    # coordinator-failover leg: primary kill -9 mid-trace must lose no
    # streams and re-grant no leases (same-epoch probe path)
    cf = result["coord_failover"]
    assert cf["streams_lost"] == 0
    assert cf["lease_regrants"] == 0
    assert 0 < cf["ready_s"] < cf["pr3_cold_restart_ref_s"]
    # fleet-supervisor leg: planner scale-up on the burst, worker kill -9
    # auto-healed, coordinator kill -9 absorbed, drain scale-down — and
    # not one stream lost across any of those events
    fl = result["fleet"]
    assert fl["streams_lost"] == 0, fl
    assert fl["completed"] == fl["requests"] - fl["shed"]
    assert fl["replicas_peak"] >= 2
    assert fl["healed_crashes"] >= 1
    assert fl["crash_loop_holds"] == 0
    assert fl["drained_to"] == 1
    assert fl["decisions_up"] >= 1 and fl["decisions_down"] >= 1
    assert fl["promote_s"] is not None and fl["promote_s"] < 10
    assert fl["planner_metrics_on_http"] is True
    # failure-aware routing leg: same-run cost-vs-RR A/B with one worker
    # behind a ChaosProxy tail-latency stall — the cost router must beat
    # round-robin on tail TTFT without losing a stream, open the slow
    # worker's breaker (visible on /metrics), and leave the decision's
    # score inputs retrievable from the flight recorder
    rt = result["routing"]
    assert rt["rr"]["streams_lost"] == 0, rt
    assert rt["cost"]["streams_lost"] == 0, rt
    assert rt["cost"]["ttft_p99_s"] < rt["rr"]["ttft_p99_s"], rt
    assert rt["breaker_opens"] >= 1, rt
    assert rt["hedges"]["fired"] >= 1 and rt["hedges"]["won"] >= 1, rt
    assert rt["breaker_metric_seen"] is True
    assert rt["trace_attrs_ok"] is True
    # step flight recorder leg: a warmed-shape rerun must produce ZERO
    # compile events (no false positives), and the deliberately cold
    # cohort must surface mid-trace compiles attributable to StepRecords
    stp = result["steptrace"]
    assert stp["compile"]["warm_rerun_events"] == 0, stp
    assert stp["compile"]["midrun_events"] >= 1, stp
    assert stp["compile"]["compile_records"] >= 1, stp
    assert "prefill" in stp["compile"]["compile_kinds"], stp
    assert stp["aggregates"]["records"] > 0
    assert stp["aggregates"]["occupancy_samples"] > 0
    assert stp["aggregates"]["gap_samples"] > 0
    # fleet-wide KV reuse leg: the cold index-on worker really onboarded
    # its prefixes over G4 peer pulls (blocks + bytes recorded, the
    # admission_onboard kv_transfer spans landed in the flight recorder)
    # against a populated global index. TTFT RATIOS are the artifact
    # run's acceptance (BENCH_shared_prefix_r11.json) — on a loaded CI
    # box with the smoke's one-chunk prompts, wall-clock ratios jitter,
    # so the smoke pins the structure, not the separation
    sp = result["shared_prefix"]
    assert sp["hot_ttft_p50_s"] > 0, sp
    assert sp["cold_on_ttft_p50_s"] > 0 and sp["cold_off_ttft_p50_s"] > 0
    assert sp["first_touch"] >= 1, sp
    assert sp["peer_onboarded_blocks"] > 0, sp
    assert sp["peer_onboarded_bytes"] > 0, sp
    assert sp["index_workers"] >= 1 and sp["index_blocks"] > 0, sp
    assert sp["onboard_spans"] >= 1, sp
    assert "cold_within_1p5x_hot" in sp and "on_beats_off" in sp
    # the continuous-arrival mixed-vs-legacy A/B ran on both engines.
    # jax sub-leg: CPU dispatch overhead is ~0, so only liveness is
    # asserted (the throughput separation is the on-chip/mocker story).
    ma = result["mixed_arrivals"]
    for sub in ("jax", "mocker"):
        leg = ma[sub]
        assert leg["mixed"]["tok_s"] > 0 and leg["legacy"]["tok_s"] > 0
        assert leg["mixed"]["mixed_dispatches"] > 0
        assert leg["legacy"]["mixed_dispatches"] == 0
        # the lifted gate: fused blocks stayed active under arrivals
        assert leg["mixed"]["fused_blocks"] > 0
    # mocker sub-leg prices dispatches with the v5e cost model: mixed
    # must beat the legacy alternation on dispatches per token (the
    # deterministic-ish policy effect; tok/s is asserted loosely since
    # wall-clock sleeps jitter on a loaded CI box)
    mm = ma["mocker"]
    assert mm["mixed"]["decode_dispatches_per_token"] \
        < mm["legacy"]["decode_dispatches_per_token"]
    assert mm["mixed"]["tok_s"] > mm["legacy"]["tok_s"] * 0.9
    assert ab["perstep_dispatches_per_token"] > \
        result["decode_dispatches_per_token"]
    # all four host transport planes measured (bulk, wire, inject, e2e);
    # the device-direct plane is best-effort (None when the backend's
    # client lacks the transfer server) but the key must be present
    for key in ("kv_inject_gbps", "kv_wire_gbps", "kv_bulk_gbps",
                "kv_e2e_gbps"):
        assert result[key] > 0, key
    assert "kv_direct_gbps" in result
    # long-context tiering leg: ttft_vs_context + prefetch_hit_rate land
    # in the result JSON (tier-resident prompts through the packing-
    # prefetch scheduler; the sublinear flag is the acceptance signal)
    lc = result["longctx"]
    assert [p["tokens"] for p in lc["ttft_vs_context"]] == [4096, 8192]
    assert all(p["ttft_s"] > 0 for p in lc["ttft_vs_context"])
    # hit rate is a RACE against the compute cursor — deterministic
    # promotion assertions live in tests/test_kvbm.py; the smoke only
    # pins the recording contract (a loaded CI box can lose the race)
    assert 0.0 <= lc["prefetch_hit_rate"] <= 1.0
    assert "sublinear" in lc and "ttft_scaling" in lc
