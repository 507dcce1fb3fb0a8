"""DynamoGraphDeployment controller tests.

Rendering is pure (CR dict -> manifests); the reconcile loop is exercised
end-to-end against a FAKE kubectl placed on PATH that records every
invocation and serves canned CR/child listings — the same controller code
that would talk to a live API server, no cluster required.
"""

import importlib.util
import json
import os
import stat
import subprocess
import sys

import pytest

_spec = importlib.util.spec_from_file_location(
    "graph_operator", os.path.join(os.path.dirname(__file__), "..",
                                   "deploy", "graph_operator.py"))
operator = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(operator)


def graph_cr(name="g1", services=None, generation=3):
    return {
        "metadata": {"name": name, "generation": generation},
        "spec": {
            "services": services if services is not None else {
                "coord": {"componentType": "coordinator"},
                "fe": {"componentType": "frontend", "replicas": 2},
                "decode": {"componentType": "worker", "replicas": 2,
                           "modelPath": "/models/m", "modelName": "m",
                           "args": ["--tensor-parallel-size", "4"],
                           "resources": {"limits": {"google.com/tpu": "4"}}},
                "pre": {"componentType": "prefill",
                        "modelPath": "/models/m"},
            },
        },
    }


class TestRendering:
    def test_renders_deployments_and_services(self):
        m = operator.render_graph(graph_cr(), "ns1")
        by = {(x["kind"], x["metadata"]["name"]): x for x in m}
        assert ("Deployment", "g1-coord") in by
        assert ("Service", "g1-coord") in by
        assert ("Deployment", "g1-decode") in by
        # workers are headless: no Service
        assert ("Service", "g1-decode") not in by
        dep = by[("Deployment", "g1-decode")]
        assert dep["spec"]["replicas"] == 2
        c = dep["spec"]["template"]["spec"]["containers"][0]
        # coordinator address auto-derived from the coordinator service
        assert "g1-coord:6650" in c["command"]
        assert "--tensor-parallel-size" in c["command"]
        assert c["resources"]["limits"]["google.com/tpu"] == "4"
        # prefill role flags
        pre = by[("Deployment", "g1-pre")]
        cmd = pre["spec"]["template"]["spec"]["containers"][0]["command"]
        assert "--disagg" in cmd and "prefill" in cmd

    def test_labels_and_determinism(self):
        a = operator.render_graph(graph_cr(), "ns1")
        b = operator.render_graph(graph_cr(), "ns1")
        assert json.dumps(a) == json.dumps(b)
        for x in a:
            assert x["metadata"]["labels"][operator.GRAPH_LABEL] == "g1"

    def test_rejects_unknown_component(self):
        cr = graph_cr(services={"x": {"componentType": "gpuworker"}})
        with pytest.raises(ValueError, match="componentType"):
            operator.render_graph(cr, "ns1")


FAKE_KUBECTL = r'''#!/usr/bin/env python3
import json, os, sys
log = os.environ["FAKE_KUBECTL_LOG"]
args = sys.argv[1:]
stdin = ""
if not sys.stdin.isatty():
    try:
        stdin = sys.stdin.read()
    except Exception:
        pass
with open(log, "a") as f:
    f.write(json.dumps({"args": args, "stdin": stdin}) + "\n")
def has(*words):
    return all(w in args for w in words)
if has("apply") and os.environ.get("FAKE_APPLY_FAILS"):
    sys.stderr.write("server unavailable")
    sys.exit(1)
if has("get") and any(a.startswith("dynamographdeployments") for a in args):
    print(open(os.environ["FAKE_CRS"]).read())
elif has("get", "deployment"):
    # children listing: one stale deployment to prune + a live one
    print(json.dumps({"items": [
        {"metadata": {"name": "g1-old"},
         "spec": {"replicas": 1}, "status": {"availableReplicas": 1}},
        {"metadata": {"name": "g1-decode"},
         "spec": {"replicas": 2}, "status": {"availableReplicas": 2}},
        {"metadata": {"name": "g1-coord"},
         "spec": {"replicas": 1}, "status": {"availableReplicas": 1}},
        {"metadata": {"name": "g1-fe"},
         "spec": {"replicas": 2}, "status": {"availableReplicas": 2}},
        {"metadata": {"name": "g1-pre"},
         "spec": {"replicas": 1}, "status": {"availableReplicas": 1}},
    ]}))
elif has("get", "service"):
    print(json.dumps({"items": [
        {"metadata": {"name": "g1-coord"}},
        {"metadata": {"name": "g1-gone"}},
    ]}))
else:
    pass  # apply/delete/patch: just recorded
'''


class TestReconcileLoop:
    def test_full_pass_applies_prunes_and_updates_status(self, tmp_path):
        kdir = tmp_path / "bin"
        kdir.mkdir()
        kubectl = kdir / "kubectl"
        kubectl.write_text(FAKE_KUBECTL)
        kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
        log = tmp_path / "calls.jsonl"
        crs = tmp_path / "crs.json"
        crs.write_text(json.dumps({"items": [graph_cr()]}))

        env = dict(os.environ)
        env["PATH"] = f"{kdir}:{env['PATH']}"
        env["FAKE_KUBECTL_LOG"] = str(log)
        env["FAKE_CRS"] = str(crs)
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                          "deploy", "graph_operator.py"),
             "--once", "--kube-namespace", "ns1"],
            env=env, capture_output=True, timeout=60)
        assert r.returncode == 0, r.stderr.decode()

        calls = [json.loads(line) for line in log.read_text().splitlines()]
        # 1) children applied as one List
        applies = [c for c in calls if c["args"][:1] == ["apply"]]
        assert len(applies) == 1
        applied = json.loads(applies[0]["stdin"])
        names = {(i["kind"], i["metadata"]["name"])
                 for i in applied["items"]}
        assert ("Deployment", "g1-decode") in names
        assert ("Service", "g1-coord") in names
        # 2) stale children pruned, live ones kept
        deletes = [c["args"] for c in calls if "delete" in c["args"]]
        deleted = {(a[a.index("delete") + 1], a[a.index("delete") + 2])
                   for a in deletes}
        assert ("deployment", "g1-old") in deleted
        assert ("service", "g1-gone") in deleted
        assert ("deployment", "g1-decode") not in deleted
        # 3) status subresource patched Ready (all children available)
        patches = [c["args"] for c in calls if "patch" in c["args"]]
        assert any("--subresource=status" in a for a in patches)
        (patch_args,) = [a for a in patches if "--subresource=status" in a]
        body = json.loads(patch_args[patch_args.index("-p") + 1])
        assert body["status"]["state"] == "Ready"
        assert body["status"]["observedGeneration"] == 3

    def test_apply_failure_marks_failed_and_requeues_fast(self, tmp_path):
        """kubectl/apply failure: the CR transitions to status Failed AND
        the controller loop requeues after --retry-interval instead of
        waiting the full reconcile interval (the role of
        controller-runtime's error requeue)."""
        import asyncio

        kdir = tmp_path / "bin"
        kdir.mkdir()
        kubectl = kdir / "kubectl"
        kubectl.write_text(FAKE_KUBECTL)
        kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
        log = tmp_path / "calls.jsonl"
        crs = tmp_path / "crs.json"
        crs.write_text(json.dumps({"items": [graph_cr()]}))
        env = dict(os.environ)
        env["PATH"] = f"{kdir}:{env['PATH']}"
        env["FAKE_KUBECTL_LOG"] = str(log)
        env["FAKE_CRS"] = str(crs)
        env["FAKE_APPLY_FAILS"] = "1"
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                          "deploy", "graph_operator.py"),
             "--once", "--kube-namespace", "ns1"],
            env=env, capture_output=True, timeout=60)
        assert r.returncode == 0, r.stderr.decode()
        calls = [json.loads(line) for line in log.read_text().splitlines()]
        patches = [c["args"] for c in calls
                   if "patch" in c["args"] and "--subresource=status"
                   in c["args"]]
        body = json.loads(patches[0][patches[0].index("-p") + 1])
        assert body["status"]["state"] == "Failed"

        # requeue timing: a failing pass sleeps retry_interval, a clean
        # pass sleeps the full interval (reconcile_once stubbed)
        sleeps = []
        results = iter([(1, 1), (1, 0)])

        async def fake_reconcile(ns):
            return next(results)

        async def fake_sleep(t):
            sleeps.append(t)
            if len(sleeps) >= 2:
                raise asyncio.CancelledError

        orig_reconcile = operator.reconcile_once
        orig_sleep = operator.asyncio.sleep
        operator.reconcile_once = fake_reconcile
        operator.asyncio.sleep = fake_sleep
        try:
            with pytest.raises(asyncio.CancelledError):
                asyncio.run(operator.run_controller(
                    "ns1", interval=30.0, retry_interval=2.0))
        finally:
            operator.reconcile_once = orig_reconcile
            operator.asyncio.sleep = orig_sleep
        assert sleeps == [2.0, 30.0]

    def test_invalid_graph_marked_failed(self, tmp_path):
        kdir = tmp_path / "bin"
        kdir.mkdir()
        kubectl = kdir / "kubectl"
        kubectl.write_text(FAKE_KUBECTL)
        kubectl.chmod(kubectl.stat().st_mode | stat.S_IEXEC)
        log = tmp_path / "calls.jsonl"
        crs = tmp_path / "crs.json"
        crs.write_text(json.dumps({"items": [graph_cr(
            services={"bad": {"componentType": "nope"}})]}))
        env = dict(os.environ)
        env["PATH"] = f"{kdir}:{env['PATH']}"
        env["FAKE_KUBECTL_LOG"] = str(log)
        env["FAKE_CRS"] = str(crs)
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                          "deploy", "graph_operator.py"),
             "--once", "--kube-namespace", "ns1"],
            env=env, capture_output=True, timeout=60)
        assert r.returncode == 0, r.stderr.decode()
        calls = [json.loads(line) for line in log.read_text().splitlines()]
        patches = [c["args"] for c in calls if "patch" in c["args"]]
        body = json.loads(patches[0][patches[0].index("-p") + 1])
        assert body["status"]["state"] == "Failed"
        # nothing applied for an invalid graph
        assert not any(c["args"][:1] == ["apply"] for c in calls)
