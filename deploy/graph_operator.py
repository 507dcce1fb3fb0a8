"""DynamoGraphDeployment controller: declarative graph CRs -> Deployments.

Role parity: the reference's Go operator reconciling
``DynamoGraphDeployment`` CRDs into component Deployments/Services
(``deploy/cloud/operator/api/v1alpha1/dynamographdeployment_types.go``,
``internal/controller/dynamographdeployment_controller.go``). The rebuild
keeps the same division of labor but stays dependency-free: a reconcile
loop over ``kubectl`` (the image carries no kubernetes client library),
with ALL manifest generation in pure functions (``render_graph``) so the
controller's logic is unit-testable without a cluster.

Reconcile semantics per CR:

- every entry of ``spec.services`` becomes one Deployment (+ one Service
  when the component exposes a port: coordinator, frontend, system
  ports), labeled ``dynamo.tpu/graph=<cr-name>`` and
  ``dynamo.tpu/service=<svc-name>``;
- ``kubectl apply`` is idempotent — unchanged manifests are no-ops, spec
  edits roll the Deployment;
- children labeled for the graph but no longer in the spec are PRUNED
  (declarative delete, the part ``deploy/reconciler.py``'s imperative
  scale/patch loop cannot do);
- status is written back via the ``status`` subresource
  (``state: Ready|Progressing|Failed`` + observedGeneration), so
  ``kubectl get dgd`` shows rollout state.

The planner's runtime scale decisions still flow through
``deploy/reconciler.py`` (coordinator-KV -> replica patches); this
controller owns the declarative shape. Run:
``python deploy/graph_operator.py --kube-namespace dynamo``.

SCOPE (also stated in docs/deployment.md): poll-based (no watches — next
``--interval`` pass picks up changes; kubectl failures requeue after
``--retry-interval``), no admission webhooks (invalid specs surface as
``state: Failed``), single-namespace. One instance per namespace.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import sys
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("operator")

GROUP = "dynamo.tpu"
PLURAL = "dynamographdeployments"
DEFAULT_IMAGE = "dynamo-tpu:latest"
GRAPH_LABEL = "dynamo.tpu/graph"
SERVICE_LABEL = "dynamo.tpu/service"

# componentType -> (module, default port). Port 0 = headless (no Service).
COMPONENTS = {
    "coordinator": ("dynamo_tpu.frontend.coordinator", 6650),
    "frontend": ("dynamo_tpu.frontend.main", 8080),
    "worker": ("dynamo_tpu.worker.main", 0),
    "prefill": ("dynamo_tpu.worker.main", 0),
    "planner": ("dynamo_tpu.planner.main", 0),
}


# --------------------------------------------------------------- rendering

def _component_args(cr_name: str, svc_name: str, svc: Dict[str, Any],
                    coordinator: str) -> List[str]:
    ctype = svc.get("componentType", "worker")
    module, port = COMPONENTS[ctype]
    args = ["python", "-m", module]
    if ctype == "coordinator":
        args += ["--port", str(svc.get("port") or port)]
    elif ctype == "frontend":
        args += ["--coordinator", coordinator,
                 "--http-port", str(svc.get("port") or port)]
    elif ctype in ("worker", "prefill"):
        args += ["--coordinator", coordinator,
                 "--model-path", svc.get("modelPath", "/models/default")]
        if svc.get("modelName"):
            args += ["--model-name", svc["modelName"]]
        if ctype == "prefill":
            args += ["--disagg", "prefill", "--component", svc_name]
    elif ctype == "planner":
        args += ["--coordinator", coordinator]
    args += list(svc.get("args", []))
    return args


def render_graph(cr: Dict[str, Any],
                 kube_namespace: str) -> List[Dict[str, Any]]:
    """Pure CR -> child manifests (Deployments + Services).

    Deterministic output (sorted service order) so ``kubectl apply``
    diffs are stable across reconciles."""
    name = cr["metadata"]["name"]
    spec = cr.get("spec", {}) or {}
    services: Dict[str, Any] = spec.get("services", {}) or {}
    for svc_name, svc in services.items():
        ctype = (svc or {}).get("componentType", "worker")
        if ctype not in COMPONENTS:
            raise ValueError(f"unknown componentType {ctype!r} "
                             f"for service {svc_name!r}")
    coordinator = spec.get("coordinator") or ""
    if not coordinator:
        coord_svcs = [s for s, v in services.items()
                      if v.get("componentType") == "coordinator"]
        if coord_svcs:
            svc = coord_svcs[0]
            port = services[svc].get("port") or COMPONENTS["coordinator"][1]
            coordinator = f"{name}-{svc}:{port}"
        elif any((v or {}).get("componentType", "worker") != "coordinator"
                 for v in services.values()):
            # every non-coordinator component needs the address; deploying
            # with '--coordinator ""' would crash-loop silently — fail the
            # CR with a visible validation message instead
            raise ValueError(
                "graph has no spec.coordinator and no coordinator "
                "service — components would start with an empty "
                "coordinator address")
    manifests: List[Dict[str, Any]] = []
    for svc_name in sorted(services):
        svc = services[svc_name] or {}
        ctype = svc.get("componentType", "worker")
        full = f"{name}-{svc_name}"
        labels = {GRAPH_LABEL: name, SERVICE_LABEL: svc_name,
                  "app": full}
        envs = list(spec.get("envs", [])) + list(svc.get("envs", []))
        container: Dict[str, Any] = {
            "name": ctype,
            "image": svc.get("image", DEFAULT_IMAGE),
            "command": _component_args(name, svc_name, svc, coordinator),
        }
        if envs:
            container["env"] = envs
        if svc.get("resources"):
            container["resources"] = svc["resources"]
        port = svc.get("port") or COMPONENTS[ctype][1]
        if port:
            container["ports"] = [{"containerPort": port}]
        manifests.append({
            "apiVersion": "apps/v1",
            "kind": "Deployment",
            "metadata": {"name": full, "namespace": kube_namespace,
                         "labels": labels},
            "spec": {
                "replicas": int(svc.get("replicas", 1)),
                "selector": {"matchLabels": {"app": full}},
                "template": {
                    "metadata": {"labels": labels},
                    "spec": {"containers": [container]},
                },
            },
        })
        if port:
            manifests.append({
                "apiVersion": "v1",
                "kind": "Service",
                "metadata": {"name": full, "namespace": kube_namespace,
                             "labels": labels},
                "spec": {
                    "selector": {"app": full},
                    "ports": [{"port": port, "targetPort": port}],
                },
            })
    return manifests


# --------------------------------------------------------------- kubectl

async def _kubectl(*args: str, stdin: Optional[bytes] = None
                   ) -> Tuple[int, bytes, bytes]:
    proc = await asyncio.create_subprocess_exec(
        "kubectl", *args,
        stdin=asyncio.subprocess.PIPE if stdin is not None else None,
        stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE)
    out, err = await proc.communicate(stdin)
    return proc.returncode, out, err


async def list_graph_crs(kube_namespace: str) -> List[Dict[str, Any]]:
    rc, out, err = await _kubectl("-n", kube_namespace, "get",
                                  f"{PLURAL}.{GROUP}", "-o", "json")
    if rc != 0:
        raise RuntimeError(f"kubectl get {PLURAL} failed: {err.decode()}")
    return json.loads(out).get("items", [])


async def apply_manifests(manifests: List[Dict[str, Any]]) -> bool:
    if not manifests:
        return True
    doc = json.dumps({"apiVersion": "v1", "kind": "List",
                      "items": manifests}).encode()
    rc, _out, err = await _kubectl("apply", "-f", "-", stdin=doc)
    if rc != 0:
        logger.error("kubectl apply failed: %s", err.decode())
    return rc == 0


async def prune_children(cr_name: str, keep: Dict[str, List[str]],
                         kube_namespace: str) -> None:
    """Delete Deployments/Services labeled for this graph but absent from
    the current spec (declarative removal of renamed/dropped services).
    ``keep`` maps kind -> kept names PER KIND: a Service that shares its
    name with a kept Deployment (service dropped its port / changed
    componentType) must still be pruned."""
    for kind in ("deployment", "service"):
        rc, out, _err = await _kubectl(
            "-n", kube_namespace, "get", kind, "-l",
            f"{GRAPH_LABEL}={cr_name}", "-o", "json")
        if rc != 0:
            continue
        kept = keep.get(kind, [])
        for item in json.loads(out).get("items", []):
            name = item["metadata"]["name"]
            if name not in kept:
                logger.info("pruning %s/%s (no longer in graph %s)",
                            kind, name, cr_name)
                await _kubectl("-n", kube_namespace, "delete", kind, name,
                               "--ignore-not-found")


async def graph_state(cr: Dict[str, Any], kube_namespace: str) -> str:
    """Ready when every child Deployment has its replicas available."""
    name = cr["metadata"]["name"]
    rc, out, _err = await _kubectl(
        "-n", kube_namespace, "get", "deployment", "-l",
        f"{GRAPH_LABEL}={name}", "-o", "json")
    if rc != 0:
        return "Unknown"
    items = json.loads(out).get("items", [])
    if not items:
        return "Progressing"
    for d in items:
        want = (d.get("spec", {}) or {}).get("replicas", 1)
        have = (d.get("status", {}) or {}).get("availableReplicas", 0) or 0
        if have < want:
            return "Progressing"
    return "Ready"


async def update_status(cr: Dict[str, Any], state: str,
                        kube_namespace: str) -> None:
    name = cr["metadata"]["name"]
    patch = json.dumps({"status": {
        "state": state,
        "observedGeneration": cr["metadata"].get("generation", 0),
    }})
    rc, _out, err = await _kubectl(
        "-n", kube_namespace, "patch", f"{PLURAL}.{GROUP}", name,
        "--subresource=status", "--type=merge", "-p", patch)
    if rc != 0:
        logger.warning("status patch for %s failed: %s", name, err.decode())


# --------------------------------------------------------------- reconcile

async def reconcile_once(kube_namespace: str) -> Tuple[int, int]:
    """One full pass over every graph CR; returns (cr_count, failed_count).
    A CR whose apply failed is marked ``Failed`` and counts toward the
    failed total, which the controller loop uses to REQUEUE sooner than
    the normal interval (the role of controller-runtime's error requeue
    backoff)."""
    crs = await list_graph_crs(kube_namespace)
    failed = 0
    for cr in crs:
        name = cr["metadata"]["name"]
        try:
            manifests = render_graph(cr, kube_namespace)
        except ValueError as e:
            logger.error("graph %s invalid: %s", name, e)
            await update_status(cr, "Failed", kube_namespace)
            # invalid specs do NOT requeue fast: re-running cannot fix a
            # bad CR — the user must edit it (the next normal pass sees it)
            continue
        ok = await apply_manifests(manifests)
        keep: Dict[str, List[str]] = {"deployment": [], "service": []}
        for m in manifests:
            keep[m["kind"].lower()].append(m["metadata"]["name"])
        await prune_children(name, keep, kube_namespace)
        state = (await graph_state(cr, kube_namespace)) if ok else "Failed"
        if not ok:
            failed += 1
        await update_status(cr, state, kube_namespace)
    return len(crs), failed


async def run_controller(kube_namespace: str, interval: float,
                         retry_interval: float = 2.0) -> None:
    logger.info("graph controller reconciling %s/%s every %.0fs",
                kube_namespace, PLURAL, interval)
    while True:
        failed = 0
        try:
            _n, failed = await reconcile_once(kube_namespace)
        except Exception:  # noqa: BLE001 — controller must outlive blips
            logger.exception("reconcile pass failed")
            failed = 1  # API-server/kubectl blip: retry soon
        await asyncio.sleep(retry_interval if failed else interval)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--kube-namespace", default="default")
    p.add_argument("--interval", type=float, default=10.0)
    p.add_argument("--retry-interval", type=float, default=2.0,
                   help="requeue delay after a pass with kubectl failures")
    p.add_argument("--once", action="store_true",
                   help="single reconcile pass (CI / cron)")
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    if args.once:
        asyncio.run(reconcile_once(args.kube_namespace))
        return
    try:
        asyncio.run(run_controller(args.kube_namespace, args.interval,
                                   args.retry_interval))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    sys.exit(main())
