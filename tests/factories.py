"""Functional-option fixture factories (parity: the reference's `pkg/test`
builders — MakeFakeNode `node.go:15-40`, MakeFakePod `pod.go:13-47`, and the
per-workload-kind MakeFake* with With* options).

Usage:
    node = make_node("n1", cpu="8", with_labels={"zone": "z1"},
                     with_taints=[taint("dedicated", "batch")])
    pod = make_pod("p1", cpu="500m", with_node_selector={"zone": "z1"})
    deploy = make_deployment("web", replicas=3, cpu="1",
                             with_tolerations=[toleration("dedicated")])
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from open_simulator_tpu.core.objects import Node, Pod


def taint(key: str, value: str = "", effect: str = "NoSchedule") -> dict:
    return {"key": key, "value": value, "effect": effect}


def toleration(
    key: str, value: str = "", operator: str = "", effect: str = ""
) -> dict:
    t: dict = {"key": key}
    if operator:
        t["operator"] = operator
    if value:
        t["value"] = value
    if effect:
        t["effect"] = effect
    return t


def spread_constraint(
    topology_key: str,
    max_skew: int = 1,
    when_unsatisfiable: str = "DoNotSchedule",
    match_labels: Optional[Dict[str, str]] = None,
) -> dict:
    return {
        "maxSkew": max_skew,
        "topologyKey": topology_key,
        "whenUnsatisfiable": when_unsatisfiable,
        "labelSelector": {"matchLabels": match_labels or {}},
    }


def make_node(
    name: str,
    cpu: str = "4",
    memory: str = "8Gi",
    pods: str = "110",
    with_labels: Optional[Dict[str, str]] = None,
    with_taints: Optional[List[dict]] = None,
    with_annotations: Optional[Dict[str, str]] = None,
    with_capacity: Optional[Dict[str, str]] = None,
) -> Node:
    """MakeFakeNode parity: 110-pod capacity default, hostname label set."""
    res = {"cpu": cpu, "memory": memory, "pods": pods, **(with_capacity or {})}
    return Node.from_dict(
        {
            "metadata": {
                "name": name,
                "labels": {
                    "kubernetes.io/hostname": name, **(with_labels or {})
                },
                "annotations": with_annotations or {},
            },
            "spec": {"taints": with_taints or []},
            "status": {"allocatable": dict(res), "capacity": dict(res)},
        }
    )


def _pod_spec(
    cpu: str,
    memory: str,
    with_node_selector=None,
    with_tolerations=None,
    with_affinity=None,
    with_spread=None,
    with_host_ports=None,
    with_priority=None,
    with_scheduler=None,
    with_node_name=None,
) -> dict:
    container: dict = {
        "name": "c",
        "image": "img",
        "resources": {"requests": {"cpu": cpu, "memory": memory}},
    }
    if with_host_ports:
        container["ports"] = [
            {"containerPort": p, "hostPort": p} for p in with_host_ports
        ]
    spec: dict = {"containers": [container]}
    if with_node_selector:
        spec["nodeSelector"] = dict(with_node_selector)
    if with_tolerations:
        spec["tolerations"] = list(with_tolerations)
    if with_affinity:
        spec["affinity"] = with_affinity
    if with_spread:
        spec["topologySpreadConstraints"] = list(with_spread)
    if with_priority is not None:
        spec["priority"] = with_priority
    if with_scheduler:
        spec["schedulerName"] = with_scheduler
    if with_node_name:
        spec["nodeName"] = with_node_name
    return spec


def make_pod(
    name: str,
    namespace: str = "default",
    cpu: str = "100m",
    memory: str = "128Mi",
    with_labels: Optional[Dict[str, str]] = None,
    with_annotations: Optional[Dict[str, str]] = None,
    **spec_options,
) -> Pod:
    """MakeFakePod parity; spec options mirror the With* functional options."""
    return Pod.from_dict(
        {
            "metadata": {
                "name": name,
                "namespace": namespace,
                "labels": with_labels or {},
                "annotations": with_annotations or {},
            },
            "spec": _pod_spec(cpu, memory, **spec_options),
        }
    )


def _workload(
    kind: str,
    name: str,
    namespace: str,
    replicas: int,
    cpu: str,
    memory: str,
    with_labels: Optional[Dict[str, str]] = None,
    **spec_options,
) -> dict:
    labels = {"app": name, **(with_labels or {})}
    return {
        "kind": kind,
        "metadata": {"name": name, "namespace": namespace},
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": {"app": name}},
            "template": {
                "metadata": {"labels": labels},
                "spec": _pod_spec(cpu, memory, **spec_options),
            },
        },
    }


def make_deployment(name, replicas=1, namespace="default", cpu="100m",
                    memory="128Mi", **opts) -> dict:
    return _workload("Deployment", name, namespace, replicas, cpu, memory, **opts)


def make_replicaset(name, replicas=1, namespace="default", cpu="100m",
                    memory="128Mi", **opts) -> dict:
    return _workload("ReplicaSet", name, namespace, replicas, cpu, memory, **opts)


def make_statefulset(name, replicas=1, namespace="default", cpu="100m",
                     memory="128Mi", **opts) -> dict:
    return _workload("StatefulSet", name, namespace, replicas, cpu, memory, **opts)


def make_daemonset(name, namespace="default", cpu="100m", memory="128Mi",
                   **opts) -> dict:
    d = _workload("DaemonSet", name, namespace, 0, cpu, memory, **opts)
    del d["spec"]["replicas"]
    return d


def make_job(name, completions=1, parallelism=1, namespace="default",
             cpu="100m", memory="128Mi", **opts) -> dict:
    d = _workload("Job", name, namespace, 0, cpu, memory, **opts)
    del d["spec"]["replicas"]
    d["spec"]["completions"] = completions
    d["spec"]["parallelism"] = parallelism
    return d


def make_cronjob(name, namespace="default", cpu="100m", memory="128Mi",
                 **opts) -> dict:
    inner = make_job(name, namespace=namespace, cpu=cpu, memory=memory, **opts)
    return {
        "kind": "CronJob",
        "metadata": {"name": name, "namespace": namespace},
        "spec": {
            "schedule": "* * * * *",
            "jobTemplate": {"spec": inner["spec"]},
        },
    }


def build_state(n_nodes: int, n_pods: int):
    """Encoded (NodeStatic, Carry, PodBatch) for a tiled plan: `n_nodes`
    nodes over 3 zones and instance types (every 10th tainted), and
    `n_pods` pods split over 4 templates (zone-spread web and sidecar, a
    tolerating batch job, a selector-pinned cache). Seeded, so every call
    with the same sizes encodes the same state."""
    from open_simulator_tpu.ops.encode import (
        Encoder,
        encode_nodes,
        encode_pods,
        initial_selector_counts,
    )
    from open_simulator_tpu.ops.state import (
        carry_from_table,
        node_static_from_table,
    )
    from open_simulator_tpu.ops.tile import tile_pod_batch

    rng = np.random.default_rng(0)
    nodes = []
    for i in range(n_nodes):
        taints = (
            [{"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
            if i % 10 == 0
            else []
        )
        nodes.append(
            Node.from_dict(
                {
                    "metadata": {
                        "name": f"node-{i}",
                        "labels": {
                            "kubernetes.io/hostname": f"node-{i}",
                            "topology.kubernetes.io/zone": f"az-{i % 3}",
                            "node.kubernetes.io/instance-type": ["m5.4x", "m5.8x", "c5.9x"][i % 3],
                        },
                    },
                    "spec": {"taints": taints},
                    "status": {
                        "allocatable": {
                            "cpu": str(16 + 16 * int(rng.integers(0, 3))),
                            "memory": f"{32 + 32 * int(rng.integers(0, 3))}Gi",
                            "pods": "110",
                        }
                    },
                }
            )
        )

    # Workload templates: a service with zone spread, a tolerating batch job,
    # a selector-pinned cache, a plain web tier.
    templates = []
    tmpl_specs = [
        dict(
            cpu="500m", mem="512Mi", labels={"app": "web"},
            spread=True, tol=False, sel=None,
        ),
        dict(
            cpu="2", mem="4Gi", labels={"app": "batch"},
            spread=False, tol=True, sel=None,
        ),
        dict(
            cpu="1", mem="2Gi", labels={"app": "cache"},
            spread=False, tol=False, sel={"node.kubernetes.io/instance-type": "m5.8x"},
        ),
        dict(
            cpu="250m", mem="256Mi", labels={"app": "sidecar"},
            spread=True, tol=False, sel=None,
        ),
    ]
    for t, s in enumerate(tmpl_specs):
        spec = {
            "containers": [
                {"name": "c", "resources": {"requests": {"cpu": s["cpu"], "memory": s["mem"]}}}
            ]
        }
        if s["spread"]:
            spec["topologySpreadConstraints"] = [
                {
                    "maxSkew": 50,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": s["labels"]},
                }
            ]
        if s["tol"]:
            spec["tolerations"] = [
                {"key": "dedicated", "operator": "Equal", "value": "batch",
                 "effect": "NoSchedule"}
            ]
        if s["sel"]:
            spec["nodeSelector"] = s["sel"]
        templates.append(
            Pod.from_dict(
                {
                    "metadata": {
                        "name": f"tpl-{t}", "namespace": "bench", "labels": s["labels"],
                    },
                    "spec": spec,
                }
            )
        )

    share = n_pods // len(templates)
    counts = [share] * len(templates)
    counts[0] += n_pods - share * len(templates)

    enc = Encoder()
    enc.register_pods(templates)
    table = encode_nodes(enc, nodes)
    tmpl_batch = encode_pods(enc, templates)
    batch = tile_pod_batch(tmpl_batch, counts)
    ns = node_static_from_table(enc, table)
    carry = carry_from_table(table, initial_selector_counts(enc, table, []))
    return ns, carry, batch
