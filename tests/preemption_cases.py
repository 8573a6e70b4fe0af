"""Seeded tiered clusters for the preemption tests: nodes full of bound pods
at mixed priorities and a few preemptors, in one of several kinds that each
exercise one part of DefaultPreemption. Pods carry fixed names, so two runs
of one case can be compared pod by pod."""

from __future__ import annotations

import numpy as np

from open_simulator_tpu.engine.simulator import AppResource, ClusterResource

KINDS = ("resources", "pdb", "taints", "never", "anti", "spread")
ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"


def _node(i: int, rng, kind: str) -> dict:
    name = f"n{i:03d}"
    spec: dict = {}
    if kind == "taints":
        r = rng.random()
        if r < 0.2:
            spec["taints"] = [{"key": "dedicated", "value": "x",
                               "effect": "NoSchedule"}]
        elif r < 0.3:
            spec["unschedulable"] = True
    return {
        "kind": "Node",
        "metadata": {"name": name,
                     "labels": {HOST: name, ZONE: f"z{i % 3}"}},
        "spec": spec,
        "status": {"allocatable": {"cpu": "4", "memory": "16Gi", "pods": "20"}},
    }


def _pod(name: str, cpu_m: int, mem_mi: int, priority: int, labels: dict,
         node: str = "", extra: dict = None) -> dict:
    spec = {
        "containers": [{"name": "c", "image": "i", "resources": {"requests": {
            "cpu": f"{cpu_m}m", "memory": f"{mem_mi}Mi"}}}],
        "priority": priority,
    }
    if node:
        spec["nodeName"] = node
    spec.update(extra or {})
    return {"kind": "Pod",
            "metadata": {"name": name, "namespace": "default", "labels": labels},
            "spec": spec,
            "status": {"phase": "Running"} if node else {}}


def _preemptor_spec(kind: str, j: int) -> tuple:
    """(labels, extra spec) of preemptor j for the case's kind."""
    labels = {"app": "hi"}
    extra: dict = {}
    if kind == "never" and j % 2 == 0:
        extra["preemptionPolicy"] = "Never"
    if kind == "anti":
        extra["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"app": "b"}},
                "topologyKey": HOST}]}}
    if kind == "spread":
        labels = {"app": "spread"}
        extra["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": ZONE,
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "spread"}}}]
    return labels, extra


def case(seed: int, n_nodes: int, kind: str):
    """-> (cluster objects as dicts, preemptor pod dicts), seeded."""
    rng = np.random.default_rng([seed, n_nodes, KINDS.index(kind)])
    nodes = [_node(i, rng, kind) for i in range(n_nodes)]
    bound = []
    for nd in nodes:
        name = nd["metadata"]["name"]
        used, j = 0, 0
        while used < 3500:  # nearly full: most preemptors must evict
            cpu = int(rng.choice([250, 500, 1000]))
            if used + cpu > 4000:
                continue
            used += cpu
            j += 1
            bound.append(_pod(
                f"{name}-b{j - 1}", cpu, int(rng.choice([256, 512, 1024])),
                int(rng.choice([0, 5, 10, 50, 100, 100, 200])),
                {"app": str(rng.choice(["a", "b", "c"]))}, node=name))
    others = []
    if kind == "pdb":
        others = [
            {"kind": "PodDisruptionBudget",
             "metadata": {"name": "pdb-a", "namespace": "default"},
             "spec": {"selector": {"matchLabels": {"app": "a"}},
                      "minAvailable": int(rng.integers(0, n_nodes))}},
            {"kind": "PodDisruptionBudget",
             "metadata": {"name": "pdb-b", "namespace": "default"},
             "spec": {"selector": {"matchLabels": {"app": "b"}},
                      "maxUnavailable": 1}},
        ]
    pre = []
    for j in range(max(8, n_nodes // 4)):
        labels, extra = _preemptor_spec(kind, j)
        prio = 0 if (kind == "never" and j % 4 == 3) else int(
            rng.choice([100, 150, 1000]))
        pre.append(_pod(f"pre-{j}", int(rng.choice([1000, 1500, 2500])),
                        int(rng.choice([512, 2048])), prio, labels, extra=extra))
    return nodes + bound + others, pre


def simulate_case(seed: int, n_nodes: int, kind: str, **kw):
    from open_simulator_tpu.engine.simulator import simulate

    objs, pre = case(seed, n_nodes, kind)
    cluster = ClusterResource.from_objects(objs)
    return simulate(cluster, [AppResource(name="pre", objects=pre)], **kw)


def digest(result) -> dict:
    """What a preemption test compares: placements, the preempted pods in
    order with node and preemptor, and the unscheduled pods with reasons."""
    return {
        "placed": [[st.node.name, [p.meta.name for p in st.pods]]
                   for st in result.node_status],
        "preempted": [[v.pod.meta.name, v.node, v.by] for v in result.preempted],
        "unscheduled": [[u.pod.meta.name, u.reason] for u in result.unscheduled],
    }



#: constraints between pods that read domain counts, for the path tests:
#: mode -> (bound pods' anti-affinity against the preemptors, preemptor spec)
COUPLED = {
    "symmetry": True,
    "host_affinity": {"affinity": {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"app": "a"}}, "topologyKey": HOST}]}}},
    "zone_affinity": {"affinity": {"podAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"app": "b"}}, "topologyKey": ZONE}]}}},
    "host_spread": {"topologySpreadConstraints": [{
        "maxSkew": 2, "topologyKey": HOST, "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"app": "b"}}}]},
}


def coupled_case(seed: int, n_nodes: int, mode: str):
    """The `resources` case with one kind of constraint that reads domain
    counts: on the preemptors, or (`symmetry`) required anti-affinity
    against them on a third of the bound pods, by zone or by host."""
    objs, pre = case(seed, n_nodes, "resources")
    rng = np.random.default_rng([seed, n_nodes])
    if COUPLED[mode] is True:
        for o in objs:
            if o["kind"] == "Pod" and rng.random() < 0.3:
                o["spec"]["affinity"] = {"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": {"app": "hi"}},
                        "topologyKey": str(rng.choice([ZONE, HOST]))}]}}
    else:
        for p in pre:
            p["spec"].update(COUPLED[mode])
    return objs, pre
