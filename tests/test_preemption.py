"""DefaultPreemption: victim selection, PDB classification, node picking.

Parity target: vendor/k8s.io/kubernetes/pkg/scheduler/framework/plugins/
defaultpreemption/default_preemption.go (selectVictimsOnNode :578,
filterPodsWithPDBViolation :736, pickOneNodeForPreemption :443,
PodEligibleToPreemptOthers :231).
"""

import copy

import numpy as np
import pytest

from open_simulator_tpu.core.objects import LabelSelector, Node, Pod
from open_simulator_tpu.engine.preemption import (
    PodDisruptionBudget,
    PreemptionResult,
    _fits,
    pick_one_node,
    select_victims_on_node,
    try_preempt,
)
from open_simulator_tpu.engine.simulator import (
    AppResource,
    ClusterResource,
    Simulator,
    UnscheduledPod,
    simulate,
)
from open_simulator_tpu.models.profiles import ExtenderConfig
from open_simulator_tpu.utils import metrics


def mknode(name, cpu="4", mem="8Gi", taints=None):
    return Node.from_dict(
        {
            "metadata": {"name": name},
            "spec": {"taints": taints or []},
            "status": {
                "allocatable": {"cpu": cpu, "memory": mem, "pods": "110"}
            },
        }
    )


def mkpod(name, cpu="1", priority=0, labels=None, ns="default", node="", policy=None):
    spec = {
        "containers": [
            {"name": "c", "image": "img", "resources": {"requests": {"cpu": cpu}}}
        ],
        "priority": priority,
    }
    if node:
        spec["nodeName"] = node
    if policy:
        spec["preemptionPolicy"] = policy
    return Pod.from_dict(
        {
            "metadata": {"name": name, "namespace": ns, "labels": labels or {}},
            "spec": spec,
        }
    )


def bound(node_name, *pods):
    for p in pods:
        p.node_name = node_name
        p.phase = "Running"
    return list(pods)


# ---------------------------------------------------------------------------
# selectVictimsOnNode
# ---------------------------------------------------------------------------

def test_minimal_victim_set_via_reprieve():
    node = mknode("n", cpu="4")
    low1 = mkpod("low1", cpu="2", priority=1)
    low2 = mkpod("low2", cpu="2", priority=2)
    preemptor = mkpod("hi", cpu="2", priority=100)
    res = select_victims_on_node(
        preemptor, node, bound("n", low1, low2), [], {}
    )
    # Removing just one 2-cpu victim suffices; the higher-priority low2 is
    # reprieved first, so low1 is the victim.
    assert res is not None
    assert [v.meta.name for v in res.victims] == ["low1"]
    assert res.num_pdb_violations == 0


def test_no_preemption_when_insufficient_even_after_evictions():
    node = mknode("n", cpu="4")
    low = mkpod("low", cpu="1", priority=1)
    preemptor = mkpod("hi", cpu="8", priority=100)  # never fits
    assert select_victims_on_node(preemptor, node, bound("n", low), [], {}) is None


def test_equal_priority_pods_are_not_victims():
    node = mknode("n", cpu="2")
    peer = mkpod("peer", cpu="2", priority=100)
    preemptor = mkpod("hi", cpu="2", priority=100)
    assert select_victims_on_node(preemptor, node, bound("n", peer), [], {}) is None


def test_pdb_protected_pods_reprieved_first():
    node = mknode("n", cpu="4")
    protected = mkpod("protected", cpu="2", priority=1, labels={"app": "db"})
    plain = mkpod("plain", cpu="2", priority=1)
    pdb = PodDisruptionBudget(
        name="db-pdb",
        namespace="default",
        selector=__import__(
            "open_simulator_tpu.core.objects", fromlist=["LabelSelector"]
        ).LabelSelector.from_dict({"matchLabels": {"app": "db"}}),
        disruptions_allowed=0,
    )
    preemptor = mkpod("hi", cpu="2", priority=100)
    res = select_victims_on_node(
        preemptor, node, bound("n", protected, plain), [pdb], {0: 0}
    )
    # Evicting one pod suffices; the PDB-violating pod is reprieved first, so
    # the plain pod is chosen and no budget is violated.
    assert res is not None
    assert [v.meta.name for v in res.victims] == ["plain"]
    assert res.num_pdb_violations == 0


# ---------------------------------------------------------------------------
# pickOneNodeForPreemption tiebreaks
# ---------------------------------------------------------------------------

def test_pick_node_prefers_fewer_pdb_violations():
    a = PreemptionResult("a", [mkpod("v", priority=5)], num_pdb_violations=1)
    b = PreemptionResult("b", [mkpod("v", priority=50)], num_pdb_violations=0)
    assert pick_one_node([a, b]).node == "b"


def test_pick_node_prefers_lower_max_victim_priority():
    a = PreemptionResult("a", [mkpod("v1", priority=50)], 0)
    b = PreemptionResult("b", [mkpod("v2", priority=5)], 0)
    assert pick_one_node([a, b]).node == "b"


def test_pick_node_prefers_fewer_victims():
    a = PreemptionResult("a", [mkpod("v1", priority=5), mkpod("v2", priority=5)], 0)
    b = PreemptionResult("b", [mkpod("v3", priority=5), mkpod("v4", priority=5),], 0)
    # equal so far: same max priority, compare sums -> a has 10, b has 10;
    # same victim count -> first wins
    assert pick_one_node([a, b]).node == "a"
    c = PreemptionResult("c", [mkpod("v5", priority=10)], 0)
    # c loses on max-victim-priority (10 > 5) despite fewer victims
    assert pick_one_node([a, c]).node == "a"


# ---------------------------------------------------------------------------
# try_preempt + engine integration
# ---------------------------------------------------------------------------

def test_preemption_policy_never_blocks():
    node = mknode("n", cpu="2")
    low = mkpod("low", cpu="2", priority=1)
    preemptor = mkpod("hi", cpu="2", priority=100, policy="Never")
    assert try_preempt(preemptor, [node], {"n": bound("n", low)}, []) is None


def test_tainted_node_is_unresolvable():
    node = mknode(
        "n", cpu="4",
        taints=[{"key": "k", "value": "v", "effect": "NoSchedule"}],
    )
    low = mkpod("low", cpu="4", priority=1)
    preemptor = mkpod("hi", cpu="2", priority=100)
    assert try_preempt(preemptor, [node], {"n": bound("n", low)}, []) is None


def test_end_to_end_preemption():
    # One 4-cpu node filled by low-priority pods; a high-priority pod arrives.
    cluster = ClusterResource(nodes=[mknode("w", cpu="4")])
    low_pods = [mkpod(f"low{i}", cpu="2", priority=1) for i in range(2)]
    cluster.pods.extend(low_pods)
    hi = mkpod("hi", cpu="2", priority=1000)
    app = AppResource(name="critical", objects=[hi.raw | {"kind": "Pod"}])
    result = simulate(cluster, [app])
    assert not result.unscheduled
    assert len(result.preempted) == 1
    assert result.preempted[0].by == "default/hi"
    # the preemptor landed on the node
    placed = {p.meta.name for st in result.node_status for p in st.pods}
    assert "hi" in placed
    assert result.preempted[0].pod.meta.name not in placed


def test_end_to_end_no_preemption_for_priorityless_pod():
    cluster = ClusterResource(nodes=[mknode("w", cpu="4")])
    cluster.pods.extend([mkpod(f"low{i}", cpu="2", priority=1) for i in range(2)])
    plain = mkpod("plain", cpu="2", priority=0)
    app = AppResource(name="app", objects=[plain.raw | {"kind": "Pod"}])
    result = simulate(cluster, [app])
    assert len(result.unscheduled) == 1
    assert not result.preempted


# ---------------------------------------------------------------------------
# device-filter-backed victim feasibility (Simulator._device_fits_many)
# ---------------------------------------------------------------------------

def test_device_fits_sees_anti_affinity_where_host_model_cannot():
    """Node A looks preemptible under the resources-only host model (evicting
    its low-priority pod frees enough cpu) and wins the host tiebreak with
    fewer victims — but a higher-priority pod labeled app=guard stays on A
    and the preemptor carries required anti-affinity against it, so the real
    filters reject A post-eviction (selectVictimsOnNode's filter dry run,
    default_preemption.go:598-626). The kernel-backed fits must route the
    preemption to node B instead."""
    node_a = mknode("a", cpu="4")
    node_b = mknode("b", cpu="4")
    for n in (node_a, node_b):
        n.meta.labels["kubernetes.io/hostname"] = n.meta.name

    guard = mkpod("guard", cpu="500m", priority=1000, labels={"app": "guard"})
    victim_a = mkpod("victim-a", cpu="3", priority=1)
    victim_b1 = mkpod("victim-b1", cpu="1500m", priority=1)
    victim_b2 = mkpod("victim-b2", cpu="1500m", priority=1)

    preemptor = mkpod("pre", cpu="3", priority=100)
    preemptor.affinity.anti_required = Pod.from_dict(
        {
            "metadata": {"name": "proto", "namespace": "default"},
            "spec": {
                "containers": [{"name": "c", "image": "i"}],
                "affinity": {
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {
                                "labelSelector": {
                                    "matchLabels": {"app": "guard"}
                                },
                                "topologyKey": "kubernetes.io/hostname",
                            }
                        ]
                    }
                },
            },
        }
    ).affinity.anti_required

    # host-only model sanity: it WOULD nominate A (fewer victims)
    wrong = try_preempt(
        preemptor,
        [node_a, node_b],
        {
            "a": bound("a", guard, victim_a),
            "b": bound("b", victim_b1, victim_b2),
        },
        [],
    )
    assert wrong is not None and wrong.node == "a"

    # end-to-end through the engine: device filters veto A, B's victims go
    cluster = ClusterResource(
        nodes=[node_a, node_b],
        pods=bound("a", guard, victim_a)
        + bound("b", victim_b1, victim_b2)
        + [preemptor],
    )
    result = simulate(cluster, [])
    assert not result.unscheduled
    assert {p.pod.meta.name for p in result.preempted} == {
        "victim-b1", "victim-b2"
    }
    placed = {
        p.meta.name: st.node.name
        for st in result.node_status
        for p in st.pods
    }
    assert placed["pre"] == "b"
    assert placed["guard"] == "a"
    assert placed["victim-a"] == "a"


def test_device_fits_eviction_clears_anti_affinity_conflict():
    """The victim ITSELF carries the label the preemptor's required
    anti-affinity rejects: hypothetically evicting it must CLEAR the
    selector count at the node (a sign error doubles it instead), making
    the node feasible and the preemption succeed."""
    node = mknode("solo", cpu="4")
    node.meta.labels["kubernetes.io/hostname"] = "solo"

    victim = mkpod("victim", cpu="3", priority=1, labels={"app": "bad"})
    preemptor = mkpod("pre", cpu="3", priority=100)
    preemptor.affinity.anti_required = Pod.from_dict(
        {
            "metadata": {"name": "proto", "namespace": "default"},
            "spec": {
                "containers": [{"name": "c", "image": "i"}],
                "affinity": {
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {
                                "labelSelector": {"matchLabels": {"app": "bad"}},
                                "topologyKey": "kubernetes.io/hostname",
                            }
                        ]
                    }
                },
            },
        }
    ).affinity.anti_required

    cluster = ClusterResource(
        nodes=[node], pods=bound("solo", victim) + [preemptor]
    )
    result = simulate(cluster, [])
    assert not result.unscheduled
    assert [p.pod.meta.name for p in result.preempted] == ["victim"]
    placed = {
        p.meta.name: st.node.name
        for st in result.node_status
        for p in st.pods
    }
    assert placed == {"pre": "solo"}


def _borrowed_victim_case():
    """The setup above with the preemptor as an app pod and a GPU victim
    (gpu-index annotation) bound by the caller."""
    node = Node.from_dict(
        {
            "metadata": {
                "name": "solo", "labels": {"kubernetes.io/hostname": "solo"}
            },
            "status": {
                "allocatable": {
                    "cpu": "4", "memory": "8Gi", "pods": "110",
                    "alibabacloud.com/gpu-count": "1",
                    "alibabacloud.com/gpu-mem": "16Gi",
                }
            },
        }
    )
    victim = mkpod("victim", cpu="3", priority=1, labels={"app": "bad"})
    victim.meta.annotations.update({
        "alibabacloud.com/gpu-mem": "8Gi",
        "alibabacloud.com/gpu-count": "1",
        "alibabacloud.com/gpu-index": "0",
    })
    pre = mkpod("pre", cpu="3", priority=100).raw
    pre["spec"]["affinity"] = {
        "podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {
                    "labelSelector": {"matchLabels": {"app": "bad"}},
                    "topologyKey": "kubernetes.io/hostname",
                }
            ]
        }
    }
    cluster = ClusterResource(nodes=[node], pods=bound("solo", victim))
    return cluster, [AppResource(name="critical", objects=[pre | {"kind": "Pod"}])]


def _placements(result):
    return {
        p.meta.name: st.node.name for st in result.node_status for p in st.pods
    }


def test_preemption_copies_borrowed_victim_and_leaves_caller_pod():
    cluster, apps = _borrowed_victim_case()
    victim = cluster.pods[0]
    snapshot = copy.deepcopy(victim)
    copies = metrics.BOUND_POD_COPIES.value()
    r1 = simulate(cluster, apps)
    assert victim == snapshot
    assert (victim.node_name, victim.phase) == ("solo", "Running")
    assert victim.meta.annotations["alibabacloud.com/gpu-index"] == "0"
    assert not r1.unscheduled
    assert _placements(r1) == {"pre": "solo"}
    assert len(r1.preempted) == 1
    evicted = r1.preempted[0].pod
    assert evicted is not victim and evicted.meta.name == "victim"
    assert (evicted.node_name, evicted.phase) == ("", "Pending")
    assert "alibabacloud.com/gpu-index" not in evicted.meta.annotations
    assert metrics.BOUND_POD_COPIES.value() == copies + 1
    r2 = simulate(cluster, apps)
    assert _placements(r2) == _placements(r1)
    assert r2.preempted == r1.preempted
    assert victim == snapshot


@pytest.mark.parametrize("path", ("device", "batch"))
def test_preemption_rollback_leaves_borrowed_victim_bound(
    path, monkeypatch, stub_factory
):
    """The retry after eviction fails: the eviction rolls back, and the
    caller's pod is still bound, listed on its node and not preempted. The
    failure is forced where each retry path reschedules: the device program
    `retry_one`, or `_schedule_batch_host` when an extender is interested in
    the preemptor."""
    from open_simulator_tpu.ops import preempt

    cluster, apps = _borrowed_victim_case()
    victim = cluster.pods[0]
    snapshot = copy.deepcopy(victim)
    real = Simulator._schedule_batch_host
    seen = []

    def fail_retry(self, pods):
        if any(p.meta.name == "pre" for p in pods):
            seen.append(pods)
            if len(seen) == 2:   # the retry after the eviction
                return [UnscheduledPod(pod=p, reason="forced") for p in pods]
        return real(self, pods)

    monkeypatch.setattr(Simulator, "_schedule_batch_host", fail_retry)
    kw = {}
    if path == "device":
        real_retry = preempt.retry_one

        def fail_on_device(*args, **kwargs):
            seen.append(args)
            carry, (_, *outs) = real_retry(*args, **kwargs)
            return carry, (np.int32(-1), *outs)

        monkeypatch.setattr(preempt, "retry_one", fail_on_device)
    else:
        stub = stub_factory({})
        kw["extenders"] = [
            ExtenderConfig(url_prefix=stub.url, filter_verb="filter")
        ]
    retries = metrics.PREEMPTION_RETRY.value(path=path)
    copies = metrics.BOUND_POD_COPIES.value()
    result = simulate(cluster, apps, **kw)
    assert len(seen) == 2
    assert victim == snapshot
    assert not result.preempted
    assert [u.pod.meta.name for u in result.unscheduled] == ["pre"]
    assert [p for st in result.node_status for p in st.pods] == [victim]
    assert result.node_status[0].pods[0] is victim
    assert metrics.BOUND_POD_COPIES.value() == copies + 1
    assert metrics.PREEMPTION_RETRY.value(path=path) == retries + 1


def test_lane_parallel_driver_matches_sequential():
    """try_preempt with fits_many_fn must pick the same node and victims as
    the per-node sequential driver — randomized over cluster shapes."""
    import random

    rng = random.Random(20260730)
    for trial in range(25):
        n_nodes = rng.randint(1, 5)
        nodes = [mknode(f"n{i}", cpu="4") for i in range(n_nodes)]
        bound_by_node = {}
        for n in nodes:
            pods = []
            for j in range(rng.randint(0, 4)):
                pods.append(
                    mkpod(
                        f"{n.meta.name}-p{j}",
                        cpu=rng.choice(["500m", "1", "2"]),
                        priority=rng.choice([0, 1, 5, 50, 1000]),
                        labels={"grp": rng.choice(["a", "b"])},
                    )
                )
            for p in pods:
                p.node_name = n.meta.name
            bound_by_node[n.meta.name] = pods
        pdbs = []
        if rng.random() < 0.5:
            pdbs.append(
                PodDisruptionBudget(
                    name="pdb", namespace="default",
                    selector=LabelSelector.from_dict(
                        {"matchLabels": {"grp": "a"}}
                    ),
                    min_available=str(rng.randint(0, 3)),
                )
            )
        preemptor = mkpod(
            "pre", cpu=rng.choice(["2", "3", "4"]), priority=100
        )

        seq = try_preempt(preemptor, nodes, bound_by_node, pdbs)

        def fits_many(pod, items):
            return [_fits(pod, node, remaining) for node, remaining in items]

        par = try_preempt(
            preemptor, nodes, bound_by_node, pdbs, fits_many_fn=fits_many
        )
        if seq is None:
            assert par is None, f"trial {trial}"
        else:
            assert par is not None, f"trial {trial}"
            assert par.node == seq.node, f"trial {trial}"
            assert [v.meta.name for v in par.victims] == [
                v.meta.name for v in seq.victims
            ], f"trial {trial}"
            assert par.num_pdb_violations == seq.num_pdb_violations
