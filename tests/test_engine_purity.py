"""Regression tests: simulate() must not mutate its inputs — the capacity
search re-probes the same ClusterResource many times (code-review finding:
pending cluster pods were bound in place, corrupting later probes)."""

import copy

import yaml

from open_simulator_tpu.core.objects import Node, Pod
from open_simulator_tpu.engine.capacity import new_fake_nodes
from open_simulator_tpu.engine.simulator import AppResource, ClusterResource, simulate


def _cluster():
    node = Node.from_dict(
        {
            "metadata": {"name": "n1", "labels": {"kubernetes.io/hostname": "n1"}},
            "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "110"}},
        }
    )
    pending = Pod.from_dict(
        {
            "metadata": {"name": "pending", "namespace": "d"},
            "spec": {
                "containers": [
                    {"name": "c", "image": "img", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}}
                ]
            },
        }
    )
    return ClusterResource(nodes=[node], pods=[pending])


def test_simulate_does_not_mutate_cluster_pods():
    cluster = _cluster()
    r1 = simulate(cluster, [])
    assert cluster.pods[0].node_name == ""          # caller's pod untouched
    assert cluster.pods[0].phase == "Pending"
    r2 = simulate(cluster, [])                      # identical re-run
    assert not r1.unscheduled and not r2.unscheduled
    assert [len(s.pods) for s in r1.node_status] == [len(s.pods) for s in r2.node_status]


def _bound_cluster():
    """A GPU node and a plain node holding pre-bound pods (one with a
    gpu-index annotation), one pending pod and a two-pod app."""
    gpu = {"alibabacloud.com/gpu-count": "2", "alibabacloud.com/gpu-mem": "32Gi"}
    nodes = [
        Node.from_dict({
            "metadata": {"name": name, "labels": {"kubernetes.io/hostname": name}},
            "status": {"allocatable": {"cpu": "8", "memory": "16Gi", "pods": "110", **extra}},
        })
        for name, extra in (("g0", gpu), ("n1", {}))
    ]

    def pod(name, node="", annotations=None):
        return Pod.from_dict({
            "metadata": {"name": name, "namespace": "d", "annotations": annotations or {}},
            "spec": {
                "nodeName": node,
                "containers": [
                    {"name": "c", "image": "img", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}}
                ],
            },
        })

    pods = [
        pod("gpu-bound", "g0", {
            "alibabacloud.com/gpu-mem": "4Gi",
            "alibabacloud.com/gpu-count": "1",
            "alibabacloud.com/gpu-index": "1",
        }),
        pod("bound", "n1"),
        pod("pending"),
    ]
    for p in pods[:2]:
        p.phase = "Running"
    apps = [AppResource(name="a", objects=[
        pod(f"app{i}").raw | {"kind": "Pod"} for i in range(2)
    ])]
    return ClusterResource(nodes=nodes, pods=pods), apps


def test_simulate_borrows_bound_pods_and_leaves_them_unchanged():
    cluster, apps = _bound_cluster()
    snapshot = copy.deepcopy(cluster.pods)
    r1 = simulate(cluster, apps)
    r2 = simulate(cluster, apps)
    assert cluster.pods == snapshot
    assert cluster.pods[0].meta.annotations["alibabacloud.com/gpu-index"] == "1"
    assert r1 == r2
    assert not r1.unscheduled and not r1.preempted
    listed = {id(p) for st in r1.node_status for p in st.pods}
    assert {id(p) for p in cluster.pods[:2]} <= listed   # the caller's objects
    assert id(cluster.pods[2]) not in listed              # pending: a copy is placed
    assert sum(len(st.pods) for st in r1.node_status) == 5


def test_fake_node_names_unique_and_stable():
    template = _cluster().nodes[0]
    a = new_fake_nodes(template, 1000)
    names = [n.meta.name for n in a]
    assert len(set(names)) == 1000
    b = new_fake_nodes(template, 1000)
    assert names == [n.meta.name for n in b]        # probe-independent


def test_negative_gpu_count_annotation_rejected():
    pod = Pod.from_dict(
        {
            "metadata": {
                "name": "g",
                "annotations": {
                    "alibabacloud.com/gpu-count": "-2",
                    "alibabacloud.com/gpu-mem": "4",
                },
            },
            "spec": {"containers": []},
        }
    )
    # negative counts are rejected and the default is 0 (parity:
    # GetGpuCountFromPodAnnotation, utils/pod.go:71-79) — the pod is then
    # unschedulable everywhere, like the reference's AllocateGpuId bail-out
    assert pod.gpu_count_request() == 0
