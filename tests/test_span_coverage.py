"""One root span covers a whole simulate() call, `host-sync` spans count the
host's device reads, and the benchmark's span readers read them."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from open_simulator_tpu.core.objects import Node
from open_simulator_tpu.engine.simulator import (
    AppResource,
    ClusterResource,
    Simulator,
    simulate,
)
from open_simulator_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEVEN = ["prepare-cluster", "expand-workloads", "encode-cluster", "encode",
         "schedule", "bind-placements", "decode-result"]


def _cluster():
    nodes = [
        Node.from_dict({
            "metadata": {"name": f"n{i}",
                         "labels": {"kubernetes.io/hostname": f"n{i}"}},
            "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                       "pods": "10"}},
        })
        for i in range(3)
    ]
    return ClusterResource(nodes=nodes)


def _apps():
    deploy = {
        "kind": "Deployment",
        "metadata": {"name": "d", "namespace": "x"},
        "spec": {
            "replicas": 5,
            "template": {
                "metadata": {"labels": {"app": "d"}},
                "spec": {"containers": [
                    {"name": "c", "image": "i",
                     "resources": {"requests": {"cpu": "1"}}}
                ]},
            },
        },
    }
    return [AppResource(name="a", objects=[deploy])]


def _last_root(name):
    return [r for r in tracing.recent_timings() if r["name"] == name][-1]


def _count(tree, name):
    return (tree["name"] == name) + sum(
        _count(c, name) for c in tree.get("children", ()))


def test_simulate_root_has_exactly_the_seven_children_in_order():
    simulate(_cluster(), _apps())
    root = _last_root("simulate")
    assert [c["name"] for c in root["children"]] == SEVEN
    # the children tile the call: the root's own time is a sliver of it
    kids = sum(c["duration_s"] for c in root["children"])
    assert kids <= root["duration_s"] + 1e-3


def test_host_syncs_per_call_are_positive_and_repeat():
    counts = []
    for _ in range(2):
        simulate(_cluster(), _apps())
        root = _last_root("simulate")
        counts.append(_count(root, "host-sync"))
        schedule = next(c for c in root["children"] if c["name"] == "schedule")
        assert _count(schedule, "host-sync") >= 1
    assert counts[0] > 0
    assert counts[0] == counts[1]


def test_simulator_run_opens_one_root_per_run():
    sim = Simulator(_cluster())
    assert _last_root("prepare-cluster")["meta"] == {"pods": 0}
    # by span id: the history is a ring, so a full one keeps its length
    seen = {r["span_id"] for r in tracing.recent_timings()}
    sim.run(_apps())
    new = [r for r in tracing.recent_timings() if r["span_id"] not in seen]
    assert [r["name"] for r in new] == ["simulate"]
    assert [c["name"] for c in new[0]["children"]] == SEVEN[1:]


def test_fetch_returns_host_values_inside_a_host_sync_span():
    with tracing.span("fetch-probe") as root:
        a, b = tracing.fetch((jnp.arange(3), jnp.ones(2)), "probe-site")
    assert isinstance(a, np.ndarray) and a.tolist() == [0, 1, 2]
    assert isinstance(b, np.ndarray)
    (sync,) = root.children
    assert sync.name == "host-sync" and sync.meta == {"site": "probe-site"}


def _ctx(spans, calls=2):
    return bench_run.Context(
        calls=calls, spans=spans, fast_paths={}, setup_compile_s=0.0,
        window_compiles=0, trace=None, info={},
    )


_SPANS = {
    "simulate": (10.0, 2), "prepare-cluster": (4.0, 2),
    "expand-workloads": (1.0, 2), "encode-cluster": (1.0, 2),
    "encode": (0.5, 4), "schedule": (2.0, 4), "bind-placements": (0.6, 4),
    "decode-result": (0.3, 2), "host-sync": (1.5, 30),
}


@pytest.mark.parametrize("metric,want", [
    ("prepare_ms", 2000.0),
    ("bind_ms", 300.0),
    ("simulate_self_ms", 300.0),
    ("host_sync_ms", 750.0),
    ("host_syncs", 15.0),
])
def test_span_readers(metric, want):
    read = bench_run._reader(ROOT, metric)
    assert read(_ctx(_SPANS)) == pytest.approx(want)
    # a program without the span reads nothing, and does not raise
    assert read(_ctx({"simulate": (10.0, 2), "schedule": (2.0, 4)})) is None
