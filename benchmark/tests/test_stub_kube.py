"""The stub Kubernetes API gives the program's live snapshot the same
cluster that the generator makes directly from the seed."""

from benchmark import stub_kube
from benchmark.tests.tiny import REPO, shrunk

SEED = 2_147_483_659  # above 32 signed bits


def _cfg():
    """The envelope at its tiny size, from its overlay."""
    return shrunk(REPO, "configs", "envelope_5k", "benchmark/configs/envelope_5k.json")


def _facts(cluster):
    nodes = [(n.name, dict(n.meta.labels), dict(n.allocatable)) for n in cluster.nodes]
    pods = [(p.key, p.node_name, dict(p.requests), dict(p.meta.labels), p.phase)
            for p in cluster.pods]
    return nodes, pods


def test_snapshot_through_stub_equals_direct_cluster():
    from open_simulator_tpu.engine.simulator import ClusterResource
    from open_simulator_tpu.utils.kubeclient import KubeClient, KubeConfig, snapshot_cluster

    cfg = _cfg()
    nodes, pods = stub_kube.cluster_objects(cfg, SEED)
    direct = ClusterResource.from_objects(nodes + pods)
    with stub_kube.serve(cfg, SEED) as url:
        live = snapshot_cluster(KubeClient(KubeConfig(server=url)))
    assert len(live.nodes) == cfg["node_groups"][0]["count"] == 50
    assert len(live.pods) == cfg["bound"]["count"] == 1000
    assert _facts(live) == _facts(direct)
