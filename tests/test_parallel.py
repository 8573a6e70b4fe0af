"""Multi-device sharding tests on the virtual 8-device CPU mesh: the sharded
engine must produce bit-identical placements to the single-device engine."""

import numpy as np

import jax

from open_simulator_tpu.ops.kernels import schedule_batch, weights_array
from open_simulator_tpu.ops.tile import tile_pod_batch
from open_simulator_tpu.parallel.mesh import (
    make_mesh,
    shard_state,
    sharded_schedule_batch,
)


def synthetic(n_nodes, n_pods):
    from __graft_entry__ import _synthetic_state

    return _synthetic_state(n_nodes=n_nodes, n_pods=n_pods)


def test_eight_device_mesh_available():
    assert len(jax.devices()) == 8


def test_sharded_matches_single_device():
    ns, carry, rows = synthetic(64, 96)
    w = weights_array()
    carry_ref, nodes_ref, reasons_ref, *_ = schedule_batch(ns, carry, rows, w)

    mesh = make_mesh()
    ns_sh, carry_sh = shard_state(mesh, ns, carry)
    fn = sharded_schedule_batch(mesh)
    carry_out, nodes_sh, reasons_sh, *_ = fn(ns_sh, carry_sh, rows, w)

    np.testing.assert_array_equal(np.asarray(nodes_ref), np.asarray(nodes_sh))
    np.testing.assert_array_equal(np.asarray(reasons_ref), np.asarray(reasons_sh))
    # carry shards gather back to the same free matrix
    np.testing.assert_allclose(
        np.asarray(carry_ref.free), np.asarray(carry_out.free), rtol=0, atol=1e-4
    )


def test_dryrun_multichip_entrypoint():
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(8)


def test_product_engine_sharded_matches_single_device():
    """simulate(mesh=...) — the PRODUCT path (grouped scheduler under GSPMD) —
    must place every pod exactly where the single-device run does, on the
    same fixture the e2e suite uses."""
    import os

    from open_simulator_tpu.api.config import SimonConfig
    from open_simulator_tpu.engine.apply import build_apps, build_cluster
    from open_simulator_tpu.engine.simulator import simulate
    from open_simulator_tpu.parallel.mesh import product_mesh

    from open_simulator_tpu.core.workloads import reset_name_rng

    cfg = SimonConfig.load(
        os.path.join(os.path.dirname(__file__), "fixtures", "simon-config.yaml")
    )
    # identical generated pod names across the two independent builds
    reset_name_rng()
    ref = simulate(build_cluster(cfg), build_apps(cfg))
    reset_name_rng()
    sharded = simulate(build_cluster(cfg), build_apps(cfg), mesh=product_mesh(8))

    def placements(res):
        return sorted(
            (p.key, st.node.name) for st in res.node_status for p in st.pods
        )

    assert placements(sharded) == placements(ref)
    assert [u.pod.key for u in sharded.unscheduled] == [
        u.pod.key for u in ref.unscheduled
    ]
    assert [u.reason for u in sharded.unscheduled] == [
        u.reason for u in ref.unscheduled
    ]


def test_tile_pod_batch_matches_full_encoding():
    """Tiling template rows must schedule identically to encoding every pod."""
    from open_simulator_tpu.core.objects import Node, Pod
    from open_simulator_tpu.ops.encode import (
        Encoder,
        encode_nodes,
        encode_pods,
        initial_selector_counts,
    )
    from open_simulator_tpu.ops.state import (
        carry_from_table,
        node_static_from_table,
        pod_rows_from_batch,
    )

    nodes = [
        Node.from_dict(
            {
                "metadata": {"name": f"n{i}", "labels": {"kubernetes.io/hostname": f"n{i}"}},
                "status": {"allocatable": {"cpu": "8", "memory": "16Gi", "pods": "110"}},
            }
        )
        for i in range(4)
    ]

    def pod(name):
        return Pod.from_dict(
            {
                "metadata": {"name": name, "namespace": "d", "labels": {"app": "a"}},
                "spec": {
                    "containers": [
                        {"name": "c", "image": "img", "resources": {"requests": {"cpu": "1", "memory": "1Gi"}}}
                    ]
                },
            }
        )

    w = weights_array()

    # full encoding
    full_pods = [pod(f"p{i}") for i in range(10)]
    enc1 = Encoder()
    enc1.register_pods(full_pods)
    t1 = encode_nodes(enc1, nodes)
    b1 = encode_pods(enc1, full_pods)
    out1 = schedule_batch(
        node_static_from_table(enc1, t1),
        carry_from_table(t1, initial_selector_counts(enc1, t1, [])),
        pod_rows_from_batch(b1),
        w,
    )

    # template + tile
    enc2 = Encoder()
    tmpl = [pod("tpl")]
    enc2.register_pods(tmpl)
    t2 = encode_nodes(enc2, nodes)
    b2 = tile_pod_batch(encode_pods(enc2, tmpl), [10])
    out2 = schedule_batch(
        node_static_from_table(enc2, t2),
        carry_from_table(t2, initial_selector_counts(enc2, t2, [])),
        pod_rows_from_batch(b2),
        w,
    )
    np.testing.assert_array_equal(
        np.asarray(out1[1])[:10], np.asarray(out2[1])[:10]
    )
    assert b2.keys[:3] == ["d/tpl-0", "d/tpl-1", "d/tpl-2"]


def test_fast_path_under_mesh_matches_single_device():
    """The trajectory fast path (ops/fast.py) under GSPMD node-axis sharding:
    big groups route through build_trajectory + light_scan with sharded
    ns/carry — placements, reasons and the exit carry must equal the
    unsharded run exactly (the `simon apply --devices N` path at scale)."""
    from tests.factories import build_state
    from open_simulator_tpu.ops.fast import schedule_batch_fast

    ns, carry, batch = build_state(64, 512)
    w = weights_array()
    carry_ref, nodes_ref, reasons_ref, *_ = schedule_batch_fast(
        ns, carry, batch, w, force_fast=True
    )

    mesh = make_mesh()
    ns_sh, carry_sh = shard_state(mesh, ns, carry)
    carry_out, nodes_sh, reasons_sh, *_ = schedule_batch_fast(
        ns_sh, carry_sh, batch, w, force_fast=True
    )
    np.testing.assert_array_equal(nodes_ref, nodes_sh)
    np.testing.assert_array_equal(reasons_ref, reasons_sh)
    for name in carry_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(carry_ref, name)),
            np.asarray(getattr(carry_out, name)),
            err_msg=f"carry field {name}",
        )


def test_extender_path_under_mesh(stub_factory):
    """The per-pod extender path (probe_step/commit_step) compiles and runs
    under GSPMD node-axis sharding, matching the single-device run with the
    same pass-through extender."""
    from open_simulator_tpu.core.objects import Node
    from open_simulator_tpu.core.workloads import reset_name_rng
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )
    from open_simulator_tpu.models.profiles import ExtenderConfig
    from open_simulator_tpu.parallel.mesh import product_mesh

    stub = stub_factory({})   # pass-through: keep all, score 0
    ext = [
        ExtenderConfig(
            url_prefix=stub.url,
            filter_verb="filter", prioritize_verb="prioritize",
        )
    ]

    def nodes():
        return [
            Node.from_dict(
                {
                    "metadata": {
                        "name": f"m{i}",
                        "labels": {"kubernetes.io/hostname": f"m{i}"},
                    },
                    "status": {
                        "allocatable": {
                            "cpu": "8", "memory": "16Gi", "pods": "110"
                        }
                    },
                }
            )
            for i in range(16)
        ]

    app = AppResource(
        name="m",
        objects=[
            {
                "kind": "Deployment",
                "metadata": {"name": "w", "namespace": "m"},
                "spec": {
                    "replicas": 6,
                    "template": {
                        "metadata": {"labels": {"app": "w"}},
                        "spec": {
                            "containers": [
                                {"name": "c", "image": "i",
                                 "resources": {"requests": {"cpu": "2"}}}
                            ]
                        },
                    },
                },
            }
        ],
    )
    reset_name_rng()
    single = simulate(ClusterResource(nodes=nodes()), [app], extenders=ext)
    reset_name_rng()
    sharded = simulate(
        ClusterResource(nodes=nodes()), [app], extenders=ext,
        mesh=product_mesh(8),
    )

    def key(r):
        return sorted(
            (p.key, st.node.name)
            for st in r.node_status
            for p in st.pods
        )

    assert key(single) == key(sharded)
    assert not single.unscheduled and not sharded.unscheduled
