"""Trajectory fast path must be bit-identical to the naive scan.

The oracle is `schedule_batch` (ops/kernels.py) — the sequential
one-commit-at-a-time semantics of the reference's scheduleOne cycle
(generic_scheduler.go:131-175). Every scenario checks placements, failure
reasons, allocation takes AND the final carry (all leaves, exact equality).
"""

import json

import numpy as np
import pytest

from open_simulator_tpu.core.objects import (
    ANNO_GPU_COUNT_POD,
    ANNO_GPU_MEM_POD,
    ANNO_NODE_LOCAL_STORAGE,
    ANNO_POD_LOCAL_STORAGE,
    Node,
    Pod,
)
from open_simulator_tpu.ops.encode import (
    Encoder,
    encode_nodes,
    encode_pods,
    initial_anti_counts,
    initial_port_counts,
    initial_selector_counts,
)
from open_simulator_tpu.ops.fast import schedule_batch_fast
from open_simulator_tpu.ops.kernels import schedule_batch, weights_array
from open_simulator_tpu.ops.state import (
    carry_from_table,
    node_static_from_table,
    pod_rows_from_batch,
)
from open_simulator_tpu.ops.tile import tile_pod_batch


def _assert_identical(ns, carry0, batch, force_fast=True, filter_on=None):
    """Run oracle + fast path on the same state; demand exact equality."""
    w = weights_array()
    rows = pod_rows_from_batch(batch)
    carry_ref, nodes_ref, reasons_ref, take_ref, vg_ref, dev_ref = schedule_batch(
        ns, carry0, rows, w, filter_on=filter_on
    )
    carry_f, nodes_f, reasons_f, take_f, vg_f, dev_f = schedule_batch_fast(
        ns, carry0, batch, w, force_fast=force_fast, filter_on=filter_on
    )
    total = int(batch.valid.sum())
    np.testing.assert_array_equal(np.asarray(nodes_ref)[:total], nodes_f[:total])
    np.testing.assert_array_equal(np.asarray(reasons_ref)[:total], reasons_f[:total])
    np.testing.assert_array_equal(np.asarray(take_ref)[:total], take_f[:total])
    np.testing.assert_array_equal(np.asarray(vg_ref)[:total], vg_f[:total])
    np.testing.assert_array_equal(np.asarray(dev_ref)[:total], dev_f[:total])
    # Final carry: bit-identical so subsequent batches diverge nowhere.
    # The oracle scan also commits the (all-invalid) padding rows — they are
    # no-ops by construction, so state equality is still exact.
    for name in carry_ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(carry_ref, name)),
            np.asarray(getattr(carry_f, name)),
            err_msg=f"carry field {name}",
        )
    return nodes_f


def _encode(nodes, templates, counts, bound=()):
    from open_simulator_tpu.ops.encode import aggregate_usage

    enc = Encoder()
    enc.register_pods(templates)
    for pod, _ in bound:
        enc.register_pods([pod])
    table = encode_nodes(enc, nodes, existing_usage=aggregate_usage(list(bound)))
    batch = tile_pod_batch(encode_pods(enc, templates), counts)
    ns = node_static_from_table(enc, table)
    carry = carry_from_table(
        table,
        initial_selector_counts(enc, table, list(bound)),
        port_counts=initial_port_counts(enc, table, list(bound)),
        anti_counts=initial_anti_counts(enc, table, list(bound)),
    )
    return ns, carry, batch


def _node(name, cpu="16", mem="32Gi", pods="16", labels=None, taints=None):
    return Node.from_dict(
        {
            "metadata": {
                "name": name,
                "labels": {"kubernetes.io/hostname": name, **(labels or {})},
            },
            "spec": {"taints": taints or []},
            "status": {
                "allocatable": {"cpu": cpu, "memory": mem, "pods": pods}
            },
        }
    )


def _pod(name, cpu="500m", mem="512Mi", labels=None, spec_extra=None, anno=None):
    spec = {
        "containers": [
            {"name": "c", "resources": {"requests": {"cpu": cpu, "memory": mem}}}
        ]
    }
    spec.update(spec_extra or {})
    return Pod.from_dict(
        {
            "metadata": {
                "name": name,
                "namespace": "fast",
                "labels": labels or {},
                "annotations": anno or {},
            },
            "spec": spec,
        }
    )


def test_fast_matches_naive_tiled_mix():
    """The tiled plan workload: spread + tolerations + selectors, 4 templates."""
    from tests.factories import build_state

    ns, carry, batch = build_state(24, 400)
    _assert_identical(ns, carry, batch)


def test_fast_triggers_without_force_on_big_groups():
    """The heuristic itself must pick the fast path for plan-shaped groups
    (nodes cap at 110 pods; groups of 600 >> 2*J)."""
    from tests.factories import build_state

    ns, carry, batch = build_state(16, 2400)
    _assert_identical(ns, carry, batch, force_fast=False)


def test_fast_overflow_reasons():
    """More pods than cluster capacity: the unschedulable tail's failure
    attribution must match the oracle exactly."""
    nodes = [_node(f"n-{i}", cpu="4", pods="6") for i in range(6)]
    zones = [{"topology.kubernetes.io/zone": f"z-{i % 2}"} for i in range(6)]
    for n, z in zip(nodes, zones):
        n.meta.labels.update(z)
    tmpl = _pod(
        "t",
        cpu="1",
        labels={"app": "web"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 2,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "web"}},
                }
            ]
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [64])
    nodes_out = _assert_identical(ns, carry, batch)
    assert (nodes_out == -1).sum() > 0  # overflow actually happened


def test_fast_hard_spread():
    """DoNotSchedule spread: domains block and unblock as others fill — the
    carry-coupled mask must replay exactly."""
    nodes = []
    for i in range(9):
        nodes.append(
            _node(
                f"n-{i}",
                cpu="32",
                pods="20",
                labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
            )
        )
    tmpl = _pod(
        "t",
        cpu="250m",
        labels={"app": "spread"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "spread"}},
                },
                {
                    "maxSkew": 3,
                    "topologyKey": "kubernetes.io/hostname",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "spread"}},
                },
            ]
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [120])
    _assert_identical(ns, carry, batch)


def test_fast_required_anti_affinity():
    """Required anti-affinity by hostname: each node takes exactly one pod;
    symmetry counts must evolve identically (own_anti path)."""
    nodes = [_node(f"n-{i}", pods="30") for i in range(8)]
    tmpl = _pod(
        "t",
        cpu="100m",
        labels={"app": "solo"},
        spec_extra={
            "affinity": {
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "solo"}},
                            "topologyKey": "kubernetes.io/hostname",
                        }
                    ]
                }
            }
        },
    )
    other = _pod("o", cpu="100m", labels={"app": "other"})
    ns, carry, batch = _encode(nodes, [tmpl, other], [24, 24])
    nodes_out = _assert_identical(ns, carry, batch)
    assert (nodes_out[:24] >= 0).sum() == 8  # one per node, 16 blocked


def test_fast_pod_affinity_zone():
    """Required pod affinity over zones incl. the first-pod-of-group case."""
    nodes = [
        _node(
            f"n-{i}",
            pods="30",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="100m",
        labels={"app": "pack"},
        spec_extra={
            "affinity": {
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "pack"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        }
                    ]
                }
            }
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [40])
    _assert_identical(ns, carry, batch)


def test_fast_host_ports():
    """Host ports: one pod per node, self-conflict afterwards — trajectory
    port feasibility and reason attribution must match."""
    nodes = [_node(f"n-{i}", pods="30") for i in range(5)]
    tmpl = _pod(
        "t",
        cpu="100m",
        spec_extra={
            "containers": [
                {
                    "name": "c",
                    "resources": {"requests": {"cpu": "100m"}},
                    "ports": [{"containerPort": 80, "hostPort": 8080}],
                }
            ]
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [12])
    nodes_out = _assert_identical(ns, carry, batch)
    assert (nodes_out >= 0).sum() == 5


def test_fast_gpu_share_group():
    """GPU share packing: per-device free memory is trajectory state; takes
    (device ids) must match the two-pointer/tightest-fit oracle."""
    def gpu_node(name, count, per_dev_gib):
        total = count * per_dev_gib
        res = {
            "cpu": "64",
            "memory": "256Gi",
            "pods": "110",
            "alibabacloud.com/gpu-count": str(count),
            "alibabacloud.com/gpu-mem": f"{total}Gi",
        }
        return Node.from_dict(
            {
                "metadata": {"name": name},
                "status": {"allocatable": dict(res), "capacity": dict(res)},
            }
        )

    nodes = [gpu_node(f"g-{i}", 4, 16) for i in range(4)]
    single = _pod(
        "s", cpu="1", mem="1Gi",
        anno={ANNO_GPU_MEM_POD: "4Gi", ANNO_GPU_COUNT_POD: "1"},
    )
    multi = _pod(
        "m", cpu="1", mem="1Gi",
        anno={ANNO_GPU_MEM_POD: "8Gi", ANNO_GPU_COUNT_POD: "2"},
    )
    ns, carry, batch = _encode(nodes, [single, multi], [30, 20])
    nodes_out = _assert_identical(ns, carry, batch)
    assert (nodes_out >= 0).sum() > 0


def test_fast_open_local_storage():
    """Open-Local: VG binpack consumes trajectory state; vg takes and the
    final vg_free must match exactly."""
    def st_node(name, vg_gib):
        node = _node(name, cpu="32", pods="110")
        node.meta.annotations[ANNO_NODE_LOCAL_STORAGE] = json.dumps(
            {
                "vgs": [
                    {"name": "pool", "capacity": str(vg_gib << 30), "requested": "0"}
                ],
                "devices": [],
            }
        )
        return node

    nodes = [st_node(f"s-{i}", 40 + 10 * i) for i in range(4)]
    tmpl = _pod(
        "t", cpu="250m",
        anno={
            ANNO_POD_LOCAL_STORAGE: json.dumps(
                [{"name": "data", "kind": "LVM", "size": str(5 << 30)}]
            )
        },
    )
    plain = _pod("p", cpu="250m")
    ns, carry, batch = _encode(nodes, [tmpl, plain], [28, 12])
    nodes_out = _assert_identical(ns, carry, batch)
    assert (nodes_out[:28] >= 0).sum() > 0


def test_fast_taints_and_selectors():
    """Static-mask variety: tainted nodes, tolerating group, selector-pinned
    group, plus bound pods seeding nonzero carry counts."""
    nodes = []
    for i in range(8):
        taints = (
            [{"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
            if i % 2 == 0
            else []
        )
        nodes.append(
            _node(
                f"n-{i}",
                pods="20",
                labels={"tier": "gold" if i % 3 == 0 else "silver"},
                taints=taints,
            )
        )
    tol = _pod(
        "tol", cpu="200m", labels={"app": "b"},
        spec_extra={
            "tolerations": [
                {"key": "dedicated", "operator": "Equal", "value": "batch",
                 "effect": "NoSchedule"}
            ]
        },
    )
    pinned = _pod(
        "pin", cpu="200m", labels={"app": "c"},
        spec_extra={"nodeSelector": {"tier": "gold"}},
    )
    bound_pod = _pod("pre", cpu="1", labels={"app": "b"})
    bound_pod.node_name = "n-1"
    ns, carry, batch = _encode(
        nodes, [tol, pinned], [30, 20], bound=[(bound_pod, "n-1")]
    )
    _assert_identical(ns, carry, batch)


def test_fast_small_group_falls_back():
    """Without force_fast, tiny groups must take the grouped path and still
    be exact (the dispatch itself is under test here)."""
    nodes = [_node(f"n-{i}") for i in range(4)]
    tmpl = _pod("t", cpu="250m")
    ns, carry, batch = _encode(nodes, [tmpl], [10])
    _assert_identical(ns, carry, batch, force_fast=False)


def test_fast_resources_filter_disabled_falls_back():
    """A profile disabling NodeResourcesFit voids the trajectory bound (the
    resource filter is what caps per-node commits) — the dispatcher must fall
    back to the grouped path and stay exact."""
    import jax.numpy as jnp

    from open_simulator_tpu.ops.kernels import F_RESOURCES, NUM_FILTERS

    nodes = [_node(f"n-{i}", cpu="2", pods="4") for i in range(3)]
    tmpl = _pod("t", cpu="1")
    ns, carry, batch = _encode(nodes, [tmpl], [80])
    fo = np.ones(NUM_FILTERS, bool)
    fo[F_RESOURCES] = False
    fo_j = jnp.asarray(fo)

    w = weights_array()
    rows = pod_rows_from_batch(batch)
    _, nodes_ref, reasons_ref, *_ = schedule_batch(ns, carry, rows, w, fo_j)
    _, nodes_f, reasons_f, *_ = schedule_batch_fast(
        ns, carry, batch, w, force_fast=True, filter_on=fo_j
    )
    total = int(batch.valid.sum())
    np.testing.assert_array_equal(np.asarray(nodes_ref)[:total], nodes_f[:total])
    np.testing.assert_array_equal(np.asarray(reasons_ref)[:total], reasons_f[:total])
    # with the filter off, every pod lands despite 3x4 pod slots
    assert (nodes_f[:total] >= 0).all()


def test_fast_filter_disable_parity_when_fast():
    """Disabling a non-resource filter (NodePorts) keeps the fast path active
    and bit-identical to the oracle with the same mask."""
    import jax.numpy as jnp

    from open_simulator_tpu.ops.kernels import F_NODE_PORTS, NUM_FILTERS

    nodes = [_node(f"n-{i}", pods="40") for i in range(4)]
    tmpl = _pod(
        "t",
        cpu="100m",
        spec_extra={
            "containers": [
                {
                    "name": "c",
                    "resources": {"requests": {"cpu": "100m"}},
                    "ports": [{"containerPort": 80, "hostPort": 8080}],
                }
            ]
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [20])
    fo = np.ones(NUM_FILTERS, bool)
    fo[F_NODE_PORTS] = False
    fo_j = jnp.asarray(fo)

    w = weights_array()
    rows = pod_rows_from_batch(batch)
    _, nodes_ref, reasons_ref, *_ = schedule_batch(ns, carry, rows, w, fo_j)
    _, nodes_f, reasons_f, *_ = schedule_batch_fast(
        ns, carry, batch, w, force_fast=True, filter_on=fo_j
    )
    total = int(batch.valid.sum())
    np.testing.assert_array_equal(np.asarray(nodes_ref)[:total], nodes_f[:total])
    np.testing.assert_array_equal(np.asarray(reasons_ref)[:total], reasons_f[:total])
    assert (nodes_f[:total] >= 0).all()  # port conflicts no longer filter


def test_sort_path_fires_for_plain_groups():
    """Groups with purely node-local scoring must take the one-sort path
    (PATH_COUNTS proves which strategy ran; parity alone cannot)."""
    from open_simulator_tpu.ops import fast

    nodes = [_node(f"n-{i}", cpu="16", pods="12") for i in range(6)]
    tmpl = _pod("t", cpu="500m")
    ns, carry, batch = _encode(nodes, [tmpl], [60])
    before = dict(fast.PATH_COUNTS)
    _assert_identical(ns, carry, batch)
    assert fast.PATH_COUNTS["sort"] > before["sort"]


def test_sort_path_monotonicity_fallback_is_exact():
    """A pod whose balanced-allocation gain outweighs its least-allocated
    loss produces an INCREASING score sequence — the sort path must detect
    it (mono check) and the scan fallback must stay exact.

    Nodes are memory-saturated by bound pods (memfrac ~0.9, cpufrac ~0.01);
    each cpu-heavy incoming pod narrows |cpufrac - memfrac| by ~0.09 while
    least-allocated drops only ~0.055 — the combined score rises."""
    from open_simulator_tpu.ops import fast

    nodes = [_node(f"n-{i}", cpu="10", mem="100Gi", pods="40") for i in range(4)]
    hogs = []
    for i, nd in enumerate(nodes):
        hog = _pod(f"hog-{i}", cpu="100m", mem="90Gi")
        hog.node_name = nd.meta.name
        hogs.append((hog, nd.meta.name))
    tmpl = _pod("t", cpu="1", mem="1Gi")
    ns, carry, batch = _encode(nodes, [tmpl], [30], bound=hogs)
    before = dict(fast.PATH_COUNTS)
    _assert_identical(ns, carry, batch)
    after = dict(fast.PATH_COUNTS)
    assert after["sort_fallback"] > before["sort_fallback"], (
        f"expected the mono check to trip; counters {after}"
    )


@pytest.fixture(params=["domain", "micro"])
def spread_path(request):
    """Run a spread scenario through both strategies: the domain-merge path
    (default) and the micro scan (forced by DM_CAP=0). Yields the expected
    PATH_COUNTS key; both must be bit-identical to the oracle."""
    from open_simulator_tpu.ops import fast

    old = fast.DM_CAP
    if request.param == "micro":
        fast.DM_CAP = 0
    try:
        yield request.param
    finally:
        fast.DM_CAP = old


def _assert_spread_path(nodes, tmpl, count, path, min_unscheduled=1):
    from open_simulator_tpu.ops import fast

    ns, carry, batch = _encode(nodes, [tmpl], [count])
    before = dict(fast.PATH_COUNTS)
    nodes_out = _assert_identical(ns, carry, batch)
    assert fast.PATH_COUNTS[path] > before[path], (
        f"expected the {path} path; deltas "
        f"{ {k: fast.PATH_COUNTS[k] - before[k] for k in before} }"
    )
    total = int(batch.valid.sum())
    assert (nodes_out[:total] == -1).sum() >= min_unscheduled
    return nodes_out


def test_spread_soft_groups(spread_path):
    """Soft non-hostname spread with no other coupling: exact through domain
    block/unblock and the overflow tail, on both spread strategies."""
    nodes = [
        _node(
            f"n-{i}", cpu="8", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "soft"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 3,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "soft"}},
                }
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 100, spread_path)


def test_spread_hard_plus_soft(spread_path):
    """DoNotSchedule zone spread stacked with a soft row: domains block and
    unblock as others fill; the masks must replay the oracle exactly
    including the overflow tail's reasons."""
    nodes = [
        _node(
            f"n-{i}", cpu="4" if i < 3 else "32", pods="12",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "hard"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "hard"}},
                },
                {
                    "maxSkew": 4,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "hard"}},
                },
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 120, spread_path)


def test_spread_hard_only(spread_path):
    """ONLY DoNotSchedule constraints (no soft row): the spread score must
    hit the raw=0 -> sp=100 constant branch exactly while the hard mask
    still gates placements."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(6)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "hardonly"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 2,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "hardonly"}},
                }
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 70, spread_path)


def test_spread_two_keys(spread_path):
    """Two constraints on DIFFERENT topology keys: the domain path's
    combined classes are (zone, rack) tuples; counts under each constraint
    aggregate across classes sharing that key's domain."""
    nodes = [
        _node(
            f"n-{i}", cpu="2", pods="14",
            labels={
                "topology.kubernetes.io/zone": f"z-{i % 2}",
                "rack": f"r-{i % 4}",
            },
        )
        for i in range(12)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "mk"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 3,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "mk"}},
                },
                {
                    "maxSkew": 2,
                    "topologyKey": "rack",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "mk"}},
                },
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 60, spread_path)


def test_spread_eligibility_split(spread_path):
    """A nodeSelector restricts spread eligibility to a node subset: classes
    split on the eligibility bit, ineligible nodes never count toward
    domains, and DoNotSchedule minimums consider eligible domains only."""
    nodes = [
        _node(
            f"n-{i}", cpu="8", pods="14",
            labels={
                "topology.kubernetes.io/zone": f"z-{i % 3}",
                "tier": "gold" if i % 2 == 0 else "silver",
            },
        )
        for i in range(10)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "el"},
        spec_extra={
            "nodeSelector": {"tier": "gold"},
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "el"}},
                }
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 60, spread_path)


def test_spread_missing_key_nodes(spread_path):
    """Nodes without the topology key: soft counts treat them as count-0,
    the hard constraint excludes them entirely."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels=(
                {"topology.kubernetes.io/zone": f"z-{i % 3}"} if i < 6 else {}
            ),
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "hardonly"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 2,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "hardonly"}},
                }
            ]
        },
    )
    _assert_spread_path(nodes, tmpl, 80, spread_path)


@pytest.mark.parametrize("hard", [True, False])
def test_domain_path_parity_tight_zones(hard):
    """Small tight zones under soft (and optionally DoNotSchedule) zone
    spread take the domain-merge path, and its placements, reasons, takes
    and carry stay exactly oracle-identical, with and without the hard-mask
    branch."""
    from open_simulator_tpu.ops import fast

    nodes = [
        _node(
            f"n-{i}", cpu="4" if i < 3 else "32", pods="12",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    constraints = [
        {
            "maxSkew": 4,
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": "hard"}},
        }
    ]
    if hard:
        constraints.insert(0, {
            "maxSkew": 1,
            "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "hard"}},
        })
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "hard"},
        spec_extra={"topologySpreadConstraints": constraints},
    )
    ns, carry, batch = _encode(nodes, [tmpl], [120])
    before = dict(fast.PATH_COUNTS)
    _assert_identical(ns, carry, batch)
    # the domain path (not the micro scan) produced the checked result
    assert fast.PATH_COUNTS["domain"] > before["domain"]


def test_spread_with_host_ports(spread_path):
    """hostPort pods under zone spread: ports are node-local (still
    domain-eligible), each node takes exactly one pod before its port
    conflicts with itself — the lane feasibility must gate identically on
    both spread strategies."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="20",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(6)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "hp"},
        spec_extra={
            "containers": [
                {
                    "name": "c",
                    "resources": {"requests": {"cpu": "500m", "memory": "512Mi"}},
                    "ports": [{"containerPort": 80, "hostPort": 8080}],
                }
            ],
            "topologySpreadConstraints": [
                {
                    "maxSkew": 2,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "hp"}},
                }
            ],
        },
    )
    nodes_out = _assert_spread_path(nodes, tmpl, 10, spread_path)
    # one pod per node (port self-conflict), 4 overflow
    placed = nodes_out[:10][nodes_out[:10] >= 0]
    assert len(placed) == 6 and len(set(placed.tolist())) == 6


def test_spread_filter_disabled_profile(spread_path):
    """A scheduler profile disabling PodTopologySpread must neutralize the
    DoNotSchedule mask on the domain path exactly as on the micro scan
    (the `| ~filter_on[F_SPREAD]` branch)."""
    import jax.numpy as jnp

    from open_simulator_tpu.ops import fast
    from open_simulator_tpu.ops.kernels import F_SPREAD, NUM_FILTERS

    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(6)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "nofilter"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "nofilter"}},
                }
            ]
        },
    )
    ns, carry, batch = _encode(nodes, [tmpl], [40])
    fo = jnp.ones(NUM_FILTERS, bool).at[F_SPREAD].set(False)
    before = dict(fast.PATH_COUNTS)
    _assert_identical(ns, carry, batch, filter_on=fo)
    key = "domain" if spread_path == "domain" else "micro"
    assert fast.PATH_COUNTS[key] > before[key]


def _assert_domain_fires(nodes, tmpls, counts):
    from open_simulator_tpu.ops import fast

    ns, carry, batch = _encode(nodes, tmpls, counts)
    before = dict(fast.PATH_COUNTS)
    out = _assert_identical(ns, carry, batch)
    assert fast.PATH_COUNTS["domain"] > before["domain"], (
        f"expected the domain path; deltas "
        f"{ {k: fast.PATH_COUNTS[k] - before[k] for k in before} }"
    )
    return out


def test_domain_required_anti_affinity():
    """Required pod ANTI-affinity (one pod per zone) through the domain
    path: the per-class cnt==0 verdict must flip as classes fill, exactly
    like the oracle's pod_affinity_mask."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "exc"},
        spec_extra={
            "affinity": {
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "exc"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        }
                    ]
                }
            }
        },
    )
    out = _assert_domain_fires(nodes, [tmpl], [70])
    placed = out[:70][out[:70] >= 0]
    assert len(placed) == 3  # one per zone, anti-affinity blocks the rest


def test_domain_required_affinity_first_pod():
    """Required pod affinity with self-match: the first pod lands anywhere
    (the total==0 special case), later pods must co-locate in its zone."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "co"},
        spec_extra={
            "affinity": {
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "co"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        }
                    ]
                }
            }
        },
    )
    out = _assert_domain_fires(nodes, [tmpl], [40])
    placed = out[:40][out[:40] >= 0]
    zones = {int(p) % 3 for p in placed}
    assert len(placed) == 30 and len(zones) == 1  # all in the first zone


def test_domain_preferred_affinity_score():
    """Preferred pod affinity through the domain path: the per-class
    min-max-normalized score must steer pods toward the populated zone,
    bit-identical to the oracle's score_inter_pod_affinity."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="20",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "pref"},
        spec_extra={
            "affinity": {
                "podAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "weight": 100,
                            "podAffinityTerm": {
                                "labelSelector": {
                                    "matchLabels": {"app": "pref"}
                                },
                                "topologyKey": "topology.kubernetes.io/zone",
                            },
                        }
                    ]
                }
            }
        },
    )
    _assert_domain_fires(nodes, [tmpl], [60])


def test_domain_spread_plus_affinity():
    """Spread AND preferred affinity in one group (the full
    partial8 + w_ipa*ipa + w_sp*sp fold) plus a second template whose
    required anti-affinity symmetry repels the first — all through the
    domain path, oracle-exact."""
    nodes = [
        _node(
            f"n-{i}", cpu="16", pods="12",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    both = _pod(
        "t0",
        cpu="500m",
        labels={"app": "w"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 2,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "w"}},
                }
            ],
            "affinity": {
                "podAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "weight": 10,
                            "podAffinityTerm": {
                                "labelSelector": {"matchLabels": {"app": "w"}},
                                "topologyKey": "topology.kubernetes.io/zone",
                            },
                        }
                    ]
                }
            },
        },
    )
    repeller = _pod(
        "t1",
        cpu="500m",
        labels={"app": "lone"},
        spec_extra={
            "affinity": {
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "w"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        }
                    ]
                }
            }
        },
    )
    _assert_domain_fires(nodes, [both, repeller], [50, 6])


def test_domain_pure_anti_symmetry():
    """A group with NO constraints of its own, coupled ONLY through another
    template's required anti-affinity (symmetry): plain pods must avoid the
    zones holding the repeller, through the domain path."""
    nodes = [
        _node(
            f"n-{i}", cpu="32", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i % 3}"},
        )
        for i in range(9)
    ]
    plain = _pod("t0", cpu="500m", labels={"app": "w"})
    repeller = _pod(
        "t1",
        cpu="500m",
        labels={"app": "lone"},
        spec_extra={
            "affinity": {
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        {
                            "labelSelector": {"matchLabels": {"app": "w"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        }
                    ]
                }
            }
        },
    )
    out = _assert_domain_fires(nodes, [repeller, plain], [2, 40])
    placed_plain = out[2:42][out[2:42] >= 0]
    # the two repeller pods hold two zones; plain pods fit only in the third
    assert len(placed_plain) == 30
    assert len({int(p) % 3 for p in placed_plain}) == 1


def test_domain_cap_falls_back_to_micro():
    """A group spanning more combined classes than DM_CAP must take the
    micro scan (the [Dc] state would not beat it), still exact."""
    from open_simulator_tpu.ops import fast

    nodes = [
        _node(
            f"n-{i}", cpu="8", pods="10",
            labels={"topology.kubernetes.io/zone": f"z-{i}"},  # 8 distinct
        )
        for i in range(8)
    ]
    tmpl = _pod(
        "t",
        cpu="500m",
        labels={"app": "many"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 5,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "ScheduleAnyway",
                    "labelSelector": {"matchLabels": {"app": "many"}},
                }
            ]
        },
    )
    old = fast.DM_CAP
    fast.DM_CAP = 4
    try:
        ns, carry, batch = _encode(nodes, [tmpl], [90])
        before = dict(fast.PATH_COUNTS)
        _assert_identical(ns, carry, batch)
        assert fast.PATH_COUNTS["micro"] > before["micro"]
        assert fast.PATH_COUNTS["domain"] == before["domain"]
    finally:
        fast.DM_CAP = old
