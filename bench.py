"""Headline benchmark: the BASELINE.json north-star config.

Capacity-plans a 100k-pod workload onto a 10k-node simulated cluster — the
full pod×node Filter/Score/Select sweep with sequential commit — on one TPU
chip, and reports scheduling throughput.

Baseline: the reference publishes no numbers (BASELINE.md); the driver-defined
target is 100k pods onto 10k nodes in <60s on a v5e-8, i.e. 1667 pods/s.
vs_baseline is throughput relative to that target (>1.0 beats it).

Output: one JSON line, e.g.
  {"metric": "schedule_100k_pods_10k_nodes", "value": 2560.0,
   "unit": "pods/s", "vs_baseline": 1.54, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

TARGET_PODS_PER_SEC = 100_000 / 60.0  # driver north star


def _watchdog_fired_total() -> int:
    """Total osim_watchdog_fired_total across stages (this process)."""
    from open_simulator_tpu.utils.metrics import WATCHDOG_FIRED

    return int(
        sum(s["value"] for s in WATCHDOG_FIRED.snapshot()["samples"])
    )


def build_state(n_nodes: int, n_pods: int):
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


# ---------------------------------------------------------------------------
# The five BASELINE.json configs, driven END-TO-END through the product
# engine (simulate()/plan_capacity — workload expansion, validation, encode,
# compile and decode all included in the reported wall).
# ---------------------------------------------------------------------------

def _mk_node(name, cpu, mem, pods="110", labels=None, capacity_extra=None):
    from open_simulator_tpu.core.objects import Node

    res = {"cpu": cpu, "memory": mem, "pods": pods}
    if capacity_extra:
        res.update(capacity_extra)
    return Node.from_dict(
        {
            "metadata": {
                "name": name,
                "labels": {"kubernetes.io/hostname": name, **(labels or {})},
            },
            "status": {"allocatable": dict(res), "capacity": dict(res)},
        }
    )


def _mk_deploy(name, replicas, cpu, mem, labels=None, spec_extra=None, anno=None):
    spec = {
        "containers": [
            {"name": "c", "image": "img",
             "resources": {"requests": {"cpu": cpu, "memory": mem}}}
        ]
    }
    spec.update(spec_extra or {})
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "bench"},
        "spec": {
            "replicas": replicas,
            "template": {
                "metadata": {
                    "labels": {"app": name, **(labels or {})},
                    "annotations": anno or {},
                },
                "spec": spec,
            },
        },
    }


def config_stock():
    """Config 1: the stock quickstart sample (cluster + 5 apps incl. a chart
    + the add-node capacity search), through the full Applier. Uses the
    first-party example/ tree; falls back to the reference's demo_1 only
    when example/ is missing from the checkout."""
    import io

    from open_simulator_tpu.api.config import AppInConfig, SimonConfig
    from open_simulator_tpu.engine.apply import run_apply

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "example")
    if os.path.isdir(os.path.join(here, "cluster", "demo")):
        cfg = SimonConfig.load(os.path.join(here, "simon-config.yaml"))
    else:
        ref = "/root/reference/example"
        cfg = SimonConfig(
            custom_config=f"{ref}/cluster/demo_1",
            new_node=f"{ref}/newnode/demo_1",
            app_list=[
                AppInConfig(
                    name="yoda", path=f"{ref}/application/charts/yoda", chart=True
                ),
                AppInConfig(name="simple", path=f"{ref}/application/simple"),
                AppInConfig(name="complicated", path=f"{ref}/application/complicate"),
                AppInConfig(name="open_local", path=f"{ref}/application/open_local"),
                AppInConfig(name="more_pods", path=f"{ref}/application/more_pods"),
            ],
        )
    t0 = time.time()
    outcome = run_apply(cfg, out=io.StringIO())
    wall = time.time() - t0
    added = outcome.plan.nodes_added if outcome.plan else 0
    return {
        "wall_s": round(wall, 2),
        "nodes_added": added,
        "unscheduled": len(outcome.result.unscheduled),
    }


def _simulate_config(nodes, deploys):
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )

    t0 = time.time()
    result = simulate(
        ClusterResource(nodes=nodes),
        [AppResource(name="bench", objects=deploys)],
    )
    wall = time.time() - t0
    placed = sum(len(st.pods) for st in result.node_status)
    return wall, placed, len(result.unscheduled)


def config_fit(n_pods=1_000, n_nodes=100):
    """Config 2: NodeResourcesFit-only bin-packing, 1k pods x 100 nodes."""
    nodes = [_mk_node(f"n-{i}", "32", "64Gi") for i in range(n_nodes)]
    deploys = [
        _mk_deploy("web", n_pods // 2, "500m", "1Gi"),
        _mk_deploy("api", n_pods - n_pods // 2, "1", "2Gi"),
    ]
    wall, placed, unsched = _simulate_config(nodes, deploys)
    return {
        "wall_s": round(wall, 2),
        "value": round(n_pods / wall, 1),
        "scheduled": placed,
        "unscheduled": unsched,
    }


def config_spread_affinity(n_pods=10_000, n_nodes=1_000):
    """Config 3: PodTopologySpread + InterPodAffinity, 10k pods x 1k nodes
    across 3 zones."""
    nodes = [
        _mk_node(
            f"n-{i}", "32", "64Gi",
            labels={"topology.kubernetes.io/zone": f"az-{i % 3}"},
        )
        for i in range(n_nodes)
    ]
    spread = {
        "topologySpreadConstraints": [
            {
                "maxSkew": 50,
                "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"app": "front"}},
            }
        ]
    }
    affinity = {
        "affinity": {
            "podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "weight": 10,
                        "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {"app": "front"}},
                            "topologyKey": "topology.kubernetes.io/zone",
                        },
                    }
                ]
            }
        }
    }
    deploys = [
        _mk_deploy("front", n_pods // 2, "250m", "512Mi", spec_extra=spread),
        _mk_deploy("back", n_pods - n_pods // 2, "500m", "1Gi",
                   spec_extra=affinity),
    ]
    wall, placed, unsched = _simulate_config(nodes, deploys)
    return {
        "wall_s": round(wall, 2),
        "value": round(n_pods / wall, 1),
        "scheduled": placed,
        "unscheduled": unsched,
    }


def config_gpushare(n_pods=5_000, n_nodes=320):
    """Config 4: the gpushare example shape scaled to 5k GPU pods (8x16GiB
    devices per node, mixed 4/8 GiB share requests)."""
    gpu_extra = {
        "alibabacloud.com/gpu-count": "8",
        "alibabacloud.com/gpu-mem": "128Gi",
    }
    nodes = [
        _mk_node(f"g-{i}", "64", "256Gi", capacity_extra=gpu_extra)
        for i in range(n_nodes)
    ]
    deploys = [
        _mk_deploy(
            "train", n_pods // 2, "2", "8Gi",
            anno={"alibabacloud.com/gpu-mem": "8Gi",
                  "alibabacloud.com/gpu-count": "1"},
        ),
        _mk_deploy(
            "infer", n_pods - n_pods // 2, "1", "4Gi",
            anno={"alibabacloud.com/gpu-mem": "4Gi",
                  "alibabacloud.com/gpu-count": "1"},
        ),
    ]
    wall, placed, unsched = _simulate_config(nodes, deploys)
    return {
        "wall_s": round(wall, 2),
        "value": round(n_pods / wall, 1),
        "scheduled": placed,
        "unscheduled": unsched,
    }


def config_plan(n_pods=100_000, n_nodes=10_000):
    """Config 5 — the north star: full capacity plan, 100k pods onto a
    10k-node cluster sized so the workload overflows and the add-node search
    must run. Wall includes workload expansion, validation, encode, all
    probe simulations and every compile."""
    from open_simulator_tpu.engine.capacity import plan_capacity
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
    )

    # Sized so the workload genuinely overflows (~37.5k cpu demand vs ~30k
    # capacity at full scale) and the add-node search must bracket + bisect.
    nodes = [
        _mk_node(
            f"n-{i}", "3", "6Gi",
            labels={"topology.kubernetes.io/zone": f"az-{i % 3}"},
        )
        for i in range(n_nodes)
    ]
    spread = {
        "topologySpreadConstraints": [
            {
                "maxSkew": 50,
                "topologyKey": "topology.kubernetes.io/zone",
                "whenUnsatisfiable": "ScheduleAnyway",
                "labelSelector": {"matchLabels": {"app": "web"}},
            }
        ]
    }
    deploys = [
        _mk_deploy("web", n_pods // 2, "500m", "1Gi", spec_extra=spread),
        _mk_deploy("batch", n_pods - n_pods // 2, "250m", "512Mi"),
    ]
    template = _mk_node("new-node", "32", "64Gi")
    cluster = ClusterResource(nodes=nodes)
    apps = [AppResource(name="bench", objects=deploys)]
    t0 = time.time()
    plan = plan_capacity(cluster, apps, template)
    wall = time.time() - t0
    return {
        "wall_s": round(wall, 2),
        "value": round(n_pods / wall, 1),
        "nodes_added": plan.nodes_added if plan else -1,
        "attempts": plan.attempts if plan else 0,
        "under_60s": wall < 60.0,
    }


def config_capacity_sweep(n_base=2, n_replicas=48):
    """Config: serial-vs-batched capacity search on the same fixture, same
    process. Required pod anti-affinity on hostname makes the demand-based
    lower bound useless (estimate ~1 node, true answer ~replicas-base), so
    the serial path must walk the full exponential bracket + bisection
    (>=8 probe simulations) while the batched path (plan_capacity
    sweep_mode=batched, docs/batching.md) closes the same bracket in <=3
    vmapped device calls. `capacity_sweep_speedup` is the recorded
    serial/batched wall-clock ratio."""
    from open_simulator_tpu.engine.capacity import plan_capacity
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
    )

    anti = {
        "affinity": {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "labelSelector": {"matchLabels": {"app": "lonely"}},
                        "topologyKey": "kubernetes.io/hostname",
                    }
                ]
            }
        }
    }

    def fixture():
        nodes = [_mk_node(f"n-{i}", "32", "64Gi") for i in range(n_base)]
        deploys = [_mk_deploy("lonely", n_replicas, "500m", "1Gi",
                              spec_extra=anti)]
        cluster = ClusterResource(nodes=nodes)
        apps = [AppResource(name="bench", objects=deploys)]
        template = _mk_node("new-node", "32", "64Gi")
        return cluster, apps, template

    def one(mode):
        from open_simulator_tpu.core.workloads import reset_name_rng

        reset_name_rng()  # identical pod names => comparable searches
        cluster, apps, template = fixture()
        t0 = time.time()
        plan = plan_capacity(cluster, apps, template, sweep_mode=mode)
        return time.time() - t0, plan

    serial_wall, serial_plan = one("serial")
    batched_wall, batched_plan = one("batched")
    out = {
        "serial_wall_s": round(serial_wall, 2),
        "batched_wall_s": round(batched_wall, 2),
        "capacity_sweep_speedup": round(serial_wall / batched_wall, 2),
        "serial_probes": serial_plan.attempts if serial_plan else -1,
        "batched_calls": batched_plan.batched_calls if batched_plan else -1,
        "nodes_added": batched_plan.nodes_added if batched_plan else -1,
        "wall_s": round(serial_wall + batched_wall, 2),
    }
    if (serial_plan is None) != (batched_plan is None) or (
        serial_plan is not None
        and serial_plan.nodes_added != batched_plan.nodes_added
    ):
        out["error"] = (
            f"serial/batched disagree: "
            f"{serial_plan and serial_plan.nodes_added} vs "
            f"{batched_plan and batched_plan.nodes_added}"
        )
    return out


def config_multi_scenario(n_scenarios=64, n_nodes=64, n_pods=400):
    """Config: one simulate_batch() call sweeping 64 what-if node-count
    scenarios of a 400-pod workload — the scenario axis rides a single
    vmapped program (docs/batching.md), so the sweep costs one compile and
    one (bucketed) device call instead of 64 serial simulations.
    `scenarios_per_second` sits next to `pods_per_second`: the former is
    the sweep's own throughput, the latter counts every lane's pods."""
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        Scenario,
        simulate_batch,
    )

    nodes = [_mk_node(f"n-{i}", "16", "32Gi") for i in range(n_nodes)]
    deploys = [_mk_deploy("web", n_pods, "500m", "1Gi")]
    cluster = ClusterResource(nodes=nodes)
    apps = [AppResource(name="bench", objects=deploys)]
    # node counts cycle over the top half so every lane keeps a distinct
    # prefix of the cluster but all lanes share one padded node tensor
    scenarios = [
        Scenario(
            name=f"s-{i}",
            node_count=n_nodes // 2 + (i % (n_nodes // 2 + 1)),
        )
        for i in range(n_scenarios)
    ]
    t0 = time.time()
    results = simulate_batch(cluster, apps, scenarios)
    wall = time.time() - t0
    placed = sum(len(st.pods) for r in results for st in r.node_status)
    return {
        "wall_s": round(wall, 2),
        "scenarios": n_scenarios,
        "scenarios_per_second": round(n_scenarios / wall, 2),
        "pods_per_second": round(n_scenarios * n_pods / wall, 1),
        "scheduled": placed,
        "unscheduled": sum(len(r.unscheduled) for r in results),
    }


def config_warm_start():
    """Config: the compile-lifecycle headline. Cold leg: `simon warmup`'s
    engine — AOT-compile every audited jit entry at canonical shapes plus
    the capacity-sweep rehearsal, banking the persistent compile cache;
    ALL compile time lives here. Warm leg: the identical full capacity
    sweep re-run against warm caches under CompileCounter, demanding ZERO
    cold compiles — so the warm wall-clock excludes compile time by
    construction (a counted invariant), not by subtraction."""
    from open_simulator_tpu.analysis.jaxpr_audit import _run_sweeps
    from open_simulator_tpu.engine.warmup import run_warmup
    from open_simulator_tpu.ops.fast import reset_scenario_programs
    from open_simulator_tpu.utils.platform import CompileCounter

    t0 = time.time()
    report = run_warmup()
    cold_s = time.time() - t0
    reset_scenario_programs()
    t1 = time.time()
    with CompileCounter() as counter:
        plan, plan_b = _run_sweeps()
    warm_s = time.time() - t1
    out = {
        "wall_s": round(warm_s, 2),
        "cold_wall_s": round(cold_s, 2),
        "warm_wall_s": round(warm_s, 2),
        "warm_speedup": round(cold_s / warm_s, 1) if warm_s > 0 else None,
        "warmup_entries": len(report.entries),
        "warmup_cold_compiles": report.cold_compiles,
        "warm_backend_compiles": counter.backend_compiles,
        "warm_persistent_hits": counter.persistent_hits,
        "warm_cold_compiles": counter.cold_compiles,
        "nodes_added": plan.nodes_added,
        "batched_nodes_added": plan_b.nodes_added,
        "cache_dir": report.cache_dir,
    }
    if not report.ok:
        out["error"] = f"warmup missed audited entries: {report.missing}"
    elif counter.cold_compiles != 0:
        out["error"] = (
            f"warm leg paid {counter.cold_compiles} cold compile(s); "
            "warm start must exclude all compile time"
        )
    elif plan.nodes_added != plan_b.nodes_added:
        out["error"] = (
            f"serial/batched sweep answers diverged: "
            f"{plan.nodes_added} vs {plan_b.nodes_added}"
        )
    return out


def config_sharded_smoke(n_scenarios=8, n_nodes=24, n_pods=120):
    """Config: scenario-axis sharding equivalence. The same what-if sweep
    runs unsharded and sharded across a 2-device mesh (scenario lanes split
    over devices, node tensors replicated — parallel/mesh.shard_scenarios);
    per-lane placements and unscheduled reasons must be byte-identical.
    _run_segment provisions the 2 virtual CPU devices for this segment via
    --xla_force_host_platform_device_count, so it runs in every CI lane."""
    import jax

    from open_simulator_tpu.core.workloads import reset_name_rng
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        Scenario,
        simulate_batch,
    )
    from open_simulator_tpu.parallel.mesh import product_mesh

    ndev = len(jax.devices())
    if ndev < 2:
        return {"error": f"sharded smoke needs >=2 devices, have {ndev}"}

    def _digest(r) -> str:
        doc = {
            "placements": {
                st.node.name: sorted(p.key for p in st.pods)
                for st in r.node_status
            },
            "unscheduled": sorted(
                (u.pod.key, u.reason) for u in r.unscheduled
            ),
        }
        return json.dumps(doc, sort_keys=True)

    nodes = [_mk_node(f"n-{i}", "8", "16Gi") for i in range(n_nodes)]
    cluster = ClusterResource(nodes=nodes)
    apps = [AppResource(
        name="bench", objects=[_mk_deploy("web", n_pods, "500m", "1Gi")]
    )]
    scenarios = [
        Scenario(name=f"s-{i}", node_count=n_nodes // 2 + i)
        for i in range(n_scenarios)
    ]
    reset_name_rng()
    t0 = time.time()
    base = simulate_batch(cluster, apps, scenarios)
    unsharded_s = time.time() - t0
    mesh = product_mesh(2)
    reset_name_rng()
    t1 = time.time()
    sharded = simulate_batch(cluster, apps, scenarios, mesh=mesh)
    sharded_s = time.time() - t1
    mismatches = [
        sc.name
        for sc, a, b in zip(scenarios, base, sharded)
        if _digest(a) != _digest(b)
    ]
    out = {
        "wall_s": round(sharded_s, 2),
        "unsharded_wall_s": round(unsharded_s, 2),
        "sharded_wall_s": round(sharded_s, 2),
        "devices": ndev,
        "scenarios": n_scenarios,
        "lanes_identical": not mismatches,
    }
    if mismatches:
        out["error"] = f"sharded lanes diverged: {mismatches}"
    return out


def config_preempt(n_nodes=60, n_low=400, n_high=100):
    """Config 6: priority-tiered preemption. A low-priority tier fills the
    cluster (400 x 1cpu on 60 x 8cpu = 80 cpu headroom), then a
    high-priority tier (100 x 2cpu, priority 100) arrives: ~40 pods fit in
    the headroom and the rest must evict low-priority victims through the
    device victim search (ops/preempt.py). Measures the cost the reference
    pays in selectVictimsOnNode's per-node filter dry runs
    (default_preemption.go:578-626)."""
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )

    def one_run():
        nodes = [_mk_node(f"n-{i}", "8", "32Gi") for i in range(n_nodes)]
        low = _mk_deploy("low-tier", n_low, "1", "1Gi")
        high = _mk_deploy(
            "high-tier", n_high, "2", "1Gi", spec_extra={"priority": 100}
        )
        t0 = time.time()
        result = simulate(
            ClusterResource(nodes=nodes),
            [AppResource(name="bench", objects=[low, high])],
        )
        return time.time() - t0, result

    n_pods = n_low + n_high
    # Cold: compiles dominate (the search, eviction and table-update
    # programs). Warm: a second identical run in the same process
    # reuses every executable — the steady state a server-mode or capacity-
    # search caller sees, and what the persistent XLA cache gives a fresh
    # process. The reference pays neither (plain Go calls) but its per-probe
    # cost is a full filter dry run per candidate node
    # (default_preemption.go:578-626).
    cold_wall, cold_res = one_run()
    warm_wall, result = one_run()
    placed = sum(len(st.pods) for st in result.node_status)
    assert len(result.preempted) == len(cold_res.preempted)
    return {
        "wall_s": round(warm_wall, 2),
        "value": round(n_pods / warm_wall, 1),
        "cold_wall_s": round(cold_wall, 2),
        "cold_value": round(n_pods / cold_wall, 1),
        "scheduled": placed,
        "unscheduled": len(result.unscheduled),
        "preempted": len(result.preempted),
    }


def config_extender(n_pods=1_000, n_nodes=100):
    """Config 7: the extender tax, wave vs serial. A local pass-through HTTP
    extender (filter + prioritize, interested in every pod) forces all 1k
    pods down the extender path — the cost the reference pays in
    findNodesThatPassExtenders/prioritizeNodes per scheduling cycle
    (core/extender.go:273-381). Two legs against the same in-process mock:
    the wave pipeline (engine/extender_wave.py, default) and a
    `legacy_serial` baseline (OSIM_EXTENDER_WAVE=0 + OSIM_EXTENDER_KEEPALIVE=0
    — the pre-wave engine transport included: per-pod probe→HTTP→commit on a
    fresh urllib connection per request). Placement multisets must match
    exactly (the tentpole's byte-identity contract) and the wave leg's
    schedule-extenders span must beat serial by the `speedup_x >= 3`
    acceptance bar (errors below it — CI enforces)."""
    import socket
    import threading

    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )
    from open_simulator_tpu.models.profiles import ExtenderConfig
    from open_simulator_tpu.utils import httppool, metrics

    class _LeanExtender:
        """Raw-socket HTTP/1.1 pass-through extender: keep-alive like a real
        (Go net/http) backend, thread per connection, TCP_NODELAY both ways.
        The mock's server-side Python is GIL-bound work the client cannot
        overlap, so it is kept lean (no BaseHTTPRequestHandler, responses
        cached by node set) and each request charges HANDLER_LATENCY_S of
        GIL-free handler time — a generously fast real extender. A
        zero-latency in-process mock measures only serialized client-side
        Python, which no concurrency can compress; the latency is what any
        out-of-process backend actually exhibits and is identical for both
        legs."""

        HANDLER_LATENCY_S = 0.0005

        def __init__(self):
            self.sock = socket.create_server(("127.0.0.1", 0), backlog=128)
            self.port = self.sock.getsockname()[1]
            threading.Thread(target=self._accept, daemon=True).start()

        def _accept(self):
            while True:
                try:
                    conn, _ = self.sock.accept()
                except OSError:
                    return  # closed
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                threading.Thread(
                    target=self._serve, args=(conn,), daemon=True
                ).start()

        _resp_cache: dict = {}

        def _serve(self, conn):
            f = conn.makefile("rb")
            try:
                while True:
                    line = f.readline()
                    if not line:
                        return
                    path = line.split()[1]
                    length, close = 0, False
                    while True:
                        h = f.readline()
                        if h in (b"\r\n", b"\n", b""):
                            break
                        k, _, v = h.partition(b":")
                        k = k.lower()
                        if k == b"content-length":
                            length = int(v)
                        elif k == b"connection" and b"close" in v.lower():
                            close = True  # urllib's fresh-connection mode
                    body = json.loads(f.read(length) or b"{}")
                    names = body.get("NodeNames") or []
                    key = (path.endswith(b"/filter"), tuple(names))
                    data = self._resp_cache.get(key)
                    if data is None:
                        if key[0]:
                            resp = {
                                "NodeNames": names, "FailedNodes": {},
                                "Error": "",
                            }
                        else:
                            resp = [{"Host": n, "Score": 5} for n in names]
                        payload = json.dumps(resp).encode()
                        data = self._resp_cache[key] = (
                            b"HTTP/1.1 200 OK\r\n"
                            b"Content-Type: application/json\r\n"
                            b"Content-Length: %d\r\n\r\n" % len(payload)
                            + payload
                        )
                    time.sleep(self.HANDLER_LATENCY_S)
                    conn.sendall(data)
                    if close:
                        return
            except (OSError, ValueError, IndexError):
                pass
            finally:
                try:
                    f.close()
                except OSError:
                    pass
                conn.close()

        def close(self):
            try:
                self.sock.close()
            except OSError:
                pass

    httpd = _LeanExtender()

    def span_sum():
        _, s, _ = metrics.SPAN_DURATION.child_state(span="schedule-extenders")
        return s

    def leg(wave_env: str, keepalive_env: str):
        """One mode, warm-measured: a cold pass pays the jit compiles, a
        second pass is timed (wall + the schedule-extenders span delta)."""
        prev = {
            k: os.environ.get(k)
            for k in ("OSIM_EXTENDER_WAVE", "OSIM_EXTENDER_KEEPALIVE")
        }
        os.environ["OSIM_EXTENDER_WAVE"] = wave_env
        os.environ["OSIM_EXTENDER_KEEPALIVE"] = keepalive_env
        try:
            cfg = ExtenderConfig(
                url_prefix=f"http://127.0.0.1:{httpd.port}",
                filter_verb="filter",
                prioritize_verb="prioritize",
                node_cache_capable=True,  # NodeNames wire: dispatch cost only
            )
            apps = [
                AppResource(
                    name="bench",
                    objects=[_mk_deploy("ext-load", n_pods, "500m", "256Mi")],
                )
            ]

            def one():
                nodes = [
                    _mk_node(f"n-{i}", "16", "64Gi") for i in range(n_nodes)
                ]
                t0 = time.time()
                res = simulate(
                    ClusterResource(nodes=nodes), apps, extenders=[cfg]
                )
                return time.time() - t0, res
            cold_wall, _ = one()
            s0 = span_sum()
            warm_wall, result = one()
            span_s = span_sum() - s0
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            httppool.reset_pools()  # no warm sockets leak across legs
        placements = sorted(
            (
                p.meta.annotations.get("simon/workload-name", ""),
                st.node.name,
            )
            for st in result.node_status
            for p in st.pods
        )
        return {
            "wall_s": round(warm_wall, 2),
            "cold_wall_s": round(cold_wall, 2),
            "span_s": round(span_s, 3),
            "value": round(n_pods / warm_wall, 1),
            "scheduled": len(placements),
            "unscheduled": len(result.unscheduled),
        }, placements

    try:
        wave, wave_placed = leg("", "1")       # default: wave pipeline on
        serial, serial_placed = leg("0", "0")  # pre-wave engine + transport
    finally:
        httpd.close()
        httppool.reset_pools()
    speedup = (
        round(serial["span_s"] / wave["span_s"], 2) if wave["span_s"] else 0.0
    )
    out = {
        **wave,
        "legacy_serial": serial,
        "speedup_x": speedup,
        "identical_placements": wave_placed == serial_placed,
    }
    if wave_placed != serial_placed:
        out["error"] = (
            "wave placements diverge from legacy serial: byte-identity "
            "contract broken"
        )
    elif speedup < 3.0:
        out["error"] = (
            f"extender wave speedup {speedup}x is below the 3x acceptance "
            f"bar (span {wave['span_s']}s vs serial {serial['span_s']}s)"
        )
    return out


def config_sanitize_overhead(n_pods=1_000, n_nodes=100):
    """Config 8: the OSIM_SANITIZE=1 checkify tax. The same
    NodeResourcesFit sweep as fit_1k_100n, run plain and then sanitized in
    one process — ops/sanitize.py reads the env var per dispatch, so the
    flip needs no re-import. Each mode runs twice and reports its second,
    warm wall (the sanitized mode compiles its own checkify-wrapped
    executables on the first pass); overhead_x is warm-vs-warm."""
    nodes = [_mk_node(f"n-{i}", "32", "64Gi") for i in range(n_nodes)]
    deploys = [
        _mk_deploy("web", n_pods // 2, "500m", "1Gi"),
        _mk_deploy("api", n_pods - n_pods // 2, "1", "2Gi"),
    ]

    def run_mode(flag: str):
        prev = os.environ.get("OSIM_SANITIZE")
        os.environ["OSIM_SANITIZE"] = flag
        try:
            cold, _, _ = _simulate_config(nodes, deploys)
            warm, placed, unsched = _simulate_config(nodes, deploys)
        finally:
            if prev is None:
                os.environ.pop("OSIM_SANITIZE", None)
            else:
                os.environ["OSIM_SANITIZE"] = prev
        return cold, warm, placed, unsched

    p_cold, p_warm, p_placed, p_unsched = run_mode("0")
    s_cold, s_warm, s_placed, s_unsched = run_mode("1")
    out = {
        "wall_s": round(s_warm, 2),
        "value": round(n_pods / s_warm, 1),
        "plain_wall_s": round(p_warm, 2),
        "sanitized_wall_s": round(s_warm, 2),
        "plain_cold_wall_s": round(p_cold, 2),
        "sanitized_cold_wall_s": round(s_cold, 2),
        "overhead_x": round(s_warm / p_warm, 2) if p_warm > 0 else None,
        "scheduled": p_placed,
        "unscheduled": p_unsched,
    }
    if (s_placed, s_unsched) != (p_placed, p_unsched):
        # the sanitizer must be observational — a placement drift is a bug
        out["error"] = (
            f"sanitized run placed {s_placed}/{s_unsched} vs plain "
            f"{p_placed}/{p_unsched}"
        )
    return out


def config_serving_concurrent(
    n_clients=16, n_requests=4, queue_depth=8, coalesce_ms=50.0
):
    """Config 9: the overload-safe serving path (docs/serving.md). M
    concurrent clients burst identical deploy-apps requests at an embedded
    server with a bounded admission queue and a coalescing window; reports
    p50/p99 latency, req/s, shed rate, and the mean coalesced batch size —
    "heavy traffic" as a number. Every response must be definite (200 or a
    shed 429-with-Retry-After); anything else is reported as an error."""
    import threading
    import urllib.error
    import urllib.request

    from open_simulator_tpu.server import server as server_mod
    from open_simulator_tpu.utils import metrics

    def raw_node(name):
        res = {"cpu": "32", "memory": "64Gi", "pods": "110"}
        return {
            "kind": "Node",
            "metadata": {
                "name": name, "labels": {"kubernetes.io/hostname": name},
            },
            "status": {"allocatable": dict(res), "capacity": dict(res)},
        }

    body = json.dumps(
        {
            "cluster": {"objects": [raw_node(f"n-{i}") for i in range(20)]},
            "apps": [
                {
                    "name": "web",
                    "objects": [_mk_deploy("web", 100, "500m", "1Gi")],
                }
            ],
        }
    ).encode()

    srv = server_mod.make_server(
        0, queue_depth=queue_depth, coalesce_ms=coalesce_ms
    )
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{port}/api/deploy-apps"

    def one(timeout=120.0):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        t0 = time.time()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, time.time() - t0
        except urllib.error.HTTPError as e:
            e.read()
            return e.code, time.time() - t0
        except Exception:
            return -1, time.time() - t0

    # Warm pass: compile the simulate executables before the timed burst so
    # the latency distribution measures serving, not first-compile.
    warm_status, _ = one()
    try:
        if warm_status != 200:
            return {"error": f"warm-up request returned {warm_status}"}
        metrics.REGISTRY.reset()

        outcomes: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(n_clients)

        def client():
            barrier.wait()
            mine = [one() for _ in range(n_requests)]
            with lock:
                outcomes.extend(mine)

        threads = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0

        total = len(outcomes)
        ok_lat = sorted(lat for code, lat in outcomes if code == 200)
        shed = sum(1 for code, _ in outcomes if code in (429, 503))
        other = total - len(ok_lat) - shed
        _, co_sum, co_count = metrics.COALESCED_BATCH.child_state(mode="fanout")
        shed_by_reason = {
            s["labels"]["reason"]: int(s["value"])
            for s in metrics.REQUESTS_SHED.snapshot()["samples"]
        }

        def pct(p):
            if not ok_lat:
                return None
            return round(
                1000 * ok_lat[min(len(ok_lat) - 1, int(p * len(ok_lat)))], 1
            )

        out = {
            "wall_s": round(wall, 2),
            "value": round(len(ok_lat) / wall, 1) if wall > 0 else 0.0,
            "unit": "req/s",
            "clients": n_clients,
            "requests": total,
            "ok": len(ok_lat),
            "shed": shed,
            "shed_rate": round(shed / total, 3) if total else 0.0,
            "shed_by_reason": shed_by_reason,
            "p50_latency_ms": pct(0.50),
            "p99_latency_ms": pct(0.99),
            "queue_depth": queue_depth,
            "coalesce_ms": coalesce_ms,
            "coalesced_batch_mean": (
                round(co_sum / co_count, 2) if co_count else 0.0
            ),
        }
        if other:
            # 200 and shed-with-Retry-After are the only acceptable answers
            out["error"] = (
                f"{other} request(s) got a non-200/non-shed response: "
                f"{sorted({c for c, _ in outcomes if c not in (200, 429, 503)})}"
            )
        return out
    finally:
        srv.shutdown()
        srv.server_close()


def config_serving_saturation(
    n_clients=8, n_requests=12, queue_depth=16, n_nodes=10, replicas=12
):
    """Config 11: sustained serving throughput at queue saturation
    (docs/serving.md "continuous batching"). M closed-loop clients — each
    fires its next request the moment the previous answer lands — post
    bodies that differ only in score weights, so every pack is a
    multi-lane batched device call. n_clients defaults to one full
    SCENARIO_BUCKET (8): the pack heuristic dispatches at a full bucket,
    so the steady state is back-to-back full-occupancy device calls. The workload runs twice on the same
    machine: once against the replaced architecture (coalesce-window
    latency floor + cold per-pack dispatch: OSIM_SERVER_LOOP=0 and the
    loop's legacy_floor switch) and once against the continuous-batching
    loop (no floor, warm ScenarioSession packs). Reports sustained req/s
    for both, lane occupancy mean, p50/p99, and the speedup; the
    acceptance bar is speedup_x >= 2, and any non-200 response is an
    error (closed-loop clients never overrun the queue, so zero shed)."""
    import os
    import threading
    import urllib.error
    import urllib.request

    from open_simulator_tpu.server import server as server_mod
    from open_simulator_tpu.utils import metrics

    def raw_node(name):
        res = {"cpu": "32", "memory": "64Gi", "pods": "110"}
        return {
            "kind": "Node",
            "metadata": {
                "name": name, "labels": {"kubernetes.io/hostname": name},
            },
            "status": {"allocatable": dict(res), "capacity": dict(res)},
        }

    base_body = {
        "cluster": {"objects": [raw_node(f"n-{i}") for i in range(n_nodes)]},
        "apps": [
            {
                "name": "web",
                "objects": [_mk_deploy("web", replicas, "500m", "1Gi")],
            }
        ],
    }
    bodies = [
        json.dumps(
            dict(base_body, weights={"least_allocated": 50 + i})
        ).encode()
        for i in range(n_clients)
    ]

    def run_mode(loop_on: bool) -> dict:
        os.environ["OSIM_SERVER_LOOP"] = "1" if loop_on else "0"
        with server_mod._sessions_lock:
            server_mod._sessions.clear()
        srv = server_mod.make_server(
            0, queue_depth=queue_depth, pack_window_ms=50.0
        )
        if not loop_on:
            # faithful baseline: the pre-loop worker waited the window out
            # on EVERY batch, then dispatched cold
            srv.admission._loop.legacy_floor = True
        port = srv.server_address[1]
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{port}/api/deploy-apps"

        def one(payload, timeout=120.0):
            req = urllib.request.Request(
                url, data=payload,
                headers={"Content-Type": "application/json"},
            )
            t0 = time.time()
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    r.read()
                    return r.status, time.time() - t0
            except urllib.error.HTTPError as e:
                e.read()
                return e.code, time.time() - t0
            except Exception:
                return -1, time.time() - t0

        try:
            # warm pass: compile the batched scenario program (full pack of
            # n_clients lanes) before the timed run
            warm_outcomes: list = []
            warm_lock = threading.Lock()
            warm_barrier = threading.Barrier(n_clients)

            def warm_client(i):
                warm_barrier.wait()
                res = one(bodies[i])
                with warm_lock:
                    warm_outcomes.append(res)

            threads = [
                threading.Thread(target=warm_client, args=(i,))
                for i in range(n_clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            bad = [c for c, _ in warm_outcomes if c != 200]
            if bad:
                return {"error": f"warm-up returned {sorted(set(bad))}"}
            metrics.REGISTRY.reset()

            outcomes: list = []
            lock = threading.Lock()
            barrier = threading.Barrier(n_clients)

            def client(i):
                barrier.wait()
                mine = [one(bodies[i]) for _ in range(n_requests)]
                with lock:
                    outcomes.extend(mine)

            threads = [
                threading.Thread(target=client, args=(i,))
                for i in range(n_clients)
            ]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.time() - t0

            ok_lat = sorted(lat for code, lat in outcomes if code == 200)
            bad_codes = sorted({c for c, _ in outcomes if c != 200})
            _, occ_sum, occ_count = metrics.LANE_OCCUPANCY.child_state()
            _, it_sum, it_count = metrics.LOOP_ITERATION.child_state()

            def pct(p):
                if not ok_lat:
                    return None
                return round(
                    1000
                    * ok_lat[min(len(ok_lat) - 1, int(p * len(ok_lat)))],
                    1,
                )

            mode = {
                "wall_s": round(wall, 2),
                "req_s": (
                    round(len(ok_lat) / wall, 1) if wall > 0 else 0.0
                ),
                "ok": len(ok_lat),
                "requests": len(outcomes),
                "p50_latency_ms": pct(0.50),
                "p99_latency_ms": pct(0.99),
                "lane_occupancy_mean": (
                    round(occ_sum / occ_count, 3) if occ_count else None
                ),
                "loop_iterations": int(it_count),
            }
            if bad_codes:
                mode["error"] = (
                    f"non-200 response(s) at saturation: {bad_codes}"
                )
            return mode
        finally:
            srv.shutdown()
            srv.server_close()

    prior = os.environ.get("OSIM_SERVER_LOOP")
    try:
        baseline = run_mode(loop_on=False)
        loop = run_mode(loop_on=True)
    finally:
        if prior is None:
            os.environ.pop("OSIM_SERVER_LOOP", None)
        else:
            os.environ["OSIM_SERVER_LOOP"] = prior
        with server_mod._sessions_lock:
            server_mod._sessions.clear()

    for mode_name, mode in (("baseline", baseline), ("loop", loop)):
        if mode.get("error"):
            return {"error": f"{mode_name}: {mode['error']}"}
    speedup = (
        round(loop["req_s"] / baseline["req_s"], 2)
        if baseline["req_s"]
        else 0.0
    )
    out = {
        "value": loop["req_s"],
        "unit": "req/s",
        "wall_s": round(baseline["wall_s"] + loop["wall_s"], 2),
        "clients": n_clients,
        "requests_per_client": n_requests,
        "queue_depth": queue_depth,
        "baseline_req_s": baseline["req_s"],
        "speedup_x": speedup,
        "p50_latency_ms": loop["p50_latency_ms"],
        "p99_latency_ms": loop["p99_latency_ms"],
        "lane_occupancy_mean": loop["lane_occupancy_mean"],
        "baseline": baseline,
        "loop": loop,
    }
    if speedup < 2.0:
        out["error"] = (
            f"continuous-batching speedup {speedup}x is below the 2x "
            f"acceptance bar ({loop['req_s']} vs {baseline['req_s']} req/s)"
        )
    return out


def config_resident_delta_10k(n_nodes=10_000, n_deltas=30, touched=8):
    """Config 10: the resident-state delta path (engine/resident.py) at 10k
    nodes. A ResidentCluster cold-encodes once, then absorbs `n_deltas`
    refreshes that each bind `touched` new pods; the per-sync delta wall
    (host row re-encode + jitted scatters) is compared against the full
    `encode_nodes` re-encode the non-resident path would pay per refresh.
    The acceptance bar is speedup_x >= 10; the run ends with one forced
    drift-detector pass, so a digest divergence (or any repair during the
    walk) is reported as an error, not a faster-but-wrong number."""
    import statistics
    import tempfile
    import time

    from open_simulator_tpu.core.objects import Pod
    from open_simulator_tpu.engine.resident import ResidentCluster
    from open_simulator_tpu.ops.encode import encode_nodes

    nodes = [_mk_node(f"r-{i}", "32", "64Gi") for i in range(n_nodes)]

    def bound_pod(serial: int, node_name: str) -> Pod:
        return Pod.from_dict(
            {
                "metadata": {"name": f"b-{serial}", "namespace": "bench"},
                "spec": {
                    "nodeName": node_name,
                    "containers": [
                        {
                            "name": "c",
                            "image": "img",
                            "resources": {
                                "requests": {"cpu": "500m", "memory": "1Gi"}
                            },
                        }
                    ],
                },
            }
        )

    pods = [bound_pod(i, f"r-{i % n_nodes}") for i in range(256)]
    prev = os.environ.get("OSIM_RESIDENT_VERIFY_EVERY")
    os.environ["OSIM_RESIDENT_VERIFY_EVERY"] = "0"  # time pure applies
    try:
        res = ResidentCluster(journal_dir=tempfile.mkdtemp(prefix="osim-bench-"))
        t0 = time.time()
        res.sync(nodes, pods)  # cold start: full encode + device upload
        cold_wall = time.time() - t0

        full_walls = []
        for _ in range(3):
            t0 = time.time()
            encode_nodes(
                res.enc, nodes,
                existing_usage=res._usage, existing_gpu=res._gpu_usage,
                n_pad=res._host.n, min_axes=res._axes,
            )
            full_walls.append(time.time() - t0)

        serial = len(pods)
        delta_walls = []
        for k in range(n_deltas):
            for j in range(touched):
                serial += 1
                pods.append(bound_pod(serial, f"r-{(serial * 131) % n_nodes}"))
            t0 = time.time()
            res.sync(nodes, pods)
            delta_walls.append(time.time() - t0)
        delta_walls = delta_walls[1:]  # first sync pays the scatter-jit trace

        full_ms = 1000 * statistics.median(full_walls)
        delta_ms = 1000 * statistics.median(delta_walls)
        verified = res.verify_now()
    finally:
        if prev is None:
            os.environ.pop("OSIM_RESIDENT_VERIFY_EVERY", None)
        else:
            os.environ["OSIM_RESIDENT_VERIFY_EVERY"] = prev

    speedup = full_ms / delta_ms if delta_ms > 0 else None
    out = {
        "wall_s": round(sum(full_walls) + sum(delta_walls) + cold_wall, 2),
        "value": round(speedup, 1) if speedup else None,
        "unit": "x faster than full re-encode",
        "nodes": n_nodes,
        "deltas": n_deltas,
        "touched_rows_per_delta": touched,
        "cold_encode_ms": round(1000 * cold_wall, 1),
        "full_encode_ms": round(full_ms, 1),
        "delta_apply_ms": round(delta_ms, 2),
        "speedup_x": round(speedup, 1) if speedup else None,
        "verified": bool(verified),
        "repairs": res.repairs,
    }
    if not verified or res.repairs:
        out["error"] = (
            f"drift during bench: verified={verified} repairs={res.repairs}"
        )
    elif speedup is not None and speedup < 10:
        out["error"] = (
            f"delta apply only {speedup:.1f}x faster than full re-encode "
            "(acceptance floor is 10x)"
        )
    return out


def _hetero_template(name="new-node"):
    """A realistic heterogeneous capacity template: zone/instance-type
    labels, a taint, GPUs, open-local storage — the loop encode pays every
    axis it would pay in production, so the stamped-vs-loop ratio is the
    honest one."""
    import json as _json

    from open_simulator_tpu.core.objects import ANNO_NODE_LOCAL_STORAGE

    GiB = 1 << 30
    template = _mk_node(
        name, "32", "64Gi",
        labels={
            "topology.kubernetes.io/zone": "az-1",
            "node.kubernetes.io/instance-type": "ecs.gn7.8xlarge",
            "disk": "ssd",
        },
        capacity_extra={
            "alibabacloud.com/gpu-count": "4",
            "alibabacloud.com/gpu-mem": f"{4 * 16384}Mi",
        },
    )
    template.meta.annotations[ANNO_NODE_LOCAL_STORAGE] = _json.dumps(
        {
            "vgs": [{"name": "vg-open", "capacity": str(400 * GiB),
                     "requested": str(40 * GiB)}],
            "devices": [{"name": "sdb", "device": "/dev/sdb",
                         "capacity": str(200 * GiB), "mediaType": "ssd",
                         "isAllocated": False}],
        }
    )
    return template


def _preflight_verdict(config):
    """The statically machine-checked fits-in-HBM verdict that `simon
    preflight --write-budgets` banked for ``config`` in the checked-in
    budget book, or None. Lets the bench line carry the static
    peak-HBM/collective proof next to the measured throughput without
    recompiling anything here."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "budgets",
        "preflight.json",
    )
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f).get("verdicts", {}).get(config)
    except (OSError, ValueError):
        return None


def _config_plan_scaled(n_pods, n_nodes):
    """Million-scale node axis (docs/performance.md, node-bucket ladder):
    one segment publishing the four acceptance numbers together —

      - stamped-vs-loop encode wall at n_nodes clones, byte-identity
        asserted on every NodeTable array (floor: 10x);
      - full capacity plan pods/s at (n_pods, n_nodes) scale;
      - distinct compiled scenario programs, all on ladder rungs;
      - per-device HBM bytes for the node-sharded vs replicated table
        (>= 2 devices; sharded must be strictly smaller)."""
    import numpy as np

    from open_simulator_tpu.engine.capacity import new_fake_nodes, plan_capacity
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
    )
    from open_simulator_tpu.ops.encode import (
        _STAMP_FIELDS,
        Encoder,
        encode_nodes,
        node_bucket,
    )
    from open_simulator_tpu.ops.fast import (
        reset_scenario_programs,
        scenario_programs,
    )

    out = {}

    # --- template-stamped encode: loop vs stamped at n_nodes clones -------
    clones = new_fake_nodes(_hetero_template(), n_nodes)
    t0 = time.time()
    t_loop = encode_nodes(Encoder(), clones, stamp=False)
    loop_s = time.time() - t0
    stamped_s = float("inf")
    enc_stamp = None
    for _ in range(3):
        enc = Encoder()
        t0 = time.time()
        t_stamp = encode_nodes(enc, clones, stamp=True)
        if time.time() - t0 < stamped_s:
            stamped_s = time.time() - t0
            enc_stamp = enc
    byte_identical = all(
        np.asarray(getattr(t_loop, f)).tobytes()
        == np.asarray(getattr(t_stamp, f)).tobytes()
        for f in _STAMP_FIELDS
    ) and t_loop.names == t_stamp.names
    speedup = loop_s / stamped_s if stamped_s > 0 else None
    out["encode_loop_ms"] = round(1000 * loop_s, 1)
    out["encode_stamped_ms"] = round(1000 * stamped_s, 1)
    out["encode_stamped_speedup"] = round(speedup, 1) if speedup else None
    out["encode_byte_identical"] = bool(byte_identical)
    if not byte_identical:
        out["error"] = "stamped encode is not byte-identical to loop encode"
    elif speedup is not None and speedup < 10:
        out["error"] = (
            f"stamped encode only {speedup:.1f}x faster than loop "
            "(acceptance floor is 10x)"
        )

    # --- per-device HBM: node-sharded vs replicated table -----------------
    import jax

    if len(jax.devices()) >= 2:
        from open_simulator_tpu.ops.state import node_static_from_table
        from open_simulator_tpu.parallel.mesh import (
            hbm_bytes_per_device,
            node_sharding,
            product_mesh_2d,
            replicated,
        )

        mesh = product_mesh_2d(1, len(jax.devices()))
        ns = node_static_from_table(enc_stamp, t_stamp)
        rep = hbm_bytes_per_device(jax.device_put(ns, replicated(mesh, ns)))
        shd = hbm_bytes_per_device(jax.device_put(ns, node_sharding(mesh)))
        out["hbm_bytes_per_device_replicated"] = max(rep.values())
        out["hbm_bytes_per_device_sharded"] = max(shd.values())
        if max(shd.values()) >= max(rep.values()):
            out["error"] = out.get("error") or (
                "node-sharded table not smaller per device than replicated"
            )
        del ns

    # --- full capacity plan at (n_pods, n_nodes) scale --------------------
    # Sized like plan_100k_10k: the workload genuinely overflows (~0.375
    # cpu/pod demand vs 3 cpu/node supply) so the add-node search runs. No
    # spread constraint here — spread chunks the commit scan at every skew
    # boundary (plan_100k_10k covers that at scale); these segments measure
    # raw plan throughput on the node-bucket ladder, where whole
    # deployments batch through the group fast path.
    nodes = [
        _mk_node(
            f"n-{i}", "3", "6Gi",
            labels={"topology.kubernetes.io/zone": f"az-{i % 3}"},
        )
        for i in range(n_nodes)
    ]
    deploys = [
        _mk_deploy("web", n_pods // 2, "500m", "1Gi"),
        _mk_deploy("batch", n_pods - n_pods // 2, "250m", "512Mi"),
    ]
    template = _mk_node("new-node", "32", "64Gi")
    reset_scenario_programs()
    t0 = time.time()
    plan = plan_capacity(
        ClusterResource(nodes=nodes),
        [AppResource(name="bench", objects=deploys)],
        template,
    )
    wall = time.time() - t0
    out["wall_s"] = round(wall + loop_s + 3 * stamped_s, 2)
    out["plan_wall_s"] = round(wall, 2)
    out["value"] = round(n_pods / wall, 1)
    out["unit"] = "pods/s"
    out["nodes_added"] = plan.nodes_added if plan else -1
    out["attempts"] = plan.attempts if plan else 0
    out["batched_calls"] = plan.batched_calls if plan else 0
    # the commit engine the sweep routed through: OSIM_WAVE_COMMIT=1 runs
    # this whole segment on the conflict-parallel wave driver (byte-
    # identical placements; rounds/fallbacks in `simon metrics`), the
    # default is auto (wave only on parallel backends — see ops/wave.py)
    from open_simulator_tpu.ops import wave as wave_mod

    out["commit_engine"] = (
        "wave" if wave_mod.wave_enabled(n_pods) else "serial"
    )

    # --- distinct programs: every one on a ladder rung --------------------
    progs = scenario_programs()
    out["distinct_programs"] = sum(len(p) for p in progs.values())
    out["ladder_rungs_touched"] = sorted({n for (n, _p) in progs})
    off = [n for (n, _p) in progs if node_bucket(n) != n]
    if off:
        out["error"] = out.get("error") or f"off-ladder node paddings: {off}"

    # --- static preflight verdict (budgets/preflight.json) ----------------
    if (n_pods, n_nodes) == (1_000_000, 100_000):
        vd = _preflight_verdict("plan_1m_100k")
        if vd is not None:
            out["preflight_ok"] = bool(vd.get("ok"))
            out["preflight_peak_gib"] = vd.get("peak_gib")
            out["preflight_mesh"] = vd.get("mesh")
            out["preflight_node_table_sharded"] = vd.get(
                "node_table_sharded"
            )
            if not vd.get("ok"):
                out["error"] = out.get("error") or (
                    "preflight verdict failed: plan_1m_100k does not fit "
                    "per-device HBM (or node table replicated) — see "
                    "`simon preflight`"
                )
    return out


def config_prove_smoke(n_universes=512):
    """The `simon prove` engine-vs-oracle checker on a strided sample of
    the small-scope corpus: tracks the exhaustive checker's device
    throughput and pins `universes_checked` into the bench JSON. The CI
    prove job runs the full 151,875-universe corpus against the banked
    contract; this is the bench-side heartbeat with the same engine path
    (stamped-gather packing onto the scenario axis, one device call at
    this sample size)."""
    from open_simulator_tpu.analysis.semantics import run_prove

    out = {"n_universes": n_universes}
    t0 = time.time()
    report = run_prove(smoke=n_universes, chunk=n_universes)
    wall = time.time() - t0
    out["wall_s"] = round(wall, 2)
    out["universes_checked"] = report.universes_checked
    out["device_calls"] = report.device_calls
    out["divergences"] = report.divergence_total
    out["digest"] = report.digest
    out["value"] = round(report.universes_checked / wall, 1)
    out["unit"] = "universes/s"
    if report.divergence_total:
        out["error"] = (
            f"{report.divergence_total} oracle divergence(s); minimized "
            f"counterexample: {report.minimized}"
        )
    return out


def config_interleave_smoke():
    """The `simon interleave` protocol model checker under quick bounds:
    explored-states throughput of the cooperative-scheduler explorer
    over all five protocol scenarios. The report itself is
    wall-clock-free by design (same seed => byte-identical), so the
    timing lives here, bench-side. Any invariant violation on the real
    protocols — or a scenario exhausting its run budget — is an error."""
    from open_simulator_tpu.analysis.interleave import run_interleave

    out = {}
    t0 = time.time()
    report = run_interleave(quick=True)
    wall = time.time() - t0
    states = sum(s.states for s in report.scenarios)
    out["wall_s"] = round(wall, 2)
    out["runs"] = sum(s.runs for s in report.scenarios)
    out["states"] = states
    out["pruned"] = sum(s.pruned for s in report.scenarios)
    out["scenarios"] = {
        s.name: {"runs": s.runs, "states": s.states,
                 "completed": s.completed}
        for s in report.scenarios
    }
    out["digest"] = report.to_dict()["digest"]
    out["value"] = round(states / wall, 1)
    out["unit"] = "states/s"
    if not report.ok:
        bad = [f"{s.name}:{v.invariant}"
               for s in report.scenarios for v in s.violations]
        incomplete = [s.name for s in report.scenarios if not s.completed]
        out["error"] = (
            f"interleave not clean on real protocols: "
            f"violations={bad} budget-exhausted={incomplete}"
        )
    return out


def config_plan_200k_20k():
    """CPU-scaled million-node segment: 200k pods / 20k nodes (CI publishes
    this one; plan_1m_100k is the full-scale variant)."""
    return _config_plan_scaled(200_000, 20_000)


def config_plan_1m_100k():
    """The full million-scale segment: 1M pods / 100k nodes."""
    return _config_plan_scaled(1_000_000, 100_000)


def config_checkpoint_overhead(n_pods=10_000, n_nodes=100, chunk=1024):
    """Config: the chunked-commit checkpoint tax (docs/durability.md). The
    same 10k-pod commit scan dispatched once monolithically and once
    chunked (OSIM_COMMIT_CHUNK) under a live PlanCheckpointer — every
    chunk journaled, a carry+prefix snapshot every 4 chunks, all into a
    throwaway run dir. Each mode runs twice and reports its warm wall
    (the chunked program compiles separately on the first pass);
    overhead_x is warm-vs-warm and must stay within 5%: checkpointing is
    host-side bookkeeping between device dispatches, not extra device
    work. The two final carries must also digest-match bit-for-bit — the
    chunked driver's byte-identity contract, asserted at bench scale."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from open_simulator_tpu.durable import RunJournal
    from open_simulator_tpu.durable.checkpoint import (
        PlanCheckpointer,
        installed,
    )
    from open_simulator_tpu.ops import fast
    from open_simulator_tpu.ops import state as state_mod
    from open_simulator_tpu.ops.kernels import weights_array
    from open_simulator_tpu.utils import metrics

    ns, carry, batch = build_state(n_nodes, n_pods)
    s_pad = fast.scenario_bucket(1)
    w_s = jnp.asarray(np.stack([np.asarray(weights_array())] * s_pad))
    valid_s = jnp.asarray(np.stack([np.asarray(ns.valid)] * s_pad))

    def run_once():
        import jax

        carry_s = state_mod.stack_carry(carry, s_pad)
        t0 = time.time()
        out = fast.schedule_scenarios_host(
            ns, carry_s, batch, w_s, valid_s, 1
        )
        jax.block_until_ready(out[0])
        wall = time.time() - t0
        return wall, fast.scenario_carry_digest(out[0])

    def _put_env(key, val):
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val

    def run_mode(chunked: bool):
        prev = os.environ.get("OSIM_COMMIT_CHUNK")
        prev_every = os.environ.get("OSIM_CKPT_EVERY")
        run_dir = None
        try:
            if chunked:
                os.environ["OSIM_COMMIT_CHUNK"] = str(chunk)
                os.environ["OSIM_CKPT_EVERY"] = "4"
                run_dir = tempfile.mkdtemp(prefix="osim-ckpt-bench-")
                journal = RunJournal.open(run_dir)
                try:
                    with installed(PlanCheckpointer(journal)):
                        cold, _ = run_once()
                        warm, digest = run_once()
                finally:
                    journal.close()
            else:
                os.environ.pop("OSIM_COMMIT_CHUNK", None)
                cold, _ = run_once()
                warm, digest = run_once()
        finally:
            _put_env("OSIM_COMMIT_CHUNK", prev)
            _put_env("OSIM_CKPT_EVERY", prev_every)
            if run_dir:
                shutil.rmtree(run_dir, ignore_errors=True)
        return cold, warm, digest

    m_cold, m_warm, m_digest = run_mode(chunked=False)
    bytes0 = metrics.CHECKPOINT_BYTES.value()
    chunks0 = metrics.PLAN_CHUNKS.value()
    c_cold, c_warm, c_digest = run_mode(chunked=True)
    overhead = (c_warm / m_warm) if m_warm > 0 else None
    out = {
        "wall_s": round(c_warm, 2),
        "value": round(n_pods / c_warm, 1) if c_warm > 0 else None,
        "monolithic_wall_s": round(m_warm, 2),
        "chunked_wall_s": round(c_warm, 2),
        "monolithic_cold_wall_s": round(m_cold, 2),
        "chunked_cold_wall_s": round(c_cold, 2),
        "overhead_x": round(overhead, 3) if overhead else None,
        "chunk": chunk,
        "chunks_dispatched": int(metrics.PLAN_CHUNKS.value() - chunks0),
        "checkpoint_bytes": int(metrics.CHECKPOINT_BYTES.value() - bytes0),
        "digest": f"{c_digest:08x}",
    }
    if c_digest != m_digest:
        out["error"] = (
            f"chunked digest {c_digest:08x} != monolithic {m_digest:08x}; "
            "the chunked driver must be byte-identical"
        )
    elif overhead is not None and overhead > 1.05:
        out["error"] = (
            f"checkpoint overhead {overhead:.3f}x exceeds the 1.05x budget"
        )
    return out


def config_wave_commit_10k(
    n_pods=10_000, n_nodes=500, wave_pods=1_280, wave=256, wave_rounds=8
):
    """Config: the conflict-parallel wave commit (ops/wave.py, ROADMAP
    item 1) against the serial scan it replaces.

    Three legs:
      1. serial oracle — the monolithic decide+commit scan over n_pods
         (one schedule_step per pod); its warm wall is the baseline and
         its placements/carry digest are the reference.
      2. commit phase — the serial leg's choices replayed through
         `ops.fast:commit_choices` (the row-wise commit scan): the only
         inherently sequential part of the wave engine. The acceptance
         floor is >= 10x faster than the serial scan on CPU — the
         sequential-depth reduction the wave engine buys — and the final
         carry must digest-match the serial leg bit-for-bit.
      3. wave engine — the full Jacobi round driver (OSIM_WAVE_COMMIT=1)
         over a wave_pods prefix-sized workload, reporting
         rounds-to-converge, conflicts, and bounded-rounds fallbacks
         from the metrics registry, plus its own serial-reference digest
         equality. Total wall is reported, NOT gated: on a single-core
         CPU host a probe round costs about one serial scan of the wave
         (element-throughput-bound), so the data-parallel win needs a
         parallel backend — docs/performance.md works the numbers.
    """
    import jax
    import jax.numpy as jnp

    from open_simulator_tpu.ops import fast
    from open_simulator_tpu.ops import state as state_mod
    from open_simulator_tpu.ops.kernels import weights_array
    from open_simulator_tpu.utils import metrics

    def msum(counter) -> float:
        return sum(
            s["value"] for s in counter.snapshot()["samples"]
        )

    saved = {
        k: os.environ.get(k)
        for k in (
            "OSIM_WAVE_COMMIT", "OSIM_WAVE_SIZE", "OSIM_WAVE_ROUNDS",
            "OSIM_COMMIT_CHUNK",
        )
    }

    def _put_env(key, val):
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val

    def serial_run(ns, carry, batch, w_s, valid_s, s_pad):
        carry_s = state_mod.stack_carry(carry, s_pad)
        t0 = time.time()
        out = fast.schedule_scenarios_host(
            ns, carry_s, batch, w_s, valid_s, 1
        )
        jax.block_until_ready(out[0])
        return time.time() - t0, out

    def hist_stats(hist):
        snap = hist.snapshot()
        if not snap["samples"]:
            return 0, 0.0
        s = snap["samples"][0]
        return int(s["count"]), float(s["sum"])

    out = {}
    try:
        os.environ.pop("OSIM_COMMIT_CHUNK", None)
        os.environ["OSIM_WAVE_COMMIT"] = "0"

        # --- leg 1: the serial oracle at n_pods --------------------------
        ns, carry, batch = build_state(n_nodes, n_pods)
        s_pad = fast.scenario_bucket(1)
        w_s = jnp.asarray(np.stack([np.asarray(weights_array())] * s_pad))
        valid_s = jnp.asarray(np.stack([np.asarray(ns.valid)] * s_pad))
        serial_run(ns, carry, batch, w_s, valid_s, s_pad)  # compile
        t_serial, ref = serial_run(ns, carry, batch, w_s, valid_s, s_pad)
        ref_digest = fast.scenario_carry_digest(ref[0])
        p_pad = int(batch.p)
        nodes_ref = np.asarray(ref[1])

        # --- leg 2: the commit phase (row-wise replay of the choices) ----
        rows = fast.pod_rows_from_batch(batch)
        choices = jnp.asarray(
            np.broadcast_to(nodes_ref[:1], (s_pad, p_pad)).copy()
        )
        count = jnp.int32(p_pad)

        def commit_run():
            carry_s = state_mod.stack_carry(carry, s_pad)
            t0 = time.time()
            r = fast.commit_choices(ns, carry_s, rows, valid_s, choices, count)
            jax.block_until_ready(r[0])
            return time.time() - t0, r

        commit_run()  # compile
        t_commit, rep = commit_run()
        commit_digest = fast.scenario_carry_digest(rep[0])
        commit_speedup = t_serial / t_commit if t_commit > 0 else None

        # --- leg 3: the wave round driver at wave_pods -------------------
        ns_w, carry_w, batch_w = build_state(n_nodes, wave_pods)
        valid_w = jnp.asarray(np.stack([np.asarray(ns_w.valid)] * s_pad))
        serial_run(ns_w, carry_w, batch_w, w_s, valid_w, s_pad)  # compile
        t_sw, ref_w = serial_run(ns_w, carry_w, batch_w, w_s, valid_w, s_pad)
        ref_w_digest = fast.scenario_carry_digest(ref_w[0])

        os.environ["OSIM_WAVE_COMMIT"] = "1"
        os.environ["OSIM_WAVE_SIZE"] = str(wave)
        os.environ["OSIM_WAVE_ROUNDS"] = str(wave_rounds)
        serial_run(ns_w, carry_w, batch_w, w_s, valid_w, s_pad)  # compile
        rounds_n0, rounds_s0 = hist_stats(metrics.COMMIT_ROUNDS)
        conflicts0 = msum(metrics.WAVE_CONFLICTS)
        fallbacks0 = msum(metrics.WAVE_FALLBACKS)
        t_wave, wout = serial_run(ns_w, carry_w, batch_w, w_s, valid_w, s_pad)
        wave_digest = fast.scenario_carry_digest(wout[0])
        rounds_n1, rounds_s1 = hist_stats(metrics.COMMIT_ROUNDS)
        n_waves = rounds_n1 - rounds_n0
        rounds_total = rounds_s1 - rounds_s0

        out = {
            "wall_s": round(t_serial + t_commit + t_sw + t_wave, 2),
            "value": round(n_pods / t_commit, 1) if t_commit > 0 else None,
            "unit": "pods/s (commit phase)",
            "serial_wall_s": round(t_serial, 2),
            "serial_pods_s": round(n_pods / t_serial, 1),
            "commit_wall_s": round(t_commit, 3),
            "commit_phase_speedup_x": (
                round(commit_speedup, 1) if commit_speedup else None
            ),
            "wave_pods": wave_pods,
            "wave_size": wave,
            "wave_rounds_budget": wave_rounds,
            "wave_wall_s": round(t_wave, 2),
            "wave_serial_wall_s": round(t_sw, 2),
            "wave_total_speedup_x": (
                round(t_sw / t_wave, 2) if t_wave > 0 else None
            ),
            "waves_dispatched": n_waves,
            "rounds_to_converge_mean": (
                round(rounds_total / n_waves, 1) if n_waves else None
            ),
            "wave_conflicts": int(msum(metrics.WAVE_CONFLICTS) - conflicts0),
            "wave_fallbacks": int(msum(metrics.WAVE_FALLBACKS) - fallbacks0),
            "digest": f"{ref_digest:08x}",
        }
        if commit_digest != ref_digest:
            out["error"] = (
                f"commit-phase digest {commit_digest:08x} != serial "
                f"{ref_digest:08x}; the row-wise commit must be "
                "byte-identical"
            )
        elif wave_digest != ref_w_digest:
            out["error"] = (
                f"wave-engine digest {wave_digest:08x} != serial "
                f"{ref_w_digest:08x}; the fixpoint driver must be "
                "byte-identical"
            )
        elif commit_speedup is not None and commit_speedup < 10:
            out["error"] = (
                f"commit-phase speedup {commit_speedup:.1f}x below the "
                "10x acceptance floor"
            )
    finally:
        for k, v in saved.items():
            _put_env(k, v)
    return out


CONFIGS = {
    "stock": config_stock,
    "fit_1k_100n": config_fit,
    "sanitize_overhead_1k": config_sanitize_overhead,
    "spread_aff_10k_1k": config_spread_affinity,
    "gpushare_5k": config_gpushare,
    "plan_100k_10k": config_plan,
    "capacity_sweep_batched": config_capacity_sweep,
    "multi_scenario_64": config_multi_scenario,
    "warm_start_100k": config_warm_start,
    "sharded_2dev_smoke": config_sharded_smoke,
    "preempt_tiered": config_preempt,
    "extender_1k": config_extender,
    "serving_concurrent": config_serving_concurrent,
    "serving_saturation": config_serving_saturation,
    "resident_delta_10k": config_resident_delta_10k,
    "prove_smoke": config_prove_smoke,
    "interleave_smoke": config_interleave_smoke,
    "plan_200k_20k": config_plan_200k_20k,
    "plan_1m_100k": config_plan_1m_100k,
    "checkpoint_overhead": config_checkpoint_overhead,
    "wave_commit_10k": config_wave_commit_10k,
}

# Excluded from `--configs all`: run them by name (CI runs plan_200k_20k
# on its own schedule; plan_1m_100k is the full-scale local run).
SLOW_CONFIGS = {"plan_200k_20k", "plan_1m_100k"}


def _fmt_count(n: int) -> str:
    return f"{n // 1000}k" if n >= 1000 else str(n)


def _run_headline(pods: int, nodes: int) -> dict:
    """The headline kernel benchmark, in-process (called in a child)."""
    import jax

    from open_simulator_tpu.ops.fast import (
        DEFAULT_GROUP_CHUNK,
        schedule_batch_fast,
    )
    from open_simulator_tpu.ops.kernels import weights_array

    def phase(msg: str) -> None:
        # Stderr breadcrumbs: when a deadline kills this child, the last
        # line says which phase hung (encode vs compile pass vs timed pass).
        print(f"[headline {time.strftime('%H:%M:%S')}] {msg}",
              file=sys.stderr, flush=True)

    from open_simulator_tpu.utils.tracing import span

    t_enc0 = time.time()
    with span("encode", pods=pods, nodes=nodes):
        ns, carry, batch = build_state(nodes, pods)
    t_enc = time.time() - t_enc0
    phase(f"encode done in {t_enc:.1f}s (pods={pods} nodes={nodes})")
    w = weights_array()
    # Cap on per-group device-program length (scan steps per dispatch);
    # a smaller chunk bounds what each dispatch asks of the device.
    try:
        chunk = int(
            os.environ.get("OSIM_HEADLINE_CHUNK", str(DEFAULT_GROUP_CHUNK))
        )
    except ValueError:
        raise SystemExit(
            f"OSIM_HEADLINE_CHUNK must be a positive integer, got "
            f"{os.environ['OSIM_HEADLINE_CHUNK']!r}"
        )
    if chunk <= 0:
        # chunk<=0 would make the fast-path chunking loop spin forever
        raise SystemExit(
            f"OSIM_HEADLINE_CHUNK must be a positive integer, got {chunk}"
        )

    # Warm up with one full untimed pass (same shapes => same executables),
    # then one timed pass. The grouped scheduler's per-group chunking
    # (schedule_batch_grouped max_group_chunk) bounds each device program to a
    # few seconds — a single 100k-step scan trips the TPU worker's watchdog.
    phase("warm pass (compiles) starting")
    t0 = time.time()
    with span("schedule-warm", pods=pods):
        schedule_batch_fast(ns, carry, batch, w, max_group_chunk=chunk)
    compile_s = time.time() - t0
    phase(f"warm pass done in {compile_s:.1f}s; timed pass starting")

    t1 = time.time()
    with span("schedule-timed", pods=pods):
        _, placed, *_ = schedule_batch_fast(
            ns, carry, batch, w, max_group_chunk=chunk
        )
    run = time.time() - t1
    phase(f"timed pass done in {run:.2f}s")
    scheduled = int((placed >= 0).sum())
    pods_per_sec = pods / run

    from open_simulator_tpu.ops.fast import PATH_COUNTS

    out = {
        "paths": {k: v for k, v in PATH_COUNTS.items() if v},
        "metric": f"schedule_{_fmt_count(pods)}_pods_{_fmt_count(nodes)}_nodes",
        "value": round(pods_per_sec, 1),
        "unit": "pods/s",
        "vs_baseline": round(pods_per_sec / TARGET_PODS_PER_SEC, 3),
        "wall_s": round(run, 2),
        "compile_s": round(compile_s, 2),
        "encode_s": round(t_enc, 2),
        "scheduled": scheduled,
        "pods": pods,
        "nodes": nodes,
        "device": str(jax.devices()[0]),
    }
    if chunk != DEFAULT_GROUP_CHUNK:
        # a non-default dispatch granularity changes what the number means —
        # stamp it so the JSON is never mistaken for a default-chunk figure
        out["group_chunk"] = chunk
    return out


# Per-segment wall-clock deadlines (seconds). Generous vs expected runtimes
# (headline ≈ 30 s run + compiles) but bounded: an in-process device hang
# cannot be interrupted, so every segment runs in a killable child process.
SEGMENT_TIMEOUT_S = {
    "headline": 1200.0,
    "stock": 900.0,
    "fit_1k_100n": 600.0,
    "sanitize_overhead_1k": 900.0,
    "spread_aff_10k_1k": 900.0,
    "gpushare_5k": 900.0,
    "plan_100k_10k": 1200.0,
    "capacity_sweep_batched": 900.0,
    "multi_scenario_64": 600.0,
    "warm_start_100k": 900.0,
    "sharded_2dev_smoke": 600.0,
    "preempt_tiered": 900.0,
    "extender_1k": 900.0,
    "serving_concurrent": 600.0,
    "serving_saturation": 900.0,
    "resident_delta_10k": 900.0,
    # Three legs (serial oracle scan, replayed row-wise commit phase, wave
    # driver) plus compiles; ~1 min warm on a 1-core CPU host.
    "wave_commit_10k": 900.0,
    # The scaled plan segments run the default batched sweep, which commits
    # per-pod (no group fast path inside schedule_scenarios yet): on a CPU
    # host they are wall-hours, which is why they sit in SLOW_CONFIGS and
    # CI runs plan_200k_20k in its own push-only job.
    "plan_200k_20k": 7200.0,
    "plan_1m_100k": 14400.0,
}


def _segment_main(name: str, pods: int, nodes: int) -> int:
    """Child-process entry: run one segment, print its JSON to stdout."""
    from open_simulator_tpu.utils.platform import (
        enable_compilation_cache,
        install_compile_listener,
    )

    enable_compilation_cache()
    install_compile_listener()
    try:
        if name == "headline":
            out = _run_headline(pods, nodes)
        else:
            out = CONFIGS[name]()
    except Exception as e:  # noqa: BLE001 - report, don't crash the parent
        out = {"error": f"{type(e).__name__}: {e}"}
    if isinstance(out, dict) and "metrics" not in out:
        # phase histograms / compile-cache behavior / failure reasons for
        # this segment's process (each segment is its own child, so the
        # snapshot is per-segment)
        from open_simulator_tpu.utils.metrics import COMPILE_CACHE, REGISTRY

        out["metrics"] = REGISTRY.snapshot()
        # explicit top-of-doc compile count so BENCH_*.json diffs catch
        # recompile regressions without digging through the metrics tree
        out["compiles"] = int(COMPILE_CACHE.value(event="backend_compile"))
    if isinstance(out, dict):
        import jax

        # every segment names the device it ran on
        out.setdefault("device", str(jax.devices()[0]))
    print(json.dumps(out), flush=True)
    return 0


def _run_segment(name: str, pods: int, nodes: int) -> dict:
    """Run one segment in a killable child under its deadline."""
    if "jax" in sys.modules:
        # a parent that has touched JAX holds the chip, and the child that
        # needs it then fails or hangs
        raise RuntimeError(
            "bench parent imported jax before a segment child"
        )
    env = dict(os.environ)
    if name in ("sharded_2dev_smoke", "plan_200k_20k", "plan_1m_100k"):
        # these segments need >=2 devices on every CI lane (the sharding
        # smoke proves placement equivalence; the plan segments report
        # per-device HBM for the node-sharded vs replicated table):
        # provision 2 virtual CPU devices — the flag only affects the host
        # platform, so they are deliberately CPU-pinned
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2"
        ).strip()
    deadline = SEGMENT_TIMEOUT_S.get(name, 900.0)
    cmd = [
        sys.executable, "-u", os.path.abspath(__file__),
        "--segment", name, "--pods", str(pods), "--nodes", str(nodes),
    ]
    t0 = time.time()
    try:
        r = subprocess.run(
            cmd, env=env, timeout=deadline, capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return {
            "error": f"timeout after {deadline:.0f}s (device hang?)",
            "wall_s": round(time.time() - t0, 2),
        }
    for line in (r.stderr or "").splitlines()[-12:]:
        if "WARNING" not in line and "cpu_aot_loader" not in line:
            print(f"  [{name}] {line[:300]}", file=sys.stderr, flush=True)
    tail = (r.stdout or "").strip().splitlines()
    if r.returncode != 0 or not tail:
        err = (r.stderr or "").strip().splitlines()
        return {
            "error": f"rc={r.returncode}: {err[-1] if err else 'no output'}"
        }
    try:
        return json.loads(tail[-1])
    except json.JSONDecodeError:
        return {"error": f"unparseable output: {tail[-1][:200]}"}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pods", type=int, default=100_000)
    parser.add_argument("--nodes", type=int, default=10_000)
    parser.add_argument("--quick", action="store_true", help="tiny smoke sizes")
    parser.add_argument(
        "--configs", default="all",
        help="comma list of end-to-end configs to run alongside the headline "
        f"kernel benchmark ({', '.join(CONFIGS)}), 'all', or 'none'; "
        f"'all' skips the slow configs ({', '.join(sorted(SLOW_CONFIGS))}) — "
        "name them explicitly to run them",
    )
    parser.add_argument(
        "--segment", default="",
        help="(internal) run one segment in-process: headline or a config name",
    )
    parser.add_argument(
        "--run-dir", default="",
        help="journal this bench run into DIR (one JSONL record per "
        "completed segment) so a crashed/wedged run can be resumed",
    )
    parser.add_argument(
        "--resume", nargs="?", const=True, default=False, metavar="RUN_DIR",
        help="resume a journaled bench run: completed segments are replayed "
        "from the journal, not re-measured (RUN_DIR defaults to --run-dir)",
    )
    args = parser.parse_args()
    if args.segment:
        return _segment_main(args.segment, args.pods, args.nodes)
    if args.quick:
        args.pods, args.nodes = 2_000, 200

    run_dir = args.run_dir or (
        args.resume if isinstance(args.resume, str) else ""
    )
    resume = bool(args.resume)
    if resume and not run_dir:
        parser.error("--resume needs a run dir (positional or --run-dir)")

    journal = None
    done_segments: dict = {}
    if run_dir:
        from open_simulator_tpu.durable import RunJournal, completed_segments
        from open_simulator_tpu.utils.metrics import RUN_RESUMED

        journal = RunJournal.open(run_dir)
        if not journal.has("run_start"):
            journal.append(
                "run_start", kind="bench", pods=args.pods, nodes=args.nodes,
                configs=args.configs,
            )
        if resume:
            RUN_RESUMED.inc()
            journal.append("run_resume")
            done_segments = completed_segments(journal.events())
            if done_segments:
                print(
                    f"resuming: {len(done_segments)} journaled segment(s) "
                    f"will be replayed, not re-measured "
                    f"({', '.join(sorted(done_segments))})",
                    file=sys.stderr, flush=True,
                )

    # Validate --configs up front so a typo fails fast even with --quick.
    if args.configs in ("none", "all"):
        wanted = ([] if args.configs == "none"
                  else [c for c in CONFIGS if c not in SLOW_CONFIGS])
    else:
        wanted = [c.strip() for c in args.configs.split(",") if c.strip()]
        unknown = [c for c in wanted if c not in CONFIGS]
        if unknown:
            parser.error(
                f"--configs: unknown config(s) {unknown}; "
                f"choose from {', '.join(CONFIGS)}, all, none"
            )

    platform = os.environ.get("JAX_PLATFORMS", "") or "(jax default)"

    def run_seg(name: str, pods: int, nodes: int) -> dict:
        """One segment through the journal: replayed if already committed,
        measured (and committed on success) otherwise. Failed segments are
        NOT journaled, so a resume re-runs exactly what never succeeded."""
        if name in done_segments:
            print(
                f"bench segment {name}: replayed from journal",
                file=sys.stderr, flush=True,
            )
            return dict(done_segments[name])
        res = _run_segment(name, pods, nodes)
        if journal is not None and "error" not in res:
            journal.append("segment", segment=name, result=res)
        return res

    # In --quick mode stay in-process (CI speed).
    if args.quick:
        from open_simulator_tpu.utils.platform import (
            enable_compilation_cache,
            install_compile_listener,
        )

        if "headline" in done_segments:
            print(
                "bench segment headline: replayed from journal",
                file=sys.stderr, flush=True,
            )
            result = dict(done_segments["headline"])
        else:
            enable_compilation_cache()
            install_compile_listener()
            result = _run_headline(args.pods, args.nodes)
            if journal is not None:
                journal.append("segment", segment="headline", result=result)
        # The serial-vs-batched capacity sweep is cheap enough to keep in
        # the quick profile, and the speedup ratio is only meaningful when
        # both paths run in the same process (shared caches, same backend).
        if "capacity_sweep_batched" in done_segments:
            print(
                "bench segment capacity_sweep_batched: replayed from journal",
                file=sys.stderr, flush=True,
            )
            sweep = dict(done_segments["capacity_sweep_batched"])
        else:
            sweep = config_capacity_sweep()
            if journal is not None and "error" not in sweep:
                journal.append(
                    "segment", segment="capacity_sweep_batched", result=sweep
                )
        result["capacity_sweep_batched"] = sweep
        result["capacity_sweep_speedup"] = sweep.get("capacity_sweep_speedup")
        # The compile-lifecycle headline stays in the quick profile: cold
        # wall (warmup pays every compile) vs warm wall (the same sweep,
        # zero cold compiles asserted — warm start excludes all compile
        # time by construction).
        if "warm_start_100k" in done_segments:
            print(
                "bench segment warm_start_100k: replayed from journal",
                file=sys.stderr, flush=True,
            )
            warm = dict(done_segments["warm_start_100k"])
        else:
            warm = config_warm_start()
            if journal is not None and "error" not in warm:
                journal.append(
                    "segment", segment="warm_start_100k", result=warm
                )
        result["warm_start_100k"] = warm
        result["cold_wall_s"] = warm.get("cold_wall_s")
        result["warm_wall_s"] = warm.get("warm_wall_s")
        result["requested_platform"] = platform
        from open_simulator_tpu.utils.metrics import COMPILE_CACHE, REGISTRY

        result["metrics"] = REGISTRY.snapshot()
        result["compiles"] = int(COMPILE_CACHE.value(event="backend_compile"))
        result["watchdog_fired"] = _watchdog_fired_total()
        if journal is not None:
            journal.append("run_end", outcome="ok")
            from open_simulator_tpu.durable import atomic_write

            atomic_write(
                os.path.join(run_dir, "bench.json"),
                json.dumps(result, sort_keys=True) + "\n",
            )
        print(json.dumps(result))
        return 0

    # Every segment runs in its own killable subprocess under a deadline,
    # on the platform JAX_PLATFORMS names; results flush to stderr as they
    # land. A segment that fails or times out fails the run (non-zero exit)
    # after the remaining segments ran: no segment is re-run elsewhere.
    result = run_seg("headline", args.pods, args.nodes)
    result["requested_platform"] = platform
    print(f"headline: {json.dumps(result)}", file=sys.stderr, flush=True)
    failed = ["headline"] if "error" in result else []

    # End-to-end BASELINE configs (through simulate()/run_apply/plan_capacity;
    # wall includes expansion, validation, encode, compile and decode).
    # Progress lines go to stderr; the single stdout JSON line stays the
    # driver contract, carrying the per-config results under "configs".
    if wanted:
        configs_out = {}
        for name in wanted:
            print(f"bench config {name}...", file=sys.stderr, flush=True)
            configs_out[name] = run_seg(name, args.pods, args.nodes)
            print(
                f"bench config {name}: {json.dumps(configs_out[name])}",
                file=sys.stderr, flush=True,
            )
            if "error" in configs_out[name]:
                failed.append(name)
        result["configs"] = configs_out

    result["watchdog_fired"] = _watchdog_fired_total()
    if failed:
        result["failed_segments"] = failed
    if journal is not None:
        journal.append("run_end", outcome="failed" if failed else "ok")
        from open_simulator_tpu.durable import atomic_write

        atomic_write(
            os.path.join(run_dir, "bench.json"),
            json.dumps(result, sort_keys=True) + "\n",
        )
    print(json.dumps(result))
    if failed:
        print(
            f"bench: segment(s) failed on {platform}: {', '.join(failed)}",
            file=sys.stderr, flush=True,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
