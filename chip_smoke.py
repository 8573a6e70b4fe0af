"""Chip smoke: the product path, once, on one TPU chip.

Runs `simon apply` (``open_simulator_tpu.cli.main.main``) with the default
auto-plan on the north-star capacity plan (BASELINE.json config 5, the
shapes listed under ``assumed`` in benchmark/configs/plan_100k_10k.json):
100k pods onto a 10k-node, 3-zone cluster that
overflows, so the add-node search brackets and bisects. The result is
checked on the host with numpy, and the placement digests of the stock
example and of the 10k-pod x 1k-node spread+affinity config (BASELINE.json
config 3) are compared with the same runs on the host CPU, in a child
pinned to ``JAX_PLATFORMS=cpu``.

``--chips 4`` runs only the multi-chip path (``simon apply --devices 4``)
and the same plan on one of those chips, requires equal placement digests,
and checks that the node axis is split over all four devices.

Every number printed before the last line was measured on the chip the last
line names. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a failed phase exits non-zero without it, and so does a process in which
JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

ZONE = "topology.kubernetes.io/zone"
HERE = os.path.dirname(os.path.abspath(__file__))
#: BASELINE.json config 5 at full size
PLAN_PODS, PLAN_NODES = 100_000, 10_000


class SmokeFailure(Exception):
    """A phase of the smoke failed."""


def require_tpu(count: int):
    """The TPU devices, or SmokeFailure: no run here ever falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's devices are {devices[0].platform} "
            f"({len(devices)} x {devices[0].device_kind})"
        )
    if len(devices) < count:
        raise SmokeFailure(f"need {count} TPU chips, JAX sees {len(devices)}")
    return devices


# --------------------------------------------------------------------------
# simon/v1alpha1 Config writers


def _node(name: str, cpu: str, mem: str, zone: str = "") -> dict:
    labels = {"kubernetes.io/hostname": name}
    if zone:
        labels[ZONE] = zone
    res = {"cpu": cpu, "memory": mem, "pods": "110"}
    return {
        "apiVersion": "v1",
        "kind": "Node",
        "metadata": {"name": name, "labels": labels},
        "status": {"allocatable": dict(res), "capacity": dict(res)},
    }


def _deploy(name: str, replicas: int, cpu: str, mem: str,
            spec_extra: dict | None = None) -> dict:
    spec = {
        "containers": [
            {"name": "c", "image": "img",
             "resources": {"requests": {"cpu": cpu, "memory": mem}}}
        ],
        **(spec_extra or {}),
    }
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "bench"},
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": {"app": name}},
            "template": {"metadata": {"labels": {"app": name}}, "spec": spec},
        },
    }


def _spread(app: str, when: str) -> dict:
    return {
        "topologySpreadConstraints": [
            {
                "maxSkew": 50,
                "topologyKey": ZONE,
                "whenUnsatisfiable": when,
                "labelSelector": {"matchLabels": {"app": app}},
            }
        ]
    }


def _write_docs(path: str, docs: list) -> None:
    # JSON is YAML: one document per object, written without a YAML emitter
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write("\n---\n".join(json.dumps(d) for d in docs) + "\n")


def write_config(root: str, name: str, nodes: list, deploys: list,
                 new_node: dict | None = None) -> str:
    """A simon/v1alpha1 Config under `root`; returns its path."""
    _write_docs(os.path.join(root, "cluster", "nodes.yaml"), nodes)
    _write_docs(os.path.join(root, "app", "deploy.yaml"), deploys)
    spec = {
        "cluster": {"customConfig": "cluster"},
        "appList": [{"name": name, "path": "app"}],
    }
    if new_node is not None:
        _write_docs(os.path.join(root, "newnode", "node.yaml"), [new_node])
        spec["newNode"] = "newnode"
    cfg = os.path.join(root, "simon-config.yaml")
    with open(cfg, "w") as fh:
        json.dump({"apiVersion": "simon/v1alpha1", "kind": "Config",
                   "metadata": {"name": name}, "spec": spec}, fh)
    return cfg


def plan_config(root: str, seed: int, n_pods: int, n_nodes: int) -> str:
    """BASELINE.json config 5: 3-cpu/6Gi nodes over
    three zones, `web` (zone spread, maxSkew 50) and `batch`; ~37.5k cpu
    of demand against ~30k of capacity, so the search must add 32-cpu
    `new-node`s. The seed orders the node manifests."""
    order = np.random.default_rng(seed).permutation(n_nodes)
    nodes = [_node(f"n-{i}", "3", "6Gi", f"az-{i % 3}") for i in order]
    deploys = [
        _deploy("web", n_pods // 2, "500m", "1Gi",
                _spread("web", "ScheduleAnyway")),
        _deploy("batch", n_pods - n_pods // 2, "250m", "512Mi"),
    ]
    return write_config(root, "plan", nodes, deploys,
                        new_node=_node("new-node", "32", "64Gi"))


def spread_affinity_config(root: str, seed: int, n_pods: int = 10_000,
                           n_nodes: int = 1_000) -> str:
    """BASELINE.json config 3: `front` under
    a DoNotSchedule zone spread, `back` preferring `front`'s zone."""
    order = np.random.default_rng(seed).permutation(n_nodes)
    nodes = [_node(f"n-{i}", "32", "64Gi", f"az-{i % 3}") for i in order]
    affinity = {
        "affinity": {
            "podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {
                        "weight": 10,
                        "podAffinityTerm": {
                            "labelSelector": {"matchLabels": {"app": "front"}},
                            "topologyKey": ZONE,
                        },
                    }
                ]
            }
        }
    }
    deploys = [
        _deploy("front", n_pods // 2, "250m", "512Mi",
                _spread("front", "DoNotSchedule")),
        _deploy("back", n_pods - n_pods // 2, "500m", "1Gi", affinity),
    ]
    return write_config(root, "spread-affinity", nodes, deploys)


# --------------------------------------------------------------------------
# running `simon apply`


def simon_apply(cfg: str, work: str, *extra: str):
    """`simon apply -f cfg` in this process; returns (rc, ApplyOutcome,
    wall seconds, CompileCounter). The outcome is observed by wrapping
    engine.apply.run_apply, which the CLI looks up at call time."""
    import open_simulator_tpu.engine.apply as apply_mod
    from open_simulator_tpu.cli.main import main
    from open_simulator_tpu.utils.platform import CompileCounter

    seen = []
    real = apply_mod.run_apply

    def observed(*a, **k):
        seen.append(real(*a, **k))
        return seen[-1]

    os.makedirs(work, exist_ok=True)
    argv = ["apply", "-f", cfg, "--output-file",
            os.path.join(work, "report.txt"), *extra]
    apply_mod.run_apply = observed
    try:
        with CompileCounter() as cc:
            t0 = time.perf_counter()
            rc = main(argv)
            wall = time.perf_counter() - t0
    finally:
        apply_mod.run_apply = real
    if not seen:
        raise SmokeFailure(f"simon apply {cfg} exited rc {rc} before running")
    return rc, seen[0], wall, cc


def cpu_digests(cfgs: dict, work: str) -> dict:
    """name -> (rc, placement digest) of `simon apply` on the host CPU, one
    child per config pinned to JAX_PLATFORMS=cpu (it never loads libtpu)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = {}
    for name, cfg in cfgs.items():
        d = os.path.join(work, f"cpu-{name}")
        procs[name] = (d, subprocess.Popen(
            [sys.executable, "-m", "open_simulator_tpu.cli.main", "apply",
             "-f", cfg, "--run-dir", d,
             "--output-file", os.path.join(work, f"cpu-{name}.txt")],
            env=env, cwd=HERE, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        ))
    out = {}
    for name, (d, p) in procs.items():
        try:
            _, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            raise SmokeFailure(f"CPU run of {name} timed out")
        try:
            with open(os.path.join(d, "outcome.json")) as fh:
                digest = json.load(fh)["placement_digest"]
        except (OSError, ValueError, KeyError):
            tail = (err or "").strip().splitlines()[-3:]
            raise SmokeFailure(
                f"CPU run of {name} wrote no outcome (rc {p.returncode}): "
                f"{tail}"
            )
        out[name] = (p.returncode, digest)
    return out


# --------------------------------------------------------------------------
# host-side checks (numpy)


def check_plan(outcome, n_pods: int, max_skew: int = 50) -> dict:
    """Every pod placed, no node over its allocatable cpu/memory/pods,
    nodes added, and the `web` zone spread within maxSkew."""
    res = outcome.result
    if res.unscheduled:
        raise SmokeFailure(f"{len(res.unscheduled)} pods unscheduled")
    st = res.node_status
    placed = sum(len(s.pods) for s in st)
    if placed != n_pods:
        raise SmokeFailure(f"placed {placed} pods, want {n_pods}")
    alloc = np.array(
        [[s.node.allocatable.get(k, 0) for k in ("cpu", "memory", "pods")]
         for s in st], np.int64,
    )
    used = np.array(
        [[sum(p.requests.get("cpu", 0) for p in s.pods),
          sum(p.requests.get("memory", 0) for p in s.pods),
          len(s.pods)] for s in st], np.int64,
    )
    over = np.flatnonzero((used > alloc).any(axis=1))
    if over.size:
        raise SmokeFailure(
            f"{over.size} node(s) over allocatable, e.g. "
            f"{st[over[0]].node.name}: used {used[over[0]].tolist()} > "
            f"{alloc[over[0]].tolist()}"
        )
    plan = outcome.plan
    if plan is None or plan.nodes_added <= 0:
        raise SmokeFailure("the capacity search added no node")
    zones = np.array([s.node.meta.labels.get(ZONE, "") for s in st])
    web = np.array(
        [sum(p.meta.labels.get("app") == "web" for p in s.pods) for s in st]
    )
    per_zone = {
        str(z): int(web[zones == z].sum()) for z in sorted(set(zones)) if z
    }
    skew = max(per_zone.values()) - min(per_zone.values())
    if skew > max_skew:
        raise SmokeFailure(
            f"web zone skew {skew} > maxSkew {max_skew}: {per_zone}"
        )
    return {"nodes_added": plan.nodes_added, "attempts": plan.attempts,
            "web_per_zone": per_zone, "web_skew": skew}


def node_axis_split(ns, n_devices: int) -> list:
    """(device id, row range) of each shard of the node table; fails unless
    the node axis is split over `n_devices` distinct devices."""
    arr = ns.alloc
    shards = sorted(
        (s.device.id, s.index[0].start or 0,
         s.index[0].stop if s.index[0].stop is not None else arr.shape[0])
        for s in arr.addressable_shards
    )
    devices = {d for d, _, _ in shards}
    rows = sorted((a, b) for _, a, b in shards)
    tiles = all(b0 == a1 for (_, b0), (a1, _) in zip(rows, rows[1:]))
    if (len(devices) != n_devices or rows[0][0] != 0
            or rows[-1][1] != arr.shape[0] or not tiles):
        raise SmokeFailure(
            f"node table not split over {n_devices} devices: {shards}"
        )
    return shards


# --------------------------------------------------------------------------
# phases


def one_chip(work: str, seed: int) -> None:
    from open_simulator_tpu.engine.apply import placement_digest
    from open_simulator_tpu.utils import metrics

    n_pods = PLAN_PODS
    cfg = plan_config(os.path.join(work, "plan"), seed, n_pods, PLAN_NODES)
    rc, outcome, wall, cc = simon_apply(cfg, os.path.join(work, "plan"))
    if rc != 0:
        raise SmokeFailure(f"simon apply on the plan exited rc {rc}")
    facts = check_plan(outcome, n_pods)
    print(
        f"chip plan ({n_pods} pods x {PLAN_NODES} nodes, simon apply): "
        f"wall_s={wall} "
        f"compile_s={cc.compile_seconds} pods_per_s={n_pods / wall} "
        f"compile_requests={cc.backend_compiles} "
        f"cold_compiles={cc.cold_compiles} "
        f"nodes_added={facts['nodes_added']} attempts={facts['attempts']} "
        f"web_per_zone={facts['web_per_zone']} device={outcome.device}",
        flush=True,
    )

    cfgs = {
        "stock": os.path.join(HERE, "example", "simon-config.yaml"),
        "spread_aff_10k_1k": spread_affinity_config(
            os.path.join(work, "spread"), seed
        ),
    }
    chip = {}
    for name, c in cfgs.items():
        rc, out, wall, cc = simon_apply(c, os.path.join(work, name))
        chip[name] = (rc, placement_digest(out.result))
        print(f"chip {name}: rc={rc} wall_s={wall} "
              f"compile_s={cc.compile_seconds} digest={chip[name][1]}",
              flush=True)
    cpu = cpu_digests(cfgs, work)
    for name in cfgs:
        same = chip[name] == cpu[name]
        print(f"digest {name}: chip={chip[name][1]} (rc {chip[name][0]}) "
              f"cpu={cpu[name][1]} (rc {cpu[name][0]}) "
              f"{'match' if same else 'MISMATCH'}", flush=True)
        if not same:
            raise SmokeFailure(f"{name}: chip and CPU placements differ")
    print(
        'osim_compile_cache_total{event="persistent_hit"}='
        f"{int(metrics.COMPILE_CACHE.value(event='persistent_hit'))} "
        f'{{event="backend_compile"}}='
        f"{int(metrics.COMPILE_CACHE.value(event='backend_compile'))}",
        flush=True,
    )


def four_chips(work: str, seed: int) -> None:
    import open_simulator_tpu.parallel.mesh as mesh_mod
    from open_simulator_tpu.engine.apply import placement_digest

    n_pods = PLAN_PODS
    cfg = plan_config(os.path.join(work, "plan"), seed, n_pods, PLAN_NODES)
    sharded = []
    real = mesh_mod.shard_state

    def observed(mesh, ns, carry):
        out = real(mesh, ns, carry)
        sharded.append(out[0])
        return out

    mesh_mod.shard_state = observed
    try:
        rc4, out4, wall4, cc4 = simon_apply(
            cfg, os.path.join(work, "four"), "--devices", "4"
        )
    finally:
        mesh_mod.shard_state = real
    if rc4 != 0:
        raise SmokeFailure(f"simon apply --devices 4 exited rc {rc4}")
    if not sharded:
        raise SmokeFailure("--devices 4 never sharded the node table")
    shards = node_axis_split(sharded[-1], 4)
    facts4 = check_plan(out4, n_pods)
    print(f"chip plan --devices 4: wall_s={wall4} "
          f"compile_s={cc4.compile_seconds} pods_per_s={n_pods / wall4} "
          f"nodes_added={facts4['nodes_added']} "
          f"attempts={facts4['attempts']} node_shards={shards}", flush=True)

    rc1, out1, wall1, cc1 = simon_apply(cfg, os.path.join(work, "one"))
    if rc1 != 0:
        raise SmokeFailure(f"simon apply on one chip exited rc {rc1}")
    print(f"chip plan --devices 1: wall_s={wall1} "
          f"compile_s={cc1.compile_seconds} pods_per_s={n_pods / wall1}",
          flush=True)
    d4, d1 = placement_digest(out4.result), placement_digest(out1.result)
    print(f"digest plan: four_chips={d4} one_chip={d1} "
          f"{'match' if d4 == d1 else 'MISMATCH'}", flush=True)
    if d4 != d1:
        raise SmokeFailure("four-chip and one-chip placements differ")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the multi-chip path and its one-chip comparison",
    )
    args = parser.parse_args(argv)
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        devices = require_tpu(args.chips)
        if args.chips == 4:
            four_chips(work, args.seed)
        else:
            one_chip(work, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
