"""Randomized oracle-parity fuzz (bounded, fixed seeds).

Generates workloads mixing every coupled feature — spread (soft/hard,
multiple keys), required/preferred (anti-)affinity, node selectors,
taints/tolerations, host ports — against heterogeneous node sets, and
demands `schedule_batch_fast` reproduce the sequential oracle EXACTLY
(placements, reasons, takes, final carry via `_assert_identical`).

The same generator ran as a long soak during development (hundreds of
rounds across seeds); this bounded version keeps a
few representative seeds in CI so path-dispatch regressions can't land
silently.
"""

import os
import random

import pytest

from tests.test_fast import _assert_identical, _encode, _node, _pod

ZONES = ["z-0", "z-1", "z-2"]


def _rand_nodes(rng, n):
    nodes = []
    for i in range(n):
        labels = {"topology.kubernetes.io/zone": rng.choice(ZONES)}
        if rng.random() < 0.5:
            labels["rack"] = f"r-{rng.randrange(4)}"
        if rng.random() < 0.3:
            labels["tier"] = rng.choice(["gold", "silver"])
        nodes.append(_node(
            f"n-{i}", cpu=str(rng.choice([4, 8, 16])),
            mem=f"{rng.choice([8, 16, 64])}Gi",
            pods=str(rng.choice([5, 10])),
            labels=labels,
            taints=[{"key": "dedicated", "value": "batch",
                     "effect": "NoSchedule"}] if rng.random() < 0.2 else [],
        ))
    return nodes


def _rand_tmpl(rng, t):
    spec = {}
    if rng.random() < 0.6:
        spec["topologySpreadConstraints"] = [
            {
                "maxSkew": rng.choice([1, 2, 5]),
                "topologyKey": rng.choice(
                    ["topology.kubernetes.io/zone", "rack"]),
                "whenUnsatisfiable": rng.choice(
                    ["ScheduleAnyway", "DoNotSchedule"]),
                "labelSelector": {"matchLabels": {"app": f"a{t}"}},
            }
            for _ in range(rng.randrange(1, 3))
        ]
    if rng.random() < 0.35:
        term = {
            "labelSelector": {"matchLabels": {"app": f"a{t}"}},
            "topologyKey": "topology.kubernetes.io/zone",
        }
        kind = "podAntiAffinity" if rng.random() < 0.5 else "podAffinity"
        if rng.random() < 0.5:
            spec["affinity"] = {kind: {
                "requiredDuringSchedulingIgnoredDuringExecution": [term]}}
        else:
            spec["affinity"] = {kind: {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 10, "podAffinityTerm": term}]}}
    if rng.random() < 0.25:
        spec["nodeSelector"] = {"tier": "gold"}
    if rng.random() < 0.25:
        spec["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                "value": "batch", "effect": "NoSchedule"}]
    containers = [{
        "name": "c",
        "resources": {"requests": {
            "cpu": rng.choice(["250m", "500m", "1"]),
            "memory": rng.choice(["256Mi", "512Mi"])}},
    }]
    if rng.random() < 0.2:
        containers[0]["ports"] = [
            {"containerPort": 80, "hostPort": 8000 + rng.randrange(2)}]
    spec["containers"] = containers
    return _pod(f"t{t}", labels={"app": f"a{t}"}, spec_extra=spec)


def _seeds():
    """CI keeps 3 representative seeds; OSIM_FUZZ_SEEDS widens the sweep for
    soaks, e.g. OSIM_FUZZ_SEEDS=100-139 (range) or =5,8,13 (list); each seed
    runs 3 generator rounds. The round-4 soak covered seeds 100-139 (40
    fresh seeds): every case bit-identical to the oracle."""
    base = [3, 17, 29]
    extra = os.environ.get("OSIM_FUZZ_SEEDS", "")
    if not extra:
        return base
    out = []
    try:
        for part in extra.split(","):
            part = part.strip()
            if not part:
                continue
            if "-" in part:
                lo, hi = part.split("-", 1)
                if int(lo) > int(hi):
                    raise ValueError(f"reversed range {part!r}")
                out.extend(range(int(lo), int(hi) + 1))
            else:
                out.append(int(part))
    except ValueError:
        raise ValueError(
            f"OSIM_FUZZ_SEEDS={extra!r}: expected comma-separated ints "
            "or lo-hi ranges (e.g. 100-139 or 5,8,13)"
        )
    return base + out


@pytest.mark.parametrize("seed", _seeds())
def test_fuzz_oracle_parity(seed):
    rng = random.Random(seed)
    for _ in range(3):
        nodes = _rand_nodes(rng, rng.choice([5, 9, 16]))
        tmpls = [_rand_tmpl(rng, t) for t in range(rng.randrange(1, 3))]
        counts = [rng.choice([3, 17, 40]) for _ in tmpls]
        ns, carry, batch = _encode(nodes, tmpls, counts)
        _assert_identical(ns, carry, batch)
