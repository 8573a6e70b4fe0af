"""chip_smoke.py off the chip: it refuses a CPU-only process, and its
host-side checks catch what they claim to catch."""

import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_fails_without_a_tpu(where, tmp_path):
    """No TPU (JAX_PLATFORMS=cpu), in the checkout or in a directory that
    holds chip_smoke.py and nothing else: non-zero exit, no "ok"."""
    cwd = REPO
    if where == "alone":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def _node(name, cpu=3000, mem=6 << 30, zone="az-0"):
    labels = {cs.ZONE: zone} if zone else {}
    return SimpleNamespace(
        name=name,
        allocatable={"cpu": cpu, "memory": mem, "pods": 110},
        meta=SimpleNamespace(labels=labels),
    )


def _pod(app, cpu=500, mem=1 << 30):
    return SimpleNamespace(
        requests={"cpu": cpu, "memory": mem},
        meta=SimpleNamespace(labels={"app": app}),
    )


def _outcome(placements, unscheduled=(), nodes_added=1):
    st = [SimpleNamespace(node=n, pods=list(p)) for n, p in placements]
    return SimpleNamespace(
        result=SimpleNamespace(node_status=st, unscheduled=list(unscheduled)),
        plan=SimpleNamespace(nodes_added=nodes_added, attempts=3),
    )


def _good():
    return [
        (_node("a", zone="az-0"), [_pod("web")] * 2),
        (_node("b", zone="az-1"), [_pod("web"), _pod("batch", cpu=250)]),
        (_node("new", cpu=32000, mem=64 << 30, zone=""), [_pod("web")] * 9),
    ]


def test_check_plan_accepts_a_sound_plan():
    facts = cs.check_plan(_outcome(_good()), n_pods=13)
    assert facts["nodes_added"] == 1
    assert facts["web_per_zone"] == {"az-0": 2, "az-1": 1}


@pytest.mark.parametrize(
    "case, match",
    [
        ("unscheduled", "unscheduled"),
        ("missing", "placed 12"),
        ("over_cpu", "over allocatable"),
        ("no_nodes_added", "added no node"),
        ("skew", "zone skew"),
    ],
)
def test_check_plan_rejects(case, match):
    placements = _good()
    n_pods, kw = 13, {}
    if case == "unscheduled":
        kw["unscheduled"] = [object()]
    elif case == "missing":
        placements[2] = (placements[2][0], [_pod("web")] * 8)
    elif case == "over_cpu":
        placements[0] = (placements[0][0], [_pod("web", cpu=2000)] * 2)
    elif case == "no_nodes_added":
        kw["nodes_added"] = 0
    elif case == "skew":
        placements[0] = (placements[0][0], [_pod("web", cpu=1, mem=1)] * 60)
        n_pods = 71
    with pytest.raises(cs.SmokeFailure, match=match):
        cs.check_plan(_outcome(placements, **kw), n_pods=n_pods)


def test_node_axis_split_sees_every_device():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:4]
    x = np.zeros((16, 3), np.float32)
    split = jax.device_put(
        x, NamedSharding(Mesh(devices, ("nodes",)), P("nodes", None))
    )
    shards = cs.node_axis_split(SimpleNamespace(alloc=split), 4)
    assert [s[1:] for s in shards] == [(0, 4), (4, 8), (8, 12), (12, 16)]
    on_one = jax.device_put(x, devices[0])
    with pytest.raises(cs.SmokeFailure, match="not split"):
        cs.node_axis_split(SimpleNamespace(alloc=on_one), 4)
