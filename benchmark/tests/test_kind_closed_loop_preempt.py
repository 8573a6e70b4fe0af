"""The kind `closed_loop_preempt` with its timed path broken underneath it:
`correct` has to come out false for each fault a preemption cell can have,
and for the control, which judges the reference's own choices made with the
reprieve order reversed. The faults are planted in the Simulator's victim
search and eviction as `simulate` calls them. The check names read here are
those of this kind's reference, benchmark/reference/tiers.py."""

import numpy as np
import pytest

from benchmark.tests.helpers import cells, run_cell
from benchmark.tests.tiny import REPO

CELLS = cells(REPO, kind="closed_loop_preempt")


def _dropped_victim(real):
    def search(self, pod):
        res = real(self, pod)
        if res is not None and len(res.victims) > 1:
            res.victims = res.victims[:-1]
        return res
    return search


def _higher_victim(real):
    def search(self, pod):
        res = real(self, pod)
        if res is not None:
            ni = self._name_index_map()[res.node]
            vt = self._victims
            extra = [p for p in vt.pods[ni, : vt.count[ni]]
                     if p.priority >= pod.priority]
            res.victims = res.victims + extra[:1]
        return res
    return search


def _left_unschedulable(real):
    def search(self, pod):
        return None
    return search


def _second_node(real):
    """The cascade's runner-up: the search run for every node, the node
    picked on the host from the sorted cascade keys, second place."""
    from open_simulator_tpu.ops.preempt import SearchOut

    def search(ns, carry, table, row, prio, fo, pdb, *, all_nodes=False, **kw):
        got = real(ns, carry, table, row, prio, fo, pdb, all_nodes=True, **kw)
        cand = np.flatnonzero(np.asarray(got.cand))
        if cand.size < 2:
            return real(ns, carry, table, row, prio, fo, pdb, **kw)
        q, vq = np.asarray(got.qidx), np.asarray(got.victims)
        qprio = np.take_along_axis(np.asarray(table.prio), q, axis=1).astype(np.int64)
        key = sorted((int(got.n_viol[n]), int(qprio[n][vq[n]].max()),
                      int(np.sum(qprio[n][vq[n]] + (1 << 31))), int(vq[n].sum()), int(n))
                     for n in cand)
        n = key[1][-1]
        return SearchOut(np.int32(n), got.cand, q[n], vq[n], got.n_viol[n])
    return search


def _no_evictions(real):
    """The preemptor is placed, its victims stay bound and unreported."""
    def evict(self, victims, ni, by):
        n = len(self._preempted)
        takes = real(self, victims, ni, by)
        del self._preempted[n:]
        self._gone.difference_update(id(v) for v in victims)
        return takes
    return evict


FAULTS = {
    "dropped_victim": ("_search_victims", _dropped_victim, "preempt_missed"),
    "higher_victim": ("_search_victims", _higher_victim, "priority_inverted"),
    "left_unschedulable": ("_search_victims", _left_unschedulable, "preempt_missed"),
    "second_node": ("victim_search", _second_node, "victims_wrong"),
    "no_evictions": ("_evict", _no_evictions, "infeasible"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(tiny_root, cell, fault, monkeypatch):
    from open_simulator_tpu.engine.simulator import Simulator
    from open_simulator_tpu.ops import preempt

    name, make, check = FAULTS[fault]
    owner = preempt if name == "victim_search" else Simulator
    monkeypatch.setattr(owner, name, make(getattr(owner, name)))
    res = run_cell(tiny_root, cell, 99)
    assert not res["correct"], res["checks"]
    assert res["checks"][check]["value"] > 0, res["checks"]


@pytest.mark.parametrize("seed", (7, 4_000_000_001))
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell, seed):
    res = run_cell(tiny_root, cell, seed, "--control", "1")
    assert res["checks"]["victims_wrong"]["value"] > 0
    assert not res["correct"]


def test_watchdog_ends_a_stuck_call():
    """A call that runs past the traffic's `watchdog_s` outside compile
    requests ends the process at once, with exit 4 and a message; a call
    inside the limit does not, nor one whose time goes to a compile request
    (announced by JAX's start and duration events, played here by hand)."""
    import os
    import subprocess
    import sys

    code = f"""
import importlib.util, sys, time
sys.path.insert(0, {REPO!r})
spec = importlib.util.spec_from_file_location(
    "drv", {REPO!r} + "/benchmark/traffic/closed_loop_preempt.py")
drv = importlib.util.module_from_spec(spec)
sys.modules["drv"] = drv
spec.loader.exec_module(drv)
w = drv._Watchdog(1.0)
w.begin("quick"); time.sleep(0.3); w.end()
from jax import monitoring
from open_simulator_tpu.utils.platform import BACKEND_COMPILE_EVENT
w.begin("compiling"); monitoring.record_event(w.START_EVENT); time.sleep(2.5)
monitoring.record_event_duration_secs(BACKEND_COMPILE_EVENT, 2.5); time.sleep(0.3); w.end()
w.begin("stuck"); time.sleep(60)
print("not ended")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 4, p.stderr[-2000:]
    assert "the stuck call ran past 1 s" in p.stderr
    assert "not ended" not in p.stdout
