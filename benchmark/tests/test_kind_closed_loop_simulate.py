"""The kind `closed_loop_simulate` with its timed path broken underneath it:
`correct` has to come out false for each fault a scheduling cell can have,
and for the control. The faults are planted in the commit engine's entry as
Simulator calls it, and, for the tie-break, in the cluster `simulate` is
handed. The control puts the reference's own bfloat16 choices in the
program's place, on three seeds of each cell. The check names read here are
those of this kind's reference, benchmark/reference/replay.py."""

import numpy as np
import pytest

from benchmark.tests.helpers import cells, run_cell
from benchmark.tests.tiny import REPO

CELLS = cells(REPO, kind="closed_loop_simulate")


def _broken(real, fault):
    def schedule_batch_fast(ns, carry, batch, weights, **kw):
        out = list(real(ns, carry, batch, weights, **kw))
        placed = np.array(out[1]).copy()
        hit = np.flatnonzero(placed >= 0)
        n_valid = int(np.asarray(ns.valid).sum())
        if fault == "answer_altered":  # every 4th placement moved one node on
            placed[hit[::4]] = (placed[hit[::4]] + 1) % n_valid
        elif fault == "half_left_out":  # half of the batch never scheduled
            placed[::2] = -1
        elif fault == "state_unchanged":  # the step hands back the state it got
            out[0] = carry
            if hit.size:
                placed[hit] = placed[hit[0]]
        out[1] = placed
        return tuple(out)

    return schedule_batch_fast


def _ties_to_highest(real):
    """`simulate` on the cluster's nodes in reverse order: every choice is
    the same best score, but the program's lowest-index tie-break now lands
    on the highest index of the caller's order."""
    def simulate(cluster, apps, **kw):
        import dataclasses

        return real(dataclasses.replace(cluster, nodes=cluster.nodes[::-1]), apps, **kw)

    return simulate


@pytest.mark.parametrize(
    "fault", ("answer_altered", "half_left_out", "state_unchanged", "ties_to_highest"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(tiny_root, cell, fault, monkeypatch):
    from open_simulator_tpu.engine import simulator

    if fault == "ties_to_highest":
        monkeypatch.setattr(simulator, "simulate", _ties_to_highest(simulator.simulate))
    else:
        monkeypatch.setattr(simulator, "schedule_batch_fast",
                            _broken(simulator.schedule_batch_fast, fault))
    res = run_cell(tiny_root, cell, 99)
    assert not res["correct"], res["checks"]
    if fault == "ties_to_highest":  # every pick a best score: only the tie check fails
        checks = res["checks"]
        assert checks["ties_lost"]["value"] > 0
        assert checks["score_gap_max"]["value"] <= checks["score_gap_max"]["limit"]


@pytest.mark.parametrize("seed", (7, 1_000_000_007, 4_000_000_001))
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell, seed):
    res = run_cell(tiny_root, cell, seed, "--control", "1")
    gap = res["checks"]["score_gap_max"]
    assert gap["value"] > gap["limit"]
    assert not res["correct"]
