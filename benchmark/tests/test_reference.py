"""The plain reference against `simulate` at a tiny size of each
configuration, and the comparison failing once the reference drops the
configuration's hard constraints."""

import numpy as np
import pytest

from benchmark.reference import replay
from benchmark.tests.helpers import cells, run_cell
from benchmark.tests.tiny import REPO

SEEDS = (11, 2_500_000_001)
CELLS = cells(REPO, kind="closed_loop_simulate")  # the cells replay.py judges


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_simulate(tiny_root, cell, seed):
    res = run_cell(tiny_root, cell, seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["score_gap_max"]["value"] == 0.0
    assert res["window"]["sampled"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_dropping_hard_constraints_fails(tiny_root, cell, monkeypatch):
    """Without its filters (resources, node affinity, spread, pod
    (anti-)affinity) the reference disagrees with the program."""
    monkeypatch.setattr(replay, "feasible",
                        lambda model, st, family: np.ones(model.n, bool))
    res = run_cell(tiny_root, cell, SEEDS[0])
    assert not res["correct"]


def test_quantities():
    assert replay.resources({"cpu": "500m", "memory": "1Gi", "pods": "110"}).tolist() \
        == [500.0, 1024.0, 110.0]
    with pytest.raises(ValueError):
        replay.quantity("1e3")
