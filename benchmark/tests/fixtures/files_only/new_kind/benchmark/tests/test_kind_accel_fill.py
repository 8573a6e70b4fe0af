"""The kind `accel_fill` with its timed path broken underneath it: `correct`
has to come out false when the commit engine hands back the state it got
and piles every placement onto one node. The check name read here is that
of this kind's reference, benchmark/reference/accel_capacity.py."""

import numpy as np
import pytest

from benchmark.tests.helpers import cells, run_cell
from benchmark.tests.tiny import REPO


def _piled_up(real):
    def schedule_batch_fast(ns, carry, batch, weights, **kw):
        out = list(real(ns, carry, batch, weights, **kw))
        placed = np.array(out[1]).copy()
        hit = np.flatnonzero(placed >= 0)
        out[0] = carry
        if hit.size:
            placed[hit] = placed[hit[0]]
        out[1] = placed
        return tuple(out)

    return schedule_batch_fast


@pytest.mark.parametrize("cell", cells(REPO, kind="accel_fill"))
def test_fault_is_caught(tiny_root, cell, monkeypatch):
    from open_simulator_tpu.engine import simulator

    monkeypatch.setattr(simulator, "schedule_batch_fast",
                        _piled_up(simulator.schedule_batch_fast))
    res = run_cell(tiny_root, cell, 99)
    assert not res["correct"], res["checks"]
    assert res["checks"]["over_allocated_nodes"]["value"] > 0
