"""Grouped kernel must be placement-identical to the naive scan."""

import numpy as np

from open_simulator_tpu.ops.grouped import group_runs, schedule_batch_grouped
from open_simulator_tpu.ops.kernels import schedule_batch, weights_array
from open_simulator_tpu.ops.state import pod_rows_from_batch


def _state(n_nodes, n_pods, seed=3):
    from __graft_entry__ import _synthetic_state

    return _synthetic_state(n_nodes=n_nodes, n_pods=n_pods, seed=seed)


def test_group_runs_detects_templates():
    from open_simulator_tpu.core.objects import Node, Pod
    from open_simulator_tpu.ops.encode import Encoder, encode_nodes, encode_pods
    from open_simulator_tpu.ops.tile import tile_pod_batch

    def pod(name, cpu):
        return Pod.from_dict(
            {
                "metadata": {"name": name, "namespace": "d"},
                "spec": {
                    "containers": [
                        {"name": "c", "resources": {"requests": {"cpu": cpu}}}
                    ]
                },
            }
        )

    enc = Encoder()
    tmpls = [pod("a", "1"), pod("b", "2")]
    enc.register_pods(tmpls)
    encode_nodes(enc, [Node.from_dict({"metadata": {"name": "n"}, "status": {"allocatable": {"cpu": "64", "pods": "110"}}})])
    batch = tile_pod_batch(encode_pods(enc, tmpls), [5, 3])
    assert group_runs(batch) == [(0, 5), (5, 3)]


def test_grouped_matches_naive_on_synthetic_mix():
    # _synthetic_state alternates tolerations every 5 pods and spread selectors
    # every pod, so runs are short — a worst case for grouping, best for parity.
    ns, carry, rows = _state(32, 48)
    w = weights_array()
    _, nodes_ref, reasons_ref, *_ = schedule_batch(ns, carry, rows, w)

    # rebuild the PodBatch (numpy) for the grouped API
    import jax

    from open_simulator_tpu.ops import encode as enc_mod

    # _synthetic_state returns device rows; reconstruct a batch-like object
    # by re-encoding. Simpler: drive grouped path on the same arrays.
    class FakeBatch:
        pass

    # Use the real constructor path instead:
    from __graft_entry__ import _synthetic_state as build

    # grouped path needs the numpy batch; rebuild state with the same seed
    from open_simulator_tpu.core.objects import Node, Pod  # noqa

    # Recreate via tests.factories.build_state for a template-tiled case below instead.
    del FakeBatch

    # For this test, wrap rows back into numpy arrays with batch semantics:
    batch = _rows_to_batch(rows)
    carry2, nodes_grp, reasons_grp, *_ = schedule_batch_grouped(ns, carry, batch, w)
    total = int(batch.valid.sum())  # padding rows: naive computes throwaway
    np.testing.assert_array_equal(np.asarray(nodes_ref)[:total], nodes_grp[:total])
    np.testing.assert_array_equal(np.asarray(reasons_ref)[:total], reasons_grp[:total])


def _rows_to_batch(rows):
    """PodRow pytree (stacked arrays) -> PodBatch for the grouped API."""
    from open_simulator_tpu.ops.encode import PodBatch

    d = {k: np.asarray(getattr(rows, k)) for k in rows._fields}
    return PodBatch(keys=[f"p/{i}" for i in range(d["req"].shape[0])], **d)


def test_grouped_matches_naive_on_tiled_templates():
    from tests.factories import build_state

    ns, carry, batch = build_state(64, 256)
    w = weights_array()
    rows = pod_rows_from_batch(batch)
    _, nodes_ref, reasons_ref, *_ = schedule_batch(ns, carry, rows, w)
    _, nodes_grp, reasons_grp, *_ = schedule_batch_grouped(ns, carry, batch, w)
    total = int(batch.valid.sum())
    np.testing.assert_array_equal(np.asarray(nodes_ref)[:total], nodes_grp[:total])
    np.testing.assert_array_equal(np.asarray(reasons_ref)[:total], reasons_grp[:total])
