"""Plain reference of the traffic kind `accel_fill`: the pods on a node ask
for no more of any resource than the node allocates, extended resources
included; a node that does not list a resource allocates none of it, and
every pod takes one of the node's `pods`. Imports nothing of the program."""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

_SUFFIXES = (("Ki", 2.0**10), ("Mi", 2.0**20), ("Gi", 2.0**30), ("m", 1e-3))


def quantity(text) -> float:
    """A Kubernetes quantity with one of the suffixes above, or none."""
    text = str(text)
    for suffix, scale in _SUFFIXES:
        if text.endswith(suffix):
            return float(text[: -len(suffix)]) * scale
    return float(text)


class Capacity:
    """Nodes' allocatable and families' requests on one resource axis."""

    def __init__(self, nodes: List[dict], families: Dict[str, dict]) -> None:
        names = sorted({r for n in nodes for r in n["allocatable"]}
                       | {r for f in families.values() for r in f["requests"]} | {"pods"})
        self.alloc = np.array([[quantity(n["allocatable"].get(r, 0)) for r in names]
                               for n in nodes])
        self.row = {f: i for i, f in enumerate(families)}
        self.need = np.array([[1.0 if r == "pods" else quantity(f["requests"].get(r, 0))
                               for r in names] for f in families.values()])

    def over_allocated(self, family: Sequence[str], node: Sequence[int]) -> int:
        """Nodes over their allocatable once a pod of `family[i]` sits on
        node `node[i]`, for every i."""
        use = np.zeros_like(self.alloc)
        np.add.at(use, np.asarray(node, np.int64),
                  self.need[np.asarray([self.row[f] for f in family], np.int64)])
        return int((use > self.alloc).any(axis=1).sum())
