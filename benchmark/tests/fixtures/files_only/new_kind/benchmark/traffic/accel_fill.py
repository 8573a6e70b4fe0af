"""Driver of the traffic kind `accel_fill`: one caller, back-to-back
`simulate` calls of the traffic's deployments onto node pools, some of whose
nodes carry the extended resource `example.com/accel`, and some nodes of
each pool holding bound pods of one family. Beside `pods_per_s` it reports
`accel_pods_per_s`: pods asking for the extended resource placed per
second of the window.

The configuration's schema (`pools`, a list of `bound` families) is read
here, not by benchmark/generator.py. The window, the calls and the reading
of their answers are those of `closed_loop_simulate`; after the window the
plain reference (benchmark/reference/accel_capacity.py) judges every call.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark import generator
from benchmark.reference import accel_capacity
from benchmark.traffic import closed_loop_simulate


class Driver(closed_loop_simulate.Driver):
    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        from open_simulator_tpu.core.objects import Node, Pod

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.families = cfg["families"]
        ns = cfg["namespace"]
        nodes, first = [], []
        for p in cfg["pools"]:
            first.append(len(nodes))
            nodes += [{"name": f"{p['prefix']}{i}",
                       "labels": {generator.HOSTNAME: f"{p['prefix']}{i}"},
                       "allocatable": dict(p["allocatable"])}
                      for i in range(int(p["count"]))]
        # each bound family on a fixed number of its pool's nodes, picked by the seed
        rng = generator.rng_for(seed, "bound")
        bound_node, self.bound_family = [], []
        for b in cfg["bound"]:
            count, per_node = int(b["nodes"]), int(b["per_node"])
            picked = rng.choice(int(cfg["pools"][b["pool"]]["count"]), count, replace=False)
            bound_node += np.repeat(first[b["pool"]] + np.sort(picked), per_node).tolist()
            self.bound_family += [b["family"]] * (count * per_node)
        self.cluster = generator.Cluster(
            nodes=nodes, bound_family=None, bound_node=np.array(bound_node, np.int32),
            candidates=[], namespace=ns)
        self.nodes = [Node.from_dict(generator.node_object(n)) for n in nodes]
        self.cands = []
        self.index = {n["name"]: i for i, n in enumerate(nodes)}
        self.bound = [
            Pod.from_dict(generator.bound_pod_object(
                i, nodes[ni]["name"], self.families[f], ns))
            for i, (ni, f) in enumerate(zip(bound_node, self.bound_family))
        ]
        self.records = []

    def run_window(self, seconds: float) -> dict:
        win = super().run_window(seconds)
        placed = sum(int((answer >= 0).sum()) for rec in self.records
                     for dep, answer in zip(rec.deployments, rec.answers)
                     if "example.com/accel" in self.families[dep["family"]]["requests"])
        win["e2e"]["accel_pods_per_s"] = placed / win["seconds"]
        return win

    def check(self, control: bool) -> Dict[str, float]:
        """Every call's undecided pods, and its nodes over their allocatable
        once its placements join the bound pods. The reference judges
        limits, not choices, so `control` changes nothing here."""
        cap = accel_capacity.Capacity(self.cluster.nodes, self.families)
        undecided = over = 0
        for rec in self.records:
            undecided += rec.undecided
            family, node = list(self.bound_family), self.cluster.bound_node.tolist()
            for dep, answer in zip(rec.deployments, rec.answers):
                placed = answer[answer >= 0]
                family += [dep["family"]] * placed.size
                node += placed.tolist()
            over += cap.over_allocated(family, node)
        return {"undecided": undecided, "over_allocated_nodes": over}
