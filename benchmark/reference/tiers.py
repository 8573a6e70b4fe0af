"""Plain reference of DefaultPreemption on a cluster of priority tiers.

numpy, integer millicores and bytes; imports nothing of the program. It
models what the configuration `borg_tiers_5k` holds: nodes with cpu, memory
and a pod count, pods that request cpu and memory at a priority, no taints,
no PDBs, no constraint between pods. For one preemptor it does what
kube-scheduler's DefaultPreemption does (default_preemption.go):

  - on each node, remove every pod of lower priority; the node is a
    candidate if the preemptor then fits;
  - reprieve the removed pods from the most important down (higher priority
    first, ties in bind order): a pod whose return still lets the preemptor
    fit stays, the others are the victims (no PDBs, so one class);
  - among nodes with victims pick the one with the lowest highest victim
    priority, then the lowest sum of (priority + 2^31), then the fewest
    victims, then the lowest node index.

`State` is a cluster's pods in bind order per node; the driver replays the
program's own answers into it (teacher forcing) and asks the reference at
sampled preemptors.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

_OFFSET = 1 << 31


class State:
    """Pods per node in bind order: [N, M] slot arrays, -1 uid when empty."""

    def __init__(self, alloc: np.ndarray, width: int = 64) -> None:
        self.alloc = np.asarray(alloc, np.int64)          # [N, 3] cpu m, mem B, pods
        n = self.alloc.shape[0]
        self.uid = np.full((n, width), -1, np.int64)
        self.prio = np.zeros((n, width), np.int64)
        self.req = np.zeros((n, width, 2), np.int64)       # cpu m, mem B
        self.used = np.zeros(n, np.int64)                  # slots taken, in order
        self.where: Dict[int, Tuple[int, int]] = {}

    def copy(self) -> "State":
        s = State.__new__(State)
        s.alloc = self.alloc
        s.uid, s.prio, s.req = self.uid.copy(), self.prio.copy(), self.req.copy()
        s.used, s.where = self.used.copy(), dict(self.where)
        return s

    def add_many(self, uid, node, prio, cpu, mem) -> None:
        """Pods bound in this order (vectorised over many)."""
        uid, node = np.asarray(uid, np.int64), np.asarray(node, np.int64)
        order = np.argsort(node, kind="stable")
        ns = node[order]
        starts = np.searchsorted(ns, ns, side="left")
        slot = self.used[ns] + np.arange(ns.size) - starts
        if ns.size and int(slot.max()) >= self.uid.shape[1]:
            self._grow(int(slot.max()) + 1)
        self.uid[ns, slot] = uid[order]
        self.prio[ns, slot] = np.asarray(prio, np.int64)[order]
        self.req[ns, slot, 0] = np.asarray(cpu, np.int64)[order]
        self.req[ns, slot, 1] = np.asarray(mem, np.int64)[order]
        np.add.at(self.used, ns, 1)
        self.where.update(zip(uid[order].tolist(), zip(ns.tolist(), slot.tolist())))

    def add(self, uid: int, node: int, prio: int, cpu: int, mem: int) -> None:
        self.add_many([uid], [node], [prio], [cpu], [mem])

    def remove(self, uid: int) -> bool:
        at = self.where.pop(int(uid), None)
        if at is None:
            return False
        self.uid[at] = -1
        return True

    def _grow(self, width: int) -> None:
        w = 1 << (width - 1).bit_length()
        pad = w - self.uid.shape[1]
        self.uid = np.pad(self.uid, ((0, 0), (0, pad)), constant_values=-1)
        self.prio = np.pad(self.prio, ((0, 0), (0, pad)))
        self.req = np.pad(self.req, ((0, 0), (0, pad), (0, 0)))

    # -- filters ---------------------------------------------------------
    def free(self, keep: np.ndarray) -> np.ndarray:
        """[N, 3] free cpu, memory and pod slots with the pods `keep` bound."""
        used = np.stack([np.sum(np.where(keep, self.req[..., 0], 0), axis=1),
                         np.sum(np.where(keep, self.req[..., 1], 0), axis=1),
                         np.sum(keep, axis=1)], axis=1)
        return self.alloc - used

    def fits(self, node: int, cpu: int, mem: int) -> bool:
        f = self.free(self.uid >= 0)[node]
        return bool(f[0] >= cpu and f[1] >= mem and f[2] >= 1)

    def over_allocated(self) -> int:
        return int(np.sum(np.any(self.free(self.uid >= 0) < 0, axis=1)))

    # -- DefaultPreemption -------------------------------------------------
    def preempt(self, prio: int, cpu: int, mem: int,
                reverse: bool = False) -> Optional[Tuple[int, List[int]]]:
        """(node, victim uids in reprieve order) or None. `reverse` reprieves
        the least important first: the control's deliberate fault."""
        live = self.uid >= 0
        potential = live & (self.prio < prio)
        free = self.free(live & ~potential)
        need = np.array([cpu, mem, 1], np.int64)
        fit0 = np.all(free >= need, axis=1) & np.any(potential, axis=1)
        key = np.where(potential, self.prio if reverse else -self.prio, np.iinfo(np.int64).max)
        queue = np.argsort(key, axis=1, kind="stable")     # ties keep bind order
        n_q = potential.sum(axis=1)
        rows = np.arange(self.uid.shape[0])
        victim = np.zeros(self.uid.shape, bool)
        for j in range(int(n_q[fit0].max(initial=0))):
            s = queue[:, j]
            active = fit0 & (j < n_q)
            back = np.stack([self.req[rows, s, 0], self.req[rows, s, 1],
                             np.ones_like(s)], axis=1)
            ok = np.all(free - back >= need, axis=1)
            free = np.where((active & ok)[:, None], free - back, free)
            victim[rows, s] = victim[rows, s] | (active & ~ok)
        count = victim.sum(axis=1)
        cand = fit0 & (count > 0)
        if not cand.any():
            return None
        top = np.where(victim, self.prio, np.iinfo(np.int64).min).max(axis=1)
        total = np.where(victim, self.prio + _OFFSET, 0).sum(axis=1)
        pool = cand
        for v in (top, total, count):
            pool = pool & (v == v[pool].min())
        node = int(np.flatnonzero(pool)[0])
        order = [s for s in queue[node, : n_q[node]] if victim[node, s]]
        return node, [int(self.uid[node, s]) for s in order]
