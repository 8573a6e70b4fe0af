"""Driver of the traffic kind `closed_loop_preempt`: one caller, back-to-back
`simulate` calls that roll production Deployments out onto a cluster of
priority tiers, where every production pod has to preempt.

The configuration's `tiers` say how many bound pods of each tier every node
holds, their requests and the priority range each pod's priority is drawn
from; the seed draws each priority and the order of each node's pods. Each
call deploys the traffic's Deployments (requests and priority each) in
order. The window runs whole calls until `--seconds` have passed. End to
end it reports `pods_per_s`: measured production pods decided (placed, or
reported unschedulable) over the window's whole time; victims do not count.

A watchdog ends the run (exit 4, with a message) when one call, in warm-up
or in the window, runs past the traffic's `watchdog_s` outside compile
requests: a program that cannot preempt at this size fails at once instead
of holding the chip.

After the window the plain reference (benchmark/reference/tiers.py) replays
the program's answers call by call (teacher forcing) and judges them.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
from typing import Dict, List

import numpy as np

from benchmark import generator
from benchmark.reference import tiers as ref

_MI = 1 << 20


def _milli(q: str) -> int:
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def _bytes(q: str) -> int:
    for suffix, mult in (("Ki", 1 << 10), ("Mi", _MI), ("Gi", 1 << 30)):
        if q.endswith(suffix):
            return int(q[: -len(suffix)]) * mult
    return int(q)


@dataclasses.dataclass
class CallRecord:
    """A call's production pods in the order the program handled them."""
    uid: np.ndarray           # i64[P]
    prio: np.ndarray          # i64[P]
    req: np.ndarray           # i64[P, 2] cpu m, memory B
    node: np.ndarray          # i64[P] placement, -1 unschedulable
    victims: List[List[int]]  # uids evicted for each pod, in eviction order
    undecided: int
    decided: int
    seconds: float = 0.0


class _Watchdog:
    """Ends the process when the open call runs past `limit` seconds outside
    compile requests: finished ones (CompileCounter seconds) and one in
    progress (from the compile request's start event)."""

    START_EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self, limit: float) -> None:
        from jax import monitoring

        from open_simulator_tpu.utils.platform import (
            BACKEND_COMPILE_EVENT, CompileCounter)

        self.limit = limit
        self.cc = CompileCounter().__enter__()
        self._done_event = BACKEND_COMPILE_EVENT
        self._lock = threading.Lock()
        self._call = None            # (label, start, compile seconds then)
        self._depth, self._since = 0, 0.0
        monitoring.register_event_listener(self._on_start)
        monitoring.register_event_duration_secs_listener(self._on_done)
        threading.Thread(target=self._watch, daemon=True).start()

    def _on_start(self, event: str, **kw) -> None:
        if event == self.START_EVENT:
            with self._lock:
                if self._depth == 0:
                    self._since = time.monotonic()
                self._depth += 1

    def _on_done(self, event: str, duration: float, **kw) -> None:
        if event == self._done_event:
            with self._lock:
                self._depth = max(0, self._depth - 1)

    def begin(self, label: str) -> None:
        with self._lock:
            self._depth = 0
            self._call = (label, time.monotonic(), self.cc.compile_seconds)

    def end(self) -> None:
        with self._lock:
            self._call = None

    def _outside_compiles(self):
        with self._lock:
            if self._call is None:
                return None, 0.0
            label, t0, c0 = self._call
            now = time.monotonic()
            compiling = now - self._since if self._depth else 0.0
            return label, now - t0 - (self.cc.compile_seconds - c0) - compiling

    def _watch(self) -> None:
        while True:
            time.sleep(0.5)
            label, spent = self._outside_compiles()
            if label is not None and spent > self.limit:
                print(f"benchmark: the {label} call ran past {self.limit:.0f} s "
                      f"outside compile requests ({spent:.1f} s); ending the run",
                      file=sys.stderr, flush=True)
                os._exit(4)


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        from open_simulator_tpu.core.objects import Node, Pod

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.watchdog = _Watchdog(float(traffic.get("watchdog_s", 120)))
        cluster = generator.build_cluster(cfg, seed)
        self.namespace = cluster.namespace
        self.nodes = [Node.from_dict(generator.node_object(n)) for n in cluster.nodes]
        self.index = {n["name"]: i for i, n in enumerate(cluster.nodes)}
        self.alloc = np.array(
            [[_milli(n["allocatable"]["cpu"]), _bytes(n["allocatable"]["memory"]),
              int(n["allocatable"]["pods"])] for n in cluster.nodes], np.int64)
        self._bind_tiers(cluster.nodes)
        self.bound = [Pod.from_dict(o) for o in self._bound_objects(cluster.nodes)]
        self.deps = traffic["deployments"]
        self.records: List[CallRecord] = []

    # -- the cluster ------------------------------------------------------
    def _bind_tiers(self, nodes: List[dict]) -> None:
        """Every node's tier pods, in a seeded order per node, each at a
        priority drawn from its tier's range: arrays in bind order."""
        tiers = self.cfg["tiers"]
        per_node = np.concatenate([np.full(t["per_node"], i) for i, t in enumerate(tiers)])
        rng = generator.rng_for(self.seed, "tiers")
        n = len(nodes)
        order = rng.permuted(np.tile(per_node, (n, 1)), axis=1)      # [N, slots]
        self.b_tier = order.reshape(-1)
        self.b_node = np.repeat(np.arange(n), per_node.size)
        lo = np.array([t["priority"][0] for t in tiers])[self.b_tier]
        hi = np.array([t["priority"][1] for t in tiers])[self.b_tier]
        self.b_prio = rng.integers(lo, hi + 1)
        req = np.array([[_milli(t["requests"]["cpu"]), _bytes(t["requests"]["memory"])]
                        for t in tiers], np.int64)
        self.b_req = req[self.b_tier]
        if self.b_tier.size != int(self.cfg["bound_count"]):
            raise ValueError(f"tiers bind {self.b_tier.size} pods, "
                             f"the configuration says {self.cfg['bound_count']}")

    def _bound_objects(self, nodes: List[dict]):
        tiers = self.cfg["tiers"]
        for i, (t, ni, prio) in enumerate(zip(self.b_tier, self.b_node, self.b_prio)):
            tier = tiers[t]
            yield {
                "kind": "Pod",
                "metadata": {"name": f"b-{i}", "namespace": self.namespace,
                             "labels": {"tier": tier["name"]}},
                "spec": {"nodeName": nodes[ni]["name"], "priority": int(prio),
                         "containers": [{"name": "c", "image": "img", "resources": {
                             "requests": dict(tier["requests"])}}]},
                "status": {"phase": "Running"},
            }

    def _deployment(self, dep: dict) -> dict:
        return {
            "kind": "Deployment",
            "metadata": {"name": dep["name"], "namespace": self.namespace},
            "spec": {"replicas": int(dep["replicas"]), "template": {
                "metadata": {"labels": {"app": dep["name"]}},
                "spec": {"priority": int(dep["priority"]), "containers": [{
                    "name": "c", "image": "img",
                    "resources": {"requests": dict(dep["requests"])}}]}}},
        }

    # -- one call ---------------------------------------------------------
    def call(self, label: str) -> CallRecord:
        import jax

        from open_simulator_tpu.engine.simulator import (
            AppResource, ClusterResource, simulate)

        self.watchdog.begin(label)
        t0 = time.perf_counter()
        cluster = ClusterResource(nodes=self.nodes, pods=self.bound)
        objs = [self._deployment(d) for d in self.deps]
        expanded: dict = {}  # a fresh cache: each deployment's pods in order
        with jax.profiler.TraceAnnotation("bench:simulate"):
            result = simulate(cluster, [AppResource(name="bench", objects=objs)],
                              expand_cache=expanded)
        with jax.profiler.TraceAnnotation("bench:extract"):
            rec = self._extract(result, expanded.get(0, {}))
        rec.seconds = time.perf_counter() - t0
        self.watchdog.end()
        return rec

    def _extract(self, result, expanded) -> CallRecord:
        b = len(self.bound)
        pods = [p for d in range(len(self.deps)) for p in expanded.get(d, [])]
        uid = {id(p): b + i for i, p in enumerate(pods)}
        node = np.full(len(pods), -1, np.int64)
        for st in result.node_status:
            ni = self.index[st.node.name]
            for p in st.pods:
                if id(p) in uid:
                    node[uid[id(p)] - b] = ni
        by_key = {p.key: i for i, p in enumerate(pods)}
        victims: List[List[int]] = [[] for _ in pods]
        for v in result.preempted:
            u = uid.get(id(v.pod))
            if u is None:                      # a bound pod's copy: b-<i>
                u = int(v.pod.meta.name[2:])
            else:                              # placed, then evicted
                node[u - b] = self.index[v.node]
            victims[by_key[v.by]].append(u)
        unsched = np.array([False] * len(pods))
        for u in result.unscheduled:
            if id(u.pod) in uid:
                unsched[uid[id(u.pod)] - b] = True
        wanted = sum(int(d["replicas"]) for d in self.deps)
        undecided = int(np.sum((node < 0) == ~unsched) + abs(wanted - len(pods)))
        decided = int(np.sum((node >= 0) | unsched))
        # the order the program committed them: the batch's placements, then
        # the preemption pass over the batch's failures, each in pod order
        first = np.array([node[i] >= 0 and not victims[i] for i in range(len(pods))])
        order = np.concatenate([np.flatnonzero(first), np.flatnonzero(~first)])
        spec = np.array([[_milli(d["requests"]["cpu"]), _bytes(d["requests"]["memory"]),
                          int(d["priority"])]
                         for k, d in enumerate(self.deps) for _ in expanded.get(k, [])],
                        np.int64).reshape(-1, 3)
        return CallRecord(
            uid=(b + order).astype(np.int64), prio=spec[order, 2],
            req=spec[order, :2], node=node[order],
            victims=[victims[i] for i in order], undecided=undecided,
            decided=decided)

    def _counters(self) -> Dict[str, float]:
        from open_simulator_tpu.utils import metrics

        return {
            **{f"preemption_{x['labels']['outcome']}": x["value"]
               for x in metrics.PREEMPTION_ATTEMPTS.snapshot()["samples"]},
            "victims_preempted": metrics.SCHEDULE_RESULT.value(result="preempted"),
            "bound_pod_copies": metrics.BOUND_POD_COPIES.value(),
        }

    def warm_up(self) -> None:
        self.call("warm-up")

    def run_window(self, seconds: float) -> dict:
        before = self._counters()
        failed = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            try:
                self.records.append(self.call("measured"))
            except Exception as exc:  # a call that raises is a failed attempt
                self.watchdog.end()
                failed += 1
                print(f"benchmark: call failed: {exc!r}", file=sys.stderr)
        window_s = time.perf_counter() - t0
        after = self._counters()
        recs = self.records
        calls = max(len(recs), 1)
        return {
            "attempted": len(recs) + failed,
            "failed": failed,
            "units": len(recs),
            "seconds": window_s,
            "e2e": {"pods_per_s": sum(r.decided for r in recs) / window_s
                    if window_s > 0 else None},
            "info": {
                "calls": len(recs),
                "call_seconds": [r.seconds for r in recs],
                "outside_calls_s": window_s - sum(r.seconds for r in recs),
                # the program's own preemption counters, per call
                **{k: (after[k] - before.get(k, 0.0)) / calls for k in after},
            },
        }

    def free(self) -> None:
        """The program's state goes before the reference runs."""
        del self.bound, self.nodes

    # -- the check, after the window ------------------------------------
    def check(self, control: bool) -> Dict[str, float]:
        base = ref.State(self.alloc)
        base.add_many(np.arange(self.b_node.size), self.b_node, self.b_prio,
                      self.b_req[:, 0], self.b_req[:, 1])
        rng = generator.rng_for(self.seed, "sample")
        out = dict(undecided=0, over_allocated_nodes=0, priority_inverted=0,
                   victims_wrong=0, preempt_missed=0, infeasible=0, sampled=0)
        prio_of = {}
        for rec in self.records:
            out["undecided"] += rec.undecided
            state = base.copy()
            prio_of.clear()
            n = rec.uid.size
            sample = set(rng.choice(n, size=min(int(self.traffic.get(
                "sample_per_call", 32)), n), replace=False).tolist())
            for i in range(n):
                pod_prio = int(rec.prio[i])
                cpu, mem = int(rec.req[i, 0]), int(rec.req[i, 1])
                placed, vs = int(rec.node[i]), rec.victims[i]
                if i in sample:
                    out["sampled"] += 1
                    want = state.preempt(pod_prio, cpu, mem)
                    got = (state.where.get(vs[0], (-1,))[0], vs) if vs else None
                    if control:  # the reference's own choice, reprieved backwards
                        got = state.preempt(pod_prio, cpu, mem, reverse=True)
                    if want is not None and got is None and placed < 0:
                        out["preempt_missed"] += 1
                    elif got is not None and (want is None or (
                            want[0], sorted(want[1])) != (got[0], sorted(got[1]))):
                        out["victims_wrong"] += 1
                for v in vs:
                    vp = int(self.b_prio[v]) if v < self.b_prio.size else prio_of.get(v)
                    if vp is None or vp >= pod_prio:
                        out["priority_inverted"] += 1
                    state.remove(v)
                if placed >= 0:
                    if not state.fits(placed, cpu, mem):
                        out["infeasible"] += 1
                    state.add(int(rec.uid[i]), placed, pod_prio, cpu, mem)
                    prio_of[int(rec.uid[i])] = pod_prio
            out["over_allocated_nodes"] += state.over_allocated()
        return out
