"""DefaultPreemption's device victim search (ops/preempt.py) against the host
driver of engine/preemption.py over the numpy filter oracle, against the
answers the lane-by-lane device probe it replaced gave, and its cost in
host reads per preemptor; the preemptor's retry program against the
eviction followed by the grouped scan's first step."""

import copy
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.analysis import oracle
from open_simulator_tpu.engine.preemption import (
    _static_unresolvable_ok,
    select_victims_on_node,
    try_preempt,
)
from open_simulator_tpu.engine.simulator import (
    AppResource,
    ClusterResource,
    Simulator,
    simulate,
)
from open_simulator_tpu.models.profiles import ExtenderConfig
from open_simulator_tpu.ops import grouped, kernels, preempt
from open_simulator_tpu.ops.encode import (
    aggregate_gpu_usage,
    aggregate_usage,
    encode_nodes,
    encode_pods,
    initial_anti_counts,
    initial_port_counts,
    initial_selector_counts,
)
from open_simulator_tpu.ops.state import node_static_from_table, pod_rows_from_batch
from open_simulator_tpu.plugins import DevicePlugin
from open_simulator_tpu.utils import metrics, tracing

from preemption_cases import COUPLED, KINDS, case, coupled_case, digest, simulate_case

PINNED = os.path.join(os.path.dirname(__file__), "fixtures", "preemption_pinned.json")
CASES = [(k, s, n) for k in KINDS for s in (1, 2, 3) for n in (12, 40)]


def _sim(seed, n_nodes, kind, **kw):
    """A Simulator holding the case's bound pods, its preemptors pending and
    registered, the device state built."""
    objs, pre = case(seed, n_nodes, kind)
    cluster = ClusterResource.from_objects(objs + pre)
    sim = Simulator(cluster, **kw)
    sim._build_device_state(sim._pending_cluster)
    return sim


_run_filters = jax.jit(kernels.run_filters)


def _post_eviction_fits(sim, bound_by_node):
    """fits_fn(pod, node, remaining): the filters on the cluster whose node
    `node` is re-encoded holding only `remaining`, every other node holding
    its bound pods; the numpy oracle where it covers the pod, the device
    filter kernel on that state otherwise."""
    nodes = sim.cluster.nodes
    enc = sim.enc
    index = {n.name: i for i, n in enumerate(nodes)}
    placed = [(p, n) for n, ps in bound_by_node.items() for p in ps]
    base = encode_nodes(enc, nodes, existing_usage=aggregate_usage(placed),
                        existing_gpu=aggregate_gpu_usage(nodes, placed))
    planes = (initial_selector_counts(enc, base, placed),
              *initial_port_counts(enc, base, placed),
              initial_anti_counts(enc, base, placed))
    ns = node_static_from_table(enc, base)
    rows = {}

    def encoded(pod):
        """(batch, None) for the oracle, (batch, device row) beyond it."""
        if id(pod) not in rows:
            batch = encode_pods(enc, [pod])
            try:
                oracle._check_supported(batch)
                rows[id(pod)] = batch, None
            except oracle.OracleUnsupported:
                rows[id(pod)] = batch, jax.tree.map(
                    lambda a: a[0], pod_rows_from_batch(batch))
        return rows[id(pod)]

    def fits(pod, node, remaining):
        ni = index[node.name]
        kept = [(p, node.name) for p in remaining]
        one = encode_nodes(enc, [node], existing_usage=aggregate_usage(kept),
                           existing_gpu=aggregate_gpu_usage([node], kept))
        table = copy.copy(base)
        for f in ("free", "gpu_free", "vg_free", "dev_free"):
            arr = getattr(base, f).copy()
            arr[ni] = getattr(one, f)[0, : arr.shape[1]]
            setattr(table, f, arr)
        cols = (initial_selector_counts(enc, one, kept),
                *initial_port_counts(enc, one, kept),
                initial_anti_counts(enc, one, kept))
        sel, p_any, p_wild, p_ipc, anti = [a.copy() for a in planes]
        for arr, col in zip((sel, p_any, p_wild, p_ipc, anti), cols):
            arr[:, ni] = col[:, 0]
        batch, row = encoded(pod)
        if row is not None:  # outside the oracle's family
            carry = kernels.Carry(table.free, sel, table.gpu_free, table.vg_free,
                                  table.dev_free, p_any, p_wild, p_ipc, anti)
            mask, _ = _run_filters(ns, carry, row)
            return bool(mask[ni])
        ocarry = oracle.OracleCarry(
            free=table.free, sel_counts=sel, gpu_free=table.gpu_free,
            vg_free=table.vg_free, dev_free=table.dev_free,
            port_any=p_any, port_wild=p_wild, port_ipc=p_ipc, anti_counts=anti)
        mask, _ = oracle.run_filters(_oracle_table(table, enc), ocarry,
                                     oracle._Pod(batch, 0))
        return bool(mask[ni])

    return fits


def _oracle_table(t, enc):
    from types import SimpleNamespace

    return SimpleNamespace(
        alloc=t.alloc, free=t.free, label_pair=t.label_pair, label_key=t.label_key,
        label_num=t.label_num, taint_key=t.taint_key, taint_val=t.taint_val,
        taint_effect=t.taint_effect, name_id=t.name_id, unsched=t.unsched,
        avoid_pods=t.avoid_pods, valid=t.valid, gpu_total=t.gpu_total,
        gpu_free=t.gpu_free, vg_free=t.vg_free, dev_free=t.dev_free,
        unsched_key_id=enc.unsched_key_id, empty_val_id=enc.empty_val_id)


def _bound_by_node(sim):
    out = {}
    for p, name in sim._bound:
        out.setdefault(name, []).append(p)
    return out


def _answer(res):
    if res is None:
        return None
    return res.node, [v.meta.name for v in res.victims], res.num_pdb_violations


@pytest.mark.parametrize("kind,seed,n_nodes", CASES)
def test_device_search_matches_host_driver_over_oracle(kind, seed, n_nodes):
    """Every preemptor of the case, on the cluster as it stands: the device
    search picks the node and victims (in order) that try_preempt picks when
    each probe is the filter oracle on the post-eviction cluster."""
    sim = _sim(seed, n_nodes, kind)
    by_node = _bound_by_node(sim)
    fits = _post_eviction_fits(sim, by_node)
    preemptors = [p for p in sim._pending_cluster if p.priority > 0][:6]
    assert preemptors
    found = 0
    for pod in preemptors:
        want = try_preempt(pod, sim.cluster.nodes, by_node, sim._pdbs, fits_fn=fits)
        got = None if pod.preemption_policy == "Never" else sim._search_victims(pod)
        assert _answer(got) == _answer(want), pod.meta.name
        if kind == "resources":  # the host resources-only model agrees too
            assert _answer(try_preempt(pod, sim.cluster.nodes, by_node, sim._pdbs)) \
                == _answer(want)
        found += want is not None
    assert found


def test_pdb_violating_victims_are_reprieved_first():
    """Of two equal victims, the one whose eviction breaks a PDB is reprieved
    first, so the other goes; with both needed, the violation is counted."""
    objs = [
        {"kind": "Node", "metadata": {"name": "n0", "labels": {}},
         "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}},
        {"kind": "PodDisruptionBudget", "metadata": {"name": "db", "namespace": "default"},
         "spec": {"selector": {"matchLabels": {"app": "db"}}, "maxUnavailable": 0}},
    ]
    for name, app in (("db-0", "db"), ("web-0", "web")):
        objs.append({"kind": "Pod", "metadata": {"name": name, "namespace": "default",
                                                 "labels": {"app": app}},
                     "spec": {"nodeName": "n0", "priority": 1, "containers": [
                         {"name": "c", "image": "i",
                          "resources": {"requests": {"cpu": "2"}}}]}})
    for cpu, victims, viol in (("2", ["web-0"], 0), ("4", ["db-0", "web-0"], 1)):
        pre = {"kind": "Pod", "metadata": {"name": "hi", "namespace": "default"},
               "spec": {"priority": 100, "containers": [
                   {"name": "c", "image": "i", "resources": {"requests": {"cpu": cpu}}}]}}
        sim = Simulator(ClusterResource.from_objects(objs + [pre]))
        sim._build_device_state(sim._pending_cluster)
        res = sim._search_victims(sim._pending_cluster[0])
        assert _answer(res) == ("n0", victims, viol)


@pytest.mark.parametrize("bound", (False, True))
def test_victim_table_is_built_only_when_a_pod_preempts(bound):
    """Prioritised pods that all fit never build the victim table; a call
    that preempts builds it at its first search."""
    objs, pre = case(1, 12, "resources")
    if not bound:
        objs = [o for o in objs if o["kind"] != "Pod"]
    sim = Simulator(ClusterResource.from_objects(objs))
    res = sim.run([AppResource(name="pre", objects=pre)])
    assert bool(res.preempted) == bound
    assert (sim._victims is not None) == bound


def _pinned():
    with open(PINNED) as fh:
        return json.load(fh)


def _placed_hash(d):
    return hashlib.sha256(json.dumps(d["placed"]).encode()).hexdigest()


@pytest.mark.parametrize("kind,seed,n_nodes", CASES)
def test_simulate_answers_as_pinned(kind, seed, n_nodes):
    """simulate on each case gives the answers pinned from the lane-by-lane
    device probe this search replaced: the placements (hashed), the
    preempted pods in order with node and preemptor, the unscheduled pods
    with their reasons."""
    want = _pinned()[f"{kind}/{seed}/{n_nodes}"]
    got = digest(simulate_case(seed, n_nodes, kind))
    assert got["preempted"] == want["preempted"]
    assert got["unscheduled"] == want["unscheduled"]
    assert _placed_hash(got) == want["placed_sha256"]


def _allow_all(ns, carry, pod):
    return ns.valid


@pytest.mark.parametrize("kind", ("anti", "spread", "pdb", *COUPLED))
def test_whole_cluster_path_agrees(kind):
    """An out-of-tree filter sends the search down the whole-cluster path
    (each node runs every filter over its own whole-cluster carry); with a
    filter that passes everything it gives the per-node path's answers,
    domain-count filters included."""
    if kind in COUPLED:
        objs, pre = coupled_case(1, 12, kind)
        run = lambda **kw: digest(simulate(  # noqa: E731
            ClusterResource.from_objects(copy.deepcopy(objs)),
            [AppResource(name="pre", objects=copy.deepcopy(pre))], **kw))
    else:
        run = lambda **kw: digest(simulate_case(2, 12, kind, **kw))  # noqa: E731
    plain = run()
    assert plain["preempted"]
    before = metrics.VICTIM_SEARCH.value(path="whole_cluster")
    whole = run(plugins=[DevicePlugin(name="allow-all", filter_fn=_allow_all)])
    assert metrics.VICTIM_SEARCH.value(path="whole_cluster") > before
    assert whole == plain


def _count(span, name):
    return (span["name"] == name) + sum(_count(c, name) for c in span.get("children", []))


def _syncs_per_preemptor(root):
    """host-sync spans under each `preempt` span of a finished root."""
    out = []

    def walk(span):
        if span["name"] == "preempt":
            out.append(_count(span, "host-sync"))
        for c in span.get("children", []):
            walk(c)

    walk(root)
    return out


@pytest.mark.parametrize("n_nodes", (40, 400))
def test_host_syncs_per_preemptor_do_not_grow_with_nodes(n_nodes):
    """The search is one host read; the eviction none; the retry, the
    eviction and one scheduling step as one device program, one. At most two
    per preemptor, at 40 nodes as at 400; every retry takes the device
    path."""
    before = metrics.VICTIM_SEARCH.value(path="device")
    retries = {p: metrics.PREEMPTION_RETRY.value(path=p) for p in ("device", "batch")}
    tried = sum(metrics.PREEMPTION_ATTEMPTS.value(outcome=o)
                for o in ("succeeded", "retry_failed"))
    res = simulate_case(1, n_nodes, "resources")
    assert res.preempted
    root = [r for r in tracing.recent_timings() if r["name"] == "simulate"][-1]
    syncs = _syncs_per_preemptor(root)
    assert len(syncs) == metrics.VICTIM_SEARCH.value(path="device") - before
    assert syncs and max(syncs) <= 2, syncs
    n_retries = metrics.PREEMPTION_RETRY.value(path="device") - retries["device"]
    assert n_retries == _count(root, "retry") > 0
    assert n_retries == sum(metrics.PREEMPTION_ATTEMPTS.value(outcome=o)
                            for o in ("succeeded", "retry_failed")) - tried
    assert metrics.PREEMPTION_RETRY.value(path="batch") == retries["batch"]


def _not_first(ns, carry, pod):
    return ns.valid & (jnp.arange(ns.valid.shape[0]) > 0)


def _first_else_most_packed(ns, carry, pod):
    return jnp.where(jnp.arange(ns.valid.shape[0]) == 0, 1e6, -carry.free[:, 0])


def _two_empty_nodes():
    """The `resources` case with nodes n000 and n001 empty: with both plugins
    the retry goes to the most packed node that fits, the evicted one;
    without the filter to n000, without the score to the empty n001."""
    objs, pre = case(2, 12, "resources")
    return [o for o in objs if o["spec"].get("nodeName") not in ("n000", "n001")], pre


def _retry_cases():
    cases = [(kind, lambda k=kind: case(2, 12, k), ()) for kind in KINDS]
    cases += [(mode, lambda m=mode: coupled_case(1, 12, m), ()) for mode in COUPLED]
    cases.append(("plugins", _two_empty_nodes, (
        DevicePlugin(name="not-first", filter_fn=_not_first),
        DevicePlugin(name="first-else-most-packed", score_fn=_first_else_most_packed,
                     weight=1000.0))))
    return cases


def _bits(tree):
    return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("name,make,plugins", _retry_cases(),
                         ids=[c[0] for c in _retry_cases()])
def test_retry_program_is_eviction_then_step_zero(name, make, plugins):
    """retry_one on a preemptor's eviction equals apply_evictions followed by
    the 32-step grouped scan with one valid step, bit for bit: every carry
    leaf and every output of step 0. Three preemptors a case, each on the
    state the previous retry left."""
    objs, pre = make()
    sim = Simulator(ClusterResource.from_objects(objs + pre), plugins=list(plugins))
    sim._build_device_state(sim._pending_cluster)
    done = 0
    for pod in [p for p in sim._pending_cluster
                if p.priority > 0 and p.preemption_policy != "Never"]:
        res = sim._search_victims(pod)
        if res is None:
            continue
        ni = sim._name_index_map()[res.node]
        sim._evict(res.victims, ni, by=pod.key)
        _, rows = sim._evict_rows
        weights, fo = sim._profiles[pod.scheduler_name]
        fo = jnp.ones(kernels.NUM_FILTERS, bool) if fo is None else jnp.asarray(fo)
        row = sim._preemptor_row(pod)
        offs = sim._victims.offs
        got_carry, got = preempt.retry_one(
            sim._ns, sim._carry, np.int32(ni), rows, row, weights, fo, offs=offs,
            extra_filters=sim._extra_filters, extra_scores=sim._extra_scores)
        want_carry = preempt.apply_evictions(sim._carry, np.int32(ni), rows, offs=offs)
        want_carry, want = grouped._group_call(
            sim._ns, want_carry, row, 32, jnp.int32(1), weights, fo,
            sim._extra_filters, sim._extra_scores)
        assert _bits(got_carry) == _bits(want_carry), name
        assert _bits(got) == _bits(jax.tree.map(lambda a: a[0], want)), name
        sim._carry = got_carry
        done += 1
        if done == 3:
            break
    assert done, name


def test_extender_sees_every_candidate(stub_factory):
    """A preemption-supporting extender receives the full candidate map: every
    node where preemption helps, each with the victims the host driver over
    the oracle selects there."""
    objs, pre = case(1, 12, "resources")
    stub = stub_factory({"preempt_allow": None})
    retries = {p: metrics.PREEMPTION_RETRY.value(path=p) for p in ("device", "batch")}
    got = digest(simulate(
        ClusterResource.from_objects(copy.deepcopy(objs)),
        [AppResource(name="p", objects=copy.deepcopy(pre[:1]))],
        extenders=[ExtenderConfig(url_prefix=stub.url, preempt_verb="preempt")]))
    sent = [b for path, b in stub.calls if path.endswith("/preempt")][0]["NodeNameToVictims"]
    # the extender is interested in the preemptor: its retry takes the batch
    # path, and the answers are those of the device path without it
    assert metrics.PREEMPTION_RETRY.value(path="batch") == retries["batch"] + 1
    assert metrics.PREEMPTION_RETRY.value(path="device") == retries["device"]
    assert got["preempted"]
    assert got == digest(simulate(ClusterResource.from_objects(objs),
                                  [AppResource(name="p", objects=pre[:1])]))

    sim = _sim(1, 12, "resources")
    by_node = _bound_by_node(sim)
    fits = _post_eviction_fits(sim, by_node)
    pod = [p for p in sim._pending_cluster if p.meta.name == "pre-0"][0]
    want = {}
    for node in sim.cluster.nodes:
        if not _static_unresolvable_ok(pod, node):
            continue
        r = select_victims_on_node(pod, node, by_node.get(node.name, []), [], {}, fits_fn=fits)
        if r is not None:
            want[node.name] = [v.meta.name for v in r.victims]
    assert len(want) > 1
    assert {n: [p["metadata"]["name"] for p in v["Pods"]] for n, v in sent.items()} == want



def test_node_sharded_mesh_gives_the_same_answers():
    """With the cluster state sharded on the node axis over four devices,
    the victim table shards with it and the answers do not change."""
    from open_simulator_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    plain = digest(simulate_case(1, 40, "pdb"))
    sharded = digest(simulate_case(1, 40, "pdb", mesh=make_mesh(jax.devices()[:4])))
    assert sharded == plain and plain["preempted"]
