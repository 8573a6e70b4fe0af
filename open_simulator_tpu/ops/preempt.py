"""DefaultPreemption's victim search on the device.

One jitted program per preemptor evaluates selectVictimsOnNode on every
node at once and picks the node the way pickOneNodeForPreemption does
(engine/preemption.py documents the semantics). It reads:

  - `VictimTable`: a device-resident table of each node's bound pods, K slots
    per node in MoreImportantPod order (priority descending, ties in bind
    order). A slot holds the pod's priority, its PDB matches and its eviction
    delta: the additive change its removal makes to the node's column of
    the carry planes in `PLANES`, packed into one f32 vector.
  - the carry, the node statics and the preemptor's encoded row.

Per node ("lane") the program removes every lower-priority pod, checks the
fit, then reprieves the queue (PDB-violating victims first, then the rest,
each class from the most important down) in a loop over queue positions
that is vectorised over the lanes. A lane's fit is `run_filters` on the
carry with only that lane's column changed:

  - node-local filters (ports, resources, storage, GPU) read the lane's own
    column, so every lane is evaluated at once on a carry whose column n
    holds lane n's state;
  - filters that read domain counts (hard topology spread, required
    inter-pod (anti)affinity and its symmetry) adjust only the lane's own
    domain by the lane's own evictions (`_lane_domain`); counts are
    integer-valued f32, so the answer equals the whole-cluster evaluation
    bit for bit;
  - out-of-tree filters (`extra_filters`) may read the carry anywhere, so
    with any of them each lane runs the whole-cluster `run_filters` on its
    own carry (`whole_cluster=True`), one lane after another.

The host reads one small pytree per preemptor: the chosen node, its queue
order and which queue positions are victims (or, for preemption-supporting
extenders, every node's).

`retry_one` then reschedules the preemptor as one program: the victims'
deltas added to the chosen node's column, and one scheduling step over every
node (`grouped.group_step`), read by the host once.
"""

from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import (
    F_GPU,
    F_NODE_PORTS,
    F_POD_AFFINITY,
    F_RESOURCES,
    F_SPREAD,
    F_STORAGE,
    HOSTNAME_KEY_IDX,
    _EPS,
    Carry,
    NodeStatic,
    PodRow,
    gpu_mask,
    local_storage_mask,
    node_affinity_mask,
    ports_mask,
    resource_fail,
    run_filters,
    taint_mask,
)
from .grouped import group_step

#: the carry planes an eviction changes, in packed order:
#: (packed key, carry field, node-major)
PLANES = (
    ("free", "free", True),
    ("sel", "sel_counts", False),
    ("gpu", "gpu_free", True),
    ("vg", "vg_free", True),
    ("dev", "dev_free", True),
    ("port_any", "port_any", False),
    ("port_wild", "port_wild", False),
    ("port_ipc", "port_ipc", False),
    ("anti", "anti_counts", False),
)

_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min
#: lanes evaluated together on the whole-cluster path
_WHOLE_CLUSTER_BATCH = 64


class VictimTable(NamedTuple):
    """Per-node bound pods, K slots a node, MoreImportantPod order."""
    prio: jnp.ndarray    # i32[N,K]
    valid: jnp.ndarray   # bool[N,K]
    delta: jnp.ndarray   # f32[N,K,T] packed eviction delta (PLANES layout)
    pdb: jnp.ndarray     # bool[N,K,B] slot's pod matches PDB b


class SearchOut(NamedTuple):
    """One preemptor's answer. With `all_nodes` every field keeps the node
    axis; otherwise `qidx`/`victims` are the chosen node's rows."""
    node: jnp.ndarray      # i32: chosen node, -1 for none
    cand: jnp.ndarray      # bool[N]: nodes with a victim set
    qidx: jnp.ndarray      # i32[K] (or [N,K]): slot at each queue position
    victims: jnp.ndarray   # bool[K] (or [N,K]): queue position is a victim
    n_viol: jnp.ndarray    # i32 (or [N]): victims violating a PDB


Offsets = Tuple[Tuple[str, int, int], ...]


def plane_offsets(carry: Carry) -> Offsets:
    """((key, start, end), ...) of each plane in the packed vector, from the
    carry's shapes; hashable, so it keys the compiled programs."""
    out, pos = [], 0
    for key, field, node_major in PLANES:
        arr = getattr(carry, field)
        n = arr.shape[1] if node_major else arr.shape[0]
        out.append((key, pos, pos + n))
        pos += n
    return tuple(out)


def packed_width(offs: Offsets) -> int:
    return offs[-1][2]


def pack_columns(carry: Carry) -> jnp.ndarray:
    """f32[N,T]: every node's packed column."""
    return jnp.concatenate(
        [getattr(carry, f) if nm else getattr(carry, f).T
         for _, f, nm in PLANES], axis=1)


def unpack_columns(carry: Carry, cols: jnp.ndarray, offs: Offsets) -> Carry:
    """`carry` with every node's column replaced by `cols` f32[N,T]."""
    planes = {}
    for (key, field, node_major), (_, s, e) in zip(PLANES, offs):
        part = cols[:, s:e]
        planes[field] = part if node_major else part.T
    return carry._replace(**planes)


def _set_column(carry: Carry, ni, col: jnp.ndarray, offs: Offsets) -> Carry:
    planes = {}
    for (key, field, node_major), (_, s, e) in zip(PLANES, offs):
        arr = getattr(carry, field)
        planes[field] = (arr.at[ni].set(col[s:e]) if node_major
                         else arr.at[:, ni].set(col[s:e]))
    return carry._replace(**planes)


def _column(carry: Carry, ni) -> jnp.ndarray:
    return jnp.concatenate(
        [getattr(carry, f)[ni] if nm else getattr(carry, f)[:, ni]
         for _, f, nm in PLANES])


def _evicted(carry: Carry, ni, deltas: jnp.ndarray, offs: Offsets) -> Carry:
    """`carry` with the victims' deltas f32[V,T] added to node `ni`'s column,
    one victim after another (zero rows pad V)."""
    col, _ = jax.lax.scan(lambda c, d: (c + d, None), _column(carry, ni), deltas)
    return _set_column(carry, ni, col, offs)


@partial(jax.jit, static_argnames=("offs",))
def apply_evictions(carry: Carry, ni, deltas: jnp.ndarray, offs: Offsets) -> Carry:
    """The eviction alone; the carry never leaves the device."""
    return _evicted(carry, ni, deltas, offs)


@partial(jax.jit, static_argnames=("offs", "extra_filters", "extra_scores"))
def retry_one(
    ns: NodeStatic,
    carry: Carry,
    ni,
    deltas: jnp.ndarray,
    row: PodRow,
    weights,
    filter_on,
    *,
    offs: Offsets,
    extra_filters=(),
    extra_scores=(),
):
    """A preemptor's retry as one program: the eviction (`apply_evictions`)
    then one scheduling step of `row` over every node, the step
    `grouped.schedule_group` scans. -> (carry, (node, reasons, gpu_take,
    vg_take, dev_take)); the step chooses the node by score, so the
    preemptor is not pinned to `ni`."""
    step = group_step(ns, row, weights, filter_on, extra_filters, extra_scores)
    return step(_evicted(carry, ni, deltas, offs), jnp.bool_(True))


@partial(jax.jit, donate_argnums=(0,))
def write_rows(table: VictimTable, rows, prio, valid, delta, pdb) -> VictimTable:
    """The host mirror's rows `rows` written into the device table."""
    return VictimTable(
        prio=table.prio.at[rows].set(prio),
        valid=table.valid.at[rows].set(valid),
        delta=table.delta.at[rows].set(delta),
        pdb=table.pdb.at[rows].set(pdb),
    )


# ---------------------------------------------------------------------------
# filters, one lane per node
# ---------------------------------------------------------------------------

def _static_fails(ns: NodeStatic, row: PodRow) -> jnp.ndarray:
    """bool[N,4]: the filters removing pods cannot fix (unschedulable, node
    name, taints, node affinity), as run_filters computes them."""
    unsched_tolerated = jnp.any(
        row.tol_valid
        & ((row.tol_key == 0) | (row.tol_key == ns.unsched_key_id))
        & (row.tol_exists | (row.tol_val == ns.empty_val_id))
        & ((row.tol_effect == 0) | (row.tol_effect == 1)),
    )
    return jnp.stack(
        [
            ns.unsched & ~unsched_tolerated,
            (row.node_name_id != 0) & (ns.name_id != row.node_name_id),
            ~taint_mask(ns, row),
            ~node_affinity_mask(ns, row),
        ],
        axis=1,
    )


def _top2_min(v: jnp.ndarray):
    """(min, argmin, min over the other entries) of f32[M]."""
    a1 = jnp.argmin(v)
    m1 = v[a1]
    m2 = jnp.min(jnp.where(jnp.arange(v.shape[0]) == a1, jnp.inf, v))
    return m1, a1, m2


def _lane_domain(ns: NodeStatic, base_row, lane_row, k, elig=None):
    """For every node n, what kernels._domain_counts(ns, row_n, k, elig)
    gives at node n, where row_n is `base_row` f32[N] with entry n replaced
    by `lane_row[n]`: (count of n's domain, min count over eligible domains,
    total), each f32[N]. Only n's own domain changes, by n's own delta."""
    elig = ns.valid if elig is None else (elig & ns.valid)
    base = jnp.where(elig, base_row, 0.0)
    lane = jnp.where(elig, lane_row, 0.0)
    delta = lane - base
    n_idx = jnp.arange(base.shape[0])

    # hostname: domains are the nodes
    h1, ha1, h2 = _top2_min(jnp.where(elig, base_row, jnp.inf))
    h_excl = jnp.where(n_idx == ha1, h2, h1)
    min_host = jnp.minimum(h_excl, jnp.where(elig, lane_row, jnp.inf))
    total_host = jnp.sum(base) + delta

    # other keys: sums over the one-hot membership, one domain adjusted
    onehot = ns.topo_onehot[k]                                  # [D,N]
    dims = (((1,), (0,)), ((), ()))
    dom = jax.lax.dot_general(onehot, base, dims,
                              precision=jax.lax.Precision.HIGHEST)
    dom_elig = jax.lax.dot_general(onehot, elig.astype(jnp.float32), dims,
                                   precision=jax.lax.Precision.HIGHEST) > 0.0
    in_key = (ns.domain_key == k) & dom_elig                    # [D]
    D = dom.shape[0]
    topo_k = ns.topo[:, k]
    has_dom = topo_k >= 0
    dn = jnp.clip(topo_k, 0, D - 1)
    own = dom[dn] + delta
    cnt_dom = jnp.where(has_dom, own, 0.0)
    d1, da1, d2 = _top2_min(jnp.where(in_key, dom, jnp.inf))
    d_excl = jnp.where(dn == da1, d2, d1)
    min_dom = jnp.where(
        has_dom, jnp.minimum(d_excl, jnp.where(in_key[dn], own, jnp.inf)), d1)
    total_dom = jnp.sum(jnp.where(in_key, dom, 0.0)) + jnp.where(
        has_dom & in_key[dn], delta, 0.0)

    is_host = k == HOSTNAME_KEY_IDX
    cnt = jnp.where(is_host, lane, cnt_dom)
    min_count = jnp.where(is_host, min_host, min_dom)
    min_count = jnp.where(jnp.isfinite(min_count), min_count, 0.0)
    total = jnp.where(is_host, total_host, total_dom)
    return cnt, min_count, total


def _lane_spread_mask(ns, base: Carry, lane: Carry, row: PodRow, na_ok):
    """kernels.spread_mask, each lane on the carry with only its column."""

    def one(topo_idx, sel_idx, max_skew, hard):
        active = (topo_idx >= 0) & hard
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0
        cnt, min_count, _ = _lane_domain(
            ns, base.sel_counts[sel_idx], lane.sel_counts[sel_idx], k, na_ok)
        ok = ((cnt + 1.0 - min_count) <= max_skew + _EPS) & has_key
        return jnp.where(active, ok, jnp.ones_like(ok))

    per_c = jax.vmap(one, in_axes=(0, 0, 0, 0), out_axes=1)(
        row.spread_topo, row.spread_sel, row.spread_skew, row.spread_hard)
    return jnp.all(per_c, axis=1)


def _lane_pod_affinity_mask(ns, base: Carry, lane: Carry, row: PodRow):
    """kernels.pod_affinity_mask, each lane on the carry with only its
    column."""

    def one(topo_idx, sel_idx, anti, required):
        active = (topo_idx >= 0) & required
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0
        cnt, _, total = _lane_domain(
            ns, base.sel_counts[sel_idx], lane.sel_counts[sel_idx], k)
        self_match = row.match_sel[sel_idx]
        aff_ok = ((cnt > 0) | (self_match & (total == 0))) & has_key
        ok = jnp.where(anti, cnt == 0, aff_ok)
        return jnp.where(active, ok, jnp.ones(ns.valid.shape, bool))

    per_a = jax.vmap(one, in_axes=(0, 0, 0, 0), out_axes=1)(
        row.aff_topo, row.aff_sel, row.aff_anti, row.aff_required)

    def one_sym(topo_idx, base_row, lane_row, match):
        active = (topo_idx >= 0) & match
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0
        cnt, _, _ = _lane_domain(ns, base_row, lane_row, k)
        ok = (cnt == 0) | ~has_key
        return jnp.where(active, ok, jnp.ones(ns.valid.shape, bool))

    per_sym = jax.vmap(one_sym, in_axes=(0, 0, 0, 0), out_axes=1)(
        ns.anti_topo, base.anti_counts, lane.anti_counts, row.match_anti)
    return jnp.all(per_a, axis=1) & jnp.all(per_sym, axis=1)


def _lane_fits_fn(ns, carry, row, filter_on, offs, extra_filters, whole_cluster):
    """fits(cols f32[N,T]) -> bool[N]: lane n fits with its column cols[n]."""
    if whole_cluster:
        def fits(cols):
            def one(n):
                lane = _set_column(carry, n, cols[n], offs)
                mask, _ = run_filters(ns, lane, row, filter_on, extra_filters)
                return mask[n]

            n = cols.shape[0]
            return jax.lax.map(one, jnp.arange(n),
                               batch_size=min(n, _WHOLE_CLUSTER_BATCH))

        return fits

    static = _static_fails(ns, row)
    static_ok = ~jnp.any(static & filter_on[None, :4], axis=1) & ns.valid
    na_ok = ~static[:, 3]

    def fits(cols):
        lane = unpack_columns(carry, cols, offs)
        fails = (
            (~ports_mask(lane, row), F_NODE_PORTS),
            (resource_fail(ns, lane, row), F_RESOURCES),
            (~_lane_spread_mask(ns, carry, lane, row, na_ok), F_SPREAD),
            (~_lane_pod_affinity_mask(ns, carry, lane, row), F_POD_AFFINITY),
            (~local_storage_mask(ns, lane, row), F_STORAGE),
            (~gpu_mask(ns, lane, row), F_GPU),
        )
        bad = jnp.zeros_like(static_ok)
        for f, idx in fails:
            bad = bad | (f & filter_on[idx])
        return static_ok & ~bad

    return fits


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def _narrow(pool, v, big):
    m = jnp.min(jnp.where(pool, v, big))
    return pool & (v == m)


@partial(jax.jit, static_argnames=("offs", "extra_filters", "whole_cluster",
                                   "all_nodes"))
def victim_search(
    ns: NodeStatic,
    carry: Carry,
    table: VictimTable,
    row: PodRow,
    prio,
    filter_on,
    pdb_allowed,
    *,
    offs: Offsets,
    extra_filters=(),
    whole_cluster: bool = False,
    all_nodes: bool = False,
) -> SearchOut:
    """selectVictimsOnNode on every node, then pickOneNodeForPreemption.

    `prio` is the preemptor's priority, `filter_on` bool[F] its profile's
    filter set, `pdb_allowed` i32[B] each PDB's DisruptionsAllowed now."""
    N, K = table.prio.shape
    static = _static_fails(ns, row)
    # nodesWherePreemptionMightHelp: the gate ignores the profile's filter set
    gate = ~jnp.any(static, axis=1) & ns.valid
    potential = table.valid & (table.prio < prio)               # [N,K]
    nq = jnp.sum(potential, axis=1).astype(jnp.int32)

    # filterPodsWithPDBViolation: budgets decremented per potential victim,
    # in MoreImportant (slot) order, each node on its own copy
    match = table.pdb & potential[:, :, None]                   # [N,K,B]
    used = jnp.cumsum(match.astype(jnp.int32), axis=1)
    viol = jnp.any(match & (pdb_allowed[None, None, :] - used < 0), axis=2)
    slots = jnp.arange(K, dtype=jnp.int32)[None, :]
    key = jnp.where(potential, jnp.where(viol, 0, K) + slots, 2 * K + slots)
    qidx = jnp.argsort(key, axis=1).astype(jnp.int32)           # [N,K]
    qviol = jnp.take_along_axis(viol, qidx, axis=1)
    qprio = jnp.take_along_axis(table.prio, qidx, axis=1)

    fits = _lane_fits_fn(ns, carry, row, filter_on, offs, extra_filters,
                         whole_cluster)
    cols0 = pack_columns(carry) + jnp.sum(
        jnp.where(potential[:, :, None], table.delta, 0.0), axis=1)
    fit0 = gate & (nq > 0) & fits(cols0)
    steps = jnp.max(jnp.where(fit0, nq, 0))

    def body(state):
        j, cols, vq = state
        slot = jax.lax.dynamic_index_in_dim(qidx, j, axis=1, keepdims=False)
        d = jnp.take_along_axis(table.delta, slot[:, None, None], axis=1)[:, 0]
        trial = cols - d                                        # reprieve it
        ok = fits(trial)
        active = fit0 & (j < nq)
        cols = jnp.where((active & ok)[:, None], trial, cols)
        vq = jax.lax.dynamic_update_index_in_dim(vq, active & ~ok, j, axis=1)
        return j + 1, cols, vq

    _, _, vq = jax.lax.while_loop(
        lambda s: s[0] < steps, body,
        (jnp.int32(0), cols0, jnp.zeros((N, K), bool)))

    count = jnp.sum(vq, axis=1).astype(jnp.int32)
    n_viol = jnp.sum(vq & qviol, axis=1).astype(jnp.int32)
    cand = fit0 & (count > 0)
    # pickOneNodeForPreemption: fewest PDB violations, lowest highest victim
    # priority, lowest sum of (priority + 2^31) — compared exactly as two
    # 16-bit halves —, fewest victims, lowest node index
    top = jnp.max(jnp.where(vq, qprio, _I32_MIN), axis=1)
    offset = jax.lax.bitcast_convert_type(qprio, jnp.uint32) ^ jnp.uint32(1 << 31)
    hi = jnp.sum(jnp.where(vq, (offset >> 16).astype(jnp.int32), 0), axis=1)
    lo = jnp.sum(jnp.where(vq, (offset & 0xFFFF).astype(jnp.int32), 0), axis=1)
    hi, lo = hi + (lo >> 16), lo & 0xFFFF
    pool = cand
    for v in (n_viol, top, hi, lo, count):
        pool = _narrow(pool, v, _I32_MAX)
    node = jnp.where(jnp.any(pool), jnp.argmax(pool), -1).astype(jnp.int32)
    if all_nodes:
        return SearchOut(node, cand, qidx, vq, n_viol)
    at = jnp.maximum(node, 0)
    return SearchOut(node, cand, qidx[at], vq[at], n_viol[at])


# ---------------------------------------------------------------------------
# host mirror
# ---------------------------------------------------------------------------

class HostVictimTable:
    """The host's copy of the VictimTable, plus the Pod in each slot.

    Rows change on the host (bind, eviction, rollback) and are written to
    the device table before the next search (`device()`), the changed rows
    only. Nothing here walks all bound pods after construction."""

    def __init__(self, n_rows: int, k: int, offs: Offsets, n_pdb: int) -> None:
        t = packed_width(offs)
        self.offs = offs
        self.k = k
        self.prio = np.zeros((n_rows, k), np.int32)
        self.valid = np.zeros((n_rows, k), bool)
        self.delta = np.zeros((n_rows, k, t), np.float32)
        self.pdb = np.zeros((n_rows, k, max(n_pdb, 1)), bool)
        self.pods = np.empty((n_rows, k), object)
        self.count = np.zeros(n_rows, np.int32)
        self._dirty: set = set()
        self._dev: Optional[VictimTable] = None

    # -- construction ---------------------------------------------------
    def fill(self, node_idx: np.ndarray, prio: np.ndarray, pods: Sequence,
             delta: np.ndarray, pdb: np.ndarray) -> None:
        """Bound pods in bind order: node index, priority, pod, delta, PDB
        matches each. Slots take MoreImportantPod order per node."""
        b = len(pods)
        if not b:
            return
        order = np.lexsort((np.arange(b), ~prio.astype(np.int32), node_idx))
        nodes_sorted = node_idx[order]
        starts = np.searchsorted(nodes_sorted, nodes_sorted, side="left")
        slot = np.arange(b) - starts
        self.prio[nodes_sorted, slot] = prio[order]
        self.valid[nodes_sorted, slot] = True
        self.delta[nodes_sorted, slot] = delta[order]
        self.pdb[nodes_sorted, slot] = pdb[order]
        objs = np.empty(b, object)
        objs[:] = list(pods)
        self.pods[nodes_sorted, slot] = objs[order]
        np.add.at(self.count, node_idx, 1)
        self._dev = None

    # -- row edits --------------------------------------------------------
    def snapshot(self, rows) -> list:
        return [(r, self.prio[r].copy(), self.valid[r].copy(),
                 self.delta[r].copy(), self.pdb[r].copy(), self.pods[r].copy(),
                 int(self.count[r])) for r in rows]

    def restore(self, saved: list) -> None:
        for r, prio, valid, delta, pdb, pods, count in saved:
            self.prio[r], self.valid[r], self.delta[r] = prio, valid, delta
            self.pdb[r], self.pods[r], self.count[r] = pdb, pods, count
            self._dirty.add(r)

    def has_room(self, ni: int) -> bool:
        return self.count[ni] < self.k

    def insert(self, ni: int, pod, prio: int, delta: np.ndarray,
               pdb: np.ndarray) -> None:
        """A pod bound last: after every slot of equal or higher priority."""
        c = int(self.count[ni])
        at = int(np.sum(self.prio[ni, :c] >= prio))
        for arr, val in ((self.prio, prio), (self.valid, True),
                         (self.delta, delta), (self.pdb, pdb),
                         (self.pods, pod)):
            arr[ni, at + 1:c + 1] = arr[ni, at:c]
            arr[ni, at] = val
        self.count[ni] = c + 1
        self._dirty.add(ni)

    def take(self, ni: int, victims: Sequence) -> np.ndarray:
        """Remove `victims` from row `ni`; -> their deltas f32[V,T] in the
        victims' order."""
        c = int(self.count[ni])
        where = {id(p): s for s, p in enumerate(self.pods[ni, :c])}
        slots = [where[id(v)] for v in victims]
        deltas = self.delta[ni, slots].copy()
        keep = np.ones(c, bool)
        keep[slots] = False
        m = int(keep.sum())
        for arr in (self.prio, self.valid, self.delta, self.pdb, self.pods):
            arr[ni, :m] = arr[ni, :c][keep]
        self.valid[ni, m:] = False
        self.pods[ni, m:] = None
        self.count[ni] = m
        self._dirty.add(ni)
        return deltas

    # -- the device copy ----------------------------------------------------
    def device(self, put) -> VictimTable:
        """The device table, current; `put` places host arrays (sharding)."""
        if self._dev is None or len(self._dirty) * 4 > self.prio.shape[0]:
            self._dev = put(VictimTable(self.prio, self.valid, self.delta, self.pdb))
        elif self._dirty:
            rows = np.array(sorted(self._dirty), np.int32)
            m = 1 << (len(rows) - 1).bit_length()
            rows = np.concatenate([rows, np.repeat(rows[:1], m - len(rows))])
            self._dev = write_rows(self._dev, rows, self.prio[rows],
                                   self.valid[rows], self.delta[rows],
                                   self.pdb[rows])
        self._dirty.clear()
        return self._dev

    def victims_in_order(self, ni: int, qidx: np.ndarray, vq: np.ndarray) -> List:
        """Victims of row `ni` in queue order."""
        return [self.pods[ni, s] for s, v in zip(qidx, vq) if v]


def table_rows_k(pods_alloc: np.ndarray, counts: np.ndarray) -> int:
    """Slots per node: a power of two covering every node's pod allocatable
    (at most 256) and its densest node now, plus one."""
    cap = int(min(int(pods_alloc.max(initial=0)), 256))
    need = max(cap, int(counts.max(initial=0)) + 1, 8)
    return 1 << (need - 1).bit_length()


def pdb_allowed_array(pdbs, healthy: Sequence[int]) -> np.ndarray:
    """i32[B] DisruptionsAllowed of each PDB at its current healthy count
    (a padded entry never violates)."""
    out = np.full(max(len(pdbs), 1), _I32_MAX // 2, np.int32)
    for i, pdb in enumerate(pdbs):
        out[i] = pdb.allowed_disruptions(int(healthy[i]))
    return out

