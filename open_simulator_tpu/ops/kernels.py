"""Device-side scheduling kernels: the pod×node Filter/Score/Select loop.

This is the TPU-native replacement for the reference's per-pod scheduleOne
cycle (`vendor/k8s.io/kubernetes/pkg/scheduler/core/generic_scheduler.go:131-175`,
16-goroutine fan-out at `internal/parallelize/parallelism.go:57`):

  - every Filter plugin is a vectorized boolean mask over all N nodes at once;
  - every Score plugin is an f32[N] kernel + its own normalize, combined by the
    profile's weights (`algorithmprovider/registry.go:71-148` defaults + the
    Simon plugin from `pkg/simulator/plugin/simon.go:45-101`);
  - host selection is a deterministic masked argmax (lowest node index wins
    ties — the reference's selectHost randomizes, we pin for reproducibility);
  - the sequential one-pod-at-a-time commit semantics of kube-scheduler are
    preserved by a `lax.scan` whose carry is the mutable cluster state
    (free resources + per-selector placement counts), so an entire pod batch
    schedules in ONE device computation with no host round-trips.

Everything here is shape-static and jit-safe; dynamic control flow is
expressed with lax.scan / jnp.where only.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .encode import (
    GPU_COUNT_IDX,
    OP_GT,
    OP_IN,
    OP_LT,
    OP_NOT_EXISTS,
    OP_NOT_IN,
    OP_PAD,
    OP_EXISTS,
)
from .sanitize import sanitizable

# Filter indices — order mirrors the kube filter plugin order so the
# first-failure reason attribution matches the reference's diagnostics.
F_UNSCHEDULABLE = 0
F_NODE_NAME = 1
F_TAINT = 2
F_NODE_AFFINITY = 3
F_NODE_PORTS = 4
F_RESOURCES = 5
F_SPREAD = 6
F_POD_AFFINITY = 7
F_STORAGE = 8
F_GPU = 9
F_EXTRA = 10  # out-of-tree device plugins (plugins/ registry)
NUM_FILTERS = 11

# Kube filter-plugin name -> filter index, for KubeSchedulerConfiguration
# enable/disable fidelity (utils.go:304-381 builds the full Filter plugin
# set; a user config may disable in-tree filters). Open-Local/Open-Gpu-Share
# are NOT listed: the reference injects them after the user config merge, so
# disabling them never takes effect (utils.go:337-347).
FILTER_PLUGIN_MAP = {
    "NodeUnschedulable": F_UNSCHEDULABLE,
    "NodeName": F_NODE_NAME,
    "TaintToleration": F_TAINT,
    "NodeAffinity": F_NODE_AFFINITY,
    "NodePorts": F_NODE_PORTS,
    "NodeResourcesFit": F_RESOURCES,
    "PodTopologySpread": F_SPREAD,
    "InterPodAffinity": F_POD_AFFINITY,
    # The volume filter family of the default provider — VolumeBinding,
    # VolumeRestrictions, NodeVolumeLimits (EBS/GCE/CSI/Azure), VolumeZone
    # (vendored algorithmprovider/registry.go:88-106) — is INERT in the
    # reference and therefore not implemented: MakeValidPod rewrites every
    # PVC volume to a hostPath volume before any pod reaches the scheduler
    # (utils.go:378-463, the `vol.PersistentVolumeClaim != nil` branch), so
    # those filters never see a PVC/bound-volume to act on, and open-local
    # storage runs through its own plugin instead (ops/kernels.py
    # local_storage_*). Config files naming them parse cleanly and their
    # enable/disable is a no-op, matching observable reference behavior.
}

FILTER_MESSAGES = (
    "node(s) were unschedulable",
    "node(s) didn't match the requested node name",
    "node(s) had taint that the pod didn't tolerate",
    "node(s) didn't match Pod's node affinity/selector",
    "node(s) didn't have free ports for the requested pod ports",
    "Insufficient resources",
    "node(s) didn't match pod topology spread constraints",
    "node(s) didn't match pod affinity/anti-affinity rules",
    "node(s) didn't have enough local storage",
    "node(s) didn't have enough free GPU memory",
    "node(s) were rejected by an out-of-tree filter plugin",
)

# Score weights, matching the default v1beta1 provider weights
# (SURVEY §2.2: registry.go:71-148) plus Simon at weight 1.
DEFAULT_WEIGHTS = {
    "balanced_allocation": 1.0,
    "least_allocated": 1.0,
    "node_affinity": 1.0,
    "taint_toleration": 1.0,
    "topology_spread": 2.0,
    "inter_pod_affinity": 1.0,
    "prefer_avoid_pods": 10000.0,
    "simon": 1.0,
    "gpu_share": 1.0,
    "open_local": 1.0,
}
# Fold order: the two carry-coupled terms come LAST (inter_pod_affinity,
# then topology_spread) so the fast paths' partial-sum prefix splits are
# exact left-fold prefixes (ops/fast.py: partial8 + w_ipa*ipa + w_sp*sp);
# node-local terms keep alphabetical order among themselves. Every path —
# naive scan, grouped, sort/micro/domain — folds in this one order, so the
# f32 summation (and every tie-break) stays internally consistent.
WEIGHT_ORDER = tuple(
    sorted(k for k in DEFAULT_WEIGHTS
           if k not in ("inter_pod_affinity", "topology_spread"))
) + ("inter_pod_affinity", "topology_spread")


def weights_array(weights: dict = DEFAULT_WEIGHTS) -> jnp.ndarray:
    return jnp.array([float(weights.get(k, 0.0)) for k in WEIGHT_ORDER], jnp.float32)


def combine_scores(by_name: dict, weights: jnp.ndarray, order=WEIGHT_ORDER):
    """Weighted score combination as an EXPLICIT left fold over `order`:
    ((w0*s0 + w1*s1) + w2*s2) + ... — every scheduling path (naive scan,
    grouped, light/sort/micro fast paths) uses this one function, so partial
    sums split exactly: fold(order) == fold(order[:-1]) + w_last*s_last by
    construction, with no reliance on XLA's reduce lowering."""
    total = None
    for i, k in enumerate(order):
        term = weights[i] * by_name[k]
        total = term if total is None else total + term
    return total


class NodeStatic(NamedTuple):
    """Immutable per-node tensors (device resident for a whole simulation)."""
    alloc: jnp.ndarray        # f32[N,R]
    label_pair: jnp.ndarray   # i32[N,L]
    label_key: jnp.ndarray    # i32[N,L]
    label_num: jnp.ndarray    # f32[N,L]
    taint_key: jnp.ndarray    # i32[N,T]
    taint_val: jnp.ndarray    # i32[N,T]
    taint_effect: jnp.ndarray  # i32[N,T]
    name_id: jnp.ndarray      # i32[N]
    unsched: jnp.ndarray      # bool[N]
    avoid_pods: jnp.ndarray   # bool[N]
    topo: jnp.ndarray         # i32[N,K] domain id or -1
    valid: jnp.ndarray        # bool[N]
    gpu_total: jnp.ndarray    # f32[N,G] per-device total GPU mem MiB (0=none)
    vg_cap: jnp.ndarray       # f32[N,V] open-local VG capacity MiB (0=pad)
    vg_name: jnp.ndarray      # i32[N,V] VG name id (0=pad)
    dev_cap: jnp.ndarray      # f32[N,DV] exclusive-device capacity MiB (0=pad)
    dev_ssd: jnp.ndarray      # bool[N,DV] device media is SSD
    has_storage: jnp.ndarray  # bool[N] node carries local storage
    domain_key: jnp.ndarray   # i32[D] topo-key index per domain id (-1 pad)
    topo_onehot: jnp.ndarray  # f32[K,D,N] domain membership (0 for missing key)
    unsched_key_id: jnp.ndarray  # i32 scalar: key id of node.kubernetes.io/unschedulable
    empty_val_id: jnp.ndarray    # i32 scalar: value id of ""
    anti_topo: jnp.ndarray    # i32[AT] topo-key index per registered required
                              # anti-affinity term (-1 pad) — IPA symmetry


class Carry(NamedTuple):
    """Mutable cluster state threaded through the scan."""
    free: jnp.ndarray        # f32[N,R]
    sel_counts: jnp.ndarray  # f32[S,N]
    gpu_free: jnp.ndarray    # f32[N,G] per-device free GPU mem MiB
                             # (tracks annotation pods only, like the
                             # reference's SchedulerCache)
    vg_free: jnp.ndarray     # f32[N,V] VG capacity - requested, MiB
    dev_free: jnp.ndarray    # f32[N,DV] 1.0 = device free, 0.0 = allocated
    port_any: jnp.ndarray    # f32[PID,N] host-port uses per (proto,port)
    port_wild: jnp.ndarray   # f32[PID,N] ... with wildcard hostIP only
    port_ipc: jnp.ndarray    # f32[PIP,N] uses per specific (proto,port,ip)
    anti_counts: jnp.ndarray  # f32[AT,N] placed pods carrying each
                              # required-anti-affinity term (IPA symmetry)


class PodRow(NamedTuple):
    """One pod's features (a slice of the PodBatch arrays)."""
    req: jnp.ndarray
    has_req: jnp.ndarray
    node_name_id: jnp.ndarray
    gpu_mem: jnp.ndarray
    gpu_num: jnp.ndarray
    sel_op: jnp.ndarray
    sel_key: jnp.ndarray
    sel_val: jnp.ndarray
    sel_num: jnp.ndarray
    has_terms: jnp.ndarray
    ns_pair: jnp.ndarray
    pref_weight: jnp.ndarray
    pref_op: jnp.ndarray
    pref_key: jnp.ndarray
    pref_val: jnp.ndarray
    pref_num: jnp.ndarray
    tol_key: jnp.ndarray
    tol_val: jnp.ndarray
    tol_exists: jnp.ndarray
    tol_effect: jnp.ndarray
    tol_valid: jnp.ndarray
    spread_topo: jnp.ndarray
    spread_sel: jnp.ndarray
    spread_skew: jnp.ndarray
    spread_hard: jnp.ndarray
    aff_topo: jnp.ndarray
    aff_sel: jnp.ndarray
    aff_anti: jnp.ndarray
    aff_required: jnp.ndarray
    aff_weight: jnp.ndarray
    lvm_req: jnp.ndarray
    lvm_vg: jnp.ndarray
    dev_req: jnp.ndarray
    dev_media_ssd: jnp.ndarray
    has_local: jnp.ndarray
    match_sel: jnp.ndarray
    owned_by_rs: jnp.ndarray
    hp_pid: jnp.ndarray
    hp_wild: jnp.ndarray
    hp_ipid: jnp.ndarray
    match_anti: jnp.ndarray
    own_anti: jnp.ndarray
    valid: jnp.ndarray


_EPS = 1e-3  # absolute slack for f32 resource comparisons (units: milli / MiB)


# ---------------------------------------------------------------------------
# node-selector term matching (shared by NodeAffinity filter + score)
# ---------------------------------------------------------------------------

def _expr_matches(ns: NodeStatic, op, key, val, num):
    """One expression vs all nodes. op/key scalar, val i32[VAL]. -> bool[N]"""
    has_key = jnp.any((ns.label_key == key) & (key != 0), axis=1)          # [N]
    pair_hit = jnp.any(
        (ns.label_pair[:, :, None] == val[None, None, :]) & (val != 0)[None, None, :],
        axis=(1, 2),
    )                                                                       # [N]
    key_rows = ns.label_key == key                                          # [N,L]
    gt = jnp.any(key_rows & (ns.label_num > num), axis=1)
    lt = jnp.any(key_rows & (ns.label_num < num), axis=1)
    return jnp.select(
        [op == OP_IN, op == OP_NOT_IN, op == OP_EXISTS, op == OP_NOT_EXISTS,
         op == OP_GT, op == OP_LT],
        [pair_hit, ~pair_hit, has_key, ~has_key, gt, lt],
        default=jnp.ones_like(has_key),  # OP_PAD: neutral inside an AND
    )


def _term_matches(ns: NodeStatic, ops, keys, vals, nums):
    """One term (AND of EXPR expressions) vs all nodes -> bool[N].
    A term with no real expressions matches nothing (upstream semantics)."""
    per_expr = jax.vmap(
        lambda o, k, v, n: _expr_matches(ns, o, k, v, n),
        in_axes=(0, 0, 0, 0),
        out_axes=1,
    )(ops, keys, vals, nums)                                  # [N,EXPR]
    non_empty = jnp.any(ops != OP_PAD)
    return jnp.all(per_expr, axis=1) & non_empty


def node_affinity_mask(ns: NodeStatic, pod: PodRow) -> jnp.ndarray:
    """NodeAffinity filter: plain nodeSelector AND required affinity terms
    (OR over terms). Parity: plugins/nodeaffinity + nodeSelector matching."""
    # nodeSelector: every listed pair must be present on the node
    wanted = pod.ns_pair                                       # [NS]
    present = jnp.any(
        ns.label_pair[:, :, None] == wanted[None, None, :], axis=1
    )                                                          # [N,NS]
    ns_ok = jnp.all(present | (wanted == 0)[None, :], axis=1)  # [N]
    term_hits = jax.vmap(
        lambda o, k, v, n: _term_matches(ns, o, k, v, n),
        in_axes=(0, 0, 0, 0),
        out_axes=1,
    )(pod.sel_op, pod.sel_key, pod.sel_val, pod.sel_num)       # [N,TERM]
    terms_ok = jnp.any(term_hits, axis=1) | ~pod.has_terms
    return ns_ok & terms_ok


def taint_mask(ns: NodeStatic, pod: PodRow) -> jnp.ndarray:
    """TaintToleration filter: every NoSchedule/NoExecute taint tolerated."""
    tk, tv, te = ns.taint_key, ns.taint_val, ns.taint_effect   # [N,T]
    # toleration axis -> [N,T,TOL]
    eff_ok = (pod.tol_effect[None, None, :] == 0) | (pod.tol_effect[None, None, :] == te[:, :, None])
    key_ok = (pod.tol_key[None, None, :] == 0) | (pod.tol_key[None, None, :] == tk[:, :, None])
    val_ok = pod.tol_exists[None, None, :] | (pod.tol_val[None, None, :] == tv[:, :, None])
    tolerated = jnp.any(
        pod.tol_valid[None, None, :] & eff_ok & key_ok & val_ok, axis=2
    )                                                          # [N,T]
    hard = (te == 1) | (te == 3)                               # NoSchedule/NoExecute
    return jnp.all(tolerated | ~hard, axis=1)


HOSTNAME_KEY_IDX = 0  # Encoder pins kubernetes.io/hostname at topo index 0


def _domain_counts(
    ns: NodeStatic,
    counts_node: jnp.ndarray,
    k: jnp.ndarray,
    elig: jnp.ndarray = None,
):
    """Per-domain sums + their per-node broadcast for topology key k.

    Two representations (TPU scatters serialize, so neither path scatters):
      - hostname (k==0): domains ≡ nodes 1:1, so the per-node count IS the
        input and no [D,N] matrix is ever materialized (a dense one-hot for
        hostname would be O(N²) memory).
      - low-cardinality keys (zone/region/...): matvec against the precomputed
        one-hot membership (f32-exact precision — bf16 MXU rounding would
        corrupt integer counts above 256), then an exact gather back to nodes.

    `elig` bool[N] restricts which nodes participate (PodTopologySpread counts
    and min only consider nodes passing the pod's node affinity/selector —
    vendored podtopologyspread/common.go calPreFilterState skips other nodes);
    None means all valid nodes.

    Returns (dom f32[D] — hostname slot returns zeros, use the host outputs —,
    cnt_n f32[N], min_count f32, total f32) where min_count is the minimum
    count over eligible domains of key k and total the sum over them."""
    elig = ns.valid if elig is None else (elig & ns.valid)
    counts = jnp.where(elig, counts_node, 0.0)
    is_host = k == HOSTNAME_KEY_IDX

    onehot = ns.topo_onehot[k]                                  # [D,N]
    dom = jax.lax.dot_general(
        onehot, counts, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )                                                           # [D]
    dom_elig = jax.lax.dot_general(
        onehot, elig.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    ) > 0.0                                                     # [D]
    topo_k = ns.topo[:, k]
    D = dom.shape[0]
    cnt_gather = jnp.where(
        topo_k >= 0, dom[jnp.clip(topo_k, 0, D - 1)], 0.0
    )
    cnt_n = jnp.where(is_host, counts, cnt_gather)

    in_key = (ns.domain_key == k) & dom_elig                    # [D]
    min_dom = jnp.min(jnp.where(in_key, dom, jnp.inf))
    min_host = jnp.min(jnp.where(elig, counts_node, jnp.inf))
    min_count = jnp.where(is_host, min_host, min_dom)
    min_count = jnp.where(jnp.isfinite(min_count), min_count, 0.0)

    total = jnp.where(is_host, jnp.sum(counts), jnp.sum(jnp.where(in_key, dom, 0.0)))
    return dom, cnt_n, min_count, total


def spread_mask(
    ns: NodeStatic, carry: Carry, pod: PodRow, na_ok: jnp.ndarray = None
) -> jnp.ndarray:
    """PodTopologySpread hard constraints.

    skew(node) = count(domain(node)) + 1 - min over eligible domains of the
    topology key, where eligibility (`na_ok`, defaults to recomputing the
    pod's node affinity/selector) restricts both the counts and the min —
    matching calPreFilterState, which skips nodes failing the pod's
    nodeSelector/required node affinity entirely."""
    elig = node_affinity_mask(ns, pod) if na_ok is None else na_ok

    def one(topo_idx, sel_idx, max_skew, hard):
        active = (topo_idx >= 0) & hard
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0                            # [N]
        _, cnt_n, min_count, _ = _domain_counts(
            ns, carry.sel_counts[sel_idx], k, elig
        )
        ok = (cnt_n + 1.0 - min_count) <= max_skew + _EPS
        ok = ok & has_key
        return jnp.where(active, ok, jnp.ones_like(ok))

    per_c = jax.vmap(one, in_axes=(0, 0, 0, 0), out_axes=1)(
        pod.spread_topo, pod.spread_sel, pod.spread_skew, pod.spread_hard
    )                                                           # [N,C]
    return jnp.all(per_c, axis=1)


def pod_affinity_mask(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """InterPodAffinity required terms.

    affinity: candidate node's domain must already hold a matching pod — OR the
    incoming pod matches its own selector and no match exists anywhere (the
    upstream first-pod-of-a-group special case).
    anti-affinity: candidate node's domain must hold none.
    symmetry: existing pods' required anti-affinity repels matching incomers —
    for every registered anti term (ns.anti_topo) the pod's labels match
    (pod.match_anti), domains already holding a carrier (carry.anti_counts)
    are infeasible (the vendored plugin's existingAntiAffinityCounts).
    """

    def one(topo_idx, sel_idx, anti, required):
        active = (topo_idx >= 0) & required
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0
        _, cnt, _, total = _domain_counts(ns, carry.sel_counts[sel_idx], k)
        self_match = pod.match_sel[sel_idx]
        aff_ok = (cnt > 0) | (self_match & (total == 0))
        aff_ok = aff_ok & has_key
        anti_ok = cnt == 0
        ok = jnp.where(anti, anti_ok, aff_ok)
        return jnp.where(active, ok, jnp.ones(ns.valid.shape, bool))

    per_a = jax.vmap(one, in_axes=(0, 0, 0, 0), out_axes=1)(
        pod.aff_topo, pod.aff_sel, pod.aff_anti, pod.aff_required
    )

    def one_sym(topo_idx, cnt_row, match):
        active = (topo_idx >= 0) & match
        k = jnp.maximum(topo_idx, 0)
        has_key = ns.topo[:, k] >= 0
        _, cnt, _, _ = _domain_counts(ns, cnt_row, k)
        ok = (cnt == 0) | ~has_key
        return jnp.where(active, ok, jnp.ones(ns.valid.shape, bool))

    per_sym = jax.vmap(one_sym, in_axes=(0, 0, 0), out_axes=1)(
        ns.anti_topo, carry.anti_counts, pod.match_anti
    )                                                           # [N,AT]
    return jnp.all(per_a, axis=1) & jnp.all(per_sym, axis=1)


# ---------------------------------------------------------------------------
# Open-Gpu-Share: per-device GPU memory packing
# (parity: pkg/simulator/plugin/open-gpu-share.go + AllocateGpuId,
#  pkg/type/open-gpu-share/cache/gpunodeinfo.go:232-290)
# ---------------------------------------------------------------------------

def _gpu_device_caps(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """floor(free_d / mem) shares each device can still hold -> f32[N,G]."""
    mem = jnp.maximum(pod.gpu_mem, 1e-9)
    caps = jnp.floor((carry.gpu_free + _EPS) / mem)
    return jnp.where(ns.gpu_total > 0, caps, 0.0)


def allocatable_gpus(ns: NodeStatic, carry: Carry) -> jnp.ndarray:
    """Number of not-fully-used devices per node -> f32[N]. This is the
    DYNAMIC value the reference writes back into node allocatable
    `alibabacloud.com/gpu-count` on every Reserve (open-gpu-share.go:183-190):
    GpuAllocatable = gpuCount - #(used >= total), so a partially-used device
    still counts (gpunodeinfo.go:355-362)."""
    usable = (carry.gpu_free > _EPS) & (ns.gpu_total > 0)
    return jnp.sum(usable.astype(jnp.float32), axis=1)


def gpu_mask(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """Open-Gpu-Share Filter: non-GPU pods pass everywhere; GPU pods need a
    feasible device packing. The two-pointer greedy of AllocateGpuId succeeds
    iff sum_d floor(free_d/mem) >= num (it never strands capacity: a device is
    only abandoned when it can't hold another share)."""
    is_gpu = pod.gpu_mem > 0
    caps = _gpu_device_caps(ns, carry, pod)
    feasible = (pod.gpu_num >= 1) & (jnp.sum(caps, axis=1) >= pod.gpu_num)
    return jnp.where(is_gpu, feasible, jnp.ones_like(feasible))


def gpu_allocate_rowwise(
    ns: NodeStatic, gpu_free: jnp.ndarray, pod: PodRow
) -> jnp.ndarray:
    """gpu_allocate's take, evaluated on EVERY node independently -> f32[N,G].

    Row n is bit-identical to `gpu_allocate(..., onehot=e_n)[0]`: the einsum
    projection there extracts the row exactly (one 1.0 times f32 values), and
    every subsequent op here is the same op applied along axis 1."""
    mem = pod.gpu_mem
    free_d = gpu_free                                    # [N,G]
    total_d = ns.gpu_total
    G = free_d.shape[1]

    elig = (total_d > 0) & (free_d >= mem - _EPS)
    tight = jnp.argmin(jnp.where(elig, free_d, jnp.inf), axis=1)    # [N]
    take_single = (
        (jnp.arange(G)[None, :] == tight[:, None]) & jnp.any(elig, axis=1)[:, None]
    ).astype(jnp.float32)

    caps = jnp.where(
        total_d > 0, jnp.floor((free_d + _EPS) / jnp.maximum(mem, 1e-9)), 0.0
    )
    prefix = jnp.cumsum(caps, axis=1) - caps
    take_multi = jnp.clip(pod.gpu_num - prefix, 0.0, caps)
    take_multi = jnp.where(
        (jnp.sum(caps, axis=1) >= pod.gpu_num)[:, None], take_multi, 0.0
    )

    take = jnp.where(pod.gpu_num == 1, take_single, take_multi)
    return jnp.where((mem > 0) & (pod.gpu_num >= 1), take, 0.0)


def gpu_allocate(
    ns: NodeStatic, carry: Carry, pod: PodRow, node_onehot: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Allocate devices on the selected node -> (take f32[G] shares per device,
    new gpu_free f32[N,G]).

    num == 1: tightest fit — the device with the least free memory that still
    fits, ties to the lowest id (gpunodeinfo.go:256-270, strict `<` keeps the
    earlier candidate).
    num > 1: the two-pointer greedy packs shares onto the lowest-id devices
    first, reusing a device while it fits (gpunodeinfo.go:271-286); that is
    exactly take_d = clip(num - prefix_d, 0, cap_d) with prefix the exclusive
    cumsum of caps."""
    sel = node_onehot.astype(jnp.float32)
    free_d = jnp.einsum("n,ng->g", sel, carry.gpu_free)
    total_d = jnp.einsum("n,ng->g", sel, ns.gpu_total)
    mem = pod.gpu_mem

    elig = (total_d > 0) & (free_d >= mem - _EPS)
    tight = jnp.argmin(jnp.where(elig, free_d, jnp.inf))
    take_single = (
        (jnp.arange(free_d.shape[0]) == tight) & jnp.any(elig)
    ).astype(jnp.float32)

    caps = jnp.where(
        total_d > 0, jnp.floor((free_d + _EPS) / jnp.maximum(mem, 1e-9)), 0.0
    )
    prefix = jnp.cumsum(caps) - caps
    take_multi = jnp.clip(pod.gpu_num - prefix, 0.0, caps)
    take_multi = jnp.where(jnp.sum(caps) >= pod.gpu_num, take_multi, 0.0)

    take = jnp.where(pod.gpu_num == 1, take_single, take_multi)
    take = jnp.where((mem > 0) & (pod.gpu_num >= 1), take, 0.0)
    gpu_free = carry.gpu_free - sel[:, None] * take[None, :] * mem
    return take, gpu_free


# ---------------------------------------------------------------------------
# Open-Local: LVM volume-group binpack + exclusive-device allocation
# (parity: pkg/simulator/plugin/open-local.go + the vendored algorithms at
#  vendor/github.com/alibaba/open-local/pkg/scheduler/algorithm/algo/common.go —
#  ProcessLVMPVCPredicate :59, ProcessDevicePVC :394, ScoreLVM :660,
#  ScoreDevice :753, and the Bind-side commit open-local.go:175-254)
# ---------------------------------------------------------------------------

def local_storage_eval(ns: NodeStatic, carry: Carry, pod: PodRow):
    """Simulate this pod's storage allocation on EVERY node at once.

    Returns (ok bool[N], vg_take f32[N,V] MiB claimed per VG, dev_take
    f32[N,DV] one-hot devices claimed, raw_score f32[N] — the plugin's
    pre-normalize 0..20 score).

    LVM volumes without an explicit VG follow the default Binpack strategy:
    each request goes to the VG with the least free space that still fits
    (common.go:575-618 sorts ascending and takes the first fit; ties break to
    the lowest VG index here where Go's unstable sort is arbitrary). Explicit
    VG requests must fit that VG (common.go:59-96). Device volumes take the
    smallest free device of the right media type whose capacity covers the
    request — the ascending device walk of CheckExclusiveResourceMeetsPVCSize
    (common.go:290-350) picks exactly that device for requests sorted
    ascending, which the encoder guarantees.
    """
    N, V = ns.vg_cap.shape
    DV = ns.dev_cap.shape[1]
    SV = pod.lvm_req.shape[0]

    def lvm_slot(state, s):
        vg_free, vg_take, ok = state
        req = pod.lvm_req[s]
        active = req > 0
        want = pod.lvm_vg[s]
        fits = (vg_free + _EPS >= req) & (ns.vg_name != 0)       # [N,V]
        elig = jnp.where(want != 0, fits & (ns.vg_name == want), fits)
        free_key = jnp.where(elig, vg_free, jnp.inf)
        choice = jnp.argmin(free_key, axis=1)                     # [N]
        any_elig = jnp.any(elig, axis=1)
        onehot = (
            (jnp.arange(V)[None, :] == choice[:, None])
            & any_elig[:, None]
            & active
        ).astype(jnp.float32)
        return (
            vg_free - onehot * req,
            vg_take + onehot * req,
            ok & (any_elig | ~active),
        ), None

    (_, vg_take, lvm_ok), _ = jax.lax.scan(
        lvm_slot,
        (carry.vg_free, jnp.zeros_like(carry.vg_free), jnp.ones(N, bool)),
        jnp.arange(SV),
    )

    def dev_slot(state, s):
        avail, dev_take, frac_sum, ok = state
        req = pod.dev_req[s]
        active = req > 0
        elig = (
            (avail > 0.5)
            & (ns.dev_ssd == pod.dev_media_ssd[s])
            & (ns.dev_cap + _EPS >= req)
            & (ns.dev_cap > 0)
        )                                                          # [N,DV]
        cap_key = jnp.where(elig, ns.dev_cap, jnp.inf)
        choice = jnp.argmin(cap_key, axis=1)
        any_elig = jnp.any(elig, axis=1)
        onehot = (
            (jnp.arange(DV)[None, :] == choice[:, None])
            & any_elig[:, None]
            & active
        ).astype(jnp.float32)
        cap_chosen = jnp.sum(onehot * ns.dev_cap, axis=1)          # [N]
        frac_sum = frac_sum + jnp.where(
            any_elig & active, req / jnp.maximum(cap_chosen, 1e-9), 0.0
        )
        return (
            avail - onehot,
            dev_take + onehot,
            frac_sum,
            ok & (any_elig | ~active),
        ), None

    (_, dev_take, dev_frac_sum, dev_ok), _ = jax.lax.scan(
        dev_slot,
        (
            carry.dev_free,
            jnp.zeros_like(carry.dev_free),
            jnp.zeros(N, jnp.float32),
            jnp.ones(N, bool),
        ),
        jnp.arange(SV),
    )

    ok = jnp.where(
        pod.has_local,
        lvm_ok & dev_ok & ns.has_storage,
        jnp.ones(N, bool),
    )

    # ScoreLVM (Binpack): mean over the VGs this pod uses of used/capacity,
    # ×10, floor'd (common.go:660-684). ScoreDevice: mean over units of
    # requested/allocated-capacity, ×10, floor'd (common.go:753-762). The
    # plugin returns their sum (open-local.go:136) before its min-max
    # NormalizeScore maps the batch to 0..100 (open-local.go:145-170).
    used = vg_take > 0
    vg_frac = jnp.where(used, vg_take / jnp.maximum(ns.vg_cap, 1e-9), 0.0)
    lvm_cnt = jnp.sum(used.astype(jnp.float32), axis=1)
    lvm_score = jnp.floor(
        jnp.where(
            lvm_cnt > 0,
            jnp.sum(vg_frac, axis=1) / jnp.maximum(lvm_cnt, 1.0) * 10.0,
            0.0,
        )
    )
    dev_cnt = jnp.sum((pod.dev_req > 0).astype(jnp.float32))
    dev_score = jnp.floor(
        jnp.where(dev_cnt > 0, dev_frac_sum / jnp.maximum(dev_cnt, 1.0) * 10.0, 0.0)
    )
    raw = jnp.where(ok & pod.has_local, lvm_score + dev_score, 0.0)
    return ok, vg_take, dev_take, raw


def local_storage_mask(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    ok, _, _, _ = local_storage_eval(ns, carry, pod)
    return ok


def score_open_local(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """Open-Local Score + its NormalizeScore. Pods without storage volumes get
    MinScore everywhere (open-local.go:113-119), which normalizes to 0."""
    _, _, _, raw = local_storage_eval(ns, carry, pod)
    return jnp.where(pod.has_local, _minmax_normalize(raw, ns.valid), 0.0)


def local_storage_commit(
    ns: NodeStatic, carry: Carry, pod: PodRow, node_onehot: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Commit the chosen node's storage allocation (the Bind-side annotation
    rewrite, open-local.go:221-247): VG requested += size, device allocated.

    Returns (vg_free f32[N,V], dev_free f32[N,DV], vg_take f32[V], dev_take
    f32[DV]) — the takes are the selected node's slice, recorded per pod so an
    eviction can reverse the allocation exactly."""
    _, vg_take_all, dev_take_all, _ = local_storage_eval(ns, carry, pod)
    sel = node_onehot.astype(jnp.float32)
    vg_take = jnp.einsum("n,nv->v", sel, vg_take_all)
    dev_take = jnp.einsum("n,nd->d", sel, dev_take_all)
    return (
        carry.vg_free - sel[:, None] * vg_take_all,
        carry.dev_free - sel[:, None] * dev_take_all,
        vg_take,
        dev_take,
    )


def ports_mask(carry: Carry, pod: PodRow) -> jnp.ndarray:
    """NodePorts filter (vendored plugins/nodeports): a requested host port
    conflicts on a node iff the same (protocol, port) is already used there
    with an overlapping hostIP — wildcard overlaps everything, specific IPs
    only themselves. Row 0 of the count tables is the pad row (all zeros), so
    padded hp slots are harmless; the explicit pid>0 guard keeps them inert
    even after carry updates."""
    any_tbl = carry.port_any[pod.hp_pid]                       # [HP,N]
    wild_tbl = carry.port_wild[pod.hp_pid]                     # [HP,N]
    ip_tbl = carry.port_ipc[pod.hp_ipid]                       # [HP,N]
    conf_wild = any_tbl > 0.0
    conf_spec = (wild_tbl > 0.0) | (ip_tbl > 0.0)
    conf = jnp.where(pod.hp_wild[:, None], conf_wild, conf_spec)
    conf = conf & (pod.hp_pid > 0)[:, None]
    return ~jnp.any(conf, axis=0)


def port_adds(pid_rows: int, pip_rows: int, pod: PodRow):
    """Per-commit increments to the port count tables for one pod ->
    (add_any f32[PID], add_wild f32[PID], add_ipc f32[PIP])."""
    active = (pod.hp_pid > 0).astype(jnp.float32)              # [HP]
    add_any = jnp.zeros(pid_rows, jnp.float32).at[pod.hp_pid].add(
        active, mode="drop"
    )
    add_wild = jnp.zeros(pid_rows, jnp.float32).at[pod.hp_pid].add(
        active * pod.hp_wild.astype(jnp.float32), mode="drop"
    )
    add_ipc = jnp.zeros(pip_rows, jnp.float32).at[pod.hp_ipid].add(
        active * (~pod.hp_wild).astype(jnp.float32) * (pod.hp_ipid > 0), mode="drop"
    )
    # never count into the pad row — keep row 0 identically zero
    add_any = add_any.at[0].set(0.0)
    add_wild = add_wild.at[0].set(0.0)
    add_ipc = add_ipc.at[0].set(0.0)
    return add_any, add_wild, add_ipc


def ports_commit(carry: Carry, pod: PodRow, onehot: jnp.ndarray):
    """Record the committed pod's host ports into the selected node's counts.
    Returns (port_any, port_wild, port_ipc). The HP-sized scatters serialize
    on device but HP is tiny (max ports per pod)."""
    sel = onehot.astype(jnp.float32)                           # [N]
    add_any, add_wild, add_ipc = port_adds(
        carry.port_any.shape[0], carry.port_ipc.shape[0], pod
    )
    return (
        carry.port_any + add_any[:, None] * sel[None, :],
        carry.port_wild + add_wild[:, None] * sel[None, :],
        carry.port_ipc + add_ipc[:, None] * sel[None, :],
    )


def resource_fail(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """NodeResourcesFit failure -> bool[N]. The whole-GPU extended resource
    (alibabacloud.com/gpu-count) is checked against its DYNAMIC allocatable —
    the number of not-fully-used devices minus already-committed whole-GPU
    requests — because the reference rewrites that allocatable on every
    Reserve (open-gpu-share.go:183-190)."""
    static_fail = jnp.any(pod.req[None, :] > carry.free + _EPS, axis=1)
    whole_req = pod.req[GPU_COUNT_IDX]
    whole_used = ns.alloc[:, GPU_COUNT_IDX] - carry.free[:, GPU_COUNT_IDX]
    whole_fail = whole_req > allocatable_gpus(ns, carry) - whole_used + _EPS
    return static_fail | whole_fail


def run_filters(
    ns: NodeStatic, carry: Carry, pod: PodRow, filter_on=None, extra_filters=()
):
    """All filter plugins -> (mask bool[N], first_fail i32[N]).

    first_fail is the index of the first failing filter per node (kube stops a
    node's filter chain at the first failure), or NUM_FILTERS when feasible.
    `filter_on` (bool[NUM_FILTERS] or None = all on) disables filter plugins
    per the scheduler profile: a disabled filter never fails a node.
    `extra_filters` is the out-of-tree registry (plugins/): jax-traceable
    `f(ns, carry, pod) -> bool[N]` predicates AND-ed into the F_EXTRA slot
    (the extraRegistry analog, simulator.go:190-203).
    """
    # NodeUnschedulable filter admits pods tolerating the synthetic
    # node.kubernetes.io/unschedulable:NoSchedule taint (plugin parity);
    # Equal with empty value tolerates it too (taint value is "").
    unsched_tolerated = jnp.any(
        pod.tol_valid
        & ((pod.tol_key == 0) | (pod.tol_key == ns.unsched_key_id))
        & (pod.tol_exists | (pod.tol_val == ns.empty_val_id))
        & ((pod.tol_effect == 0) | (pod.tol_effect == 1)),
    )
    na_ok = node_affinity_mask(ns, pod)
    extra_fail = jnp.zeros(ns.valid.shape[0], bool)
    for f in extra_filters:
        extra_fail = extra_fail | ~f(ns, carry, pod)
    fails = jnp.stack(
        [
            ns.unsched & ~unsched_tolerated,
            (pod.node_name_id != 0) & (ns.name_id != pod.node_name_id),
            ~taint_mask(ns, pod),
            ~na_ok,
            ~ports_mask(carry, pod),
            resource_fail(ns, carry, pod),
            ~spread_mask(ns, carry, pod, na_ok),
            ~pod_affinity_mask(ns, carry, pod),
            ~local_storage_mask(ns, carry, pod),
            ~gpu_mask(ns, carry, pod),
            extra_fail,
        ],
        axis=1,
    )                                                           # [N,F]
    if filter_on is not None:
        fails = fails & filter_on[None, :]
    mask = ~jnp.any(fails, axis=1) & ns.valid
    first_fail = jnp.where(
        jnp.any(fails, axis=1), jnp.argmax(fails, axis=1), NUM_FILTERS
    )
    return mask, first_fail


# ---------------------------------------------------------------------------
# Score plugins
# ---------------------------------------------------------------------------

def _minmax_normalize(score: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Simon's NormalizeScore (simon.go:76-101): min-max to 0..100; constant
    scores collapse to 0."""
    lo = jnp.min(jnp.where(valid, score, jnp.inf))
    hi = jnp.max(jnp.where(valid, score, -jnp.inf))
    rng = hi - lo
    out = jnp.where(rng > 0, (score - lo) * 100.0 / jnp.maximum(rng, 1e-9), 0.0)
    # Exact no-op for valid lanes (fl((score-lo)*100/rng) <= 100 by monotone
    # rounding when score <= hi); pins invalid lanes so the plugin contract
    # score in [0,100] (framework's checkPluginScores) holds for every lane.
    return jnp.clip(out, 0.0, 100.0)


def score_least_allocated(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """NodeResourcesLeastAllocated over cpu+memory (dims 0,1 by construction)."""
    alloc = ns.alloc[:, :2]
    free_after = carry.free[:, :2] - pod.req[None, :2]
    frac = jnp.where(alloc > 0, free_after / jnp.maximum(alloc, 1e-9), 0.0)
    return jnp.clip(jnp.mean(frac, axis=1), 0.0, 1.0) * 100.0


def score_balanced(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """NodeResourcesBalancedAllocation: 100 - |cpuFrac - memFrac|*100."""
    alloc = ns.alloc[:, :2]
    used_after = ns.alloc[:, :2] - carry.free[:, :2] + pod.req[None, :2]
    frac = jnp.where(alloc > 0, used_after / jnp.maximum(alloc, 1e-9), 0.0)
    frac = jnp.clip(frac, 0.0, 1.0)
    return (1.0 - jnp.abs(frac[:, 0] - frac[:, 1])) * 100.0


def score_simon(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """Simon worst-fit score (simon.go:45-68): max over resources of
    share(req, allocatable - req), truncated to int, then min-max normalized.
    Note the reference deliberately uses static allocatable, not current free.
    """
    req = pod.req[None, :]                       # [1,R]
    avail = ns.alloc - req                       # [N,R]
    share = jnp.where(
        req == 0,
        0.0,
        jnp.where(avail == 0, 1.0, req / jnp.where(avail == 0, 1.0, avail)),
    )
    share = jnp.where(avail < 0, 1.0, share)     # negative headroom: saturate
    raw = jnp.floor(jnp.max(share, axis=1) * 100.0)
    raw = jnp.where(pod.has_req, raw, 100.0)     # empty requests => MaxNodeScore
    return _minmax_normalize(raw, ns.valid)


def score_taint_toleration(ns: NodeStatic, pod: PodRow) -> jnp.ndarray:
    """TaintToleration score: fewer intolerable PreferNoSchedule taints is
    better; reverse-normalized like plugin DefaultNormalizeScore(reverse)."""
    tk, tv, te = ns.taint_key, ns.taint_val, ns.taint_effect
    eff_ok = (pod.tol_effect[None, None, :] == 0) | (pod.tol_effect[None, None, :] == te[:, :, None])
    key_ok = (pod.tol_key[None, None, :] == 0) | (pod.tol_key[None, None, :] == tk[:, :, None])
    val_ok = pod.tol_exists[None, None, :] | (pod.tol_val[None, None, :] == tv[:, :, None])
    tolerated = jnp.any(pod.tol_valid[None, None, :] & eff_ok & key_ok & val_ok, axis=2)
    cnt = jnp.sum(((te == 2) & ~tolerated).astype(jnp.float32), axis=1)
    max_cnt = jnp.max(jnp.where(ns.valid, cnt, 0.0))
    return jnp.clip(
        jnp.where(max_cnt > 0, (max_cnt - cnt) * 100.0 / jnp.maximum(max_cnt, 1e-9), 100.0),
        0.0,
        100.0,
    )


def score_node_affinity(ns: NodeStatic, pod: PodRow) -> jnp.ndarray:
    """NodeAffinity score: sum of matching preferred term weights, normalized
    by the max (DefaultNormalizeScore)."""
    hits = jax.vmap(
        lambda o, k, v, n: _term_matches(ns, o, k, v, n),
        in_axes=(0, 0, 0, 0),
        out_axes=1,
    )(pod.pref_op, pod.pref_key, pod.pref_val, pod.pref_num)    # [N,PREF]
    raw = jnp.sum(hits * pod.pref_weight[None, :], axis=1)
    mx = jnp.max(jnp.where(ns.valid, raw, 0.0))
    return jnp.clip(
        jnp.where(mx > 0, raw * 100.0 / jnp.maximum(mx, 1e-9), 0.0), 0.0, 100.0
    )


def score_prefer_avoid(ns: NodeStatic, pod: PodRow) -> jnp.ndarray:
    """NodePreferAvoidPods: 0 on annotated nodes for RS/RC-owned pods."""
    avoided = ns.avoid_pods & pod.owned_by_rs
    return jnp.where(avoided, 0.0, 100.0)


def score_topology_spread(
    ns: NodeStatic, carry: Carry, pod: PodRow, na_ok: jnp.ndarray = None
) -> jnp.ndarray:
    """PodTopologySpread soft constraints: lower matching-count domains score
    higher (reverse-normalized sum over ScheduleAnyway constraints). Counting
    only spans nodes passing the pod's node affinity/selector, like the
    upstream PreScore (scoring.go:146-149)."""
    elig = node_affinity_mask(ns, pod) if na_ok is None else na_ok

    def one(topo_idx, sel_idx, hard):
        active = (topo_idx >= 0) & ~hard
        k = jnp.maximum(topo_idx, 0)
        _, cnt, _, _ = _domain_counts(ns, carry.sel_counts[sel_idx], k, elig)
        return jnp.where(active, cnt, 0.0)

    raw = jnp.sum(
        jax.vmap(one, in_axes=(0, 0, 0), out_axes=1)(
            pod.spread_topo, pod.spread_sel, pod.spread_hard
        ),
        axis=1,
    )
    mx = jnp.max(jnp.where(ns.valid, raw, 0.0))
    return jnp.clip(
        jnp.where(mx > 0, (mx - raw) * 100.0 / jnp.maximum(mx, 1e-9), 100.0),
        0.0,
        100.0,
    )


def score_inter_pod_affinity(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """InterPodAffinity preferred terms: +weight per matching pod in domain for
    affinity, -weight for anti-affinity; min-max normalized to 0..100."""

    def one(topo_idx, sel_idx, anti, required, weight):
        active = (topo_idx >= 0) & ~required
        k = jnp.maximum(topo_idx, 0)
        _, cnt, _, _ = _domain_counts(ns, carry.sel_counts[sel_idx], k)
        signed = jnp.where(anti, -weight, weight) * cnt
        return jnp.where(active, signed, 0.0)

    raw = jnp.sum(
        jax.vmap(one, in_axes=(0, 0, 0, 0, 0), out_axes=1)(
            pod.aff_topo, pod.aff_sel, pod.aff_anti, pod.aff_required, pod.aff_weight
        ),
        axis=1,
    )
    any_active = jnp.any((pod.aff_topo >= 0) & ~pod.aff_required)
    normalized = _minmax_normalize(raw, ns.valid)
    return jnp.where(any_active, normalized, 0.0)


def gpu_share_raw(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """Open-Gpu-Share raw score before its NormalizeScore -> f32[N]."""
    req = pod.req[None, :]                                    # [1,R]
    alloc = ns.alloc
    R = alloc.shape[1]
    dyn = allocatable_gpus(ns, carry)                          # [N]
    alloc = jnp.where(
        (jnp.arange(R) == GPU_COUNT_IDX)[None, :], dyn[:, None], alloc
    )
    avail = alloc - req
    share = jnp.where(
        req == 0,
        0.0,
        jnp.where(avail == 0, 1.0, req / jnp.where(avail == 0, 1.0, avail)),
    )
    share = jnp.where(avail < 0, 1.0, share)
    raw = jnp.max(share, axis=1) * 100.0
    return jnp.where(pod.has_req, raw, 100.0)                 # empty req => Max


def score_gpu_share(ns: NodeStatic, carry: Carry, pod: PodRow) -> jnp.ndarray:
    """Open-Gpu-Share Score (open-gpu-share.go:85-110): the same worst-fit
    share as Simon but over the node's CURRENT allocatable — where the
    whole-GPU count dimension is the dynamic allocatable-device count — then
    min-max normalized by the plugin's own NormalizeScore."""
    return _minmax_normalize(gpu_share_raw(ns, carry, pod), ns.valid)


def run_scores(
    ns: NodeStatic,
    carry: Carry,
    pod: PodRow,
    weights: jnp.ndarray,
    extra_scores=(),
) -> jnp.ndarray:
    """Weighted sum of all normalized score plugins -> f32[N]. `extra_scores`
    is the out-of-tree registry: (fn, weight) pairs of jax-traceable
    `fn(ns, carry, pod) -> f32[N]` kernels added after the in-tree sum."""
    na_ok = node_affinity_mask(ns, pod)  # CSE-merged with run_filters' copy
    by_name = {
        "balanced_allocation": score_balanced(ns, carry, pod),
        "least_allocated": score_least_allocated(ns, carry, pod),
        "node_affinity": score_node_affinity(ns, pod),
        "taint_toleration": score_taint_toleration(ns, pod),
        "topology_spread": score_topology_spread(ns, carry, pod, na_ok),
        "inter_pod_affinity": score_inter_pod_affinity(ns, carry, pod),
        "prefer_avoid_pods": score_prefer_avoid(ns, pod),
        "simon": score_simon(ns, carry, pod),
        "gpu_share": score_gpu_share(ns, carry, pod),
        "open_local": score_open_local(ns, carry, pod),
    }
    score = combine_scores(by_name, weights)
    for fn, w in extra_scores:
        score = score + w * fn(ns, carry, pod)
    return score


# ---------------------------------------------------------------------------
# The scan: sequential commit of a pod batch in one device computation
# ---------------------------------------------------------------------------

def commit_onehot(ns: NodeStatic, carry: Carry, pod: PodRow, onehot):
    """Apply one pod's placement (onehot bool[N], all-False = no commit) to
    the carry. The single commit implementation shared by the naive scan and
    the extender per-pod path — placements must mutate state identically on
    both."""
    free = carry.free - onehot[:, None] * pod.req[None, :]
    sel_counts = carry.sel_counts + (
        pod.match_sel.astype(jnp.float32)[:, None] * onehot.astype(jnp.float32)[None, :]
    )
    gpu_take, gpu_free = gpu_allocate(ns, carry, pod, onehot)
    vg_free, dev_free, vg_take, dev_take = local_storage_commit(
        ns, carry, pod, onehot
    )
    port_any, port_wild, port_ipc = ports_commit(carry, pod, onehot)
    anti_counts = carry.anti_counts + (
        pod.own_anti[:, None] * onehot.astype(jnp.float32)[None, :]
    )
    new_carry = Carry(
        free=free, sel_counts=sel_counts, gpu_free=gpu_free,
        vg_free=vg_free, dev_free=dev_free,
        port_any=port_any, port_wild=port_wild, port_ipc=port_ipc,
        anti_counts=anti_counts,
    )
    return new_carry, gpu_take, vg_take, dev_take


def _gpu_allocate_row(free_d, total_d, pod: PodRow):
    """gpu_allocate's take for ONE node row (free_d f32[G], total_d f32[G]).
    Bit-identical to gpu_allocate's einsum-projected result for that row:
    the projection is one 1.0 times f32 values plus exact +0.0 terms, and
    every op here is the dense op applied to the extracted row (the
    gpu_allocate_rowwise argument, one row at a time)."""
    mem = pod.gpu_mem
    g = free_d.shape[0]

    elig = (total_d > 0) & (free_d >= mem - _EPS)
    tight = jnp.argmin(jnp.where(elig, free_d, jnp.inf))
    take_single = (
        (jnp.arange(g) == tight) & jnp.any(elig)
    ).astype(jnp.float32)

    caps = jnp.where(
        total_d > 0, jnp.floor((free_d + _EPS) / jnp.maximum(mem, 1e-9)), 0.0
    )
    prefix = jnp.cumsum(caps) - caps
    take_multi = jnp.clip(pod.gpu_num - prefix, 0.0, caps)
    take_multi = jnp.where(jnp.sum(caps) >= pod.gpu_num, take_multi, 0.0)

    take = jnp.where(pod.gpu_num == 1, take_single, take_multi)
    return jnp.where((mem > 0) & (pod.gpu_num >= 1), take, 0.0)


def _local_storage_take_row(vg_cap, vg_name, dev_cap, dev_ssd,
                            vg_free, dev_free, pod: PodRow):
    """local_storage_eval's takes for ONE node row (all args are that
    node's [V]/[DV] slices). Each slot step is the dense step's arithmetic
    with the node axis removed — the eval is node-local by construction
    (every op there maps axis 1 independently per row), so the takes are
    bit-identical to the dense eval's row."""
    v = vg_cap.shape[0]
    dv = dev_cap.shape[0]
    sv = pod.lvm_req.shape[0]

    def lvm_slot(state, s):
        free, take = state
        req = pod.lvm_req[s]
        active = req > 0
        want = pod.lvm_vg[s]
        fits = (free + _EPS >= req) & (vg_name != 0)
        elig = jnp.where(want != 0, fits & (vg_name == want), fits)
        free_key = jnp.where(elig, free, jnp.inf)
        choice = jnp.argmin(free_key)
        any_elig = jnp.any(elig)
        onehot = (
            (jnp.arange(v) == choice) & any_elig & active
        ).astype(jnp.float32)
        return (free - onehot * req, take + onehot * req), None

    (_, vg_take), _ = jax.lax.scan(
        lvm_slot, (vg_free, jnp.zeros_like(vg_free)), jnp.arange(sv)
    )

    def dev_slot(state, s):
        avail, take = state
        req = pod.dev_req[s]
        active = req > 0
        elig = (
            (avail > 0.5)
            & (dev_ssd == pod.dev_media_ssd[s])
            & (dev_cap + _EPS >= req)
            & (dev_cap > 0)
        )
        cap_key = jnp.where(elig, dev_cap, jnp.inf)
        choice = jnp.argmin(cap_key)
        any_elig = jnp.any(elig)
        onehot = (
            (jnp.arange(dv) == choice) & any_elig & active
        ).astype(jnp.float32)
        return (avail - onehot, take + onehot), None

    (_, dev_take), _ = jax.lax.scan(
        dev_slot, (dev_free, jnp.zeros_like(dev_free)), jnp.arange(sv)
    )
    return vg_take, dev_take


def commit_choice(ns: NodeStatic, carry: Carry, pod: PodRow, choice):
    """commit_onehot for a known node index (i32 scalar, -1 = no commit),
    in O(row) work instead of O(N): only the chosen node's row/column of
    each carry plane changes, so gather that slice, apply the dense
    commit's row arithmetic, and scatter it back (a -1/invalid choice
    scatters out of bounds and is dropped — the carry is returned
    untouched, bitwise, exactly like an all-False onehot).

    Bit-identity to commit_onehot(..., onehot=(arange(N)==choice)&ok):
    dense planes update as `x - onehot*delta` / `x + delta*onehot` —
    unchosen entries add or subtract an exact +0.0 (every delta is
    nonnegative, so no -0.0 products), which is bitwise identity, and the
    chosen row sees `1.0 * delta` which is bitwise `delta`; the gpu and
    storage takes follow the gpu_allocate_rowwise row-extraction
    argument. This is the wave engine's replay step (ops/wave.py);
    `simon prove` holds it to the banked digest over the full small-scope
    corpus."""
    n = ns.valid.shape[0]
    ok = (choice >= 0) & pod.valid
    row = jnp.where(ok, choice, 0)        # safe gather index
    idx = jnp.where(ok, choice, n)        # out-of-bounds scatters drop

    free = carry.free.at[idx].set(
        carry.free[row] - pod.req, mode="drop"
    )
    sel_counts = carry.sel_counts.at[:, idx].set(
        carry.sel_counts[:, row] + pod.match_sel.astype(jnp.float32),
        mode="drop",
    )
    anti_counts = carry.anti_counts.at[:, idx].set(
        carry.anti_counts[:, row] + pod.own_anti, mode="drop"
    )

    gpu_take = jnp.where(
        ok,
        _gpu_allocate_row(carry.gpu_free[row], ns.gpu_total[row], pod),
        jnp.zeros(carry.gpu_free.shape[1], jnp.float32),
    )
    gpu_free = carry.gpu_free.at[idx].set(
        carry.gpu_free[row] - gpu_take * pod.gpu_mem, mode="drop"
    )

    vg_take_row, dev_take_row = _local_storage_take_row(
        ns.vg_cap[row], ns.vg_name[row], ns.dev_cap[row], ns.dev_ssd[row],
        carry.vg_free[row], carry.dev_free[row], pod,
    )
    vg_take = jnp.where(ok, vg_take_row, jnp.zeros_like(vg_take_row))
    dev_take = jnp.where(ok, dev_take_row, jnp.zeros_like(dev_take_row))
    vg_free = carry.vg_free.at[idx].set(
        carry.vg_free[row] - vg_take_row, mode="drop"
    )
    dev_free = carry.dev_free.at[idx].set(
        carry.dev_free[row] - dev_take_row, mode="drop"
    )

    add_any, add_wild, add_ipc = port_adds(
        carry.port_any.shape[0], carry.port_ipc.shape[0], pod
    )
    port_any = carry.port_any.at[:, idx].set(
        carry.port_any[:, row] + add_any, mode="drop"
    )
    port_wild = carry.port_wild.at[:, idx].set(
        carry.port_wild[:, row] + add_wild, mode="drop"
    )
    port_ipc = carry.port_ipc.at[:, idx].set(
        carry.port_ipc[:, row] + add_ipc, mode="drop"
    )

    new_carry = Carry(
        free=free, sel_counts=sel_counts, gpu_free=gpu_free,
        vg_free=vg_free, dev_free=dev_free,
        port_any=port_any, port_wild=port_wild, port_ipc=port_ipc,
        anti_counts=anti_counts,
    )
    return new_carry, gpu_take, vg_take, dev_take


def schedule_step(
    ns: NodeStatic,
    weights: jnp.ndarray,
    carry: Carry,
    pod: PodRow,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    mask, first_fail = run_filters(ns, carry, pod, filter_on, extra_filters)
    score = run_scores(ns, carry, pod, weights, extra_scores)
    score = jnp.where(mask, score, -jnp.inf)
    node = jnp.argmax(score)  # first max => lowest node index tie-break
    ok = jnp.any(mask) & pod.valid
    node_out = jnp.where(ok, node, -1)

    onehot = (jnp.arange(ns.valid.shape[0]) == node) & ok
    new_carry, gpu_take, vg_take, dev_take = commit_onehot(
        ns, carry, pod, onehot
    )

    reason_counts = jnp.zeros(NUM_FILTERS, jnp.int32).at[
        jnp.clip(first_fail, 0, NUM_FILTERS - 1)
    ].add(jnp.where((first_fail < NUM_FILTERS) & ns.valid, 1, 0))
    reason_counts = jnp.where(ok, jnp.zeros_like(reason_counts), reason_counts)

    return new_carry, (
        node_out.astype(jnp.int32),
        reason_counts,
        gpu_take.astype(jnp.int32),
        vg_take,
        dev_take,
    )


@sanitizable(
    "ops.kernels:probe_step", static_argnames=("extra_filters", "extra_scores")
)
@functools.partial(jax.jit, static_argnames=("extra_filters", "extra_scores"))
def probe_step(
    ns: NodeStatic,
    carry: Carry,
    pod: PodRow,
    weights: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """Filter + score ONE pod without committing: (mask bool[N], score f32[N]
    with -inf on infeasible nodes, first_fail i32[N]). The extender path pulls
    these to the host, folds in extender filter/prioritize results, then
    commits via commit_step — the split point generic_scheduler.go sits at
    between findNodesThatPassExtenders (:263) and prioritizeNodes (:521)."""
    mask, first_fail = run_filters(ns, carry, pod, filter_on, extra_filters)
    score = run_scores(ns, carry, pod, weights, extra_scores)
    score = jnp.where(mask, score, -jnp.inf)
    return mask & ns.valid, score, first_fail


@sanitizable("ops.kernels:commit_step")
@jax.jit
def commit_step(ns: NodeStatic, carry: Carry, pod: PodRow, node):
    """Commit ONE pod to node index `node` (i32 scalar; -1 = no commit).
    Same state transition as the scan's schedule_step for the same choice."""
    ok = (node >= 0) & pod.valid
    onehot = (jnp.arange(ns.valid.shape[0]) == node) & ok
    new_carry, gpu_take, vg_take, dev_take = commit_onehot(
        ns, carry, pod, onehot
    )
    return new_carry, gpu_take.astype(jnp.int32), vg_take, dev_take


@sanitizable(
    "ops.kernels:probe_many", static_argnames=("extra_filters", "extra_scores")
)
@functools.partial(jax.jit, static_argnames=("extra_filters", "extra_scores"))
def probe_many(
    ns: NodeStatic,
    carry: Carry,
    rows: PodRow,
    weights: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """probe_step vmapped over a pod-wave axis: filter + score W pods against
    ONE carry in a single device call — (mask bool[W,N], score f32[W,N] with
    -inf on infeasible nodes, first_fail i32[W,N]). The extender wave engine
    probes a whole wave up front so the per-pod HTTP chains can run
    concurrently; callers pad W to `wave_bucket` (ops/fast.py scenario
    bucketing discipline) so the jit cache stays at a handful of shapes."""

    def one(pod):
        mask, first_fail = run_filters(ns, carry, pod, filter_on, extra_filters)
        score = run_scores(ns, carry, pod, weights, extra_scores)
        score = jnp.where(mask, score, -jnp.inf)
        return mask & ns.valid, score, first_fail

    return jax.vmap(one)(rows)


@sanitizable(
    "ops.kernels:commit_wave", static_argnames=("extra_filters", "extra_scores")
)
@functools.partial(jax.jit, static_argnames=("extra_filters", "extra_scores"))
def commit_wave(
    ns: NodeStatic,
    carry: Carry,
    rows: PodRow,
    weights: jnp.ndarray,
    expected_mask: jnp.ndarray,
    expected_ff: jnp.ndarray,
    ext_allowed: jnp.ndarray,
    ext_score: jnp.ndarray,
    want_commit: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """Pod-order commit scan for one extender wave, with conflict recheck.

    The wave's HTTP filter/prioritize calls were issued against masks probed
    at the wave-start carry (probe_many). By the time pod i commits, pods
    0..i-1 of the same wave have already mutated the carry — so each step
    re-runs the filters against the LIVE carry and compares with the mask the
    HTTP chain actually saw (`expected_mask`, plus `expected_ff` so failure
    reasons stay identical). A match proves the serial per-pod path would
    have issued byte-identical extender requests, so committing
    argmax(score' + ext_score) here IS the serial placement: score' is
    recomputed on the live carry, exactly what serial probe_step would have
    produced at this point. The first mismatch flips a sticky `blocked` flag
    — that pod and every later pod in the wave respill to the next wave
    (their serial outcome depends on commits that must land first).

    Inputs per wave lane: `ext_allowed` bool[W,N] nodes surviving the
    extender filter chain; `ext_score` f32[W,N] combined extender priority ×
    weight × scale per node (0 elsewhere); `want_commit` bool[W] lanes whose
    extender chain succeeded with a non-empty feasible set (False = failed /
    pad lanes, which only recheck). Returns (carry', nodes i32[W] (-1 = no
    commit), respill bool[W], gpu_take, vg_take, dev_take)."""

    def step(c, xs):
        carry_c, blocked = c
        pod, exp_mask, exp_ff, allowed, escore, want = xs
        mask, first_fail = run_filters(
            ns, carry_c, pod, filter_on, extra_filters
        )
        mask = mask & ns.valid
        match = jnp.all(mask == exp_mask) & jnp.all(first_fail == exp_ff)
        respill = blocked | ~match
        score = run_scores(ns, carry_c, pod, weights, extra_scores)
        allow = mask & allowed
        total = jnp.where(allow, score + escore, -jnp.inf)
        node = jnp.argmax(total)  # first max => lowest node index tie-break
        ok = want & ~respill & jnp.any(allow) & pod.valid
        node_out = jnp.where(ok, node, -1).astype(jnp.int32)
        onehot = (jnp.arange(ns.valid.shape[0]) == node) & ok
        new_carry, gpu_take, vg_take, dev_take = commit_onehot(
            ns, carry_c, pod, onehot
        )
        return (new_carry, respill), (
            node_out, respill, gpu_take.astype(jnp.int32), vg_take, dev_take
        )

    (final_carry, _), (nodes, respill, gpu_take, vg_take, dev_take) = (
        jax.lax.scan(
            step,
            (carry, jnp.bool_(False)),
            (rows, expected_mask, expected_ff, ext_allowed, ext_score,
             want_commit),
        )
    )
    return final_carry, nodes, respill, gpu_take, vg_take, dev_take


@sanitizable(
    "ops.kernels:schedule_batch",
    static_argnames=("extra_filters", "extra_scores"),
)
@functools.partial(jax.jit, static_argnames=("extra_filters", "extra_scores"))
def schedule_batch(
    ns: NodeStatic,
    carry: Carry,
    pods: PodRow,
    weights: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """Schedule a whole PodBatch sequentially on device.

    Returns (final_carry, nodes i32[P] (-1 = unschedulable), reasons i32[P,F],
    gpu_take i32[P,G] — shares allocated per device on the chosen node,
    vg_take f32[P,V] — MiB claimed per VG slot of the chosen node,
    dev_take f32[P,DV] — devices claimed on the chosen node).
    """

    def step(c, pod):
        return schedule_step(
            ns, weights, c, pod, filter_on, extra_filters, extra_scores
        )

    final_carry, (nodes, reasons, gpu_take, vg_take, dev_take) = jax.lax.scan(
        step, carry, pods
    )
    return final_carry, nodes, reasons, gpu_take, vg_take, dev_take
