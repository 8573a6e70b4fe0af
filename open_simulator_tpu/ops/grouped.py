"""Grouped scheduling: static-part hoisting for runs of identical pods.

Real batches are a few workload templates × thousands of replicas. For one
template, most of the per-step work in the naive scan is invariant:

  static per group (computed ONCE):
    - NodeUnschedulable / NodeName / TaintToleration / NodeAffinity masks
      (depend only on the pod spec and immutable node attributes)
    - Simon worst-fit score (uses static allocatable — simon.go:45-68),
      NodeAffinity-preferred, TaintToleration and NodePreferAvoidPods scores
  dynamic per step (recomputed in the inner scan):
    - NodeResourcesFit vs the free matrix
    - PodTopologySpread / InterPodAffinity masks + scores vs sel_counts
    - LeastAllocated / BalancedAllocation vs the free matrix

The inner scan step is ~5x fewer ops than the full scan step, and results are
bit-identical to `schedule_batch` because every dynamic quantity is recomputed
exactly as the naive kernel does (the hoisted parts are genuinely invariant:
per-node scores with no cross-step dependence, and normalizations whose inputs
are all static for a fixed pod spec).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .encode import PodBatch, round_up
from .kernels import (
    Carry,
    F_EXTRA,
    F_GPU,
    F_NODE_PORTS,
    F_POD_AFFINITY,
    F_RESOURCES,
    F_SPREAD,
    F_STORAGE,
    NUM_FILTERS,
    NodeStatic,
    PodRow,
    _minmax_normalize,
    combine_scores,
    gpu_allocate,
    gpu_mask,
    local_storage_commit,
    local_storage_eval,
    node_affinity_mask,
    pod_affinity_mask,
    ports_commit,
    ports_mask,
    resource_fail,
    score_balanced,
    score_gpu_share,
    score_inter_pod_affinity,
    score_least_allocated,
    score_node_affinity,
    score_prefer_avoid,
    score_simon,
    score_taint_toleration,
    score_topology_spread,
    spread_mask,
    taint_mask,
)
from .sanitize import sanitizable
from .state import pod_rows_from_batch
from ..utils.tracing import fetch

# Default cap on per-group device-program length (scan steps per dispatch) —
# one 100k-step scan trips the TPU worker's watchdog, so dispatches are
# bounded. Shared by schedule_batch_grouped and schedule_batch_fast so the
# sites cannot drift.
DEFAULT_GROUP_CHUNK = 16384


def _static_parts(ns: NodeStatic, pod: PodRow, weights: jnp.ndarray, filter_on=None):
    """Masks/scores that do not depend on the scan carry. `filter_on`
    (bool[NUM_FILTERS] or None) disables filters per the scheduler profile —
    note na_ok itself stays unmasked: PodTopologySpread eligibility reads the
    pod spec directly regardless of the NodeAffinity plugin's enablement."""
    unsched_tolerated = jnp.any(
        pod.tol_valid
        & ((pod.tol_key == 0) | (pod.tol_key == ns.unsched_key_id))
        & (pod.tol_exists | (pod.tol_val == ns.empty_val_id))
        & ((pod.tol_effect == 0) | (pod.tol_effect == 1)),
    )
    na_ok = node_affinity_mask(ns, pod)
    static_fails = jnp.stack(
        [
            ns.unsched & ~unsched_tolerated,
            (pod.node_name_id != 0) & (ns.name_id != pod.node_name_id),
            ~taint_mask(ns, pod),
            ~na_ok,
        ],
        axis=1,
    )                                                   # [N,4]
    if filter_on is not None:
        static_fails = static_fails & filter_on[None, :4]
    static_ok = ~jnp.any(static_fails, axis=1)
    static_first_fail = jnp.where(
        jnp.any(static_fails, axis=1),
        jnp.argmax(static_fails, axis=1),
        NUM_FILTERS,
    )
    static_scores = {
        "node_affinity": score_node_affinity(ns, pod),
        "taint_toleration": score_taint_toleration(ns, pod),
        "prefer_avoid_pods": score_prefer_avoid(ns, pod),
        "simon": score_simon(ns, None, pod),
    }
    return static_ok, static_first_fail, static_scores, na_ok


def group_step(
    ns: NodeStatic,
    pod: PodRow,
    weights: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """One scheduling step of `pod` over every node, its static parts
    hoisted: -> step(carry, active) -> (carry, (node i32, reasons i32[F],
    gpu_take i32[G], vg_take f32[V], dev_take f32[DV])). An inactive step
    commits nothing. schedule_group scans it; a preemptor's retry
    (ops/preempt.retry_one) runs it once."""
    static_ok, static_ff, static_scores, na_ok = _static_parts(
        ns, pod, weights, filter_on
    )
    fo = (
        jnp.ones(NUM_FILTERS, bool) if filter_on is None else filter_on
    )

    def step(c: Carry, active):
        port_ok = ports_mask(c, pod) | ~fo[F_NODE_PORTS]
        res_fail = resource_fail(ns, c, pod) & fo[F_RESOURCES]
        spread_ok = spread_mask(ns, c, pod, na_ok) | ~fo[F_SPREAD]
        aff_ok = pod_affinity_mask(ns, c, pod) | ~fo[F_POD_AFFINITY]
        # takes are re-derived inside local_storage_commit below; XLA CSE
        # collapses the two local_storage_eval calls within one jit
        storage_ok, _, _, storage_raw = local_storage_eval(ns, c, pod)
        gpu_ok = gpu_mask(ns, c, pod)
        extra_ok = jnp.ones(ns.valid.shape[0], bool)
        for f in extra_filters:
            extra_ok = extra_ok & f(ns, c, pod)
        mask = (
            static_ok & port_ok & ~res_fail & spread_ok & aff_ok & storage_ok
            & gpu_ok & extra_ok & ns.valid
        )

        # Combine in WEIGHT_ORDER exactly like run_scores so the f32
        # summation order (and therefore every tie-break) matches the naive
        # kernel.
        by_name = {
            "balanced_allocation": score_balanced(ns, c, pod),
            "least_allocated": score_least_allocated(ns, c, pod),
            "topology_spread": score_topology_spread(ns, c, pod, na_ok),
            "inter_pod_affinity": score_inter_pod_affinity(ns, c, pod),
            "gpu_share": score_gpu_share(ns, c, pod),
            "open_local": jnp.where(
                pod.has_local, _minmax_normalize(storage_raw, ns.valid), 0.0
            ),
            **static_scores,
        }
        score = combine_scores(by_name, weights)
        for fn, w in extra_scores:
            score = score + w * fn(ns, c, pod)
        score = jnp.where(mask, score, -jnp.inf)
        node = jnp.argmax(score)
        ok = jnp.any(mask) & active
        node_out = jnp.where(ok, node, -1)

        onehot = (jnp.arange(ns.valid.shape[0]) == node) & ok
        free = c.free - onehot[:, None] * pod.req[None, :]
        sel_counts = c.sel_counts + (
            pod.match_sel.astype(jnp.float32)[:, None]
            * onehot.astype(jnp.float32)[None, :]
        )
        gpu_take, gpu_free = gpu_allocate(ns, c, pod, onehot)
        vg_free, dev_free, vg_take_sel, dev_take_sel = local_storage_commit(
            ns, c, pod, onehot
        )
        port_any, port_wild, port_ipc = ports_commit(c, pod, onehot)
        anti_counts = c.anti_counts + (
            pod.own_anti[:, None] * onehot.astype(jnp.float32)[None, :]
        )

        first_fail = jnp.where(
            static_ff < NUM_FILTERS,
            static_ff,
            jnp.where(
                ~port_ok,
                F_NODE_PORTS,
                jnp.where(
                    res_fail,
                    F_RESOURCES,
                    jnp.where(
                        ~spread_ok,
                        F_SPREAD,
                        jnp.where(
                            ~aff_ok,
                            F_POD_AFFINITY,
                            jnp.where(
                                ~storage_ok,
                                F_STORAGE,
                                jnp.where(
                                    ~gpu_ok,
                                    F_GPU,
                                    jnp.where(
                                        ~extra_ok, F_EXTRA, NUM_FILTERS
                                    ),
                                ),
                            ),
                        ),
                    ),
                ),
            ),
        )
        reason_counts = jnp.zeros(NUM_FILTERS, jnp.int32).at[
            jnp.clip(first_fail, 0, NUM_FILTERS - 1)
        ].add(jnp.where((first_fail < NUM_FILTERS) & ns.valid, 1, 0))
        reason_counts = jnp.where(ok, jnp.zeros_like(reason_counts), reason_counts)

        return Carry(
            free=free, sel_counts=sel_counts, gpu_free=gpu_free,
            vg_free=vg_free, dev_free=dev_free,
            port_any=port_any, port_wild=port_wild, port_ipc=port_ipc,
            anti_counts=anti_counts,
        ), (
            node_out.astype(jnp.int32),
            reason_counts,
            gpu_take.astype(jnp.int32),
            vg_take_sel,
            dev_take_sel,
        )

    return step


def schedule_group(
    ns: NodeStatic,
    carry: Carry,
    pod: PodRow,
    group_size: int,
    valid_count: jnp.ndarray,
    weights: jnp.ndarray,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
):
    """Schedule `group_size` copies of one pod spec; only the first
    `valid_count` steps commit. Returns (carry, nodes i32[G], reasons i32[G,F]).
    """
    step = group_step(ns, pod, weights, filter_on, extra_filters, extra_scores)
    return jax.lax.scan(
        lambda c, i: step(c, i < valid_count), carry, jnp.arange(group_size)
    )


_group_jit = jax.jit(
    schedule_group,
    static_argnames=("group_size", "extra_filters", "extra_scores"),
)
# Separate statement: lint's jit-root detection keys off the `jax.jit(...)`
# assignment above, and sanitize delegates .trace back to it.
_group_jit = sanitizable(
    "ops.grouped:_group_jit",
    static_argnames=("group_size", "extra_filters", "extra_scores"),
)(_group_jit)


def _group_call(
    ns, carry, pod, group_size, valid_count, weights, filter_on=None,
    extra_filters=(), extra_scores=(),
):
    """_group_jit with defaults omitted (keeps the plain jit cache entry
    shared with callers that never pass a profile or plugins)."""
    if filter_on is None and not extra_filters and not extra_scores:
        return _group_jit(ns, carry, pod, group_size, valid_count, weights)
    return _group_jit(
        ns, carry, pod, group_size, valid_count, weights, filter_on,
        extra_filters, extra_scores,
    )


def _row_signature(batch: PodBatch) -> np.ndarray:
    """Byte-hash every pod row's feature arrays to detect identical specs.
    Uses the compiled 128-bit row hasher (native/osim_native.cpp) when
    available; blake2b otherwise."""
    from dataclasses import fields

    parts = []
    for f in fields(batch):
        if f.name in ("keys", "valid"):
            continue
        arr = getattr(batch, f.name)
        parts.append(np.ascontiguousarray(arr).reshape(batch.p, -1).view(np.uint8))
    blob = np.concatenate(parts, axis=1)

    from ..native import hash_rows

    hashed = hash_rows(blob)
    if hashed is not None:
        # host-side reinterpretation of a 128-bit digest; never enters a
        # kernel, and the view width must match the digest exactly
        return hashed.view([("a", np.uint64), ("b", np.uint64)]).reshape(-1)  # osim: lint-ok[f64-literal]

    import hashlib

    return np.array(
        [hashlib.blake2b(row.tobytes(), digest_size=8).digest() for row in blob]
    )


def group_runs(batch: PodBatch) -> List[Tuple[int, int]]:
    """(start, length) runs of consecutive identical valid rows."""
    total = int(batch.valid.sum())
    if total == 0:
        return []
    sig = _row_signature(batch)
    # Vectorized boundary detection: per-element comparison of structured
    # rows re-promotes the dtype 100k times (~0.8 s at headline scale).
    # Iterate the signature's fields generically so a digest-width or dtype
    # change in the native hasher can't silently break this.
    if sig.dtype.fields:
        diff = np.zeros(max(total - 1, 0), bool)
        for fname in sig.dtype.fields:
            col = sig[fname][:total]
            diff |= col[1:] != col[:-1]
    else:
        diff = sig[1:total] != sig[: total - 1]
    change = np.nonzero(diff)[0] + 1
    starts = np.concatenate(([0], change))
    ends = np.concatenate((change, [total]))
    return [(int(s), int(e - s)) for s, e in zip(starts, ends)]


def _bucket(n: int) -> int:
    """Scan-length bucket. Floor of 32: distinct lengths below that would
    each trace their own multi-second jit of the full scheduling graph for
    under ~0.3s of wasted inert steps."""
    return round_up(n, 32)


def schedule_batch_grouped(
    ns: NodeStatic,
    carry: Carry,
    batch: PodBatch,
    weights,
    max_group_chunk: int = DEFAULT_GROUP_CHUNK,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
) -> Tuple[Carry, np.ndarray, np.ndarray, np.ndarray]:
    """schedule_batch semantics via per-group inner scans.

    Returns (carry, nodes i32[batch.p], reasons i32[batch.p, F],
    gpu_take i32[batch.p, G], vg_take f32[batch.p, V], dev_take
    f32[batch.p, DV]) — identical to the naive kernel's output for the same
    batch.
    """
    P = batch.p
    G = ns.gpu_total.shape[1]
    V = ns.vg_cap.shape[1]
    DV = ns.dev_cap.shape[1]
    nodes_out = np.full(P, -1, np.int32)
    reasons_out = np.zeros((P, NUM_FILTERS), np.int32)
    take_out = np.zeros((P, G), np.int32)
    vg_out = np.zeros((P, V), np.float32)
    dev_out = np.zeros((P, DV), np.float32)
    rows_all = pod_rows_from_batch(batch)

    for start, length in group_runs(batch):
        row = jax.tree.map(lambda a: a[start], rows_all)
        done = 0
        while done < length:
            n = min(length - done, max_group_chunk)
            g = _bucket(n)
            carry, (nodes, reasons, take, vg_take, dev_take) = _group_call(
                ns, carry, row, g, jnp.int32(n), weights, filter_on,
                extra_filters, extra_scores,
            )
            sl = slice(start + done, start + done + n)
            nodes_np, reasons_np, take_np, vg_np, dev_np = fetch(
                (nodes, reasons, take, vg_take, dev_take), "grouped-chunk"
            )
            nodes_out[sl] = nodes_np[:n]
            reasons_out[sl] = reasons_np[:n]
            take_out[sl] = take_np[:n]
            vg_out[sl] = vg_np[:n]
            dev_out[sl] = dev_np[:n]
            done += n
    return carry, nodes_out, reasons_out, take_out, vg_out, dev_out
