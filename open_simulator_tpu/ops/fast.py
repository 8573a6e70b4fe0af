"""Trajectory-accelerated grouped scheduling: the batched fast path.

`schedule_batch_grouped` (ops/grouped.py) already hoists static filter/score
work, but still pays one FULL filter/score sweep per pod — 100k sequential
heavy scan steps leave the TPU mostly idle (SURVEY §7 hard part 1; the
reference's own loop is serial per scheduleOne, generic_scheduler.go:131-175,
so this is where a TPU-native design wins an order of magnitude, not 5%).

The key structural fact: while a group of IDENTICAL pods schedules, a node's
local state (free resources, per-device GPU memory, VG/device storage, host
port counts) changes ONLY when that node is chosen — every commit touches just
the chosen node's row/column. So for one group:

  1. Trajectory precompute (J steps, J = max commits any node can take,
     bounded by the implicit pods-slot request → typically ~110): virtually
     commit the pod to EVERY node at once per step, recording per-step
     node-local masks (resources / ports / storage / GPU), raw scores, and
     allocation takes. Row n after j steps is bit-identical to the real
     carry's row n after j commits to n, because the arithmetic per row is
     exactly the scan's commit arithmetic.
  2. Light selection scan (one step per pod): the carry is just x i32[N] —
     commits per node so far. Node-local quantities are O(N) gathers from the
     trajectory at x; the carry-coupled PodTopologySpread / InterPodAffinity
     counts are reconstructed EXACTLY as `base + match * x` (pure integer
     arithmetic in f32, exact below 2^24) and fed through the original
     `_domain_counts`, so every count, min, max and normalize is bit-identical
     to the naive kernel. The step is ~20 small ops instead of the full
     ~dozen-plugin sweep.

Placements, failure reasons, allocation takes and the exit carry are all
bit-identical to `schedule_batch` (tests/test_fast.py proves it); groups too
small to amortize the trajectory fall back to the grouped path.
"""

from __future__ import annotations

import functools
import os
import sys
import time
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .encode import PodBatch, round_up
from .grouped import (
    DEFAULT_GROUP_CHUNK,
    _bucket,
    _group_call,
    _static_parts,
    group_runs,
)
from .kernels import (
    Carry,
    F_GPU,
    F_NODE_PORTS,
    F_POD_AFFINITY,
    F_RESOURCES,
    F_SPREAD,
    F_STORAGE,
    NUM_FILTERS,
    NodeStatic,
    PodRow,
    WEIGHT_ORDER,
    _EPS,
    _domain_counts,
    _minmax_normalize,
    combine_scores,
    gpu_allocate_rowwise,
    gpu_mask,
    gpu_share_raw,
    local_storage_eval,
    port_adds,
    ports_mask,
    resource_fail,
    schedule_step,
)
from .sanitize import sanitizable
from . import delta as _delta
from . import wave as _wave
from .state import pod_rows_from_batch
from ..utils import metrics as _metrics
from ..utils.tracing import fetch

# Trajectories longer than this fall back to the per-pod grouped path (a node
# that can absorb >512 copies of one pod implies an unrealistically small
# request; the [J,N,R] trajectory would not be worth its HBM footprint).
J_CAP = 512

# Path counters (tests/diagnostics): groups scheduled per strategy since
# import. The sort path must actually fire for plain groups — parity alone
# can't tell which path produced the result.
PATH_COUNTS = {
    "sort": 0, "micro": 0, "scan": 0, "grouped": 0, "sort_fallback": 0,
    "domain": 0, "domain_fallback": 0,
}


def _count_path(path: str, n: int = 1) -> None:
    """Tally a strategy selection in PATH_COUNTS and mirror it into
    osim_fast_path_total{path=...}."""
    if n <= 0:
        return
    PATH_COUNTS[path] += n
    _metrics.FAST_PATH.inc(n, path=path)

# Max combined (domain-tuple, eligibility) classes for the domain-merge path;
# groups whose nodes span more classes take the micro scan instead. Tests may
# set this to 0 to force the micro body.
DM_CAP = 64

# Hang forensics (OSIM_PROGRESS=1): one stderr line immediately BEFORE each
# device dispatch, so when a deadline kills the process the last line names
# the exact group/path/shape that hung inside one device call.
_PROGRESS = os.environ.get("OSIM_PROGRESS", "") not in ("", "0")


def _progress(msg: str) -> None:
    if _PROGRESS:
        print(f"[osim {time.strftime('%H:%M:%S')}] {msg}",
              file=sys.stderr, flush=True)


# Channel layout of Trajectory.packed — everything the selection step needs,
# in one array so the whole per-step state fits a small [N,CH] matrix.
CH_CPU, CH_MEM, CH_RES_FAIL, CH_PORT_OK, CH_STO_OK, CH_GPU_OK = range(6)
CH_STO_RAW, CH_GPU_RAW = 6, 7
N_CH = 8


class Trajectory(NamedTuple):
    """Per-node state/score evolution for one pod spec: index j = value after
    j commits of this pod onto that node. Layout is [N, J, ...] — lane-local
    per-node selection (TPU lowers general gathers poorly).

    `packed` f32[N,J,CH] carries the selection-step channels (cpu/mem free,
    the four local feasibility bits as 0.0/1.0, and the two raw scores); the
    full-width arrays are only touched once per group (exit carry, takes)."""
    free: jnp.ndarray         # f32[N,J,R]
    gpu_free: jnp.ndarray     # f32[N,J,G]
    vg_free: jnp.ndarray      # f32[N,J,V]
    dev_free: jnp.ndarray     # f32[N,J,DV]
    gpu_take: jnp.ndarray     # f32[N,J,G]
    vg_take: jnp.ndarray      # f32[N,J,V]
    dev_take: jnp.ndarray     # f32[N,J,DV]
    packed: jnp.ndarray       # f32[N,J,N_CH]


@sanitizable("ops.fast:build_trajectory", static_argnames=("j_steps",))
@functools.partial(jax.jit, static_argnames=("j_steps",))
def build_trajectory(
    ns: NodeStatic,
    carry: Carry,
    pod: PodRow,
    weights: jnp.ndarray,
    j_steps: int,
    filter_on=None,
):
    """Virtual-commit the pod to every node j_steps times, recording the
    node-local evolution, plus the group's static masks/scores.

    Returns (Trajectory, static_ok, static_ff, static_scores, na_ok).

    Exactness: each recorded row equals the real scan carry's row after the
    same number of commits to that node, because (a) the scan's commit only
    mutates the chosen node's row/column, and (b) the arithmetic applied here
    per row is the scan's own commit expression with onehot ≡ 1 (1.0 * v == v
    exactly in f32). Rows past a node's local feasibility limit are never
    gathered: the local masks are monotone in j (free/gpu/storage only
    shrink, a host-port self-conflict is permanent), so x stops there.
    """
    add_any, add_wild, add_ipc = port_adds(
        carry.port_any.shape[0], carry.port_ipc.shape[0], pod
    )

    def step(vc: Carry, _):
        res_fail = resource_fail(ns, vc, pod)
        port_ok = ports_mask(vc, pod)
        storage_ok, vg_take_all, dev_take_all, storage_raw = local_storage_eval(
            ns, vc, pod
        )
        g_ok = gpu_mask(ns, vc, pod)
        g_raw = gpu_share_raw(ns, vc, pod)
        g_take = gpu_allocate_rowwise(ns, vc.gpu_free, pod)
        packed = jnp.stack(
            [
                vc.free[:, 0], vc.free[:, 1],
                res_fail.astype(jnp.float32), port_ok.astype(jnp.float32),
                storage_ok.astype(jnp.float32), g_ok.astype(jnp.float32),
                storage_raw, g_raw,
            ],
            axis=1,
        )                                                   # [N,CH]
        out = (
            vc.free, vc.gpu_free, vc.vg_free, vc.dev_free,
            g_take, vg_take_all, dev_take_all, packed,
        )
        vc2 = vc._replace(
            free=vc.free - pod.req[None, :],
            gpu_free=vc.gpu_free - g_take * pod.gpu_mem,
            vg_free=vc.vg_free - vg_take_all,
            dev_free=vc.dev_free - dev_take_all,
            port_any=vc.port_any + add_any[:, None],
            port_wild=vc.port_wild + add_wild[:, None],
            port_ipc=vc.port_ipc + add_ipc[:, None],
        )
        return vc2, out

    _, outs = jax.lax.scan(step, carry, None, length=j_steps)
    # scan stacks along axis 0 ([J,N,...]); move J next to the node axis so
    # per-step selection is a lane-local reduction.
    traj = Trajectory(*(jnp.moveaxis(o, 0, 1) for o in outs))
    static_ok, static_ff, static_scores, na_ok = _static_parts(
        ns, pod, weights, filter_on
    )
    return traj, static_ok, static_ff, static_scores, na_ok


def _x_onehot(x: jnp.ndarray, j_steps: int) -> jnp.ndarray:
    """bool[N,J] selector of each node's current commit count."""
    return jnp.arange(j_steps)[None, :] == x[:, None]


def _sel_j(traj_arr: jnp.ndarray, oh: jnp.ndarray) -> jnp.ndarray:
    """Select traj_arr[n, x_n] for every node via one-hot reduce.

    Exactness: exactly one J-lane is selected, the rest contribute literal
    zeros — adding zeros never changes an f32 value (the only bit change is
    -0.0 → +0.0, which nothing downstream distinguishes)."""
    if traj_arr.dtype == jnp.bool_:
        return jnp.any(traj_arr & oh, axis=1)
    if traj_arr.ndim == 2:
        return jnp.sum(traj_arr * oh.astype(traj_arr.dtype), axis=1)
    return jnp.sum(traj_arr * oh.astype(traj_arr.dtype)[:, :, None], axis=1)


class GroupFlags(NamedTuple):
    """Host-known facts about a group's pod spec, passed as STATIC jit args
    so _light_eval prunes provably-dead subgraphs at trace time. Every prune
    replaces a subcomputation with the constant the full graph would produce
    for this spec (ports_mask of a portless pod is all-true, the open-local
    score of a volume-less pod is all-zero, ...), so placements stay
    bit-identical — only tracing work and per-step kernels disappear."""
    dyn_ports: bool      # pod requests host ports (port state evolves)
    dyn_storage: bool    # pod has open-local volumes
    dyn_gpu: bool        # pod requests GPU share (gpu_free evolves)
    any_hard_spread: bool
    any_soft_spread: bool
    any_req_aff: bool    # required (anti)affinity terms
    any_pref_aff: bool   # preferred (anti)affinity terms
    any_anti_sym: bool   # existing anti-affinity terms repel this pod
    # topology spread (soft and/or hard) is the ONLY carry-coupled term and
    # uses non-hostname keys: the selection step reduces to partial9 +
    # w*spread with a small domain-count carry (the micro body)
    micro_spread: bool = False
    # EVERY carry-coupled term (spread, required/preferred inter-pod
    # affinity, anti-affinity symmetry) is domain-keyed over non-hostname
    # keys and there are no gpu/storage dynamics: the whole selection
    # reduces to the per-class domain path (domain_select)
    domain_aff: bool = False


ALL_DYNAMIC = GroupFlags(*([True] * 8))


def group_flags(row_np: dict, anti_topo_np: np.ndarray) -> GroupFlags:
    """Derive GroupFlags from one pod's numpy feature row."""
    spread_active = row_np["spread_topo"] >= 0
    soft = spread_active & ~row_np["spread_hard"]
    aff_active = row_np["aff_topo"] >= 0
    anti_match = (anti_topo_np >= 0) & row_np["match_anti"]
    f = GroupFlags(
        dyn_ports=bool((row_np["hp_pid"] > 0).any()),
        dyn_storage=bool(row_np["has_local"]),
        dyn_gpu=bool(row_np["gpu_mem"] > 0),
        any_hard_spread=bool((spread_active & row_np["spread_hard"]).any()),
        any_soft_spread=bool(soft.any()),
        any_req_aff=bool((aff_active & row_np["aff_required"]).any()),
        any_pref_aff=bool((aff_active & ~row_np["aff_required"]).any()),
        any_anti_sym=bool(anti_match.any()),
    )
    # hostname-keyed constraints count per node, not per domain — they keep
    # the general body
    keys_domainable = (
        bool((row_np["spread_topo"][spread_active] > 0).all())
        and bool((row_np["aff_topo"][aff_active] > 0).all())
        and bool((anti_topo_np[anti_match] > 0).all())
    )
    any_coupled = (
        f.any_soft_spread or f.any_hard_spread or f.any_req_aff
        or f.any_pref_aff or f.any_anti_sym
    )
    micro = (
        (f.any_soft_spread or f.any_hard_spread)
        and not f.any_req_aff
        and not f.any_pref_aff
        and not f.any_anti_sym
        and not f.dyn_gpu
        and not f.dyn_storage
        and keys_domainable
    )
    domain_aff = (
        any_coupled
        and not f.dyn_gpu
        and not f.dyn_storage
        and keys_domainable
    )
    return f._replace(micro_spread=micro, domain_aff=domain_aff)


def _light_eval(
    ns: NodeStatic,
    carry0: Carry,
    pod: PodRow,
    static_ok: jnp.ndarray,
    static_scores: dict,
    na_ok: jnp.ndarray,
    weights: jnp.ndarray,
    fo: jnp.ndarray,
    x: jnp.ndarray,
    cur: jnp.ndarray,
    flags: GroupFlags,
    hoisted: dict,
):
    """Evaluate feasibility + scores at commit state (x, cur) — shared by the
    selection scan's step and the once-per-group reason attribution. Returns
    (score f32[N] with -inf on infeasible, parts dict of effective per-filter
    bools for first-fail attribution). `hoisted` carries group-static values
    (computed once per chunk, loop-invariant): gpu_share score and the
    port/storage/gpu masks when their state cannot evolve."""
    N = ns.valid.shape[0]
    ones = jnp.ones(N, bool)
    xf = x.astype(jnp.float32)
    free2 = cur[:, CH_CPU:CH_MEM + 1]                 # [N,2]
    res_fail_x = (cur[:, CH_RES_FAIL] > 0.5) & fo[F_RESOURCES]
    if flags.dyn_ports:
        port_ok = (cur[:, CH_PORT_OK] > 0.5) | ~fo[F_NODE_PORTS]
    else:
        port_ok = ones  # a portless pod conflicts nowhere (ports_mask)
    if flags.dyn_storage:
        storage_ok = cur[:, CH_STO_OK] > 0.5
        storage_raw = cur[:, CH_STO_RAW]
    else:
        storage_ok = ones  # local_storage_eval: ok ≡ True when !has_local
    if flags.dyn_gpu:
        gpu_ok = cur[:, CH_GPU_OK] > 0.5
        gpu_raw = cur[:, CH_GPU_RAW]
    else:
        gpu_ok = ones  # gpu_mask admits non-GPU pods everywhere

    def srow(sel_idx):
        # sel_counts[sel_idx] after x commits: base + match * x — pure
        # integer f32 arithmetic, bit-equal to the scan's iterative +1s.
        return carry0.sel_counts[sel_idx] + pod.match_sel[sel_idx].astype(
            jnp.float32
        ) * xf

    # PodTopologySpread hard constraints (mirror kernels.spread_mask)
    if flags.any_hard_spread:
        def one_spread(topo_idx, sel_idx, max_skew, hard):
            active_c = (topo_idx >= 0) & hard
            k = jnp.maximum(topo_idx, 0)
            has_key = ns.topo[:, k] >= 0
            _, cnt_n, min_count, _ = _domain_counts(ns, srow(sel_idx), k, na_ok)
            ok_c = (cnt_n + 1.0 - min_count) <= max_skew + _EPS
            ok_c = ok_c & has_key
            return jnp.where(active_c, ok_c, jnp.ones_like(ok_c))

        spread_ok = jnp.all(
            jax.vmap(one_spread, in_axes=(0, 0, 0, 0), out_axes=1)(
                pod.spread_topo, pod.spread_sel, pod.spread_skew,
                pod.spread_hard,
            ),
            axis=1,
        ) | ~fo[F_SPREAD]
    else:
        spread_ok = ones  # every constraint row is inactive => all-true

    # InterPodAffinity required terms + anti-affinity symmetry
    # (mirror kernels.pod_affinity_mask)
    if flags.any_req_aff:
        def one_aff(topo_idx, sel_idx, anti, required):
            active_t = (topo_idx >= 0) & required
            k = jnp.maximum(topo_idx, 0)
            has_key = ns.topo[:, k] >= 0
            _, cnt, _, total = _domain_counts(ns, srow(sel_idx), k)
            self_match = pod.match_sel[sel_idx]
            aff_feasible = (cnt > 0) | (self_match & (total == 0))
            aff_feasible = aff_feasible & has_key
            ok_t = jnp.where(anti, cnt == 0, aff_feasible)
            return jnp.where(active_t, ok_t, jnp.ones(N, bool))

        req_ok = jnp.all(
            jax.vmap(one_aff, in_axes=(0, 0, 0, 0), out_axes=1)(
                pod.aff_topo, pod.aff_sel, pod.aff_anti, pod.aff_required
            ),
            axis=1,
        )
    else:
        req_ok = ones

    if flags.any_anti_sym:
        def one_sym(topo_idx, base_row, own, match):
            active_t = (topo_idx >= 0) & match
            k = jnp.maximum(topo_idx, 0)
            has_key = ns.topo[:, k] >= 0
            _, cnt, _, _ = _domain_counts(ns, base_row + own * xf, k)
            ok_t = (cnt == 0) | ~has_key
            return jnp.where(active_t, ok_t, jnp.ones(N, bool))

        sym_ok = jnp.all(
            jax.vmap(one_sym, in_axes=(0, 0, 0, 0), out_axes=1)(
                ns.anti_topo, carry0.anti_counts, pod.own_anti, pod.match_anti
            ),
            axis=1,
        )
    else:
        sym_ok = ones
    aff_ok = (req_ok & sym_ok) | ~fo[F_POD_AFFINITY]

    mask = (
        static_ok & port_ok & ~res_fail_x & spread_ok & aff_ok & storage_ok
        & gpu_ok & ns.valid
    )

    # Dynamic scores (mirror kernels.score_* on the reconstructed state)
    la, ba = _la_ba(ns, pod, free2)

    if flags.any_soft_spread:
        def one_ssc(topo_idx, sel_idx, hard):
            active_c = (topo_idx >= 0) & ~hard
            k = jnp.maximum(topo_idx, 0)
            _, cnt, _, _ = _domain_counts(ns, srow(sel_idx), k, na_ok)
            return jnp.where(active_c, cnt, 0.0)

        raw_sp = jnp.sum(
            jax.vmap(one_ssc, in_axes=(0, 0, 0), out_axes=1)(
                pod.spread_topo, pod.spread_sel, pod.spread_hard
            ),
            axis=1,
        )
        mx_sp = jnp.max(jnp.where(ns.valid, raw_sp, 0.0))
        sp_score = jnp.clip(
            jnp.where(
                mx_sp > 0, (mx_sp - raw_sp) * 100.0 / jnp.maximum(mx_sp, 1e-9),
                100.0,
            ),
            0.0,
            100.0,
        )
    else:
        sp_score = jnp.full(N, 100.0)  # raw ≡ 0 => mx 0 => the 100.0 branch

    if flags.any_pref_aff:
        def one_asc(topo_idx, sel_idx, anti, required, weight):
            active_t = (topo_idx >= 0) & ~required
            k = jnp.maximum(topo_idx, 0)
            _, cnt, _, _ = _domain_counts(ns, srow(sel_idx), k)
            signed = jnp.where(anti, -weight, weight) * cnt
            return jnp.where(active_t, signed, 0.0)

        raw_a = jnp.sum(
            jax.vmap(one_asc, in_axes=(0, 0, 0, 0, 0), out_axes=1)(
                pod.aff_topo, pod.aff_sel, pod.aff_anti, pod.aff_required,
                pod.aff_weight,
            ),
            axis=1,
        )
        any_active = jnp.any((pod.aff_topo >= 0) & ~pod.aff_required)
        ipa = jnp.where(any_active, _minmax_normalize(raw_a, ns.valid), 0.0)
    else:
        ipa = jnp.zeros(N)  # the where(any_active, ..., 0.0) branch

    by_name = {
        "balanced_allocation": ba,
        "least_allocated": la,
        "topology_spread": sp_score,
        "inter_pod_affinity": ipa,
        "gpu_share": (
            _minmax_normalize(gpu_raw, ns.valid)
            if flags.dyn_gpu
            else hoisted["gpu_score"]
        ),
        "open_local": (
            jnp.where(
                pod.has_local, _minmax_normalize(storage_raw, ns.valid), 0.0
            )
            if flags.dyn_storage
            else jnp.zeros(N)  # has_local False => the 0.0 branch
        ),
        **static_scores,
    }
    score = combine_scores(by_name, weights)
    score = jnp.where(mask, score, -jnp.inf)
    parts = {
        "port_ok": port_ok, "res_fail": res_fail_x, "spread_ok": spread_ok,
        "aff_ok": aff_ok, "storage_ok": storage_ok, "gpu_ok": gpu_ok,
    }
    return score, parts


def _la_ba(ns: NodeStatic, pod: PodRow, free2: jnp.ndarray):
    """LeastAllocated + BalancedAllocation from cpu/mem free values — the one
    definition all fast paths share (free2 is [N,2] or [N,J,2]; the ops are
    elementwise, so every lane is bit-identical to the per-step kernel)."""
    alloc2 = ns.alloc[:, :2]
    req2 = pod.req[:2]
    if free2.ndim == 3:
        alloc2 = alloc2[:, None, :]
        req2 = req2[None, None, :]
    else:
        req2 = req2[None, :]
    free_after = free2 - req2
    frac = jnp.where(alloc2 > 0, free_after / jnp.maximum(alloc2, 1e-9), 0.0)
    la = jnp.clip(jnp.mean(frac, axis=-1), 0.0, 1.0) * 100.0
    used_after = alloc2 - free2 + req2
    frac_b = jnp.where(alloc2 > 0, used_after / jnp.maximum(alloc2, 1e-9), 0.0)
    frac_b = jnp.clip(frac_b, 0.0, 1.0)
    ba = (1.0 - jnp.abs(frac_b[..., 0] - frac_b[..., 1])) * 100.0
    return la, ba


def _lane_rows(
    ns: NodeStatic, traj: Trajectory, pod: PodRow, static_scores: dict
) -> dict:
    """The nine node-local score rows per (node, lane) — shared by the sort
    path and the micro body so the arithmetic can never drift between them.
    Assumes gpu_free is frozen for the group (callers gate on !dyn_gpu) and
    no storage volumes / preferred affinity terms."""
    N, J, _ = traj.packed.shape
    free2 = traj.packed[:, :, CH_CPU:CH_MEM + 1]
    la, ba = _la_ba(ns, pod, free2)
    gpu_score = _minmax_normalize(traj.packed[:, 0, CH_GPU_RAW], ns.valid)

    def bcast(v):
        return jnp.broadcast_to(v[:, None], (N, J))

    return {
        "balanced_allocation": ba,
        "least_allocated": la,
        "inter_pod_affinity": jnp.zeros((N, J)),
        "gpu_share": bcast(gpu_score),
        "open_local": jnp.zeros((N, J)),
        **{k: bcast(v) for k, v in static_scores.items()},
    }


def _sortable(flags: GroupFlags) -> bool:
    """A group is sort-path eligible when every score/mask is a function of
    the node's OWN commit count alone: no spread/affinity terms (they couple
    through domain counts) and no GPU/storage volumes (their scores are
    min-max normalized over the batch's CURRENT raw values, which change as
    other nodes commit). Host ports are fine — purely node-local."""
    return not (
        flags.any_hard_spread
        or flags.any_soft_spread
        or flags.any_req_aff
        or flags.any_pref_aff
        or flags.any_anti_sym
        or flags.dyn_gpu
        or flags.dyn_storage
    )


@sanitizable("ops.fast:sort_select", static_argnames=("out_size",))
@functools.partial(jax.jit, static_argnames=("out_size",))
def sort_select(
    ns: NodeStatic,
    traj: Trajectory,
    pod: PodRow,
    static_ok: jnp.ndarray,
    static_scores: dict,
    weights: jnp.ndarray,
    valid_count: jnp.ndarray,
    out_size: int,
    filter_on=None,
):
    """Whole-group selection in ONE pass for sort-eligible groups.

    With purely node-local scores, the sequential argmax is a k-way merge of
    each node's (non-increasing) score sequence — i.e. the globally sorted
    order of all [N,J] trajectory entries. A STABLE descending sort on the
    row-major flattening reproduces the scan's tie-breaks exactly: equal
    scores resolve to the lowest flat index = lowest node index first (the
    scan's first-max argmax), and within a node to increasing commit count
    (forced by sequence order anyway).

    Returns (mono_ok, nodes i32[out_size], jidx i32[out_size], x i32[N]).
    mono_ok is False when some node's score sequence INCREASES at a step
    (balanced-allocation can rise while least-allocated falls); the caller
    must then discard this result and take the scan path — the merge
    argument needs non-increasing rows."""
    N, J, _ = traj.packed.shape
    fo = jnp.ones(NUM_FILTERS, bool) if filter_on is None else filter_on

    res_fail = traj.packed[:, :, CH_RES_FAIL] > 0.5
    port_ok = (traj.packed[:, :, CH_PORT_OK] > 0.5) | ~fo[F_NODE_PORTS]
    storage_ok = traj.packed[:, :, CH_STO_OK] > 0.5
    gpu_ok = traj.packed[:, :, CH_GPU_OK] > 0.5
    mask = (
        static_ok[:, None] & port_ok & ~res_fail & storage_ok & gpu_ok
        & ns.valid[:, None]
    )                                                      # [N,J]

    by_name = dict(_lane_rows(ns, traj, pod, static_scores))
    by_name["topology_spread"] = jnp.full((N, J), 100.0)  # no soft constraints
    score = combine_scores(by_name, weights)
    score = jnp.where(mask, score, -jnp.inf)

    mono_ok = jnp.all(score[:, 1:] <= score[:, :-1])

    flat = score.reshape(-1)
    order = jnp.argsort(-flat, stable=True)[:out_size]
    sel_score = flat[order]
    feasible = jnp.isfinite(sel_score) & (jnp.arange(out_size) < valid_count)
    sel_n = (order // J).astype(jnp.int32)
    sel_j = (order % J).astype(jnp.int32)
    nodes = jnp.where(feasible, sel_n, -1)
    jidx = jnp.where(feasible, sel_j, 0)
    x = jnp.zeros(N, jnp.int32).at[sel_n].add(feasible.astype(jnp.int32))
    return mono_ok, nodes, jidx, x


@sanitizable("ops.fast:cur_at")
@jax.jit
def cur_at(traj: Trajectory, x: jnp.ndarray) -> jnp.ndarray:
    """packed[n, x_n] for every node (reason attribution after a sort-path
    group needs the final-state channels)."""
    return _sel_j(traj.packed, _x_onehot(x, traj.packed.shape[1]))


def _hoisted_values(ns: NodeStatic, cur: jnp.ndarray, flags: GroupFlags) -> dict:
    """Group-invariant values _light_eval needs, computed once per jit call
    (outside the scan body). For a non-GPU group gpu_free never changes, so
    the gpu-share score is frozen at its entry value — cur's CH_GPU_RAW
    channel is constant across lanes for such groups."""
    out = {}
    if not flags.dyn_gpu:
        out["gpu_score"] = _minmax_normalize(cur[:, CH_GPU_RAW], ns.valid)
    return out


SP_IDX = WEIGHT_ORDER.index("topology_spread")
IPA_IDX = WEIGHT_ORDER.index("inter_pod_affinity")
assert SP_IDX == len(WEIGHT_ORDER) - 1 and IPA_IDX == SP_IDX - 1, (
    "the fast paths' partial-sum splits need the carry-coupled terms LAST "
    "in combine_scores' fold order: ..., inter_pod_affinity, topology_spread"
)


@sanitizable("ops.fast:light_scan", static_argnames=("group_size", "flags"))
@functools.partial(jax.jit, static_argnames=("group_size", "flags"))
def light_scan(
    ns: NodeStatic,
    traj: Trajectory,
    carry0: Carry,
    pod: PodRow,
    static_ok: jnp.ndarray,
    static_scores: dict,
    na_ok: jnp.ndarray,
    weights: jnp.ndarray,
    x0: jnp.ndarray,
    offset: jnp.ndarray,
    group_size: int,
    valid_count: jnp.ndarray,
    filter_on=None,
    flags: GroupFlags = ALL_DYNAMIC,
):
    """Select nodes for `group_size` pods of the group, starting from commit
    state x0 (chunks of one group thread x through; everything else is
    reconstructed from x at chunk start). Only steps with offset + i <
    valid_count commit. Returns (x, nodes i32[G], jidx i32[G]).

    The scan carry keeps `cur` = packed[n, x_n] for every node (invariant:
    a commit only advances the chosen node's lane, so one dynamic row update
    per step maintains it) — the step never re-reads the [N,J,*] trajectory.
    Failure reasons are NOT computed per step: an infeasible step commits
    nothing, so the state freezes and every later step of the group fails
    identically — light_reasons attributes the whole failure suffix once.

    flags.micro_spread selects the MICRO body: when topology spread (soft
    and/or hard, non-hostname keys) is the only carry-coupled term, the 9
    other score rows are hoisted into a per-lane partial sum and the step is
    `partial9 + w_sp * spread` (+ the DoNotSchedule skew mask from the same
    reconstructed domain counts) — an exact split of combine_scores'
    explicit left fold because topology_spread is the LAST summand
    (asserted at import)."""
    N = ns.valid.shape[0]
    j_steps = traj.packed.shape[1]
    fo = jnp.ones(NUM_FILTERS, bool) if filter_on is None else filter_on

    if flags.micro_spread:
        return _light_scan_micro(
            ns, traj, carry0, pod, static_ok, static_scores, na_ok, weights,
            x0, offset, group_size, valid_count, fo, flags,
        )

    cur0 = _sel_j(traj.packed, _x_onehot(x0, j_steps))
    hoisted = _hoisted_values(ns, cur0, flags)

    def step(carry_xc, i):
        x, cur = carry_xc
        active = (offset + i) < valid_count
        score, _ = _light_eval(
            ns, carry0, pod, static_ok, static_scores, na_ok, weights, fo,
            x, cur, flags, hoisted,
        )
        node = jnp.argmax(score)
        # any(mask) == the winning score is finite (infeasible rows are -inf)
        ok = (score[node] > -jnp.inf) & active
        node_out = jnp.where(ok, node, -1)
        jidx = jnp.where(ok, x[node], 0)

        onehot = (jnp.arange(N) == node) & ok
        x2 = x + onehot.astype(jnp.int32)
        # Maintain cur = packed[n, x_n]: refresh only the chosen node's row.
        j_next = jnp.clip(x[node] + 1, 0, j_steps - 1)
        new_row = jax.lax.dynamic_slice(
            traj.packed, (node, j_next, 0), (1, 1, N_CH)
        )[0]
        row = jnp.where(ok, new_row, cur[node][None, :])
        cur2 = jax.lax.dynamic_update_slice(cur, row, (node, 0))

        return (x2, cur2), (node_out.astype(jnp.int32), jidx.astype(jnp.int32))

    (x_final, _), (nodes, jidxs) = jax.lax.scan(
        step, (x0, cur0), jnp.arange(group_size)
    )
    return x_final, nodes, jidxs


class SpreadTables(NamedTuple):
    """Loop-invariant spread-reconstruction tables shared VERBATIM by the
    micro body and the domain-merge path — one construction site keeps their
    f32 arithmetic structurally bit-identical (the domain path's exactness
    argument depends on it). in_key_cd is None unless flags.any_hard_spread."""
    k_c: jnp.ndarray       # i32[C] topo key per constraint row
    to_c: jnp.ndarray      # f32[C,D,N] domain membership per constraint
    elig_f: jnp.ndarray    # f32[N] spread eligibility (na_ok & valid)
    match_c: jnp.ndarray   # f32[C] pod matches the constraint's selector
    base_dom: jnp.ndarray  # f32[C,D] eligible-node counts at group entry
    active_c: jnp.ndarray  # bool[C] soft rows (feed the score)
    hard_c: jnp.ndarray    # bool[C] DoNotSchedule rows (feed the mask)
    in_key_cd: jnp.ndarray | None  # bool[C,D] eligible domains of the row's key


def _spread_tables(
    ns: NodeStatic, carry0: Carry, pod: PodRow, na_ok, flags: GroupFlags
) -> SpreadTables:
    """Spread tables (non-hostname keys; soft rows feed the score, hard rows
    the mask — both share the per-row domain-count reconstruction)."""
    active_c = (pod.spread_topo >= 0) & ~pod.spread_hard          # [C]
    hard_c = (pod.spread_topo >= 0) & pod.spread_hard             # [C]
    k_c = jnp.maximum(pod.spread_topo, 0)                         # [C]
    to_c = ns.topo_onehot[k_c]                                    # [C,D,N]
    elig_f = (na_ok & ns.valid).astype(jnp.float32)               # [N]
    base_rows = carry0.sel_counts[pod.spread_sel]                 # [C,N]
    match_c = pod.match_sel[pod.spread_sel].astype(jnp.float32)   # [C]
    counts0 = jnp.where(elig_f > 0, base_rows, 0.0)               # [C,N]
    base_dom = jnp.einsum(
        "cdn,cn->cd", to_c, counts0, precision=jax.lax.Precision.HIGHEST
    )                                                             # [C,D]
    in_key_cd = None
    if flags.any_hard_spread:
        dom_elig = jnp.einsum(
            "cdn,n->cd", to_c, elig_f, precision=jax.lax.Precision.HIGHEST
        ) > 0.0                                                   # [C,D]
        in_key_cd = (ns.domain_key[None, :] == k_c[:, None]) & dom_elig
    return SpreadTables(
        k_c, to_c, elig_f, match_c, base_dom, active_c, hard_c, in_key_cd
    )


def _lane_partials(
    ns, traj, pod, static_scores, static_ok, weights, fo, prefix_end=SP_IDX
):
    """(partial, feas) per lane — the partial is the left-fold prefix of
    combine_scores through WEIGHT_ORDER[:prefix_end] (SP_IDX for the micro
    body's partial9, IPA_IDX for the domain path's partial8; `partial +
    w_ipa*ipa + w_sp*sp` then equals the full fold by construction because
    the coupled terms are last). Feasibility covers the only dynamics a
    micro/domain-eligible group has: ports and resources."""
    p9 = combine_scores(
        _lane_rows(ns, traj, pod, static_scores), weights,
        order=WEIGHT_ORDER[:prefix_end],
    )                                                             # [N,J]
    feas = (
        static_ok[:, None]
        & ((traj.packed[:, :, CH_PORT_OK] > 0.5) | ~fo[F_NODE_PORTS])
        & ~((traj.packed[:, :, CH_RES_FAIL] > 0.5) & fo[F_RESOURCES])
        & ns.valid[:, None]
    )                                                             # [N,J]
    return p9, feas


def _spread_norm(raw: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """The topology-spread score normalization (mirror of
    kernels.score_topology_spread on reconstructed counts); `valid` masks
    which entries may define the max."""
    mx = jnp.max(jnp.where(valid, raw, 0.0))
    return jnp.clip(
        jnp.where(mx > 0, (mx - raw) * 100.0 / jnp.maximum(mx, 1e-9), 100.0),
        0.0,
        100.0,
    )


def _hard_spread_ok(dom, cnt, in_key_cd, hard_c, skew, has_key, f_spread_on):
    """DoNotSchedule skew verdict (mirror kernels.spread_mask via the
    reconstructed dom — integer-exact, so bit-identical). `cnt`/`has_key`
    are per-(constraint, node) in the micro body and per-(constraint, class)
    in the domain path; the arithmetic is identical. Mask args are bool —
    the ONE definition shared by the micro body and the domain scan (the
    exactness contract depends on it)."""
    min_dom = jnp.min(jnp.where(in_key_cd, dom, jnp.inf), axis=1)
    min_c = jnp.where(jnp.isfinite(min_dom), min_dom, 0.0)
    ok = ((cnt + 1.0 - min_c[:, None]) <= skew[:, None] + _EPS) & has_key
    return jnp.all(jnp.where(hard_c[:, None], ok, True), axis=0) | ~f_spread_on


def _light_scan_micro(
    ns, traj, carry0, pod, static_ok, static_scores, na_ok, weights,
    x0, offset, group_size, valid_count, fo, flags,
):
    """The topology-spread micro body (see light_scan docstring). Traced inside
    light_scan's jit; everything here but the scan body is loop-invariant."""
    N = ns.valid.shape[0]
    j_steps = traj.packed.shape[1]
    D = ns.topo_onehot.shape[1]

    p9, feas = _lane_partials(
        ns, traj, pod, static_scores, static_ok, weights, fo
    )
    w_sp = weights[SP_IDX]
    score_lane = jnp.where(feas, p9, -jnp.inf)                    # [N,J]

    st = _spread_tables(ns, carry0, pod, na_ok, flags)
    active_c, hard_c = st.active_c, st.hard_c
    k_c, to_c, elig_f = st.k_c, st.to_c, st.elig_f
    match_c, base_dom = st.match_c, st.base_dom
    if flags.any_hard_spread:
        has_key_cn = (ns.topo[:, k_c] >= 0).T                     # [C,N]
    xf0 = x0.astype(jnp.float32)
    y0 = jnp.einsum(
        "cdn,n->cd", to_c, elig_f * xf0,
        precision=jax.lax.Precision.HIGHEST,
    )                                                             # [C,D]
    # select p9 and feasibility SEPARATELY: _sel_j's one-hot multiply would
    # turn score_lane's -inf entries into NaN (-inf * 0.0) on unselected lanes
    oh0 = _x_onehot(x0, j_steps)
    cur_s0 = jnp.where(
        _sel_j(feas, oh0), _sel_j(p9, oh0), -jnp.inf
    )                                                             # [N]

    def step(carry_xy, i):
        x, cur_s, y = carry_xy
        active = (offset + i) < valid_count
        dom = base_dom + match_c[:, None] * y                     # [C,D]
        cnt = jnp.einsum(
            "cd,cdn->cn", dom, to_c, precision=jax.lax.Precision.HIGHEST
        )                                                         # [C,N]
        raw = jnp.sum(jnp.where(active_c[:, None], cnt, 0.0), axis=0)
        sp = _spread_norm(raw, ns.valid)
        score = cur_s + w_sp * sp                                 # -inf stays
        if flags.any_hard_spread:
            spread_ok = _hard_spread_ok(
                dom, cnt, st.in_key_cd, st.hard_c, pod.spread_skew,
                has_key_cn, fo[F_SPREAD],
            )
            score = jnp.where(spread_ok, score, -jnp.inf)
        node = jnp.argmax(score)
        ok = (score[node] > -jnp.inf) & active
        node_out = jnp.where(ok, node, -1)
        jidx = jnp.where(ok, x[node], 0)

        onehot = (jnp.arange(N) == node) & ok
        x2 = x + onehot.astype(jnp.int32)
        j_next = jnp.clip(x[node] + 1, 0, j_steps - 1)
        new_s = jax.lax.dynamic_slice(score_lane, (node, j_next), (1, 1))
        new_s = jnp.where(ok, new_s, cur_s[node][None, None])
        cur_s2 = jax.lax.dynamic_update_slice(cur_s[:, None], new_s, (node, 0))[
            :, 0
        ]
        to_col = jax.lax.dynamic_slice(to_c, (0, 0, node), (to_c.shape[0], D, 1))
        y2 = y + to_col[:, :, 0] * (
            elig_f[node] * ok.astype(jnp.float32)
        )
        return (x2, cur_s2, y2), (
            node_out.astype(jnp.int32), jidx.astype(jnp.int32)
        )

    (x_final, _, _), (nodes, jidxs) = jax.lax.scan(
        step, (x0, cur_s0, y0), jnp.arange(group_size)
    )
    return x_final, nodes, jidxs


class DomainPlan(NamedTuple):
    """Host-built static structure for the domain-merge path: the partition
    of nodes into combined (spread-domain-tuple, eligibility) classes. All
    nodes in one class are interchangeable w.r.t. every carry-coupled term of
    a micro-eligible group (topology spread is domain-keyed, and these nodes
    share every constraint's domain), so the scan state shrinks from [N] to
    [Dc] — see domain_select.

    Dc is PADDED to max(4, next_pow2(real classes)) for jit-shape reuse; the
    synthetic tail classes hold counts=0 / elig=0 / combo_valid=False, so
    they are permanently exhausted and excluded from the spread max. Callers
    wanting the real class count must use combo_of_node.max()+1, not
    counts.shape[0]."""
    combo_of_node: np.ndarray  # i32[N] class id per node
    counts: np.ndarray         # i32[Dc] trajectory lanes per class (nodes * J)
    offsets: np.ndarray        # i32[Dc] class start in the combo-sorted order
    elig_combo: np.ndarray     # f32[Dc] 1.0 = class counts for spread
    combo_valid: np.ndarray    # bool[Dc] class holds >= 1 valid node
    t_onehot: np.ndarray       # f32[C,D,Dc] spread-row domain membership
    has_key: np.ndarray        # bool[C,Dc] class has spread row c's topo key
    t_aff: np.ndarray          # f32[CA,D,Dc] affinity-row domain membership
    has_key_aff: np.ndarray    # bool[CA,Dc]
    t_anti: np.ndarray         # f32[AT,D,Dc] anti-sym-term domain membership
    has_key_anti: np.ndarray   # bool[AT,Dc]


def _map_onehot(keys_np, act, uniq_cols, col_of, dc, dc_pad, n_domains):
    """(map, onehot, has_key) for one constraint family: map[r, m] = class
    m's domain under row r's topo key (-1 when inactive / key missing)."""
    R = keys_np.shape[0]
    m = np.full((R, dc_pad), -1, np.int32)
    act_rows = np.nonzero(act)[0]
    if act_rows.size:
        m[act_rows[:, None], np.arange(dc)[None, :]] = uniq_cols[
            :, [col_of[int(keys_np[r])] for r in act_rows]
        ].T
    onehot = (
        m[:, None, :] == np.arange(n_domains)[None, :, None]
    ).astype(np.float32)
    return m, onehot


def _domain_plan(
    spread_topo_np: np.ndarray,
    aff_topo_np: np.ndarray,
    anti_topo_np: np.ndarray,
    match_anti_np: np.ndarray,
    topo_np: np.ndarray,
    valid_np: np.ndarray,
    elig_np: np.ndarray,
    j_steps: int,
    n_domains: int,
):
    """Partition nodes into combined domain classes over EVERY coupled
    term's topology key (spread rows, affinity rows, matching registered
    anti-affinity terms) plus the spread-eligibility bit; None when the
    group is too fragmented (Dc > DM_CAP) to beat the scan paths."""
    s_act = spread_topo_np >= 0
    a_act = aff_topo_np >= 0
    t_act = (anti_topo_np >= 0) & match_anti_np
    keys = np.unique(np.concatenate([
        spread_topo_np[s_act], aff_topo_np[a_act], anti_topo_np[t_act],
    ]))
    col_of = {int(k): i for i, k in enumerate(keys)}
    cols = topo_np[:, keys]                                     # [N,K']
    # spread eligibility splits classes ONLY when a spread row consumes it —
    # for pure-affinity groups it would just fragment Dc for nothing
    if s_act.any():
        keymat = np.concatenate(
            [cols, elig_np[:, None].astype(np.int32)], axis=1
        )
    else:
        keymat = cols
    uniq, inv = np.unique(keymat, axis=0, return_inverse=True)
    dc = uniq.shape[0]
    if dc > DM_CAP:
        return None
    dc_pad = max(4, 1 << (dc - 1).bit_length())
    node_counts = np.bincount(inv, minlength=dc_pad)
    counts = (node_counts * j_steps).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    elig_combo = np.zeros(dc_pad, np.float32)
    if s_act.any():
        elig_combo[:dc] = uniq[:, -1]
        uniq_cols = uniq[:, :-1]                                # [dc,K']
    else:
        uniq_cols = uniq  # elig column absent (unused without spread rows)
    combo_valid = np.zeros(dc_pad, bool)
    np.logical_or.at(combo_valid, inv, valid_np)
    map_s, t_onehot = _map_onehot(
        spread_topo_np, s_act, uniq_cols, col_of, dc, dc_pad, n_domains
    )
    map_a, t_aff = _map_onehot(
        aff_topo_np, a_act, uniq_cols, col_of, dc, dc_pad, n_domains
    )
    map_t, t_anti = _map_onehot(
        anti_topo_np, t_act, uniq_cols, col_of, dc, dc_pad, n_domains
    )
    return DomainPlan(
        inv.astype(np.int32), counts, offsets, elig_combo, combo_valid,
        t_onehot, map_s >= 0, t_aff, map_a >= 0, t_anti, map_t >= 0,
    )


@sanitizable(
    "ops.fast:domain_select",
    static_argnames=("group_size", "l_cap", "flags"),
)
@functools.partial(
    jax.jit, static_argnames=("group_size", "l_cap", "flags")
)
def domain_select(
    ns: NodeStatic,
    traj: Trajectory,
    carry0: Carry,
    pod: PodRow,
    static_ok: jnp.ndarray,
    static_scores: dict,
    na_ok: jnp.ndarray,
    weights: jnp.ndarray,
    combo_of_node: jnp.ndarray,
    counts: jnp.ndarray,
    offsets: jnp.ndarray,
    elig_combo: jnp.ndarray,
    combo_valid: jnp.ndarray,
    t_onehot: jnp.ndarray,
    has_key_cm: jnp.ndarray,
    t_aff: jnp.ndarray,
    has_key_aff: jnp.ndarray,
    t_anti: jnp.ndarray,
    has_key_anti: jnp.ndarray,
    group_size: int,
    l_cap: int,
    valid_count: jnp.ndarray,
    filter_on=None,
    flags: GroupFlags = ALL_DYNAMIC,
):
    """Whole-group selection with an O(Dc) scan state for domain-eligible
    groups: every carry-coupled term — topology spread, required/preferred
    inter-pod affinity, anti-affinity symmetry — keyed by non-hostname
    topology keys (flags.domain_aff), with no gpu/storage dynamics.

    Two structural facts shrink the scan from O(N) to O(Dc) per step:
      1. The spread term is DOMAIN-keyed: every node of a combined class
         (same domain under each constraint, same eligibility) shares the
         same spread score and DoNotSchedule verdict at every step.
      2. Within a class, relative order is by the node-local partial score
         alone (the spread addend is class-constant), so the scan's pick
         sequence inside a class is the sort-path merge: one stable sort of
         all [N,J] lanes keyed (class, score desc) — ties resolve to the
         lowest flat index = the scan's first-max argmax.

    The scan then walks per-class HEAD pointers: each step scores only the
    Dc class heads (head partial + w_sp * spread(class)), pops the winner,
    and updates the [Dc] domain-count state. Cross-class ties pick the
    lowest head node index, which equals the global argmax tie-break because
    each class head is its class's lowest-index maximum.

    Exactness: head partials are the same f32 lane values; domain counts
    for spread, affinity and anti-symmetry are reconstructed with the
    shared helpers' einsum arithmetic (exact integer f32); the spread and
    min-max normalizations apply the identical expressions; and the fold
    `(partial8 + w_ipa*ipa) + w_sp*sp` is combine_scores' own left
    association — so every per-step total is bit-identical to the scan
    bodies'. mono_ok False (a lane sequence rose) voids fact 2; the caller
    falls back to the micro scan (spread-only groups) or the light scan.

    Returns (mono_ok, nodes i32[group_size], jidx i32[group_size], x i32[N]).
    """
    N, J, _ = traj.packed.shape
    Dc = counts.shape[0]
    fo = jnp.ones(NUM_FILTERS, bool) if filter_on is None else filter_on

    # partial8 lanes: the fold prefix BEFORE both coupled terms; the step
    # adds w_ipa*ipa(class) then w_sp*sp(class), reproducing the full fold.
    p8, feas = _lane_partials(
        ns, traj, pod, static_scores, static_ok, weights, fo,
        prefix_end=IPA_IDX,
    )
    score_lane = jnp.where(feas, p8, -jnp.inf)
    mono_ok = jnp.all(score_lane[:, 1:] <= score_lane[:, :-1])

    # Stable sort keyed (class asc, score desc): within a class, lanes land
    # in exactly the order the scan would pop them (ties keep flat order =
    # lowest node first, then increasing j within a node).
    flat_combo = jnp.broadcast_to(combo_of_node[:, None], (N, J)).reshape(-1)
    neg = (-score_lane).reshape(-1)
    flat_idx = jnp.arange(N * J, dtype=jnp.int32)
    _, sneg, sidx = jax.lax.sort(
        (flat_combo, neg, flat_idx), num_keys=2, is_stable=True
    )
    gidx = jnp.clip(offsets[:, None] + jnp.arange(l_cap)[None, :], 0, N * J - 1)
    in_range = jnp.arange(l_cap)[None, :] < counts[:, None]
    hscore = jnp.where(in_range, -sneg[gidx], -jnp.inf)           # [Dc,L]
    hflat = sidx[gidx]
    hnode = (hflat // J).astype(jnp.int32)
    hj = (hflat % J).astype(jnp.int32)
    cap_eff = jnp.minimum(counts, l_cap)

    # spread tables — the micro body's own construction (shared helper, so
    # the arithmetic cannot drift between the two bodies)
    st = _spread_tables(ns, carry0, pod, na_ok, flags)
    w_sp = weights[SP_IDX]
    w_ipa = weights[IPA_IDX]
    any_aff = flags.any_req_aff or flags.any_pref_aff or flags.any_anti_sym
    valid_f = ns.valid.astype(jnp.float32)

    if flags.any_req_aff or flags.any_pref_aff:
        # inter-pod affinity tables (mirror _light_eval's one_aff/one_asc:
        # _domain_counts with elig=None counts over ALL valid nodes)
        k_a = jnp.maximum(pod.aff_topo, 0)
        to_a = ns.topo_onehot[k_a]                                # [CA,D,N]
        base_rows_a = carry0.sel_counts[pod.aff_sel]              # [CA,N]
        match_a = pod.match_sel[pod.aff_sel].astype(jnp.float32)  # [CA]
        counts0_a = jnp.where(valid_f > 0, base_rows_a, 0.0)
        base_dom_a = jnp.einsum(
            "cdn,cn->cd", to_a, counts0_a,
            precision=jax.lax.Precision.HIGHEST,
        )                                                         # [CA,D]
        in_key_a = (ns.domain_key[None, :] == k_a[:, None]) & (
            jnp.einsum(
                "cdn,n->cd", to_a, valid_f,
                precision=jax.lax.Precision.HIGHEST,
            ) > 0.0
        )                                                         # [CA,D]
        req_t = (pod.aff_topo >= 0) & pod.aff_required
        pref_t = (pod.aff_topo >= 0) & ~pod.aff_required
        self_match_a = pod.match_sel[pod.aff_sel]                 # [CA] bool
        any_pref_active = jnp.any(pref_t)
    if flags.any_anti_sym:
        # anti-affinity symmetry tables (mirror _light_eval's one_sym)
        k_t = jnp.maximum(ns.anti_topo, 0)
        to_t = ns.topo_onehot[k_t]                                # [AT,D,N]
        counts0_t = jnp.where(valid_f > 0, carry0.anti_counts, 0.0)
        base_dom_t = jnp.einsum(
            "tdn,tn->td", to_t, counts0_t,
            precision=jax.lax.Precision.HIGHEST,
        )                                                         # [AT,D]
        active_sym = (ns.anti_topo >= 0) & pod.match_anti         # [AT]

    any_spread = flags.any_soft_spread or flags.any_hard_spread

    def step(carry_hy, i):
        h, y = carry_hy
        if any_spread:
            y_elig = y * elig_combo
            dom = st.base_dom + st.match_c[:, None] * jnp.einsum(
                "cdm,m->cd", t_onehot, y_elig,
                precision=jax.lax.Precision.HIGHEST,
            )                                                     # [C,D]
            cnt_cm = jnp.einsum(
                "cd,cdm->cm", dom, t_onehot,
                precision=jax.lax.Precision.HIGHEST,
            )                                                     # [C,Dc]
            raw = jnp.sum(
                jnp.where(st.active_c[:, None], cnt_cm, 0.0), axis=0
            )
            sp = _spread_norm(raw, combo_valid)                   # [Dc]
        else:
            # no active spread row: raw ≡ 0 => the 100.0 branch (the same
            # prune _light_eval applies)
            sp = jnp.full(Dc, 100.0)
        hc = jnp.clip(h, 0, l_cap - 1)[:, None]
        hs = jnp.where(
            h < cap_eff,
            jnp.take_along_axis(hscore, hc, axis=1)[:, 0],
            -jnp.inf,
        )

        ipa = jnp.zeros(Dc)
        aff_ok = jnp.ones(Dc, bool)
        if flags.any_req_aff or flags.any_pref_aff:
            # every pod of the group is identical, so its commits add
            # match_a per class commit to the row's selector counts
            dom_a = base_dom_a + match_a[:, None] * jnp.einsum(
                "cdm,m->cd", t_aff, y, precision=jax.lax.Precision.HIGHEST
            )                                                     # [CA,D]
            cnt_a = jnp.einsum(
                "cd,cdm->cm", dom_a, t_aff,
                precision=jax.lax.Precision.HIGHEST,
            )                                                     # [CA,Dc]
            if flags.any_req_aff:
                total_a = jnp.sum(
                    jnp.where(in_key_a, dom_a, 0.0), axis=1
                )                                                 # [CA]
                feasible = (cnt_a > 0) | (
                    self_match_a[:, None] & (total_a[:, None] == 0)
                )
                feasible = feasible & has_key_aff
                ok_t = jnp.where(
                    pod.aff_anti[:, None], cnt_a == 0, feasible
                )
                aff_ok = aff_ok & jnp.all(
                    jnp.where(req_t[:, None], ok_t, True), axis=0
                )
            if flags.any_pref_aff:
                signed = jnp.where(
                    pod.aff_anti, -pod.aff_weight, pod.aff_weight
                )[:, None] * cnt_a
                raw_a = jnp.sum(
                    jnp.where(pref_t[:, None], signed, 0.0), axis=0
                )                                                 # [Dc]
                # the oracle's own normalization over valid classes
                ipa = jnp.where(
                    any_pref_active,
                    _minmax_normalize(raw_a, combo_valid),
                    0.0,
                )
        if flags.any_anti_sym:
            dom_t = base_dom_t + pod.own_anti[:, None] * jnp.einsum(
                "tdm,m->td", t_anti, y, precision=jax.lax.Precision.HIGHEST
            )                                                     # [AT,D]
            cnt_t = jnp.einsum(
                "td,tdm->tm", dom_t, t_anti,
                precision=jax.lax.Precision.HIGHEST,
            )                                                     # [AT,Dc]
            ok_t = (cnt_t == 0) | ~has_key_anti
            aff_ok = aff_ok & jnp.all(
                jnp.where(active_sym[:, None], ok_t, True), axis=0
            )

        # the full fold: ((partial8 + w_ipa*ipa) + w_sp*sp)
        total = (hs + w_ipa * ipa) + w_sp * sp
        if flags.any_hard_spread:
            spread_ok = _hard_spread_ok(
                dom, cnt_cm, st.in_key_cd, st.hard_c, pod.spread_skew,
                has_key_cm, fo[F_SPREAD],
            )
            total = jnp.where(spread_ok, total, -jnp.inf)
        if any_aff:
            total = jnp.where(
                aff_ok | ~fo[F_POD_AFFINITY], total, -jnp.inf
            )
        node_h = jnp.take_along_axis(hnode, hc, axis=1)[:, 0]
        j_h = jnp.take_along_axis(hj, hc, axis=1)[:, 0]
        mx_t = jnp.max(total)
        m = jnp.argmin(jnp.where(total == mx_t, node_h, N))
        ok = (mx_t > -jnp.inf) & (i < valid_count)
        node_out = jnp.where(ok, node_h[m], -1)
        j_out = jnp.where(ok, j_h[m], 0)
        oh = (jnp.arange(Dc) == m) & ok
        return (
            h + oh.astype(jnp.int32),
            y + oh.astype(jnp.float32),
        ), (node_out.astype(jnp.int32), j_out.astype(jnp.int32))

    # The step body is tiny ([Dc]-sized ops), so per-iteration dispatch
    # overhead dominates — unrolling amortizes it without changing the op
    # sequence (group_size is a multiple of 16: _bucket_light floors at 32).
    _, (nodes, jidxs) = jax.lax.scan(
        step,
        (jnp.zeros(Dc, jnp.int32), jnp.zeros(Dc, jnp.float32)),
        jnp.arange(group_size),
        unroll=16,
    )
    sel_n = jnp.clip(nodes, 0, N - 1)
    x = jnp.zeros(N, jnp.int32).at[sel_n].add((nodes >= 0).astype(jnp.int32))
    return mono_ok, nodes, jidxs, x


@sanitizable("ops.fast:light_reasons", static_argnames=("flags",))
@functools.partial(jax.jit, static_argnames=("flags",))
def light_reasons(
    ns: NodeStatic,
    carry0: Carry,
    pod: PodRow,
    static_ok: jnp.ndarray,
    static_ff: jnp.ndarray,
    static_scores: dict,
    na_ok: jnp.ndarray,
    weights: jnp.ndarray,
    x: jnp.ndarray,
    cur: jnp.ndarray,
    filter_on=None,
    flags: GroupFlags = ALL_DYNAMIC,
) -> jnp.ndarray:
    """Failure-reason histogram i32[F] at state (x, cur) — evaluated once per
    group for its failure suffix (identical for every failed pod, because a
    failed step commits nothing). Matches the grouped path's per-step nested
    first-fail attribution exactly."""
    fo = jnp.ones(NUM_FILTERS, bool) if filter_on is None else filter_on
    _, p = _light_eval(
        ns, carry0, pod, static_ok, static_scores, na_ok, weights, fo, x, cur,
        flags, _hoisted_values(ns, cur, flags),
    )
    first_fail = jnp.where(
        static_ff < NUM_FILTERS,
        static_ff,
        jnp.where(
            ~p["port_ok"],
            F_NODE_PORTS,
            jnp.where(
                p["res_fail"],
                F_RESOURCES,
                jnp.where(
                    ~p["spread_ok"],
                    F_SPREAD,
                    jnp.where(
                        ~p["aff_ok"],
                        F_POD_AFFINITY,
                        jnp.where(
                            ~p["storage_ok"],
                            F_STORAGE,
                            jnp.where(~p["gpu_ok"], F_GPU, NUM_FILTERS),
                        ),
                    ),
                ),
            ),
        ),
    )
    return jnp.zeros(NUM_FILTERS, jnp.int32).at[
        jnp.clip(first_fail, 0, NUM_FILTERS - 1)
    ].add(jnp.where((first_fail < NUM_FILTERS) & ns.valid, 1, 0))


@sanitizable("ops.fast:gather_takes")
@jax.jit
def gather_takes(traj: Trajectory, nodes: jnp.ndarray, jidxs: jnp.ndarray):
    """Per-pod allocation takes from (chosen node, commit index) — one gather
    per group after all chunks finish."""
    N = traj.packed.shape[0]
    node_c = jnp.clip(nodes, 0, N - 1)
    placed = (nodes >= 0)[:, None]
    gpu_take = jnp.where(placed, traj.gpu_take[node_c, jidxs], 0.0)
    vg_take = jnp.where(placed, traj.vg_take[node_c, jidxs], 0.0)
    dev_take = jnp.where(placed, traj.dev_take[node_c, jidxs], 0.0)
    return gpu_take, vg_take, dev_take


@sanitizable("ops.fast:exit_carry")
@jax.jit
def exit_carry(
    ns: NodeStatic, carry0: Carry, pod: PodRow, traj: Trajectory, x: jnp.ndarray
) -> Carry:
    """Fold the group's commits (x per node) back into a Carry, bit-identical
    to the scan's iterative commits: node-local rows are gathered from the
    trajectory (capturing the scan's exact f32 subtraction sequence); the
    integer count tables are reconstructed as base + per-commit-add * x."""
    xf = x.astype(jnp.float32)
    oh = _x_onehot(x, traj.packed.shape[1])
    add_any, add_wild, add_ipc = port_adds(
        carry0.port_any.shape[0], carry0.port_ipc.shape[0], pod
    )
    return Carry(
        free=_sel_j(traj.free, oh),
        sel_counts=carry0.sel_counts
        + pod.match_sel.astype(jnp.float32)[:, None] * xf[None, :],
        gpu_free=_sel_j(traj.gpu_free, oh),
        vg_free=_sel_j(traj.vg_free, oh),
        dev_free=_sel_j(traj.dev_free, oh),
        port_any=carry0.port_any + add_any[:, None] * xf[None, :],
        port_wild=carry0.port_wild + add_wild[:, None] * xf[None, :],
        port_ipc=carry0.port_ipc + add_ipc[:, None] * xf[None, :],
        anti_counts=carry0.anti_counts + pod.own_anti[:, None] * xf[None, :],
    )


def _traj_len(
    free_np: np.ndarray, valid_np: np.ndarray, req_np: np.ndarray, length: int
):
    """Trajectory length needed for this group: the most commits any node can
    locally absorb (resource bound; every pod carries an implicit pods-slot
    request, so this is finite) + slack for f32 drift, capped by group size."""
    pos = req_np > 1e-9
    if not pos.any():
        return None
    caps = np.floor((free_np[:, pos] + _EPS) / req_np[pos]).min(axis=1)
    caps = np.clip(caps, 0.0, None)
    caps = caps[valid_np[: caps.shape[0]]]
    c_max = float(caps.max()) if caps.size else 0.0
    if not np.isfinite(c_max):
        return None
    return int(min(c_max + 2, length + 1))


def _bucket_j(j: int) -> int:
    return 1 << max(int(j) - 1, 7).bit_length()


def _bucket_light(n: int) -> int:
    """Chunk bucket for the light scan: light steps are cheap but not free,
    so pow2 buckets (up to 2x padding waste) hurt more than the few extra
    compiles of 2048-granular buckets."""
    if n <= 2048:
        return _bucket(n)
    return (n + 2047) // 2048 * 2048


def schedule_batch_fast(
    ns: NodeStatic,
    carry: Carry,
    batch: PodBatch,
    weights,
    max_group_chunk: int = DEFAULT_GROUP_CHUNK,
    force_fast: bool = False,
    filter_on=None,
    extra_filters=(),
    extra_scores=(),
) -> Tuple[Carry, np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """schedule_batch semantics (bit-identical placements/reasons/takes/carry)
    with per-group trajectory acceleration; same returns as
    schedule_batch_grouped. Groups too small to amortize a trajectory (or with
    absurdly deep ones, J > J_CAP) take the grouped per-pod scan instead.
    `force_fast` disables the amortization heuristic (tests). Out-of-tree
    plugins (extra_filters/extra_scores) may read the carry arbitrarily,
    which voids the trajectory's node-local-evolution premise — any plugin
    routes the whole batch through the grouped path."""
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
    N = ns.valid.shape[0]
    # One device->host sync for everything the host plans from: the node
    # table's valid/topology planes, the filter mask, and the batch-entry
    # free for ALL groups' trajectory lengths (free only shrinks while a
    # batch schedules — no evictions mid-batch — so per-node commit caps
    # computed from it are safe upper bounds for every later group).
    valid_np, anti_topo_np, topo_np, filter_np, free_np = fetch(
        (ns.valid, ns.anti_topo, ns.topo, filter_on, carry.free),
        "batch-entry",
    )
    # A disabled NodeResourcesFit filter voids the trajectory-length bound
    # (the resource filter is what stops a node's commit count at c_max, see
    # _traj_len) — those profiles take the per-pod grouped path.
    res_filter_on = (
        filter_np is None or bool(filter_np[F_RESOURCES])
    ) and not extra_filters and not extra_scores
    free_entry = free_np if res_filter_on else None
    n_domains = int(ns.topo_onehot.shape[1])

    for start, length in group_runs(batch):
        row = jax.tree.map(lambda a: a[start], rows_all)
        flags = group_flags(
            {
                "hp_pid": batch.hp_pid[start],
                "has_local": batch.has_local[start],
                "gpu_mem": batch.gpu_mem[start],
                "spread_topo": batch.spread_topo[start],
                "spread_hard": batch.spread_hard[start],
                "aff_topo": batch.aff_topo[start],
                "aff_required": batch.aff_required[start],
                "match_anti": batch.match_anti[start],
            },
            anti_topo_np,
        )
        j_need = (
            _traj_len(free_entry, valid_np, batch.req[start], length)
            if free_entry is not None and (force_fast or length >= 64)
            else None
        )
        # Break-even: fast cost ≈ j_need heavy trajectory steps + length
        # cheap selection steps (sort ≈ free, scan ≈ heavy/8), vs length
        # heavy steps on the grouped path — fast wins from ~1.2x j_need;
        # 1.5x keeps margin for the fixed exit/gather overhead.
        use_fast = (
            j_need is not None
            and _bucket_j(j_need) <= J_CAP
            and (force_fast or length >= max(3 * j_need // 2, 64))
        )
        if not use_fast:
            _count_path("grouped")
            done = 0
            while done < length:
                n = min(length - done, max_group_chunk)
                g = _bucket(n)
                _progress(f"group@{start} len={length} grouped chunk g={g}")
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
            continue

        j_steps = _bucket_j(j_need)
        _progress(f"group@{start} len={length} traj j={j_steps} N={N}")
        traj, static_ok, static_ff, static_scores, na_ok = build_trajectory(
            ns, carry, row, weights, j_steps, filter_on
        )
        sl = slice(start, start + length)

        def finish(nodes_dev, jidx_dev, x_dev, mono_dev=None):
            """Dispatch the group's whole tail (takes, failure-suffix reason
            row, exit carry) and fetch every host-needed value in ONE
            device_get — each host sync pays a device round trip, so
            the reason row is computed speculatively (one cheap kernel
            instead of a second sync when failures exist) and the sort/
            domain mono verdict rides the same fetch (on False the caller
            discards everything fetched and replays with a scan)."""
            take_dev, vg_dev, dev_dev = gather_takes(traj, nodes_dev, jidx_dev)
            reason_dev = light_reasons(
                ns, carry, row, static_ok, static_ff, static_scores,
                na_ok, weights, x_dev, cur_at(traj, x_dev), filter_on, flags,
            )
            carry_dev = exit_carry(ns, carry, row, traj, x_dev)
            _progress(f"group@{start} finish sync")
            mono_np, *got = fetch(
                (jnp.bool_(True) if mono_dev is None else mono_dev,
                 nodes_dev, take_dev, vg_dev, dev_dev, reason_dev),
                "group-finish",
            )
            return bool(mono_np), tuple(got), carry_dev

        def commit(got, carry_dev):
            nonlocal carry
            nodes_np, take_np, vg_np, dev_np, reason_np = got
            nodes_out[sl] = nodes_np
            take_out[sl] = take_np.astype(np.int32)
            vg_out[sl] = vg_np
            dev_out[sl] = dev_np
            if (nodes_np < 0).any():
                # A failed step commits nothing, so the whole failure suffix
                # of the group shares one state — one reason row covers it.
                reasons_out[sl][nodes_np < 0] = reason_np
            carry = carry_dev

        committed = False

        # Sort path: whole group in one device call when scores are purely
        # node-local and per-node non-increasing (checked on device; the
        # check's verdict is fetched together with the speculated tail).
        out_size = _bucket_light(length)
        if _sortable(flags) and out_size <= N * j_steps:
            _progress(f"group@{start} sort out={out_size}")
            mono, nodes_d, jidx_d, x = sort_select(
                ns, traj, row, static_ok, static_scores, weights,
                jnp.int32(length), out_size, filter_on,
            )
            mono_ok, got, carry_dev = finish(
                nodes_d[:length], jidx_d[:length], x, mono
            )
            if mono_ok:
                _count_path("sort")
                commit(got, carry_dev)
                committed = True
            else:
                # a balanced-allocation rise broke monotonicity — the merge
                # argument doesn't hold, replay with the scan below
                _count_path("sort_fallback")

        if not committed and flags.domain_aff:
            # Domain-merge path: O(Dc) scan state instead of O(N). The class
            # partition needs the pod's spread eligibility on host (one small
            # bool[N] transfer per group).
            elig_np = fetch(na_ok, "domain-eligibility") & valid_np
            plan = _domain_plan(
                batch.spread_topo[start], batch.aff_topo[start],
                anti_topo_np, batch.match_anti[start], topo_np, valid_np,
                elig_np, j_steps, n_domains,
            )
            if plan is not None:
                g = _bucket_light(length)
                l_cap = _bucket_light(min(int(plan.counts.max()), length))
                _progress(f"group@{start} domain g={g} l_cap={l_cap}")
                mono, nodes_w, jidx_w, x_w = domain_select(
                    ns, traj, carry, row, static_ok, static_scores, na_ok,
                    weights, plan.combo_of_node, plan.counts, plan.offsets,
                    plan.elig_combo, plan.combo_valid, plan.t_onehot,
                    plan.has_key, plan.t_aff, plan.has_key_aff, plan.t_anti,
                    plan.has_key_anti, g, l_cap, jnp.int32(length),
                    filter_on, flags,
                )
                mono_ok, got, carry_dev = finish(
                    nodes_w[:length], jidx_w[:length], x_w, mono
                )
                if mono_ok:
                    _count_path("domain")
                    commit(got, carry_dev)
                    committed = True
                else:
                    # a rising lane sequence voids the within-class merge
                    # argument — replay with the micro scan
                    _count_path("domain_fallback")

        if not committed:
            _count_path("micro" if flags.micro_spread else "scan")
            x = jnp.zeros(N, jnp.int32)
            chunks = []
            done = 0
            while done < length:
                n = min(length - done, max_group_chunk)
                g = _bucket_light(n)
                _progress(f"group@{start} light-scan chunk g={g} done={done}")
                x, nodes, jidxs = light_scan(
                    ns, traj, carry, row, static_ok, static_scores,
                    na_ok, weights, x, jnp.int32(done), g,
                    jnp.int32(length), filter_on, flags,
                )
                chunks.append((n, nodes, jidxs))
                done += n
            # One transfer per group (per-chunk np.asarray syncs dominated
            # the host-side cost).
            nodes_d = jnp.concatenate([c[1][: c[0]] for c in chunks])
            jidx_d = jnp.concatenate([c[2][: c[0]] for c in chunks])
            _, got, carry_dev = finish(nodes_d, jidx_d, x)
            commit(got, carry_dev)

    return carry, nodes_out, reasons_out, take_out, vg_out, dev_out


# ---------------------------------------------------------------------------
# Scenario axis: vmap the whole scan (ROADMAP item 1)
# ---------------------------------------------------------------------------

# Scenario-count bucket: the leading axis of every batched call is padded to a
# multiple of this (pad scenarios are copies of scenario 0, results discarded)
# so a sweep whose scenario count wobbles between calls still reuses one
# compiled program per (node, pod) shape.
SCENARIO_BUCKET = 8


def scenario_bucket(s: int, floor: int = 0) -> int:
    """Padded scenario count for `s` real lanes. `floor` (itself a padded
    count) keeps a warm shape warm across consecutive serving packs: a
    3-lane pack following an 8-lane pack pads back to 8 and reuses the
    compiled program instead of tracing a 8-vs-smaller shape pair (the
    continuous-batching loop passes the previous pack's pad here)."""
    return round_up(max(int(s), 1, int(floor)), SCENARIO_BUCKET)


# (N, P) shape key -> set of padded scenario counts seen: each distinct entry
# in a value set is one compiled program for that bucket. The recompile guard
# (analysis/jaxpr_audit.py) asserts every bucket stays at <= 2 programs across
# a whole capacity sweep.
_SCENARIO_PROGRAMS: dict = {}


def scenario_programs() -> dict:
    """Snapshot of {(n_nodes, n_pods): {padded scenario counts}} traced so far
    through schedule_scenarios_host."""
    return {k: set(v) for k, v in _SCENARIO_PROGRAMS.items()}


def reset_scenario_programs() -> None:
    # reachable from a watchdog-guarded driver callable, but guarded_call's
    # supervising thread parks in done.wait() until the worker finishes —
    # the callable has the drivers' shared state to itself (warmup and the
    # recompile guard call this between runs, never concurrently with a
    # sweep)
    _SCENARIO_PROGRAMS.clear()  # osim: audit-ok[race]


@sanitizable("ops.fast:schedule_scenarios", donate_argnums=(1,))
@functools.partial(jax.jit, donate_argnums=(1,))
def schedule_scenarios(
    ns: NodeStatic,
    carry_s: Carry,
    pods: PodRow,
    weights_s: jnp.ndarray,
    valid_s: jnp.ndarray,
    filter_on=None,
):
    """The naive commit scan under jax.vmap over a leading scenario axis.

    Scenarios share one padded node tensor (`ns`) and one pod sequence
    (`pods`, broadcast); what varies per scenario is the node-valid mask
    `valid_s` bool[S,N], the carry (every Carry leaf stacked on axis 0) and
    the score-weight vector `weights_s` f32[S,W].

    Exactness: every filter ANDs with ns.valid, reason counts gate on
    ns.valid, score normalization masks by it, and _domain_counts
    eligibility-masks its counts — so a row that is encoded-real but
    valid=False for a scenario is fully inert, and lane s is bit-identical
    to a serial schedule_batch over a table whose valid mask is valid_s[s].
    Vmapping the NAIVE scan (not the host-driven fast paths) keeps the whole
    sweep a single device dispatch; the fast paths prove bit-identity to
    this same scan, so per-scenario results match serial simulate() output.

    Returns (carry_s, nodes i32[S,P], reasons i32[S,P,F], gpu_take i32[S,P,G],
    vg_take f32[S,P,V], dev_take f32[S,P,DV]).
    """

    def one(valid, carry, weights):
        ns_s = ns._replace(valid=valid)

        def step(c, pod):
            return schedule_step(ns_s, weights, c, pod, filter_on)

        final, (nodes, reasons, gpu_take, vg_take, dev_take) = jax.lax.scan(
            step, carry, pods
        )
        return final, nodes, reasons, gpu_take, vg_take, dev_take

    return jax.vmap(one)(valid_s, carry_s, weights_s)


# ---------------------------------------------------------------------------
# Chunked commit driver: preemption-safe execution (docs/durability.md)
# ---------------------------------------------------------------------------

def commit_chunk_size() -> int:
    """Pods per chunk for the chunked commit driver (`OSIM_COMMIT_CHUNK`).
    0 (the default) keeps the monolithic single-scan dispatch. Any positive
    value splits the per-pod commit scan into an outer host loop of
    fixed-size chunks so a long plan can checkpoint between chunks — the
    chunk size is the rung: every chunk call compiles ONE program per
    (node-bucket, chunk) pair regardless of total pod count."""
    try:
        return max(0, int(os.environ.get("OSIM_COMMIT_CHUNK", "0") or 0))
    except ValueError:
        return 0


def scenario_carry_digest(carry_s: Carry) -> int:
    """Digest of a (stacked) carry: per-leaf device `digest_fold` reductions
    chained in Carry._fields order. Only S 4-byte scalars transfer; the
    result is bit-identical to `scenario_carry_digest_host` over the
    device_get of the same carry (delta.digest_fold_host is the numpy twin),
    which is what lets a resumed process verify a snapshot without a
    device round-trip."""
    parts = [_delta.digest_fold(getattr(carry_s, f)) for f in Carry._fields]
    return _delta.combine_digests(
        int(p) for p in fetch(parts, "carry-digest")
    )


def scenario_carry_digest_host(leaves: dict) -> int:
    """Host twin of scenario_carry_digest over {field: np.ndarray} leaves."""
    return _delta.combine_digests(
        _delta.digest_fold_host(np.asarray(leaves[f])) for f in Carry._fields
    )


def carry_to_host(carry_s: Carry) -> dict:
    """device_get every Carry leaf -> {field: np.ndarray} (snapshot form)."""
    got = fetch(carry_s, "carry-snapshot")
    return {f: np.asarray(getattr(got, f)) for f in Carry._fields}


def carry_from_host(carry_s: Carry, leaves: dict) -> Carry:
    """Re-pin host snapshot leaves onto the CURRENT carry's shardings.

    `carry_s` is whatever the resumed (or recovering) process built for the
    mesh it has NOW — its values are discarded; only its per-leaf
    NamedShardings are kept. This is the elastic-resume step: a snapshot
    taken on a 4-device mesh lands on 2 devices or plain CPU by being
    device_put against the new layout, and the commit arithmetic is
    sharding-independent (PR 14's digest-identical lanes), so the resumed
    plan stays byte-identical."""
    for f in Carry._fields:
        cur = getattr(carry_s, f)
        want = tuple(cur.shape)
        have = tuple(np.asarray(leaves[f]).shape)
        if want != have:
            raise ValueError(
                f"carry snapshot leaf {f!r} has shape {have}, current plan "
                f"expects {want} — snapshot is from a different plan shape"
            )
    return Carry(*(
        jax.device_put(
            np.asarray(leaves[f]), getattr(carry_s, f).sharding
        )
        for f in Carry._fields
    ))


@sanitizable("ops.fast:schedule_scenarios_chunked", donate_argnums=(1,))
@functools.partial(jax.jit, donate_argnums=(1,))
def schedule_scenarios_chunked(
    ns: NodeStatic,
    carry_s: Carry,
    pods: PodRow,
    weights_s: jnp.ndarray,
    valid_s: jnp.ndarray,
    count: jnp.ndarray,
    filter_on=None,
):
    """One fixed-size chunk of the scenario commit scan, count-gated.

    Per-step arithmetic is exactly schedule_scenarios' (the same
    schedule_step under the same vmap); the only addition is the `count`
    gate: step i with i >= count is a pad step whose carry writes are
    masked out leaf-by-leaf (`jnp.where` on every Carry leaf — for live
    steps the where selects the new value bitwise, so real steps are
    untouched). Chaining ceil(P/C) chunk calls over a pod sequence padded
    to a multiple of C therefore yields a final carry and (host-trimmed)
    outputs byte-identical to the single monolithic scan — the property
    tests/test_checkpoint.py asserts by digest across seeds.

    Pad-step OUTPUTS are garbage by design: pads only ever trail the last
    chunk, and the host driver trims them before concatenating. `count` is
    a traced i32 scalar so the partial last chunk reuses the full chunk's
    compiled program (one program per (N, C) shape, rung-disciplined).
    `carry_s` is donated, exactly like schedule_scenarios."""
    p_chunk = jax.tree_util.tree_leaves(pods)[0].shape[0]
    idx = jnp.arange(p_chunk, dtype=jnp.int32)

    def one(valid, carry, weights):
        ns_s = ns._replace(valid=valid)

        def step(c, xs):
            i, pod = xs
            c2, out = schedule_step(ns_s, weights, c, pod, filter_on)
            live = i < count
            c2 = jax.tree_util.tree_map(
                lambda new, old: jnp.where(live, new, old), c2, c
            )
            return c2, out

        final, (nodes, reasons, gpu_take, vg_take, dev_take) = jax.lax.scan(
            step, carry, (idx, pods)
        )
        return final, nodes, reasons, gpu_take, vg_take, dev_take

    return jax.vmap(one)(valid_s, carry_s, weights_s)


@sanitizable("ops.fast:schedule_universes", donate_argnums=(1,))
@functools.partial(jax.jit, donate_argnums=(1,))
def schedule_universes(
    ns_s: NodeStatic,
    carry_s: Carry,
    pods_s: PodRow,
    weights_s: jnp.ndarray,
    filter_on=None,
):
    """Exhaustive-checking axis: vmap the naive commit scan over universes
    where EVERYTHING varies per lane — node tensors, carry, pod sequence and
    weights (every NodeStatic/Carry/PodRow leaf stacked on axis 0, scalars
    widened to [S]).

    schedule_scenarios varies only (valid, carry, weights) around one shared
    cluster; `simon prove` (analysis/semantics.py) needs whole distinct
    *universes* per lane — different node capacities, labels, taints, pod
    requests, selectors — packed from a small catalog by stamped gather. The
    body is the same naive scan that every fast path proves bit-identity to,
    so lane u reproduces exactly what a serial schedule_batch over universe
    u's table would commit.

    Returns (carry_s, nodes i32[S,P], reasons i32[S,P,F], gpu_take i32[S,P,G],
    vg_take f32[S,P,V], dev_take f32[S,P,DV]).
    """

    def one(ns, carry, pods, weights):
        def step(c, pod):
            return schedule_step(ns, weights, c, pod, filter_on)

        final, (nodes, reasons, gpu_take, vg_take, dev_take) = jax.lax.scan(
            step, carry, pods
        )
        return final, nodes, reasons, gpu_take, vg_take, dev_take

    return jax.vmap(one)(ns_s, carry_s, pods_s, weights_s)


# ---------------------------------------------------------------------------
# Conflict-parallel wave commit (ops/wave.py; ROADMAP item 1)
# ---------------------------------------------------------------------------

@sanitizable("ops.fast:schedule_wave")
@jax.jit
def schedule_wave(
    ns: NodeStatic,
    carry_s: Carry,
    pods: PodRow,
    weights_s: jnp.ndarray,
    valid_s: jnp.ndarray,
    choices_s: jnp.ndarray,
    count: jnp.ndarray,
    filter_on=None,
):
    """One conflict-parallel commit round over a wave of W pods, vmapped
    over scenario lanes (schedule_scenarios' axis discipline: shared `ns`
    and pod wave, per-lane valid/carry/weights — plus the per-lane round
    state `choices_s` i32[S,W]).

    The round body is ops/wave.py's Jacobi step: replay the previous
    round's choices through the exact commit arithmetic (cheap scan,
    count-gated like schedule_scenarios_chunked), then re-decide all W
    pods at their own prefix carries in one data-parallel probe — the
    heavy ~dozen-plugin sweep runs W-wide instead of once per scan step.
    On the converged round (returned choices == `choices_s`) every output
    is byte-identical to the serial scan over the same wave.

    `carry_s` is NOT donated: the wave-input carry is re-read by every
    round until the host driver observes the fixpoint and adopts the exit
    carry. Returns (carry_s, choices i32[S,W], reasons i32[S,W,F],
    gpu_take i32[S,W,G], vg_take f32[S,W,V], dev_take f32[S,W,DV]).
    """

    def one(valid, carry, weights, choices):
        return _wave.wave_round(
            ns._replace(valid=valid), weights, carry, pods, choices,
            count, filter_on,
        )

    return jax.vmap(one)(valid_s, carry_s, weights_s, choices_s)


@sanitizable("ops.fast:schedule_universes_wave")
@jax.jit
def schedule_universes_wave(
    ns_s: NodeStatic,
    carry_s: Carry,
    pods_s: PodRow,
    weights_s: jnp.ndarray,
    choices_s: jnp.ndarray,
    filter_on=None,
):
    """schedule_universes' axis (EVERY leaf stacked per lane) under the
    wave round body: one Jacobi round for S whole universes at once, the
    whole pod sequence as a single wave. `simon prove --engine wave`
    drives this to a fixpoint per chunk and must reproduce the banked
    placement digest bit-for-bit — the reordered engine's admission
    proof. No count gate (every presented pod row is live) and no carry
    donation (rounds re-read the chunk-input carry)."""

    def one(ns, carry, pods, weights, choices):
        return _wave.wave_round(
            ns, weights, carry, pods, choices, None, filter_on
        )

    return jax.vmap(one)(ns_s, carry_s, pods_s, weights_s, choices_s)


def _wave_fixpoint(round_fn, shape, limit, live, site, reason):
    """Drive a wave round function to its fixpoint.

    `round_fn(choices, r)` runs round `r` on the previous round's choices
    (all -1 for round 1) and returns device outputs whose second element
    is the round's own choices. The loop stops at the first round that
    returns the choices it was given: that round's outputs are the serial
    scan's (ops/wave.py; at most W+1 rounds). From round 2 on, every
    choice in the `live` slice that changed counts as a wave conflict.
    After `limit` rounds without a fixpoint (0 = no limit) the fallback
    is counted under `reason` and no outputs are returned; the caller
    replays through its serial kernel.

    Returns (rounds, outputs or None, host choices of the last round)."""
    choices = jnp.full(shape, -1, jnp.int32)
    prev = np.full(shape, -1, np.int32)
    rounds = 0
    while True:
        rounds += 1
        out = round_fn(choices, rounds)
        ch = np.asarray(fetch(out[1], site))
        if np.array_equal(ch, prev):
            return rounds, out, ch
        if rounds > 1:
            _metrics.WAVE_CONFLICTS.inc(int((ch[live] != prev[live]).sum()))
        if limit and rounds >= limit:
            _metrics.WAVE_FALLBACKS.inc(reason=reason)
            return rounds, None, ch
        choices, prev = out[1], ch


def schedule_universes_wave_host(
    ns_s: NodeStatic,
    carry_s: Carry,
    pods_s: PodRow,
    weights_s: jnp.ndarray,
    filter_on=None,
):
    """Drive schedule_universes_wave to its fixpoint: same signature and
    return tuple as schedule_universes (which donates its carry; this
    driver instead keeps the input carry alive across rounds and returns
    the converged round's exit carry). Guaranteed to converge within W+1
    rounds (ops/wave.py); the impossible-overrun guard (round W+2) falls
    back to the serial oracle rather than looping."""
    s_pad = int(jax.tree_util.tree_leaves(carry_s)[0].shape[0])
    p_pad = int(jax.tree_util.tree_leaves(pods_s)[0].shape[1])

    def round_fn(choices, r):
        _progress(f"universes-wave S={s_pad} P={p_pad} round {r}")
        return schedule_universes_wave(
            ns_s, carry_s, pods_s, weights_s, choices, filter_on
        )

    rounds, out, _ = _wave_fixpoint(
        round_fn, (s_pad, p_pad), p_pad + 2, ..., "universes-wave-round",
        "universes_max_rounds",
    )
    if out is None:
        return schedule_universes(ns_s, carry_s, pods_s, weights_s, filter_on)
    _metrics.COMMIT_ROUNDS.observe(rounds)
    return out


def schedule_scenarios_host(
    ns: NodeStatic,
    carry_s: Carry,
    batch: PodBatch,
    weights_s: jnp.ndarray,
    valid_s: jnp.ndarray,
    s_real: int,
    filter_on=None,
):
    """Host driver for one batched call: dispatches schedule_scenarios and
    returns (carry_s, nodes, reasons, gpu_take, vg_take, dev_take) with the
    numpy outputs trimmed to the `s_real` live scenarios. `carry_s` /
    `weights_s` / `valid_s` must already be padded to scenario_bucket(s_real)
    (pad lanes = copies of scenario 0); the returned carry keeps the padded
    axis so it threads straight into the next call.

    The input `carry_s` is CONSUMED: schedule_scenarios donates it (the
    stacked carry is the big resident tensor of a sweep, and XLA reuses its
    buffers for the output carry). Callers must rebind — the stacked carry
    from ops.state.stack_carry is freshly materialized per sweep, so the
    simulator's own serial carry is never at risk.

    With the wave engine enabled (OSIM_WAVE_COMMIT / auto above
    ops.wave.WAVE_AUTO_MIN_PODS pods) each chunk of the durable chunk loop
    (_commit_in_chunks) is one wave driven to its fixpoint — byte-identical
    to the serial scan by fixpoint construction (docs/performance.md).
    Otherwise, with OSIM_COMMIT_CHUNK > 0 and more pods than one chunk,
    each chunk is one count-gated schedule_scenarios_chunked call, whose
    chained result is byte-identical to the single scan. Either way the
    loop checkpoints between chunks (durable/checkpoint.py) and recovers
    from device faults — see docs/durability.md."""
    rows = pod_rows_from_batch(batch)
    s_pad = int(valid_s.shape[0])
    p_real = int(batch.p)
    key = (int(ns.valid.shape[0]), p_real)
    _SCENARIO_PROGRAMS.setdefault(key, set()).add(s_pad)
    _metrics.SCENARIOS_PER_CALL.observe(s_real)

    def serial_chunk(carry_s, rows_c, count, site="scenario-chunk"):
        carry_s, *outs = schedule_scenarios_chunked(
            ns, carry_s, rows_c, weights_s, valid_s, jnp.int32(count),
            filter_on,
        )
        got = fetch(tuple(outs), site)
        return carry_s, tuple(np.asarray(a)[:, :count] for a in got)

    if _wave.wave_enabled(p_real):
        wave = _wave.wave_size()
        max_rounds = _wave.wave_max_rounds()

        def wave_chunk(carry_s, rows_c, count):
            def round_fn(choices, r):
                _progress(f"  wave round {r} (W={wave}, live={count})")
                return schedule_wave(
                    ns, carry_s, rows_c, weights_s, valid_s, choices,
                    jnp.int32(count), filter_on,
                )

            rounds, out, ch = _wave_fixpoint(
                round_fn, (s_pad, wave), max_rounds,
                np.s_[:s_real, :count], "wave-round", "max_rounds",
            )
            _metrics.COMMIT_ROUNDS.observe(rounds)
            if out is None:
                _progress(
                    f"  no fixpoint in {rounds} rounds; replaying the wave "
                    "through the serial chunk kernel"
                )
                return serial_chunk(carry_s, rows_c, count, "wave-fallback")
            got = fetch(out[2:], "wave-commit")
            return out[0], (np.ascontiguousarray(ch[:, :count]),) + tuple(
                np.asarray(a)[:, :count] for a in got
            )

        return _commit_in_chunks(
            ns, carry_s, rows, s_real, s_pad, p_real, wave, wave_chunk
        )
    chunk = commit_chunk_size()
    if chunk and p_real > chunk:
        return _commit_in_chunks(
            ns, carry_s, rows, s_real, s_pad, p_real, chunk, serial_chunk
        )
    _progress(
        f"scenarios S={s_real}/{s_pad} P={batch.p} N={ns.valid.shape[0]}"
    )
    carry_s, nodes, reasons, gpu_take, vg_take, dev_take = schedule_scenarios(
        ns, carry_s, rows, weights_s, valid_s, filter_on
    )
    got = fetch((nodes, reasons, gpu_take, vg_take, dev_take), "scenarios")
    return (carry_s,) + tuple(np.asarray(a)[:s_real] for a in got)


def _commit_in_chunks(
    ns: NodeStatic,
    carry_s: Carry,
    rows: PodRow,
    s_real: int,
    s_pad: int,
    p_real: int,
    size: int,
    commit,
):
    """The durable outer loop of the chunked and wave commit drivers.

    `commit(carry_s, rows_c, count)` commits one chunk of `size` pod rows,
    the first `count` of them live, and returns the exit carry with the
    host (nodes, reasons, gpu, vg, dev) outputs trimmed to `count`: one
    schedule_scenarios_chunked call, or one wave driven to its fixpoint.
    Both leave the same carry, so the plan key and the `plan_chunk`
    digest chain are the same for either, and a plan checkpointed by one
    resumes under the other.

    Per chunk: optional device-fault injection, the commit, then the
    checkpoint hook (journal `plan_chunk` + periodic atomic carry
    snapshot, durable/checkpoint.py). On resume the active checkpointer
    hands back a verified snapshot: the loop re-pins its carry onto the
    current mesh (carry_from_host), counts the covered chunks as skipped,
    and re-executes only the journal tail — cross-checking every
    re-executed chunk's digest against the journaled one. A
    DeviceLostError from the fault plane rolls back to the last good
    in-memory snapshot and replays (degraded, not failed) until the
    strike budget runs out; a wave's in-flight rounds mutate nothing, so
    they are simply discarded."""
    from ..durable import checkpoint as _checkpoint
    from ..resilience import faults as _faults
    from ..utils import flightrec as _flightrec

    N = int(ns.valid.shape[0])
    n_chunks = -(-p_real // size)
    p_pad = n_chunks * size
    if p_pad != p_real:
        rows = jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jnp.broadcast_to(a[:1], (p_pad - p_real,) + a.shape[1:])]
            ),
            rows,
        )
    _SCENARIO_PROGRAMS.setdefault((N, size), set()).add(s_pad)

    cp = _checkpoint.active_checkpointer()
    plan = None
    start_chunk = 0
    outs: list = []  # host (nodes, reasons, gpu, vg, dev) tuples, in order
    if cp is not None:
        plan = cp.begin_plan(
            n_nodes=N, p_real=p_real, s_pad=s_pad, chunk=size,
            n_chunks=n_chunks,
        )
        restore = plan.restore
        if restore is not None:
            start_chunk = restore.chunks_done
            carry_s = carry_from_host(carry_s, restore.carry)
            outs.append(restore.outputs)
            _metrics.RESUME_CHUNKS_SKIPPED.inc(start_chunk)
            _flightrec.note(
                "plan-restore", plan=plan.key, chunk=start_chunk - 1,
                digest=f"{restore.digest:08x}",
            )
            _flightrec.dump("chunk-restore", run_dir=cp.run_dir)

    # Device-loss recovery needs a host-resident rollback point; pay for it
    # only when a checkpointer is active or a device fault can actually fire.
    track = cp is not None or _faults.has_rules("device")
    last_good = None  # (chunk_idx, host carry leaves, len(outs), digest)
    if track:
        host0 = carry_to_host(carry_s)
        last_good = (
            start_chunk, host0, len(outs), scenario_carry_digest_host(host0),
        )
    strikes = 0

    i = start_chunk
    while i < n_chunks:
        rule = _faults.maybe_inject("device", f"commit-chunk:{i}")
        if rule is not None:
            try:
                _faults.apply_device_fault(rule)
            except _faults.DeviceLostError:
                strikes += 1
                if last_good is None or strikes >= 3:
                    _metrics.DEVICE_LOST.inc(handled="no")
                    raise
                _metrics.DEVICE_LOST.inc(handled="yes")
                g_chunk, g_carry, g_outs, g_digest = last_good
                _flightrec.note(
                    "device-lost", chunk=i, restored_to=g_chunk,
                    digest=f"{g_digest:08x}",
                )
                _flightrec.dump(
                    "device-lost",
                    run_dir=cp.run_dir if cp is not None else None,
                )
                carry_s = carry_from_host(carry_s, g_carry)
                del outs[g_outs:]
                i = g_chunk
                continue
        lo = i * size
        count = min(size, p_real - lo)
        _progress(
            f"scenarios S={s_real}/{s_pad} N={N} "
            f"chunk {i + 1}/{n_chunks} (C={size}, live={count})"
        )
        rows_c = jax.tree_util.tree_map(lambda a: a[lo:lo + size], rows)
        carry_s, out = commit(carry_s, rows_c, count)
        outs.append(out)
        _metrics.PLAN_CHUNKS.inc()
        if cp is not None:
            digest = scenario_carry_digest(carry_s)
            hostc = cp.on_chunk(plan, i, lo + count, digest, carry_s, outs)
            if hostc is not None:
                last_good = (i + 1, hostc, len(outs), digest)
        i += 1

    if cp is not None:
        cp.finish_plan(plan, scenario_carry_digest(carry_s))
    cat = tuple(
        np.concatenate([o[k] for o in outs], axis=1) for k in range(5)
    )
    return (carry_s,) + tuple(a[:s_real] for a in cat)
