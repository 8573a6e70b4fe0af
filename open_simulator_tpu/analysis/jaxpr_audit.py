"""jaxpr auditor: trace the fast-path kernels and inspect what XLA sees.

The AST lint (``analysis.lint``) catches what syntax shows; this layer
catches what only tracing shows. It builds one canonical encoded state —
a small synthetic cluster at the same bucket family production uses
(``node_bucket(n_nodes)`` ladder node axis, ``_bucket``-padded pod
groups) —
runs the real host dispatchers over it while *capturing* every jit-entry
call, then retraces each captured call with ``Function.trace`` and walks
the jaxpr:

* **forbidden primitives** — host callbacks and explicit transfers
  (``pure_callback``, ``io_callback``, ``debug_callback``, infeed /
  outfeed, ``device_put``...) mean a host round trip inside the kernel;
* **wide avals** — any f64/i64/u64/c128 intermediate means the f32/i32
  exactness regime leaked (x64 off: silent downcast hid the intent;
  x64 on: doubled HBM traffic).

Capturing at the dispatcher boundary (instead of hand-building each
kernel's arguments) keeps the audit signature-proof: when a kernel gains
a parameter the capture follows automatically, and the audit inspects
exactly the (shapes, dtypes, static values) production uses.

The recompile guard (:func:`run_recompile_guard`) is the dynamic half of
the shape-discipline story: it runs a small capacity-planning sweep —
the workload whose add-node search motivates bucketing in the first
place — and asserts the number of XLA backend compiles stays within the
declared shape-family budget, cross-checking its own count against the
``osim_compile_cache_total{event="backend_compile"}`` counter fed by
``utils.platform.install_compile_listener``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterator, List, Optional, Tuple

#: Primitives that imply a host round trip or an explicit transfer inside
#: traced code. Non-empty by contract (the audit refuses to run otherwise —
#: an empty set would vacuously pass).
FORBIDDEN_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "outside_call",
        "host_callback_call",
        "infeed",
        "outfeed",
        "device_put",
        "copy_to_host",
    }
)

WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})

#: jit entry points per module; captured while the canonical dispatch runs.
AUDIT_TARGETS: Dict[str, Tuple[str, ...]] = {
    "open_simulator_tpu.ops.fast": (
        "build_trajectory",
        "sort_select",
        "cur_at",
        "light_scan",
        "domain_select",
        "light_reasons",
        "gather_takes",
        "exit_carry",
        "schedule_scenarios",
        "schedule_scenarios_chunked",
        "schedule_universes",
        "schedule_wave",
        "schedule_universes_wave",
    ),
    "open_simulator_tpu.ops.grouped": ("_group_jit",),
    "open_simulator_tpu.ops.kernels": (
        "schedule_batch", "probe_step", "commit_step", "probe_many",
        "commit_wave",
    ),
    "open_simulator_tpu.ops.delta": ("apply_rows", "apply_flags", "digest_fold"),
}

#: entries the canonical state MUST exercise — a refactor that silently
#: stops routing through one of these should fail the audit, not shrink it.
REQUIRED_COVERAGE = frozenset(
    {
        "ops.fast:build_trajectory",
        "ops.fast:sort_select",
        "ops.fast:light_scan",
        "ops.fast:domain_select",
        "ops.fast:light_reasons",
        "ops.fast:cur_at",
        "ops.fast:gather_takes",
        "ops.fast:exit_carry",
        "ops.fast:schedule_scenarios",
        "ops.fast:schedule_scenarios_chunked",
        "ops.fast:schedule_universes",
        "ops.fast:schedule_wave",
        "ops.fast:schedule_universes_wave",
        "ops.grouped:_group_jit",
        "ops.kernels:schedule_batch",
        "ops.kernels:probe_step",
        "ops.kernels:commit_step",
        "ops.kernels:probe_many",
        "ops.kernels:commit_wave",
        "ops.delta:apply_rows",
        "ops.delta:apply_flags",
        "ops.delta:digest_fold",
    }
)

#: XLA backend-compile budget for the capacity sweep: every probe of the
#: search shares one node-bucket per phase, so the whole sweep should stay
#: within a handful of shape families (kernels x {bracket bucket, pinned
#: bisection bucket}), not one compile per probe.
RECOMPILE_BUDGET = 48


@dataclasses.dataclass
class TargetReport:
    name: str
    traced: bool
    n_eqns: int = 0
    primitives: List[str] = dataclasses.field(default_factory=list)
    forbidden: List[str] = dataclasses.field(default_factory=list)
    wide_avals: List[str] = dataclasses.field(default_factory=list)
    #: positional args the entry donates (__osim_donate_argnums__)
    donated: List[int] = dataclasses.field(default_factory=list)
    #: donated-invar aliasing findings: a donated arg shared an array object
    #: with another arg of the same captured call (XLA would scatter into a
    #: buffer the other argument still reads)
    donation_aliased: List[str] = dataclasses.field(default_factory=list)
    error: str = ""

    @property
    def ok(self) -> bool:
        return (
            self.traced
            and not self.forbidden
            and not self.wide_avals
            and not self.donation_aliased
            and not self.error
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "traced": self.traced,
            "ok": self.ok,
            "n_eqns": self.n_eqns,
            "forbidden": self.forbidden,
            "wide_avals": self.wide_avals,
            "donated": self.donated,
            "donation_aliased": self.donation_aliased,
            "error": self.error,
        }


@dataclasses.dataclass
class AuditReport:
    targets: List[TargetReport]
    uncovered: List[str]
    required_missing: List[str]

    @property
    def ok(self) -> bool:
        return not self.required_missing and all(t.ok for t in self.targets)

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "targets": [t.to_dict() for t in self.targets],
            "uncovered": self.uncovered,
            "required_missing": self.required_missing,
            "forbidden_primitives": sorted(FORBIDDEN_PRIMITIVES),
        }

    def render_text(self) -> str:
        out = []
        for t in sorted(self.targets, key=lambda t: t.name):
            status = "ok" if t.ok else "FAIL"
            detail = f"{t.n_eqns} eqns"
            if t.donated:
                detail += f"; donates arg(s) {t.donated}"
            if t.forbidden:
                detail += f"; forbidden: {', '.join(t.forbidden)}"
            if t.wide_avals:
                detail += f"; wide avals: {', '.join(t.wide_avals[:4])}"
            if t.donation_aliased:
                detail += f"; DONATION ALIASED: {', '.join(t.donation_aliased)}"
            if t.error:
                detail += f"; error: {t.error}"
            out.append(f"  {status:4s} {t.name} ({detail})")
        if self.uncovered:
            out.append(f"  not exercised by canonical state: {', '.join(self.uncovered)}")
        if self.required_missing:
            out.append(f"  REQUIRED but missing: {', '.join(self.required_missing)}")
        out.append(f"jaxpr audit: {'ok' if self.ok else 'FAILED'}")
        return "\n".join(out)


# --------------------------------------------------------------------------
# canonical state


def canonical_state():
    """A small synthetic cluster encoded at the production bucket family.

    24 nodes -> the 64-node `round_up` bucket `encode_nodes` always uses;
    four pod templates tiled into runs that deterministically exercise the
    dispatcher's strategies: a large plain group (trajectory + sort path),
    a zonal topology-spread group (domain path), a hostname-spread group
    (general light_scan body), and an infeasible group (light_reasons
    attribution).
    """
    from ..core.objects import Node, Pod
    from ..ops.encode import (
        Encoder,
        encode_nodes,
        encode_pods,
        initial_anti_counts,
        initial_port_counts,
        initial_selector_counts,
    )
    from ..ops.state import carry_from_table, node_static_from_table
    from ..ops.tile import tile_pod_batch

    nodes = [
        Node.from_dict(
            {
                "metadata": {
                    "name": f"audit-{i}",
                    "labels": {
                        "kubernetes.io/hostname": f"audit-{i}",
                        "topology.kubernetes.io/zone": f"az-{i % 3}",
                    },
                },
                "spec": {},
                "status": {
                    "allocatable": {"cpu": "16", "memory": "32Gi", "pods": "110"}
                },
            }
        )
        for i in range(24)
    ]

    def pod(name, cpu, labels=None, spec_extra=None):
        spec = {
            "containers": [
                {
                    "name": "c",
                    "resources": {"requests": {"cpu": cpu, "memory": "256Mi"}},
                }
            ]
        }
        spec.update(spec_extra or {})
        return Pod.from_dict(
            {
                "metadata": {"name": name, "namespace": "audit", "labels": labels or {}},
                "spec": spec,
            }
        )

    plain = pod("plain", "100m")
    spread = pod(
        "spread",
        "100m",
        labels={"app": "spread"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "spread"}},
                }
            ]
        },
    )
    # hostname-keyed spread counts per node (not per domain), which voids
    # both the sort path and the domain merge -> the general light_scan body
    host_spread = pod(
        "hspread",
        "100m",
        labels={"app": "hspread"},
        spec_extra={
            "topologySpreadConstraints": [
                {
                    "maxSkew": 1,
                    "topologyKey": "kubernetes.io/hostname",
                    "whenUnsatisfiable": "DoNotSchedule",
                    "labelSelector": {"matchLabels": {"app": "hspread"}},
                }
            ]
        },
    )
    infeasible = pod("huge", "64")  # > any node's 16 cpu -> unschedulable

    templates = [plain, spread, host_spread, infeasible]
    counts = [220, 60, 50, 30]

    enc = Encoder()
    enc.register_pods(templates)
    table = encode_nodes(enc, nodes)
    batch = tile_pod_batch(encode_pods(enc, templates), counts)
    ns = node_static_from_table(enc, table)
    carry = carry_from_table(
        table,
        initial_selector_counts(enc, table, []),
        port_counts=initial_port_counts(enc, table, []),
        anti_counts=initial_anti_counts(enc, table, []),
    )
    return ns, carry, batch


# --------------------------------------------------------------------------
# capture + trace


@dataclasses.dataclass
class _Captured:
    name: str
    fn: Any  # the original jitted Function
    args: tuple
    kwargs: dict


def _is_concrete(x: Any) -> bool:
    import jax

    return not any(
        isinstance(leaf, jax.core.Tracer) for leaf in jax.tree.leaves(x)
    )


def _short(module: str, attr: str) -> str:
    return f"{module.split('.', 1)[1]}:{attr}"


def _capture_calls() -> List[_Captured]:
    """Run the host dispatchers over the canonical state with every jit
    entry wrapped by a recorder; return first-call args per entry."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    captured: Dict[str, _Captured] = {}
    patches: List[Tuple[Any, str, Any]] = []

    def _snapshot_donated(fn, args: tuple) -> tuple:
        """Donating entries delete their donated inputs when the recorded
        call executes; keep copies so the retrace/invariant passes still
        see live concrete values."""
        donated = set(getattr(fn, "__osim_donate_argnums__", ()) or ())
        if not donated:
            return args
        return tuple(
            jax.tree.map(
                lambda a: a.copy() if hasattr(a, "dtype") else a, arg
            )
            if i in donated
            else arg
            for i, arg in enumerate(args)
        )

    try:
        for module_name, attrs in AUDIT_TARGETS.items():
            module = importlib.import_module(module_name)
            for attr in attrs:
                original = getattr(module, attr)
                name = _short(module_name, attr)

                def recorder(*args, _original=original, _name=name, **kwargs):
                    if _name not in captured and _is_concrete((args, kwargs)):
                        captured[_name] = _Captured(
                            _name,
                            _original,
                            _snapshot_donated(_original, args),
                            kwargs,
                        )
                    return _original(*args, **kwargs)

                setattr(module, attr, recorder)
                patches.append((module, attr, original))

        fast = importlib.import_module("open_simulator_tpu.ops.fast")
        grouped = importlib.import_module("open_simulator_tpu.ops.grouped")
        kernels = importlib.import_module("open_simulator_tpu.ops.kernels")
        state_mod = importlib.import_module("open_simulator_tpu.ops.state")

        ns, carry, batch = canonical_state()
        weights = kernels.weights_array()

        # the trajectory dispatcher: plain group -> build_trajectory +
        # light_scan (+ cur_at/gather_takes/exit_carry), spread group ->
        # domain path, infeasible group -> light_reasons
        fast.schedule_batch_fast(ns, carry, batch, weights, force_fast=True)
        # the per-pod grouped scan (`_group_jit`)
        grouped.schedule_batch_grouped(ns, carry, batch, weights)
        # the sequential oracle + the extender-path single-pod entries
        rows = state_mod.pod_rows_from_batch(batch)
        kernels.schedule_batch(ns, carry, rows, weights)
        row0 = _tree_first(rows)
        kernels.probe_step(ns, carry, row0, weights)
        kernels.commit_step(ns, carry, row0, jnp.int32(0))
        # the extender wave entries (engine/extender_wave.py): one bucketed
        # wave of pad-copied lanes, the exact shape discipline the wave
        # engine ships (lane 0 commits, the rest only recheck)
        w_pad = fast.scenario_bucket(2)
        rows_w = jax.tree.map(
            lambda a: jnp.broadcast_to(a[:1], (w_pad,) + a.shape[1:]), rows
        )
        mask_w, score_w, ff_w = kernels.probe_many(ns, carry, rows_w, weights)
        want_w = jnp.zeros(w_pad, bool).at[0].set(True)
        kernels.commit_wave(
            ns, carry, rows_w, weights, mask_w, ff_w, mask_w,
            jnp.zeros_like(score_w), want_w,
        )
        # the batched scenario engine (`schedule_scenarios`): a 2-lane
        # what-if sweep padded to the scenario bucket, the exact shapes
        # Simulator.run_scenarios ships (lane 1 masks off half the nodes;
        # pad lanes are copies of lane 0, as in production). Called
        # directly, not through schedule_scenarios_host, whose wave/chunk
        # routing depends on the backend and the host's core count.
        s_pad = fast.scenario_bucket(2)
        valid_s = jnp.stack([ns.valid] * s_pad)
        valid_s = valid_s.at[1, 12:].set(False)
        weights_s = jnp.stack([weights] * s_pad)
        fast.schedule_scenarios(
            ns, state_mod.stack_carry(carry, s_pad), rows,
            weights_s, valid_s, None,
        )
        # the chunked commit driver (`schedule_scenarios_chunked`,
        # OSIM_COMMIT_CHUNK > 0): one count-gated chunk at the same lane
        # shapes — a partial chunk (count < C) so the gate path is traced
        rows_c = jax.tree.map(lambda a: a[:4], rows)
        fast.schedule_scenarios_chunked(
            ns, state_mod.stack_carry(carry, s_pad), rows_c,
            weights_s, valid_s, jnp.int32(3),
        )
        # the exhaustive-checking universe engine (`schedule_universes`,
        # `simon prove`): every NodeStatic/Carry/PodRow leaf stacked to the
        # scenario bucket (scalars widened to [S]), the exact packing
        # analysis/semantics.py ships via stamped gather
        stack_leaf = lambda a: jnp.broadcast_to(  # noqa: E731
            a[None], (s_pad,) + a.shape
        )
        fast.schedule_universes(
            jax.tree.map(stack_leaf, ns),
            state_mod.stack_carry(carry, s_pad),
            jax.tree.map(stack_leaf, rows),
            weights_s,
        )
        # the conflict-parallel wave engine (ops/wave.py): one Jacobi
        # round at the chunked-driver shapes (cold -1 choices, partial
        # count so the live gate is traced) and the universes-axis round
        # `simon prove --engine wave` drives — neither donates its carry
        choices_w = jnp.full((s_pad, 4), -1, jnp.int32)
        fast.schedule_wave(
            ns, state_mod.stack_carry(carry, s_pad), rows_c,
            weights_s, valid_s, choices_w, jnp.int32(3),
        )
        fast.schedule_universes_wave(
            jax.tree.map(stack_leaf, ns),
            state_mod.stack_carry(carry, s_pad),
            jax.tree.map(stack_leaf, rows),
            weights_s,
            jnp.full(
                (s_pad, int(jax.tree.leaves(rows)[0].shape[0])),
                -1, jnp.int32,
            ),
        )
        # the resident-state delta kernels (engine/resident.py): scatter two
        # rows into the canonical free plane at production shapes (bucketed
        # index vector, pad slots dropped), flag-set on the valid vector,
        # and one drift-detector digest per representative dtype. The digest
        # runs first and the scatters get fresh copies: apply_rows /
        # apply_flags DONATE their plane argument, and the canonical
        # carry/ns must stay alive for the retrace of every other entry.
        delta = importlib.import_module("open_simulator_tpu.ops.delta")
        n = int(carry.free.shape[0])
        idx = jnp.asarray(delta.pad_indices([0, 1], n))
        rows = jnp.zeros((int(idx.shape[0]),) + carry.free.shape[1:],
                         carry.free.dtype)
        delta.digest_fold(carry.free)
        delta.apply_rows(carry.free.copy(), idx, rows)
        delta.apply_flags(ns.valid.copy(), idx,
                          jnp.zeros(int(idx.shape[0]), bool))
        del np
    finally:
        for module, attr, original in patches:
            setattr(module, attr, original)
    return list(captured.values())


def _tree_first(rows):
    import jax

    return jax.tree.map(lambda a: a[0], rows)


def _iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations of a (possibly nested) jaxpr: pjit bodies, scan/cond/
    while branches — anything carrying a sub-jaxpr in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(v: Any) -> Iterator[Any]:
    if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):  # raw Jaxpr
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _sub_jaxprs(item)


def _donation_aliasing(cap: _Captured) -> Tuple[List[int], List[str]]:
    """Donated-invar alias check: no array object of a donated positional
    arg may appear in any OTHER argument of the same captured call — XLA
    aliases donated input buffers to outputs, so a second argument reading
    the same array would observe the in-place write. Object identity is the
    right granularity here (donated buffers may already be deleted by the
    capture run, so pointer comparison is unavailable; the engine only ever
    aliases by passing the same Array object twice)."""
    import jax

    donated = sorted(getattr(cap.fn, "__osim_donate_argnums__", ()) or ())
    findings: List[str] = []
    if not donated:
        return donated, findings
    leaves_by_arg = [
        (i, [l for l in jax.tree.leaves(a) if hasattr(l, "dtype")])
        for i, a in enumerate(cap.args)
    ]
    kw_leaves = [
        (k, l)
        for k, v in sorted(cap.kwargs.items())
        for l in jax.tree.leaves(v)
        if hasattr(l, "dtype")
    ]
    for d in donated:
        if d >= len(cap.args):
            findings.append(f"arg {d} not supplied positionally")
            continue
        donated_ids = {id(l) for l in dict(leaves_by_arg)[d]}
        for i, ls in leaves_by_arg:
            if i == d:
                continue
            if any(id(l) in donated_ids for l in ls):
                findings.append(f"arg {d} aliased by arg {i}")
        for k, l in kw_leaves:
            if id(l) in donated_ids:
                findings.append(f"arg {d} aliased by kwarg {k!r}")
    return donated, findings


def _audit_one(cap: _Captured) -> TargetReport:
    report = TargetReport(name=cap.name, traced=False)
    report.donated, report.donation_aliased = _donation_aliasing(cap)
    try:
        closed = cap.fn.trace(*cap.args, **cap.kwargs).jaxpr
    except Exception as exc:  # pragma: no cover - trace failure is a finding
        report.error = f"trace failed: {exc!r}"
        return report
    report.traced = True
    prims = set()
    wide = set()
    forbidden = set()
    for eqn in _iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        prims.add(pname)
        if pname in FORBIDDEN_PRIMITIVES:
            forbidden.add(pname)
        report.n_eqns += 1
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype.name in WIDE_DTYPES:
                wide.add(f"{pname}:{dtype.name}")
    report.primitives = sorted(prims)
    report.forbidden = sorted(forbidden)
    report.wide_avals = sorted(wide)
    return report


def run_audit() -> AuditReport:
    """Capture + retrace every registered kernel; see module docstring."""
    if not FORBIDDEN_PRIMITIVES:
        raise RuntimeError("forbidden-primitive set must be non-empty")
    caps = _capture_calls()
    by_name = {c.name: c for c in caps}
    targets = [_audit_one(c) for c in caps]
    all_names = {
        _short(m, a) for m, attrs in AUDIT_TARGETS.items() for a in attrs
    }
    uncovered = sorted(all_names - set(by_name))
    required_missing = sorted(REQUIRED_COVERAGE - set(by_name))
    return AuditReport(
        targets=targets, uncovered=uncovered, required_missing=required_missing
    )


# --------------------------------------------------------------------------
# recompile guard


#: max distinct scenario-axis paddings per (node bucket, pod count) program
#: key: the batched capacity search shapes its lanes to the scenario bucket,
#: so a bucket should see at most {ladder pad, refine pad} — more means the
#: lane shaping regressed and every sweep call recompiles.
SCENARIO_PROGRAMS_PER_BUCKET = 2


@dataclasses.dataclass
class GuardResult:
    compiles: int
    budget: int
    metric_compiles: int
    nodes_added: int
    attempts: int
    batched_calls: int = 0
    batched_nodes_added: int = -1
    scenario_programs: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict
    )
    #: distinct node-axis paddings the sweep's batched programs compiled for
    ladder_rungs: List[int] = dataclasses.field(default_factory=list)

    @property
    def scenario_ok(self) -> bool:
        return self.batched_nodes_added == self.nodes_added and all(
            len(pads) <= SCENARIO_PROGRAMS_PER_BUCKET
            for pads in self.scenario_programs.values()
        )

    @property
    def ladder_ok(self) -> bool:
        """Every batched program's node axis sits exactly on a ladder rung
        (ops.encode.node_bucket is idempotent on it): the sweep never
        compiled an off-ladder node shape, so a growing search compiles at
        most SCENARIO_PROGRAMS_PER_BUCKET programs per rung it touches."""
        from ..ops.encode import node_bucket

        return all(node_bucket(n) == n for n in self.ladder_rungs)

    @property
    def ok(self) -> bool:
        return (
            0 < self.compiles <= self.budget
            and self.compiles == self.metric_compiles
            and self.scenario_ok
            and self.ladder_ok
        )

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "compiles": self.compiles,
            "budget": self.budget,
            "metric_compiles": self.metric_compiles,
            "nodes_added": self.nodes_added,
            "attempts": self.attempts,
            "batched_calls": self.batched_calls,
            "batched_nodes_added": self.batched_nodes_added,
            "scenario_programs": self.scenario_programs,
            "scenario_ok": self.scenario_ok,
            "ladder_rungs": self.ladder_rungs,
            "ladder_ok": self.ladder_ok,
        }

    def render_text(self) -> str:
        worst = max(
            (len(p) for p in self.scenario_programs.values()), default=0
        )
        return (
            f"recompile guard: {'ok' if self.ok else 'FAILED'} — "
            f"{self.compiles} backend compiles (budget {self.budget}, "
            f"metric cross-check {self.metric_compiles}) over a capacity "
            f"sweep adding {self.nodes_added} node(s) in {self.attempts} "
            f"probes; batched sweep: {self.batched_calls} call(s), "
            f"{worst} scenario program(s)/bucket "
            f"(max {SCENARIO_PROGRAMS_PER_BUCKET}), node rungs "
            f"{self.ladder_rungs} "
            f"{'on-ladder' if self.ladder_ok else 'OFF-LADDER'}, answer "
            f"{'agrees' if self.batched_nodes_added == self.nodes_added else 'DISAGREES'}"
        )


def _sweep_fixture():
    """An overloaded 3-node cluster + one Deployment that cannot fit, plus
    the clone template — the smallest sweep that makes plan_capacity walk
    its exponential + bisection phases."""
    from ..core.objects import Node
    from ..engine.simulator import AppResource, ClusterResource

    def node(name: str) -> Node:
        return Node.from_dict(
            {
                "metadata": {
                    "name": name,
                    "labels": {"kubernetes.io/hostname": name},
                },
                "spec": {},
                "status": {
                    "allocatable": {"cpu": "8", "memory": "16Gi", "pods": "110"}
                },
            }
        )

    cluster = ClusterResource(nodes=[node(f"guard-{i}") for i in range(3)])
    deployment = {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": "load", "namespace": "guard"},
        "spec": {
            "replicas": 40,
            "selector": {"matchLabels": {"app": "load"}},
            "template": {
                "metadata": {"labels": {"app": "load"}},
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "load:v1",
                            "resources": {
                                "requests": {"cpu": "2", "memory": "1Gi"}
                            },
                        }
                    ]
                },
            },
        },
    }
    apps = [AppResource(name="guard", objects=[deployment])]
    return cluster, apps, node("guard-template")


def _backend_compiles() -> int:
    from ..utils import metrics

    return int(metrics.COMPILE_CACHE.value(event="backend_compile"))


def _run_sweeps():
    """The canonical capacity sweep, serial then batched — the shared
    workload of the recompile guard and the warm-start check. Returns
    (serial plan, batched plan); raises if either fails to converge."""
    from ..core.workloads import reset_name_rng
    from ..engine.capacity import plan_capacity

    reset_name_rng()
    cluster, apps, template = _sweep_fixture()
    plan = plan_capacity(
        cluster, apps, template, max_new_nodes=256, sweep_mode="serial"
    )
    # the batched half: same fixture through the vmapped scenario
    # engine, which must (a) reach the same answer and (b) keep every
    # (node bucket, pod count) program key within its scenario-padding
    # budget — one padding per sweep phase, not one per call
    reset_name_rng()
    cluster_b, apps_b, template_b = _sweep_fixture()
    plan_b = plan_capacity(
        cluster_b, apps_b, template_b, max_new_nodes=256,
        sweep_mode="batched",
    )
    if plan is None or plan_b is None:
        raise RuntimeError("recompile-guard sweep did not converge")
    return plan, plan_b


def run_recompile_guard(budget: int = RECOMPILE_BUDGET) -> GuardResult:
    """Run the canonical capacity sweep and bound its XLA compile count.

    Counts via the jax.monitoring backend-compile event (installed into the
    metrics registry by install_compile_listener) and cross-checks the
    local listener count against the registry's
    osim_compile_cache_total{event="backend_compile"} value.
    """
    from ..utils.platform import install_compile_listener

    if not install_compile_listener():
        raise RuntimeError("jax.monitoring unavailable; cannot count compiles")

    local = {"n": 0}

    def _local_listener(event: str, duration: float, **kwargs) -> None:
        if event.endswith("backend_compile_duration"):
            local["n"] += 1

    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_local_listener)
    from ..ops.fast import reset_scenario_programs, scenario_programs

    metric_before = _backend_compiles()
    reset_scenario_programs()
    try:
        plan, plan_b = _run_sweeps()
    finally:
        try:
            monitoring._unregister_event_duration_listener_by_callback(
                _local_listener
            )
        except Exception:
            pass
    metric_delta = _backend_compiles() - metric_before
    return GuardResult(
        compiles=local["n"],
        budget=budget,
        metric_compiles=metric_delta,
        nodes_added=plan.nodes_added,
        attempts=plan.attempts,
        batched_calls=plan_b.batched_calls,
        batched_nodes_added=plan_b.nodes_added,
        scenario_programs={
            f"{n}x{p}": sorted(pads)
            for (n, p), pads in scenario_programs().items()
        },
        ladder_rungs=sorted({n for (n, _p) in scenario_programs()}),
    )


# --------------------------------------------------------------------------
# warm-start leg


@dataclasses.dataclass
class WarmStartResult:
    """Outcome of the warm-start check: the full canonical capacity sweep
    re-run after `simon warmup`, demanding that the persistent compilation
    cache absorbs every XLA compile request.

    ``cold_compiles`` counts requests the cache did NOT serve (backend
    compile events minus persistent-cache hits — in this jax version the
    duration event fires on hits too, so the raw event count alone would
    indict a perfectly warm cache). Zero cold compiles is the acceptance
    bar: the sweep may *request* compiles (a fresh process has empty
    in-process jit caches) but XLA must never actually compile."""

    backend_compiles: int
    persistent_hits: int
    nodes_added: int
    batched_nodes_added: int
    cache_dir: str = ""

    @property
    def cold_compiles(self) -> int:
        return max(0, self.backend_compiles - self.persistent_hits)

    @property
    def ok(self) -> bool:
        return (
            self.cold_compiles == 0
            and self.nodes_added == self.batched_nodes_added
        )

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "cold_compiles": self.cold_compiles,
            "backend_compiles": self.backend_compiles,
            "persistent_hits": self.persistent_hits,
            "nodes_added": self.nodes_added,
            "batched_nodes_added": self.batched_nodes_added,
            "cache_dir": self.cache_dir,
        }

    def render_text(self) -> str:
        return (
            f"warm-start check: {'ok' if self.ok else 'FAILED'} — "
            f"{self.cold_compiles} cold compile(s) "
            f"({self.backend_compiles} compile request(s), "
            f"{self.persistent_hits} persistent-cache hit(s)) over the "
            f"full capacity sweep; answers "
            f"{'agree' if self.nodes_added == self.batched_nodes_added else 'DISAGREE'}"
        )


def warm_start_check() -> WarmStartResult:
    """The warm-start leg of the recompile guard: run the full canonical
    capacity sweep (serial + batched) and demand ZERO cold compiles.

    Run this after `simon warmup` — in the same process (warmup's sweep
    rehearsal filled the in-process jit caches) or a later one sharing
    its compilation cache dir (every compile request must then
    persistent-hit).
    Either way a nonzero cold count means some program the sweep needs was
    not banked, i.e. the production run would pay a compile inside its
    capture window."""
    from ..ops.fast import reset_scenario_programs
    from ..utils.platform import (
        CompileCounter,
        enable_compilation_cache,
        install_compile_listener,
    )

    cache_dir = enable_compilation_cache()
    install_compile_listener()
    reset_scenario_programs()
    with CompileCounter() as counter:
        plan, plan_b = _run_sweeps()
    return WarmStartResult(
        backend_compiles=counter.backend_compiles,
        persistent_hits=counter.persistent_hits,
        nodes_added=plan.nodes_added,
        batched_nodes_added=plan_b.nodes_added,
        cache_dir=cache_dir or "",
    )


def report_json(audit: Optional[AuditReport], guard: Optional[GuardResult]) -> str:
    return json.dumps(
        {
            "version": 1,
            "jaxpr_audit": audit.to_dict() if audit is not None else None,
            "recompile_guard": guard.to_dict() if guard is not None else None,
        },
        indent=2,
        sort_keys=True,
    )
