"""Pre-flight program auditor: static HBM + collective audit of lowered HLO.

One layer below the jaxpr audit: every entry in the warmup registry
(`engine.warmup.warmup_registry()` — the audited jit list by
construction) is re-lowered **abstractly** at each node-ladder rung ×
mesh shape and compiled without ever executing, then three things are
extracted from the compiled artifact:

* **memory** — per-device argument/output/temp/alias/peak bytes from
  ``compiled.memory_analysis()``, cross-checked against the
  shape-arithmetic estimator in ``analysis.budget`` so the estimator —
  which also backs ``parallel.mesh.hbm_bytes_per_device`` for
  unmaterialized trees — is continuously proven against XLA's own
  accounting (outputs byte-tight; arguments as a sound upper bound,
  since XLA dedupes repeated jit parameters the caller would still
  materialize);
* **collective census** — all-gather / all-reduce / reduce-scatter /
  collective-permute / all-to-all counts and operand bytes parsed from
  the HLO text. An ``all-gather`` whose output carries a full-rung node
  dimension is **node-table replication** (the exact failure the 1M×100k
  headline must not have: GSPMD silently gathering the sharded node
  table back to every device) and fails the audit outright. Entries in
  ``LANE_PARALLEL`` must compile to *zero* collectives on scenario-only
  meshes — lanes are independent by design, any cross-device op there is
  a sharding bug. ``SCENARIO_ONLY`` entries (global-id node indexing)
  are audited at node_devices == 1 only; node-sharded combos are skipped
  visibly, never silently passed. ``FIXED_SHAPE`` entries (the
  small-scope prover engine, whose captured shapes are themselves the
  contract) are lowered once, unsharded, at the canonical point — the
  ladder does not apply to them.
* **budget diff** — measurements compared against the checked-in
  per-(entry, rung, mesh) book (``budgets/preflight.json``); regressions
  fail CI without running a single program. ``--write-budgets`` is the
  only update flow.

The **transfer audit** is the one pass that does execute: each entry is
warm-called once (compile-time constant transfers land, deliberately
outside the guard) and then re-called under
``jax.transfer_guard("disallow")`` — any steady-state per-call
host↔device transfer in the hot path raises and is reported. Donation
is handled by feeding fresh device copies per call; results are only
``block_until_ready``-ed, never indexed, inside the guard (indexing
transfers the index scalar host→device).

Abstract shapes: NodeStatic / Carry node-axis positions are derived from
``parallel.mesh`` sharding specs (single source of truth — a new field
with a node axis is picked up automatically); stacked sweep carries are
recognised by rank (base+1) and get the scenario axis at position 0;
PodRow rescales its leading pod axis; plain arrays rescale any dim equal
to the canonical node bucket (64). Python scalars/None stay concrete, so
static args survive unchanged.
"""

from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import budget as budget_mod
from .budget import BudgetBook, BudgetViolation, ProgramBudget, program_key

#: Canonical node bucket every capture runs at (ops.encode.NODE_BUCKET_FLOOR).
N_CANON = 64

#: Entries whose scenario lanes are independent by construction: on a
#: scenario-only mesh (node axis = 1 device) their programs must contain
#: ZERO collectives — any cross-device op is an accidental dependency.
LANE_PARALLEL = frozenset({
    "ops.fast:schedule_scenarios",
    "ops.fast:schedule_wave",
})

#: Entries that index nodes by *global id* (dynamic_slice over the node
#: axis inside their scan loop): node-sharding them forces GSPMD to
#: all-gather the node tables every iteration, so they are deployed on
#: scenario lanes / single devices only (the node-sharded path is
#: schedule_batch). The preflight audits them at node_devices == 1 and
#: skips node-sharded meshes *visibly* (``programs_skipped`` in the
#: report) — a capability boundary, not a suppression.
SCENARIO_ONLY = frozenset({"ops.fast:light_scan"})

#: Entries whose captured shapes ARE the contract. The small-scope prover
#: (`simon prove`, analysis/semantics.py) packs fixed bounded-scope
#: universes onto the scenario axis, so EVERY leaf of
#: ``schedule_universes`` — NodeStatic fields included — carries a leading
#: stacked axis the per-field node-axis tables know nothing about;
#: rescaling "the node dim" there rewrites the scenario axis on some
#: leaves and misses others, producing a vmap axis-size mismatch. These
#: entries are therefore lowered exactly once, at the captured shapes on
#: a single device with no resharding, and every other (rung, mesh) combo
#: is skipped *visibly* (``programs_skipped``) — a shape contract, not a
#: suppression.
FIXED_SHAPE = frozenset({
    "ops.fast:schedule_universes",
    "ops.fast:schedule_universes_wave",
})

DEFAULT_RUNGS: Tuple[int, ...] = (64, 128)
DEFAULT_MESHES: Tuple[str, ...] = ("1", "2x1", "2x2")
#: HBM per chip by ``jax.Device.device_kind``, in bytes. Source: Google
#: Cloud TPU documentation, system-architecture pages ("TPU v5e": 16 GB
#: HBM per chip; "TPU v4": 32 GiB HBM per chip). An unknown kind is an
#: error: no figure is assumed for a device nobody looked up.
HBM_BYTES_BY_KIND: Dict[str, int] = {
    "TPU v5 lite": 16 * 10 ** 9,
    "TPU v4": 32 * 1024 ** 3,
}

#: the chip this repository targets: the preflight verdict, compiled on
#: virtual CPU devices, is judged against its HBM
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_hbm_bytes(device: Any = None) -> int:
    """Per-device HBM for the verdict. A real accelerator reports its own
    ``memory_stats()["bytes_limit"]``; on the CPU (virtual devices) the
    figure is the target chip's from HBM_BYTES_BY_KIND. Raises KeyError
    for a device kind the table does not know."""
    import jax

    device = device if device is not None else jax.devices()[0]
    if device.platform != "cpu":
        stats = device.memory_stats() or {}
        if stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
        kind = device.device_kind
    else:
        kind = TARGET_DEVICE_KIND
    if kind not in HBM_BYTES_BY_KIND:
        raise KeyError(
            f"no HBM figure for device kind {kind!r} "
            f"(known: {sorted(HBM_BYTES_BY_KIND)}); pass --hbm-gib"
        )
    return HBM_BYTES_BY_KIND[kind]

#: Cross-check tolerance: the estimator must agree with memory_analysis()
#: within this envelope (XLA adds tuple/alignment padding the shape
#: arithmetic cannot see; a real replication bug is megabytes, not this).
ESTIMATE_REL_TOL = 0.02
ESTIMATE_ABS_SLACK = 64 * 1024

_COLLECTIVE_RE = re.compile(
    r"%\S+\s*=\s*(\([^)]*\)|\S+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)
_TYPED_ARRAY_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


# ---------------------------------------------------------------------------
# collective census
# ---------------------------------------------------------------------------

def _shape_str_bytes(shape_str: str) -> int:
    """Bytes of one HLO result shape string, e.g. ``f32[8,64]{1,0}`` or a
    tuple ``(f32[4,2]{1,0}, s32[4,2]{1,0})``."""
    total = 0
    for dtype, dims in _TYPED_ARRAY_RE.findall(shape_str):
        itemsize = _HLO_DTYPE_BYTES.get(dtype, 4)
        n = itemsize
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _shape_str_dims(shape_str: str) -> List[int]:
    dims: List[int] = []
    for _dtype, ds in _TYPED_ARRAY_RE.findall(shape_str):
        if ds:
            dims.extend(int(d) for d in ds.split(","))
    return dims


def collective_census(hlo_text: str) -> Tuple[Dict[str, int], int, List[Tuple[str, str]]]:
    """(kind -> count, total result bytes, [(kind, shape_str), ...]) for
    every collective op in the HLO text. ``-start`` async halves count as
    the op; ``-done`` halves carry no shape work and never match."""
    kinds: Dict[str, int] = {}
    total = 0
    ops: List[Tuple[str, str]] = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        shape_str, kind = m.group(1), m.group(2)
        kinds[kind] = kinds.get(kind, 0) + 1
        total += _shape_str_bytes(shape_str)
        ops.append((kind, shape_str))
    return kinds, total, ops


def node_table_gathers(
    ops: Sequence[Tuple[str, str]], rung: int
) -> List[str]:
    """The replication detector: ``all-gather`` results carrying a
    full-rung node dimension mean GSPMD gathered a node-axis-sharded
    table back whole. Legitimate gathers (lane scalars like ``f32[4,2]``,
    flattened sort keys like ``f32[16384]``) never show the rung as a
    distinct dimension, which the probes on every audited entry confirm."""
    flagged = []
    for kind, shape_str in ops:
        if kind != "all-gather":
            continue
        if rung in _shape_str_dims(shape_str):
            flagged.append(shape_str)
    return flagged


# ---------------------------------------------------------------------------
# mesh / abstract-shape machinery
# ---------------------------------------------------------------------------

def parse_mesh(tag: str) -> Tuple[int, int]:
    """``"1"`` -> (1, 1); ``"2x1"`` -> (scenario_devices, node_devices)."""
    t = tag.strip().lower()
    if t in ("1", "1x1"):
        return (1, 1)
    m = re.fullmatch(r"(\d+)x(\d+)", t)
    if m is None:
        raise ValueError(f"mesh tag {tag!r} is not SxN (e.g. 2x1, 2x2)")
    return (int(m.group(1)), int(m.group(2)))


def _build_mesh(tag: str):
    """The jax Mesh for a tag, or None for 1×1 (unsharded compile)."""
    from ..parallel import mesh as pmesh

    s, n = parse_mesh(tag)
    if s * n <= 1:
        return None
    return pmesh.product_mesh_2d(s, n)


def _axis_tables() -> Tuple[Dict[str, Optional[int]], Dict[str, Optional[int]]]:
    """(NodeStatic field -> node-axis dim index, Carry field -> same),
    derived from parallel.mesh's sharding specs on a throwaway 1×2 mesh
    so the preflight can never drift from the real sharding layout."""
    from ..parallel import mesh as pmesh

    probe = pmesh.product_mesh_2d(1, 2)

    def table(spec_tree) -> Dict[str, Optional[int]]:
        out: Dict[str, Optional[int]] = {}
        for field, sh in spec_tree._asdict().items():
            out[field] = next(
                (i for i, p in enumerate(sh.spec) if p == pmesh.NODE_AXIS),
                None,
            )
        return out

    return table(pmesh.node_sharding(probe)), table(pmesh.carry_sharding(probe))


def abstract_args(
    cap: Any,
    rung: int,
    mesh: Any,
    tables: Optional[Tuple[Dict[str, Optional[int]], Dict[str, Optional[int]]]] = None,
    pod_bucket: Optional[int] = None,
    resize: bool = True,
) -> Tuple[tuple, dict]:
    """Captured concrete args -> ShapeDtypeStruct avals at ``rung``.

    Array leaves become avals (node dims rescaled, NamedSharding attached
    when ``mesh`` is a 2-D product mesh); non-array leaves (None, Python
    scalars — i.e. static args) pass through concrete. ``pod_bucket``
    additionally rescales PodRow's leading axis (the 1M-pod verdict).
    ``resize=False`` (FIXED_SHAPE entries) keeps every leaf at its
    captured shape, unsharded — the ladder does not apply to them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..ops.kernels import Carry, NodeStatic, PodRow
    from ..parallel import mesh as pmesh

    if not resize:
        def fixed(leaf):
            if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
                return leaf
            return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)

        args = tuple(jax.tree.map(fixed, a) for a in cap.args)
        kwargs = {k: jax.tree.map(fixed, v) for k, v in cap.kwargs.items()}
        return args, kwargs

    if tables is None:
        tables = _axis_tables()
    ns_axis, carry_axis = tables

    def spec_for(ndim: int, node_pos: Optional[int],
                 scen_pos: Optional[int] = None):
        if mesh is None:
            return None
        parts: List[Optional[str]] = [None] * ndim
        if node_pos is not None:
            parts[node_pos] = pmesh.NODE_AXIS
        if scen_pos is not None:
            parts[scen_pos] = pmesh.SCENARIO_AXIS
        return NamedSharding(mesh, P(*parts))

    def aval(leaf, shape, node_pos, scen_pos=None):
        return jax.ShapeDtypeStruct(
            tuple(shape), leaf.dtype,
            sharding=spec_for(len(shape), node_pos, scen_pos),
        )

    def conv(arg):
        if isinstance(arg, NodeStatic):
            d = {}
            for f, leaf in arg._asdict().items():
                pos = ns_axis[f]
                shp = list(leaf.shape)
                if pos is not None:
                    shp[pos] = rung
                d[f] = aval(leaf, shp, pos)
            return NodeStatic(**d)
        if isinstance(arg, Carry):
            d = {}
            for f, leaf in arg._asdict().items():
                base = carry_axis[f]
                # stacked sweep carries carry a leading scenario axis on
                # top of the 2-D base layout (ops.state.stack_carry)
                off = 1 if leaf.ndim == 3 else 0
                pos = base + off if base is not None else None
                shp = list(leaf.shape)
                if pos is not None:
                    shp[pos] = rung
                d[f] = aval(leaf, shp, pos, 0 if off else None)
            return Carry(**d)
        if isinstance(arg, PodRow):
            def pod_leaf(leaf):
                shp = list(leaf.shape)
                if pod_bucket is not None and shp:
                    shp[0] = pod_bucket
                return aval(leaf, shp, None)
            return jax.tree.map(pod_leaf, arg)

        def one(leaf):
            if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
                return leaf
            pos = next(
                (i for i, d in enumerate(leaf.shape) if d == N_CANON), None
            )
            shp = list(leaf.shape)
            if pos is not None:
                shp[pos] = rung
            return aval(leaf, shp, pos)

        return jax.tree.map(one, arg)

    args = tuple(conv(a) for a in cap.args)
    kwargs = {k: conv(v) for k, v in cap.kwargs.items()}
    return args, kwargs


# ---------------------------------------------------------------------------
# per-program audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramAudit:
    """One (entry, rung, mesh) lowered-and-compiled program's evidence."""

    entry: str
    rung: int
    mesh: str
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    peak_bytes: int = 0
    est_argument_bytes: int = 0
    est_output_bytes: int = 0
    estimate_ok: bool = True
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    collective_bytes: int = 0
    node_gathers: List[str] = dataclasses.field(default_factory=list)
    lane_parallel_violation: bool = False
    seconds: float = 0.0
    error: str = ""

    @property
    def key(self) -> str:
        return program_key(self.entry, self.rung, self.mesh)

    @property
    def ok(self) -> bool:
        return (
            not self.error
            and self.estimate_ok
            and not self.node_gathers
            and not self.lane_parallel_violation
        )

    def to_budget(self) -> ProgramBudget:
        return ProgramBudget(
            peak_bytes=self.peak_bytes,
            argument_bytes=self.argument_bytes,
            output_bytes=self.output_bytes,
            temp_bytes=self.temp_bytes,
            alias_bytes=self.alias_bytes,
            collectives=dict(self.collectives),
            collective_bytes=self.collective_bytes,
        )

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        d["ok"] = self.ok
        d["seconds"] = round(self.seconds, 3)
        return d


def _estimate_close(est: int, real: int) -> bool:
    return abs(est - real) <= max(
        int(ESTIMATE_REL_TOL * max(est, real)), ESTIMATE_ABS_SLACK
    )


def _estimate_covers(est: int, real: int) -> bool:
    """Sound-upper-bound check: the shape-arithmetic estimate must cover
    the measured residency (small envelope for XLA tuple/alignment
    padding the arithmetic cannot see). The estimate is allowed to sit
    ABOVE the measurement: XLA dedupes repeated jit parameters into one
    executable parameter (sort_select's broadcast weight tables collapse
    76 -> 11 params), while the estimator prices the argument tree a
    caller would actually materialize — exactly what
    ``hbm_bytes_per_device`` answers for an unplaced tree."""
    return real <= est + max(
        int(ESTIMATE_REL_TOL * max(est, real)), ESTIMATE_ABS_SLACK
    )


def audit_program(
    cap: Any,
    rung: int,
    mesh_tag: str,
    tables: Optional[tuple] = None,
    pod_bucket: Optional[int] = None,
    resize: bool = True,
) -> ProgramAudit:
    """Lower-and-compile one entry at (rung, mesh) abstractly and extract
    memory stats + collective census. Never executes the program."""
    import jax

    pa = ProgramAudit(entry=cap.name, rung=int(rung), mesh=mesh_tag)
    t0 = time.perf_counter()
    try:
        mesh = _build_mesh(mesh_tag) if resize else None
        args, kwargs = abstract_args(
            cap, rung, mesh, tables=tables, pod_bucket=pod_bucket,
            resize=resize,
        )
        traced = cap.fn.trace(*args, **kwargs)
        compiled = traced.lower().compile()
        ma = compiled.memory_analysis()
        pa.argument_bytes = int(ma.argument_size_in_bytes)
        pa.output_bytes = int(ma.output_size_in_bytes)
        pa.temp_bytes = int(ma.temp_size_in_bytes)
        pa.alias_bytes = int(ma.alias_size_in_bytes)
        pa.peak_bytes = int(ma.peak_memory_in_bytes)

        # estimator cross-check: the budget arithmetic must reproduce
        # XLA's per-device accounting from shapes alone (memory_analysis
        # numbers equal the compiled module's post-SPMD entry interface,
        # byte for byte). Arguments are priced from the *intended* abstract
        # tree — the same tree hbm_bytes_per_device would price before
        # materialization — and checked as a sound upper bound, because
        # XLA dedupes repeated jit parameters into one executable
        # parameter (compiled.input_shardings follows the deduped
        # executable params, NOT in_avals — the two do not zip). Outputs
        # cannot dedupe, so they get the tight two-sided check:
        # traced.out_info and compiled.output_shardings mirror the same
        # output pytree and pair leaf-for-leaf.
        dev0 = str(jax.devices()[0])
        pa.est_argument_bytes = budget_mod.estimate_max_bytes_per_device(
            (args, kwargs), default_device=dev0
        )
        est_out = 0
        for leaf, sh in zip(
            jax.tree.leaves(traced.out_info),
            jax.tree.leaves(compiled.output_shardings),
        ):
            per = budget_mod.leaf_bytes_by_device(
                jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh),
                default_device=dev0,
            )
            est_out += max(per.values(), default=0)
        pa.est_output_bytes = est_out
        pa.estimate_ok = _estimate_covers(
            pa.est_argument_bytes, pa.argument_bytes
        ) and _estimate_close(pa.est_output_bytes, pa.output_bytes)

        kinds, coll_bytes, ops = collective_census(compiled.as_text())
        pa.collectives = kinds
        pa.collective_bytes = coll_bytes
        # at the canonical rung every fixed 64-wide dim (J_CAP-sized caps,
        # lane tables) is indistinguishable from the node dim, so the
        # replication detector only has signal at rescaled rungs
        pa.node_gathers = (
            node_table_gathers(ops, rung) if rung != N_CANON else []
        )
        s_dev, n_dev = parse_mesh(mesh_tag)
        if cap.name in LANE_PARALLEL and n_dev == 1 and s_dev > 1 and kinds:
            pa.lane_parallel_violation = True
    except Exception as e:  # pragma: no cover - exercised via error report
        pa.error = f"{type(e).__name__}: {e}"
    pa.seconds = time.perf_counter() - t0
    return pa


# ---------------------------------------------------------------------------
# transfer audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransferCheck:
    entry: str
    ok: bool
    error: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _fresh_device_args(tree: Any) -> Any:
    """Fresh device copies of every array leaf (donation-safe: a donated
    call must never consume the capture's snapshot, and a second call
    needs buffers the first call didn't eat)."""
    import jax
    import jax.numpy as jnp

    def one(leaf):
        if hasattr(leaf, "dtype") and hasattr(leaf, "shape"):
            return jnp.array(leaf)
        return leaf

    return jax.tree.map(one, tree)


def guarded_steady_state_check(fn: Any, args: tuple, kwargs: dict) -> TransferCheck:
    """Warm-call once (compile-time constants transfer here, outside the
    guard — a one-time cost is fine), then call again under
    ``jax.transfer_guard("disallow")``: any transfer the second call makes
    is a *per-call* host↔device sync in the hot path. Results are only
    block_until_ready'd inside the guard — indexing them would transfer
    the index scalar and false-positive the check."""
    import jax

    name = getattr(fn, "__name__", str(fn))
    try:
        warm = _fresh_device_args(args)
        jax.block_until_ready(fn(*warm, **kwargs))
        again = _fresh_device_args(args)
        with jax.transfer_guard("disallow"):
            jax.block_until_ready(fn(*again, **kwargs))
        return TransferCheck(entry=name, ok=True)
    except Exception as e:
        return TransferCheck(entry=name, ok=False, error=f"{type(e).__name__}: {e}")


def transfer_audit(caps: Sequence[Any]) -> List[TransferCheck]:
    """Steady-state transfer check of every captured entry at its
    canonical shapes. The only preflight pass that executes programs —
    `--no-transfers` skips it; the memory/collective matrix never runs."""
    out = []
    for cap in caps:
        chk = guarded_steady_state_check(cap.fn, cap.args, cap.kwargs)
        chk.entry = cap.name
        out.append(chk)
    return out


# ---------------------------------------------------------------------------
# plan_1m_100k verdict
# ---------------------------------------------------------------------------

def plan_verdict(
    caps: Sequence[Any],
    hbm_gib: Optional[float] = None,
    tables: Optional[tuple] = None,
) -> dict:
    """The machine-checked headline: does `plan_1m_100k`'s scenario
    program (1M pods -> pod bucket, 100k nodes -> rung 102400) fit
    per-device HBM on a 1×4 node-sharded mesh with the node table proven
    sharded (zero full-rung gathers)? Purely from the lowered program.
    ``hbm_gib`` None: the budget is device_hbm_bytes()."""
    from ..ops.encode import node_bucket, round_up

    gib = 1024 ** 3
    hbm_bytes = (
        device_hbm_bytes() if hbm_gib is None else int(hbm_gib * gib)
    )

    rung = node_bucket(100_000)
    pods = round_up(1_000_000)
    cap = next((c for c in caps if c.name in LANE_PARALLEL), None)
    verdict: Dict[str, Any] = {
        "config": "plan_1m_100k",
        "entry": cap.name if cap else "",
        "rung": rung,
        "pod_bucket": pods,
        "mesh": "1x4",
        "hbm_gib": round(hbm_bytes / gib, 3),
    }
    if cap is None:
        verdict["error"] = "schedule_scenarios not in capture registry"
        verdict["ok"] = False
        return verdict
    import jax

    if len(jax.devices()) < 4:
        verdict["error"] = (
            f"needs 4 devices for the 1x4 mesh, have {len(jax.devices())} "
            f"(run under --xla_force_host_platform_device_count)"
        )
        verdict["ok"] = False
        return verdict
    pa = audit_program(cap, rung, "1x4", tables=tables, pod_bucket=pods)
    verdict.update(
        peak_bytes=pa.peak_bytes,
        peak_gib=round(pa.peak_bytes / gib, 3),
        argument_bytes=pa.argument_bytes,
        output_bytes=pa.output_bytes,
        temp_bytes=pa.temp_bytes,
        alias_bytes=pa.alias_bytes,
        collectives=pa.collectives,
        node_gathers=pa.node_gathers,
        node_table_sharded=not pa.node_gathers,
        fits=pa.peak_bytes <= hbm_bytes,
        compile_seconds=round(pa.seconds, 2),
        error=pa.error,
    )
    verdict["ok"] = bool(
        not pa.error and verdict["fits"] and verdict["node_table_sharded"]
    )
    return verdict


# ---------------------------------------------------------------------------
# the preflight driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PreflightReport:
    programs: List[ProgramAudit]
    transfers: List[TransferCheck]
    verdict: Optional[dict]
    violations: List[BudgetViolation]
    meshes_skipped: List[str]
    budgets_path: str = ""
    seconds: float = 0.0
    #: (entry, rung, mesh) combos not compiled because the entry is
    #: SCENARIO_ONLY and the mesh shards the node axis, or the entry is
    #: FIXED_SHAPE and the combo is off the canonical point
    programs_skipped: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (
            all(p.ok for p in self.programs)
            and all(t.ok for t in self.transfers)
            and (self.verdict is None or self.verdict.get("ok", False))
            and not self.violations
        )

    def measured(self) -> Dict[str, ProgramBudget]:
        return {p.key: p.to_budget() for p in self.programs if not p.error}

    def to_book(self, base: Optional[BudgetBook] = None) -> BudgetBook:
        """A fresh budget book from this run's measurements (the
        --write-budgets flow). Keeps ``base``'s tolerance knobs and any
        budgets for programs this run didn't measure (partial matrices
        must not silently drop the rest of the book)."""
        book = BudgetBook()
        if base is not None:
            book.tolerance = base.tolerance
            book.slack_bytes = base.slack_bytes
            book.programs = dict(base.programs)
            book.verdicts = dict(base.verdicts)
        book.programs.update(self.measured())
        if self.verdict is not None:
            book.verdicts[str(self.verdict.get("config", "plan"))] = dict(
                self.verdict
            )
        return book

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "programs": [p.to_dict() for p in self.programs],
            "transfers": [t.to_dict() for t in self.transfers],
            "verdict": self.verdict,
            "violations": [v.to_dict() for v in self.violations],
            "meshes_skipped": list(self.meshes_skipped),
            "programs_skipped": list(self.programs_skipped),
            "budgets_path": self.budgets_path,
            "seconds": round(self.seconds, 2),
        }

    def render_text(self) -> str:
        lines = [
            f"preflight: {'ok' if self.ok else 'FAILED'} — "
            f"{len(self.programs)} program(s) audited in {self.seconds:.1f}s"
        ]
        mib = 1024 ** 2
        for p in sorted(self.programs, key=lambda p: p.key):
            colls = (
                ",".join(f"{k}:{v}" for k, v in sorted(p.collectives.items()))
                or "-"
            )
            status = "ok" if p.ok else "FAIL"
            lines.append(
                f"  {status:4s} {p.key:52s} peak {p.peak_bytes / mib:9.2f} MiB"
                f"  colls {colls}"
            )
            if p.error:
                lines.append(f"       error: {p.error}")
            if not p.estimate_ok:
                lines.append(
                    f"       estimator mismatch: est arg "
                    f"{p.est_argument_bytes} vs {p.argument_bytes}, est out "
                    f"{p.est_output_bytes} vs {p.output_bytes}"
                )
            if p.node_gathers:
                lines.append(
                    f"       NODE TABLE REPLICATED: all-gather {p.node_gathers}"
                )
            if p.lane_parallel_violation:
                lines.append(
                    "       lane-parallel entry emits collectives on a "
                    "scenario-only mesh"
                )
        for t in self.transfers:
            if not t.ok:
                lines.append(f"  transfer {t.entry}: {t.error}")
        if self.transfers and all(t.ok for t in self.transfers):
            lines.append(
                f"  transfers: {len(self.transfers)} entries steady-state "
                f"clean under transfer_guard"
            )
        if self.programs_skipped:
            lines.append(
                f"  skipped {len(self.programs_skipped)} combo(s) outside "
                f"entry capability (scenario-only on node-sharded meshes; "
                f"fixed-shape off the canonical point): "
                f"{', '.join(self.programs_skipped)}"
            )
        for v in self.violations:
            lines.append(f"  budget: {v.render()}")
        if self.verdict is not None:
            v = self.verdict
            if v.get("error"):
                lines.append(f"  verdict {v['config']}: ERROR {v['error']}")
            else:
                lines.append(
                    f"  verdict {v['config']}: "
                    f"{'fits' if v['fits'] else 'DOES NOT FIT'} — peak "
                    f"{v['peak_gib']} GiB/device vs {v['hbm_gib']} GiB HBM "
                    f"at mesh {v['mesh']} (rung {v['rung']}, "
                    f"{v['pod_bucket']} pods; node table "
                    f"{'sharded' if v['node_table_sharded'] else 'REPLICATED'})"
                )
        return "\n".join(lines)


def _filter_meshes(tags: Sequence[str]) -> Tuple[List[str], List[str]]:
    import jax

    have = len(jax.devices())
    use, skipped = [], []
    for t in tags:
        s, n = parse_mesh(t)
        (use if s * n <= have else skipped).append(t)
    return use, skipped


def run_preflight(
    rungs: Optional[Sequence[int]] = None,
    meshes: Optional[Sequence[str]] = None,
    entries: Optional[Sequence[str]] = None,
    caps: Optional[Sequence[Any]] = None,
    book: Optional[BudgetBook] = None,
    transfers: bool = True,
    verdict: bool = True,
    hbm_gib: Optional[float] = None,
) -> PreflightReport:
    """The full preflight: capture registry -> (entry × rung × mesh)
    abstract compile matrix -> budget diff -> transfer audit -> plan
    verdict. ``caps`` short-circuits the capture pass (tests, audit
    --memory); ``entries`` filters by audit name."""
    from ..engine.warmup import registry_captures

    t0 = time.perf_counter()
    if caps is None:
        caps = registry_captures(entries)
    elif entries is not None:
        wanted = set(entries)
        caps = [c for c in caps if c.name in wanted]
    rungs = tuple(rungs) if rungs else DEFAULT_RUNGS
    mesh_tags, skipped = _filter_meshes(tuple(meshes) if meshes else DEFAULT_MESHES)

    tables = _axis_tables()
    programs: List[ProgramAudit] = []
    programs_skipped: List[str] = []
    for cap in caps:
        if cap.name in FIXED_SHAPE:
            # the captured shapes are the contract: one compile, at the
            # canonical point, unsharded; the rest of the matrix is
            # skipped visibly (see FIXED_SHAPE)
            programs.append(
                audit_program(cap, N_CANON, "1", tables=tables,
                              resize=False)
            )
            programs_skipped.extend(
                program_key(cap.name, rung, tag)
                for rung in rungs for tag in mesh_tags
                if (rung, tag) != (N_CANON, "1")
            )
            continue
        for rung in rungs:
            for tag in mesh_tags:
                _s, n_dev = parse_mesh(tag)
                if cap.name in SCENARIO_ONLY and n_dev > 1:
                    programs_skipped.append(
                        program_key(cap.name, rung, tag)
                    )
                    continue
                programs.append(
                    audit_program(cap, rung, tag, tables=tables)
                )

    violations: List[BudgetViolation] = []
    if book is not None:
        measured = {p.key: p.to_budget() for p in programs if not p.error}
        violations = book.diff(measured)

    checks = transfer_audit(caps) if transfers else []
    vd = plan_verdict(caps, hbm_gib=hbm_gib, tables=tables) if verdict else None

    return PreflightReport(
        programs=programs,
        transfers=checks,
        verdict=vd,
        violations=violations,
        meshes_skipped=skipped,
        programs_skipped=programs_skipped,
        seconds=time.perf_counter() - t0,
    )


def report_json(report: PreflightReport) -> str:
    return json.dumps(report.to_dict(), indent=2, sort_keys=True)
