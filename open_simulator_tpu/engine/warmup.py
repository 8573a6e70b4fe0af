"""AOT warmup: compile every jit entry before the watchdog window opens.

A first real batch can pay minutes of XLA compilation inside a timed or
deadlined window. The fix is to make compilation a *phase*, not a side
effect: enumerate every audited jit
entry at its canonical bucketed shapes (the exact capture list
`simon audit` proves over, analysis/jaxpr_audit.AUDIT_TARGETS), drive each
through the AOT chain ``fn.trace(...).lower().compile()``, and let the
persistent compilation cache bank the executables. A later process that
shares the cache directory (utils.platform.compilation_cache_dir) then
serves every compile request from the
cache — `simon warmup --check` asserts exactly that (zero *cold* compiles
over the full capacity sweep, see jaxpr_audit.warm_start_check).

The registry is not a second list to keep in sync: `warmup_registry()`
replays jaxpr_audit's capture pass, so the warmup set and the audit set
are identical by construction (one entry per AUDIT_TARGETS attr), and a
jit entry added without audit coverage fails both gates at once. The
same capture list feeds `simon preflight` (analysis/hlo_audit), which
re-lowers every entry abstractly at each ladder rung × mesh shape for
the static HBM/collective budget gate — so the warmup, audit, and
preflight sets cannot drift apart either.

Node-axis shapes come from the bucket ladder (ops.encode.node_bucket):
the sweep rehearsal touches the same ladder rungs a production capacity
search rounds to, so the report's ``ladder_rungs`` names exactly the
node-axis shape family the cache banked — an off-ladder rung in a later
run is a shape the warmup could not have pre-compiled, and the recompile
guard's ``ladder_ok`` flags it.

Donation interacts cleanly: ``Function.trace`` only needs avals, so
entries that donate buffers (ops.delta scatters, the scenario commit
engine) trace fine even though the capture run consumed their originals
(the capture snapshots donated args — jaxpr_audit._snapshot_donated).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List

__all__ = [
    "EntryWarmup",
    "WarmupReport",
    "warmup_registry",
    "registry_captures",
    "run_warmup",
]


@dataclasses.dataclass
class EntryWarmup:
    """One registry entry driven through trace().lower().compile()."""

    name: str
    seconds: float
    donated: List[int] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "seconds": round(self.seconds, 4),
            "donated": list(self.donated),
        }


@dataclasses.dataclass
class WarmupReport:
    """What `simon warmup` did: per-entry AOT compiles plus the sweep
    rehearsal, with the CompileCounter's honest compile accounting.

    ``ok`` demands full registry coverage (every REQUIRED_COVERAGE entry
    captured and compiled) — NOT zero compiles; a cold process is supposed
    to compile here. Zero-compile assertions belong to the warm-start
    check, which runs after this banked the cache."""

    entries: List[EntryWarmup]
    missing: List[str]
    seconds: float
    backend_compiles: int
    persistent_hits: int
    cache_dir: str = ""
    swept: bool = True
    #: node-bucket ladder rungs the sweep rehearsal compiled programs for
    ladder_rungs: List[int] = dataclasses.field(default_factory=list)

    @property
    def cold_compiles(self) -> int:
        return max(0, self.backend_compiles - self.persistent_hits)

    @property
    def ok(self) -> bool:
        return not self.missing

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "entries": [e.to_dict() for e in self.entries],
            "missing": list(self.missing),
            "seconds": round(self.seconds, 4),
            "backend_compiles": self.backend_compiles,
            "persistent_hits": self.persistent_hits,
            "cold_compiles": self.cold_compiles,
            "cache_dir": self.cache_dir,
            "swept": self.swept,
            "ladder_rungs": list(self.ladder_rungs),
        }

    def render_text(self) -> str:
        lines = [
            f"warmup: {'ok' if self.ok else 'FAILED'} — "
            f"{len(self.entries)} entries AOT-compiled in {self.seconds:.2f}s "
            f"({self.backend_compiles} compile request(s), "
            f"{self.persistent_hits} persistent-cache hit(s), "
            f"{self.cold_compiles} cold)"
        ]
        if self.cache_dir:
            lines.append(f"  cache: {self.cache_dir}")
        if not self.swept:
            lines.append("  sweep rehearsal: skipped (--no-sweep)")
        elif self.ladder_rungs:
            lines.append(
                f"  node-bucket rungs banked: {self.ladder_rungs}"
            )
        for e in sorted(self.entries, key=lambda e: -e.seconds):
            don = (
                f"  donates {e.donated}" if e.donated else ""
            )
            lines.append(f"  {e.name:28s} {e.seconds:7.3f}s{don}")
        for name in self.missing:
            lines.append(f"  MISSING: {name} (audited but not captured)")
        return "\n".join(lines)


def warmup_registry() -> List[Any]:
    """The warmup registry: jaxpr_audit's capture list — one _Captured
    (name, jitted fn, canonical concrete args) per audited entry, produced
    by running the host dispatchers over the canonical bucketed state.

    Note the capture run itself executes every entry, so calling this on a
    cold process already populates the persistent cache; run_warmup's AOT
    pass on top is the explicit, per-entry-timed contract."""
    from ..analysis.jaxpr_audit import _capture_calls

    return _capture_calls()


def registry_captures(names: Any = None) -> List[Any]:
    """`warmup_registry()` filtered to ``names`` (audit names like
    ``"ops.fast:schedule_scenarios"``); ``None`` keeps everything.

    Raises KeyError naming the misses so a preflight run asked for an
    entry that no longer exists fails loudly instead of silently
    auditing an empty matrix."""
    caps = warmup_registry()
    if names is None:
        return caps
    wanted = set(names)
    got = [c for c in caps if c.name in wanted]
    missing = wanted - {c.name for c in got}
    if missing:
        raise KeyError(
            f"not in the capture registry: {sorted(missing)} "
            f"(known: {sorted(c.name for c in caps)})"
        )
    return got


def run_warmup(include_sweep: bool = True) -> WarmupReport:
    """Compile everything the engine will need, before anyone is timing.

    1. Configure the persistent compilation cache (compilation_cache_dir) —
       BEFORE the first compile, or the bank stays empty.
    2. Capture the registry (executes each entry once at canonical shapes).
    3. Drive every entry through trace().lower().compile() — the AOT chain
       the compile-lifecycle docs promise; per-entry seconds reported.
    4. With ``include_sweep``, rehearse the full capacity sweep
       (jaxpr_audit._run_sweeps) so auxiliary programs the sweeps build
       outside the audited entries (growth shapes, reductions) are banked
       too — this is what lets `simon warmup --check` demand zero cold
       compiles over the same sweep.
    """
    from ..analysis.jaxpr_audit import REQUIRED_COVERAGE, _run_sweeps
    from ..ops.fast import reset_scenario_programs, scenario_programs
    from ..utils.platform import (
        CompileCounter,
        enable_compilation_cache,
        install_compile_listener,
    )

    cache_dir = enable_compilation_cache()
    install_compile_listener()
    reset_scenario_programs()
    t_start = time.perf_counter()
    entries: List[EntryWarmup] = []
    with CompileCounter() as counter:
        caps = warmup_registry()
        for cap in caps:
            t0 = time.perf_counter()
            cap.fn.trace(*cap.args, **cap.kwargs).lower().compile()
            entries.append(
                EntryWarmup(
                    name=cap.name,
                    seconds=time.perf_counter() - t0,
                    donated=sorted(
                        getattr(cap.fn, "__osim_donate_argnums__", ()) or ()
                    ),
                )
            )
        if include_sweep:
            _run_sweeps()
    missing = sorted(REQUIRED_COVERAGE - {e.name for e in entries})
    return WarmupReport(
        entries=entries,
        missing=missing,
        seconds=time.perf_counter() - t_start,
        backend_compiles=counter.backend_compiles,
        persistent_hits=counter.persistent_hits,
        cache_dir=cache_dir or "",
        swept=include_sweep,
        ladder_rungs=sorted({n for (n, _p) in scenario_programs()}),
    )
