"""`simon audit` driver: the semantic verification passes.

Where `simon lint` checks *syntactic* contracts (purity, shape bucketing,
dtype discipline) and the jaxpr auditor checks *structural* ones (what
actually got traced), `simon audit` proves two semantic properties:

* **races** (`analysis.races`) — every mutation of module-level shared
  state reachable from server handler threads, thread targets, or signal
  handlers is dominated by a ``with <lock>:`` block or an explicit
  ``@guarded_by`` annotation;
* **invariants** (`analysis.invariants`) — an abstract interpretation of
  the captured jaxprs of all registered jit entry points, proving mask
  outputs stay in {0, 1}, score plugins stay in [0, 100], and no NaN
  (e.g. the ``-inf * 0.0`` sentinel pattern) can reach a selection
  primitive;
* **memory** (`analysis.hlo_audit`, opt-in via ``--memory``) — a compact
  slice of the preflight matrix: every entry lowered at the canonical
  rung on the meshes the host has devices for, collective census +
  estimator cross-check included. The full rung × mesh × budget-diff
  matrix (plus transfer guard and the plan_1m_100k verdict) lives under
  ``simon preflight``.

Both passes emit deterministic findings (stable sort keys, no wall-clock
or randomness), so the JSON report is byte-identical across runs and
diffable in CI. The runtime companion is ``OSIM_SANITIZE=1``
(`ops.sanitize`), which checks the same entries dynamically via
``checkify``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

from .races import RaceAuditReport, run_races


@dataclasses.dataclass
class SemanticAuditReport:
    races: Optional[RaceAuditReport]
    invariants: Optional[object]  # invariants.InvariantAudit (jax-importing)
    memory: Optional[object] = None  # hlo_audit.PreflightReport

    @property
    def ok(self) -> bool:
        return (
            (self.races is None or self.races.ok)
            and (self.invariants is None or self.invariants.ok)
            and (self.memory is None or self.memory.ok)
        )

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "races": self.races.to_dict() if self.races is not None else None,
            "invariants": (
                self.invariants.to_dict()
                if self.invariants is not None
                else None
            ),
            "memory": (
                self.memory.to_dict() if self.memory is not None else None
            ),
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    def render_text(self) -> str:
        parts = []
        if self.races is not None:
            parts.append(self.races.render_text())
        if self.invariants is not None:
            parts.append(self.invariants.render_text())
        if self.memory is not None:
            parts.append(self.memory.render_text())
        parts.append(f"audit: {'ok' if self.ok else 'FAILED'}")
        return "\n".join(parts)


def run_semantic_audit(
    races: bool = True,
    invariants: bool = True,
    memory: bool = False,
    package_root: Optional[str] = None,
    report_root: Optional[str] = None,
) -> SemanticAuditReport:
    """Run the requested passes. The race pass is pure-AST; the invariant
    and memory passes import jax and trace/lower the registered entries
    on the platform ``JAX_PLATFORMS`` names."""
    race_report = (
        run_races(package_root=package_root, report_root=report_root)
        if races
        else None
    )
    inv_report = None
    if invariants:
        from .invariants import run_invariants

        inv_report = run_invariants()
    mem_report = None
    if memory:
        from .hlo_audit import N_CANON, run_preflight

        # compact slice: canonical rung, whatever meshes fit the host's
        # devices; no transfer execution, no verdict, no budget diff —
        # those are `simon preflight` business
        mem_report = run_preflight(
            rungs=(N_CANON,), transfers=False, verdict=False,
        )
    return SemanticAuditReport(
        races=race_report, invariants=inv_report, memory=mem_report
    )
