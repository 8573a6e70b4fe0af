"""Numeric-invariant abstract interpreter over captured jaxprs.

The lint engine (PR 2) proves *syntactic* jit hygiene; this pass proves the
*semantic* contracts the scheduler's correctness rests on, at the same
canonical bucketed shapes the jaxpr auditor traces:

  * masks stay {0,1}-valued (bool dtype all the way to the entry outputs),
  * every score plugin lands in [0,100] (kube's checkPluginScores contract),
  * no float output of any registered jit entry can be NaN,
  * the deliberate ``-inf * 0.0 → NaN`` sentinel pattern (fast.py's score
    lanes carry -inf on infeasible nodes) can never reach a selection point
    — argmax/argmin/reduce_max/reduce_min/sort operands are proven NaN-free,
    and
  * commit-carry resource counters (free CPU/mem, GPU memory, local-storage
    VG/device capacity) stay non-negative through every commit scan.

The last proof cannot come from the interval domain alone: a scan that
subtracts a request from ``free`` each step widens ``free.lo`` to -inf at
the fixpoint, because intervals cannot express the *relational* fact that
the decrement only fires where the feasibility filter held. Instead the
scan evaluator runs a structural **guarded-decrement matcher** over each
scan body: a float carry slot whose update is
``sub(carry_in, mul(convert(bool_guard), amount))`` is non-negative by
induction when the guard's backward slice contains a feasibility
comparison against that same carry slot (``req <= free + eps``). When the
compared quantity is syntactically the decrement amount the slot is
*proved* (``guard ⇒ amount ≤ slot + ε``, so ``slot ≥ -ε`` inductively);
when the slice ties the guard to the slot but not to the amount (the GPU
take path routes through an einsum the matcher does not chase) the slot is
reported *guarded* — the residual amount bound is exactly what the
exhaustive small-scope check (``simon prove``) discharges by running every
bounded universe through the real engine. Recognition is idiom-structural,
not a general theorem prover: an unguarded decrement of a float carry slot
is a finding (``commit-carry-nonneg``) unless the scan's final carry is
dropped — build_trajectory's virtual replay decrements unconditionally by
design (onehot ≡ 1) and its recorded rows are gated by the feasibility
masks stacked alongside them, so a dropped carry is classified ``virtual``
rather than flagged. Anything else the matcher cannot classify is reported
honestly as ``unrecognized`` rather than silently trusted.

Abstract domain — per-array, element-uniform::

    AVal = (lo, hi, pos_inf, neg_inf, nan, nonzero, kind)

``[lo, hi]`` bounds the *finite* values under real-number semantics
(float overflow/underflow are out of scope, which is sound for the proofs
above: they are about NaN production and value ranges after explicit
clips). Infinities are NOT encoded in the interval: ``pos_inf``/``neg_inf``
say "an element may be exactly ±inf", which is what the NaN transfer rules
need (``inf - inf``, ``0 * inf``, ``inf / inf``). Widening a bound to
±math.inf therefore means "finite but unknown magnitude" and does not set
the flags. ``nan`` is the taint bit; ``nonzero`` is the refinement the
safe-division idiom ``x / jnp.where(d == 0, 1.0, d)`` relies on (a
``select_n`` whose predicate is ``eq(d, 0)`` excludes 0 from the
not-equal branch).

Loops (``scan``) are handled by a join/widen fixpoint on the carry;
``pjit`` recurses. Any primitive without a transfer rule produces TOP and
an ``unhandled-primitive`` finding so the rule table cannot rot silently.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

NEG = float("-inf")
POS = float("inf")

#: primitives whose operands must be NaN-free: a NaN here corrupts which
#: lane gets *selected*, not just a value (the paper's placement-policy
#: correctness concern).
SELECTION_PRIMITIVES = frozenset(
    {"argmax", "argmin", "reduce_max", "reduce_min", "sort"}
)


# ---------------------------------------------------------------------------
# The domain
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AVal:
    """Element-uniform abstraction of one array. See module docstring."""

    lo: float
    hi: float
    pos_inf: bool = False
    neg_inf: bool = False
    nan: bool = False
    nonzero: bool = False
    kind: str = "f"  # 'f' float / 'i' int / 'b' bool

    def flags(self) -> List[str]:
        out = []
        if self.pos_inf:
            out.append("+inf")
        if self.neg_inf:
            out.append("-inf")
        if self.nan:
            out.append("nan")
        if self.nonzero:
            out.append("nonzero")
        return out

    def describe(self) -> str:
        core = f"[{self.lo:g}, {self.hi:g}] {self.kind}"
        fl = self.flags()
        return core + (" {" + ",".join(fl) + "}" if fl else "")


def kind_of(dtype) -> str:
    d = np.dtype(dtype)
    if d == np.bool_:
        return "b"
    if np.issubdtype(d, np.integer):
        return "i"
    return "f"


def top(kind: str) -> AVal:
    if kind == "b":
        return AVal(0.0, 1.0, kind="b")
    return AVal(
        NEG, POS, pos_inf=(kind == "f"), neg_inf=(kind == "f"),
        nan=(kind == "f"), kind=kind,
    )


def const(v: float, kind: str = "f") -> AVal:
    return AVal(float(v), float(v), nonzero=(v != 0), kind=kind)


def from_concrete(x) -> AVal:
    """Abstraction of a concrete array (entry inputs, jaxpr consts)."""
    arr = np.asarray(x)
    kind = kind_of(arr.dtype)
    if arr.size == 0:
        return AVal(0.0, 0.0, kind=kind)
    if kind == "b":
        f = arr.astype(np.float64)
        return AVal(
            float(f.min()), float(f.max()), nonzero=bool(arr.all()), kind="b"
        )
    f = arr.astype(np.float64)
    nan = bool(np.isnan(f).any())
    pos_inf = bool((f == POS).any())
    neg_inf = bool((f == NEG).any())
    finite = f[np.isfinite(f)]
    lo = float(finite.min()) if finite.size else 0.0
    hi = float(finite.max()) if finite.size else 0.0
    nonzero = not bool((f == 0).any())
    return AVal(lo, hi, pos_inf, neg_inf, nan, nonzero, kind)


def _promote(a: AVal, b: AVal) -> str:
    ks = {a.kind, b.kind}
    if "f" in ks:
        return "f"
    if "i" in ks:
        return "i"
    return "b"


def join(a: AVal, b: AVal) -> AVal:
    return AVal(
        min(a.lo, b.lo),
        max(a.hi, b.hi),
        a.pos_inf or b.pos_inf,
        a.neg_inf or b.neg_inf,
        a.nan or b.nan,
        a.nonzero and b.nonzero,
        _promote(a, b),
    )


def widen(old: AVal, new: AVal) -> AVal:
    """Accelerate the scan fixpoint: any bound still moving goes to
    unknown-finite (±math.inf WITHOUT the inf flags — see module doc)."""
    return AVal(
        NEG if new.lo < old.lo else old.lo,
        POS if new.hi > old.hi else old.hi,
        old.pos_inf or new.pos_inf,
        old.neg_inf or new.neg_inf,
        old.nan or new.nan,
        old.nonzero and new.nonzero,
        _promote(old, new),
    )


def may_pos(a: AVal) -> bool:
    return a.hi > 0 or a.pos_inf


def may_neg(a: AVal) -> bool:
    return a.lo < 0 or a.neg_inf


def may_zero(a: AVal) -> bool:
    return (not a.nonzero) and a.lo <= 0 <= a.hi


def inf_any(a: AVal) -> bool:
    return a.pos_inf or a.neg_inf


# Bound arithmetic that never manufactures NaN: inf-inf / inf*0 at the
# BOUND level means "unknown", resolved toward the conservative side.
def _badd(x: float, y: float, side: int) -> float:
    r = x + y
    if math.isnan(r):
        return NEG if side < 0 else POS
    return r


def _bmul(x: float, y: float) -> float:
    if x == 0 or y == 0:
        return 0.0
    return x * y


def _bdiv(x: float, y: float, side: int) -> float:
    if x == 0:
        return 0.0
    if y == 0:  # callers exclude 0 from y's interval; defensive only
        return NEG if side < 0 else POS
    r = x / y
    if math.isnan(r):
        return NEG if side < 0 else POS
    return r


# ---------------------------------------------------------------------------
# Transfer rules
# ---------------------------------------------------------------------------

def _r_add(a: AVal, b: AVal) -> AVal:
    return AVal(
        _badd(a.lo, b.lo, -1),
        _badd(a.hi, b.hi, +1),
        a.pos_inf or b.pos_inf,
        a.neg_inf or b.neg_inf,
        a.nan or b.nan or (a.pos_inf and b.neg_inf) or (a.neg_inf and b.pos_inf),
        False,
        _promote(a, b),
    )


def _r_sub(a: AVal, b: AVal) -> AVal:
    return AVal(
        _badd(a.lo, -b.hi, -1),
        _badd(a.hi, -b.lo, +1),
        a.pos_inf or b.neg_inf,
        a.neg_inf or b.pos_inf,
        a.nan or b.nan or (a.pos_inf and b.pos_inf) or (a.neg_inf and b.neg_inf),
        False,
        _promote(a, b),
    )


def _r_mul(a: AVal, b: AVal) -> AVal:
    prods = (
        _bmul(a.lo, b.lo), _bmul(a.lo, b.hi),
        _bmul(a.hi, b.lo), _bmul(a.hi, b.hi),
    )
    # THE sentinel rule: ±inf times a possibly-zero factor is NaN.
    nan = (
        a.nan or b.nan
        or (inf_any(a) and may_zero(b))
        or (inf_any(b) and may_zero(a))
    )
    pos_inf = (
        (a.pos_inf and may_pos(b)) or (a.neg_inf and may_neg(b))
        or (b.pos_inf and may_pos(a)) or (b.neg_inf and may_neg(a))
    )
    neg_inf = (
        (a.pos_inf and may_neg(b)) or (a.neg_inf and may_pos(b))
        or (b.pos_inf and may_neg(a)) or (b.neg_inf and may_pos(a))
    )
    return AVal(
        min(prods), max(prods), pos_inf, neg_inf, nan,
        a.nonzero and b.nonzero, _promote(a, b),
    )


def _r_div(a: AVal, b: AVal) -> AVal:
    nan = (
        a.nan or b.nan
        or (may_zero(a) and may_zero(b))          # 0 / 0
        or (inf_any(a) and inf_any(b))            # inf / inf
    )
    if may_neg(b):  # denominator sign unknown: infs can land either side
        pos_inf = inf_any(a) or (may_zero(b) and (may_pos(a) or may_neg(a)))
        neg_inf = pos_inf
    else:
        pos_inf = a.pos_inf or (may_pos(a) and may_zero(b))
        neg_inf = a.neg_inf or (may_neg(a) and may_zero(b))
    if b.nonzero and (b.lo > 0 or b.hi < 0):
        quots = (
            _bdiv(a.lo, b.lo, -1), _bdiv(a.lo, b.hi, -1),
            _bdiv(a.hi, b.lo, +1), _bdiv(a.hi, b.hi, +1),
        )
        lo, hi = min(quots), max(quots)
    else:
        # 0 in (or arbitrarily near) the denominator range: unbounded
        lo, hi = NEG, POS
    return AVal(lo, hi, pos_inf, neg_inf, nan, False, _promote(a, b))


def _r_rem(a: AVal, b: AVal) -> AVal:
    m = max(abs(b.lo), abs(b.hi))
    lo = 0.0 if a.lo >= 0 and not a.neg_inf else -m
    hi = 0.0 if a.hi <= 0 and not a.pos_inf else m
    nan = a.nan or b.nan or inf_any(a) or (
        may_zero(b) and _promote(a, b) == "f"
    )
    return AVal(lo, hi, False, False, nan, False, _promote(a, b))


def _r_max(a: AVal, b: AVal) -> AVal:
    lo_cands = [max(a.lo, b.lo)]
    if a.neg_inf:
        lo_cands.append(b.lo)
    if b.neg_inf:
        lo_cands.append(a.lo)
    return AVal(
        min(lo_cands),
        max(a.hi, b.hi),
        a.pos_inf or b.pos_inf,
        a.neg_inf and b.neg_inf,
        a.nan or b.nan,
        False,
        _promote(a, b),
    )


def _r_min(a: AVal, b: AVal) -> AVal:
    hi_cands = [min(a.hi, b.hi)]
    if a.pos_inf:
        hi_cands.append(b.hi)
    if b.pos_inf:
        hi_cands.append(a.hi)
    return AVal(
        min(a.lo, b.lo),
        max(hi_cands),
        a.pos_inf and b.pos_inf,
        a.neg_inf or b.neg_inf,
        a.nan or b.nan,
        False,
        _promote(a, b),
    )


def _r_neg(a: AVal) -> AVal:
    return AVal(
        -a.hi, -a.lo, a.neg_inf, a.pos_inf, a.nan, a.nonzero, a.kind
    )


def _r_abs(a: AVal) -> AVal:
    if a.lo >= 0:
        lo, hi = a.lo, a.hi
    elif a.hi <= 0:
        lo, hi = -a.hi, -a.lo
    else:
        lo, hi = 0.0, max(-a.lo, a.hi)
    return AVal(lo, hi, inf_any(a), False, a.nan, a.nonzero, a.kind)


def _r_sign(a: AVal) -> AVal:
    lo = -1.0 if may_neg(a) else (0.0 if may_zero(a) else 1.0)
    hi = 1.0 if may_pos(a) else (0.0 if may_zero(a) else -1.0)
    return AVal(lo, hi, False, False, a.nan, False, a.kind)


def _r_floor(a: AVal) -> AVal:
    lo = a.lo if math.isinf(a.lo) else math.floor(a.lo)
    hi = a.hi if math.isinf(a.hi) else math.floor(a.hi)
    return AVal(lo, hi, a.pos_inf, a.neg_inf, a.nan, False, a.kind)


def _bool_out() -> AVal:
    return AVal(0.0, 1.0, kind="b")


def _sum_of(a: AVal, n: int) -> AVal:
    """Sum of exactly n elements each abstracted by `a`."""
    if n <= 0:
        return AVal(0.0, 0.0, kind=a.kind)
    return AVal(
        _bmul(float(n), a.lo) if a.lo < 0 else a.lo,
        _bmul(float(n), a.hi) if a.hi > 0 else a.hi,
        a.pos_inf,
        a.neg_inf,
        a.nan or (a.pos_inf and a.neg_inf),  # mixed ±inf sum
        False,
        a.kind,
    )


def _convert(a: AVal, new_kind: str) -> AVal:
    if new_kind == a.kind:
        return a
    if new_kind == "b":
        lo = 1.0 if a.nonzero else 0.0
        all_zero = a.lo == 0 == a.hi and not inf_any(a) and not a.nan
        return AVal(lo, 0.0 if all_zero else 1.0,
                    nonzero=a.nonzero, kind="b")
    if new_kind == "i":
        if a.nan or inf_any(a):
            return top("i")  # float->int of nan/inf is undefined
        lo = a.lo if math.isinf(a.lo) else math.floor(a.lo)
        hi = a.hi if math.isinf(a.hi) else math.ceil(a.hi)
        return AVal(lo, hi, nonzero=a.nonzero, kind="i")
    return dataclasses.replace(a, kind="f")


# ---------------------------------------------------------------------------
# Findings / reports
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, order=True)
class InvariantFinding:
    entry: str
    kind: str       # nan-output | selection-taint | score-range | unhandled-primitive
    primitive: str
    path: str       # eqn path, e.g. "scan[17]/eqn3"
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


#: verdict ladder for one float carry slot of one scan, strongest first.
CARRY_PROVED = "proved"              # guard ⇒ amount ≤ slot + ε
CARRY_GUARDED = "guarded"            # bool guard tied to slot, amount not
CARRY_NON_DECREASING = "non-decreasing"
CARRY_UNCHANGED = "unchanged"
CARRY_UNRECOGNIZED = "unrecognized"  # update shape outside the idiom set
CARRY_VIRTUAL = "virtual"            # unguarded, but the final carry is
                                     # dropped: a replay carry, not state
CARRY_UNGUARDED = "unguarded"        # decrement with no bool guard: finding


@dataclasses.dataclass(frozen=True, order=True)
class CommitCarryReport:
    """Non-negativity verdict for one float carry slot of one scan."""

    path: str    # eqn path of the scan, e.g. "eqn0/scan"
    slot: int
    shape: str
    verdict: str
    detail: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class _Scope:
    """Per-jaxpr def-use environment. `alias` links this jaxpr's invars back
    to the caller's atoms (pjit inlining), so dataflow facts like "this
    select_n's predicate is eq(d, 0)" survive the _where sub-jaxpr split."""

    __slots__ = ("def_of", "alias")

    def __init__(self) -> None:
        self.def_of: Dict = {}
        self.alias: Dict = {}


# ---------------------------------------------------------------------------
# Guarded-decrement matcher helpers (commit-carry non-negativity)
# ---------------------------------------------------------------------------

#: primitives that forward their first operand's values unchanged — the
#: matcher looks straight through them when resolving atom identity.
_SHAPE_PRIMS = frozenset(
    {"broadcast_in_dim", "reshape", "squeeze", "copy", "transpose",
     "stop_gradient"}
)

#: feasibility comparisons; eq/ne deliberately excluded (an equality on a
#: resource counter does not bound a decrement).
_ORDER_COMPARISONS = frozenset({"gt", "ge", "lt", "le"})


def _chase(defs: Dict, atom, literal_t):
    """Resolve an atom through value-preserving shape primitives."""
    while not isinstance(atom, literal_t):
        q = defs.get(atom)
        if q is None or q.primitive.name not in _SHAPE_PRIMS:
            return atom
        atom = q.invars[0]
    return atom


def _chase_eps(defs: Dict, atom, literal_t):
    """Like _chase, but also through add/sub with a literal operand — the
    commit filters compare against ``free + _EPS``, and the slop term must
    not hide the carry slot from the matcher."""
    while True:
        if isinstance(atom, literal_t):
            return atom
        q = defs.get(atom)
        if q is None:
            return atom
        name = q.primitive.name
        if name in _SHAPE_PRIMS:
            atom = q.invars[0]
            continue
        if name in ("add", "sub"):
            a, b = (_chase(defs, x, literal_t) for x in q.invars)
            if isinstance(b, literal_t):
                atom = q.invars[0]
                continue
            if name == "add" and isinstance(a, literal_t):
                atom = q.invars[1]
                continue
        return atom


def _mul_factors(defs: Dict, atom, literal_t) -> List:
    """Flatten a (possibly nested) product into its factor atoms, each
    resolved through shape primitives."""
    atom = _chase(defs, atom, literal_t)
    q = defs.get(atom) if not isinstance(atom, literal_t) else None
    if q is not None and q.primitive.name == "mul":
        return (_mul_factors(defs, q.invars[0], literal_t)
                + _mul_factors(defs, q.invars[1], literal_t))
    return [atom]


def _guard_origin(defs: Dict, factor, literal_t):
    """If `factor` is a {0,1}-valued guard (a bool converted to the carry
    dtype), return the underlying bool var; else None."""
    if isinstance(factor, literal_t):
        return None
    q = defs.get(factor)
    if (
        q is not None
        and q.primitive.name == "convert_element_type"
        and np.dtype(q.invars[0].aval.dtype) == np.bool_
    ):
        g = _chase(defs, q.invars[0], literal_t)
        return None if isinstance(g, literal_t) else g
    return None


def _comparisons_in_slice(defs: Dict, roots: Sequence, literal_t) -> List:
    """All order-comparison eqns in the backward slice of `roots` (the
    transitive defs of the guard inside one scan body)."""
    seen_vars = set()
    seen_eqns: Dict[int, object] = {}
    stack = list(roots)
    while stack:
        v = stack.pop()
        if v in seen_vars:
            continue
        seen_vars.add(v)
        q = defs.get(v)
        if q is None:
            continue
        if id(q) not in seen_eqns:
            seen_eqns[id(q)] = q
            for a in q.invars:
                if not isinstance(a, literal_t) and a in defs:
                    stack.append(a)
    return [q for q in seen_eqns.values()
            if q.primitive.name in _ORDER_COMPARISONS]


def _aval_of(env: Dict, atom, literal_t) -> AVal:
    if isinstance(atom, literal_t):
        return from_concrete(atom.val)
    got = env.get(atom)
    if got is not None:
        return got
    return top(kind_of(atom.aval.dtype))


# ---------------------------------------------------------------------------
# The interpreter
# ---------------------------------------------------------------------------

class Interpreter:
    MAX_FIXPOINT_ITERS = 8
    WIDEN_AFTER = 2

    def __init__(self, entry: str) -> None:
        self.entry = entry
        self._findings: Dict[Tuple, InvariantFinding] = {}
        self._record = True
        self.carry_proofs: List[CommitCarryReport] = []

    # -- findings -----------------------------------------------------------

    def finding(self, kind: str, primitive: str, path: str, message: str):
        if not self._record:
            return
        key = (kind, primitive, path)
        if key not in self._findings:
            self._findings[key] = InvariantFinding(
                self.entry, kind, primitive, path, message
            )

    @property
    def findings(self) -> List[InvariantFinding]:
        return sorted(self._findings.values())

    # -- jaxpr walking ------------------------------------------------------

    def run_closed(self, closed, in_avals: Sequence[AVal], path: str = "",
                   alias: Optional[Dict] = None,
                   env_out: Optional[Dict] = None) -> List[AVal]:
        consts = [from_concrete(c) for c in closed.consts]
        return self.run_jaxpr(closed.jaxpr, consts, in_avals, path, alias,
                              env_out)

    def run_jaxpr(self, jaxpr, const_avals: Sequence[AVal],
                  in_avals: Sequence[AVal], path: str = "",
                  alias: Optional[Dict] = None,
                  env_out: Optional[Dict] = None) -> List[AVal]:
        import jax
        import jax.extend.core

        literal_t = jax.extend.core.Literal
        dropvar_t = getattr(jax.core, "DropVar", ())
        env: Dict = {}
        scope = _Scope()
        if alias:
            scope.alias = alias

        for v, a in zip(jaxpr.constvars, const_avals):
            env[v] = a
        for v, a in zip(jaxpr.invars, in_avals):
            env[v] = a

        def read(atom) -> AVal:
            if isinstance(atom, literal_t):
                return from_concrete(atom.val)
            return env[atom]

        for idx, eqn in enumerate(jaxpr.eqns):
            here = f"{path}eqn{idx}"
            ins = [read(x) for x in eqn.invars]
            outs = self.eval_eqn(eqn, ins, here, scope)
            for v, out in zip(eqn.outvars, outs):
                if not isinstance(v, dropvar_t):
                    env[v] = out
                    scope.def_of[v] = eqn

        if env_out is not None:
            env_out.update(env)
        return [read(v) for v in jaxpr.outvars]

    # -- eqn dispatch -------------------------------------------------------

    def eval_eqn(self, eqn, ins: List[AVal], path: str,
                 scope: _Scope) -> List[AVal]:
        name = eqn.primitive.name

        if name in SELECTION_PRIMITIVES:
            self._check_selection(eqn, ins, path)

        if name == "jit":
            sub = eqn.params["jaxpr"]
            child_alias = {
                v: (scope, a)
                for v, a in zip(sub.jaxpr.invars, eqn.invars)
            }
            return self.run_closed(
                sub, ins,
                path=f"{path}/{eqn.params.get('name', 'pjit')}/",
                alias=child_alias,
            )
        if name == "scan":
            return self._eval_scan(eqn, ins, path)
        if name == "select_n":
            return [self._eval_select_n(eqn, ins, scope)]

        rule = _RULES.get(name)
        if rule is None:
            self.finding(
                "unhandled-primitive", name, path,
                f"no transfer rule for primitive '{name}'; result widened "
                "to TOP",
            )
            return [top(kind_of(v.aval.dtype)) for v in eqn.outvars]
        return rule(self, eqn, ins)

    def _check_selection(self, eqn, ins: List[AVal], path: str) -> None:
        name = eqn.primitive.name
        n_keys = eqn.params.get("num_keys", len(ins)) if name == "sort" else 1
        for i, a in enumerate(ins[:n_keys] if name == "sort" else ins[:1]):
            if a.nan:
                self.finding(
                    "selection-taint", name, path,
                    f"operand {i} of {name} may be NaN "
                    f"({a.describe()}): a poisoned lane can steal the "
                    "selection",
                )

    # -- select_n with eq/ne refinement ------------------------------------

    def _eval_select_n(self, eqn, ins: List[AVal], scope: _Scope) -> AVal:
        cases = list(ins[1:])
        refined = self._refine_select(eqn, scope)
        if refined is not None:
            cases[refined] = dataclasses.replace(cases[refined], nonzero=True)
        out = cases[0]
        for c in cases[1:]:
            out = join(out, c)
        return out

    @staticmethod
    def _resolve(atom, scope: Optional[_Scope]):
        """Canonical (atom, scope) pair: look through broadcast/reshape/copy
        chains and across pjit boundaries via the scope alias links."""
        import jax
        import jax.extend.core

        while True:
            if isinstance(atom, jax.extend.core.Literal) or scope is None:
                return atom, None
            d = scope.def_of.get(atom)
            if d is not None and d.primitive.name in (
                "broadcast_in_dim", "reshape", "squeeze", "copy",
            ):
                atom = d.invars[0]
                continue
            if d is None and atom in scope.alias:
                scope, atom = scope.alias[atom]
                continue
            return atom, scope

    def _refine_select(self, eqn, scope: _Scope) -> Optional[int]:
        """`where(d == 0, k, d)` lowers to `select_n(eq(d,0), d, k)`: on the
        case-0 (pred false) branch d != 0. Symmetrically for ne on case 1.
        Returns the case index to mark nonzero when the branch operand is
        the compared variable and the comparand is exactly 0."""
        import jax
        import jax.extend.core

        if len(eqn.invars) != 3:
            return None
        pred_atom, pred_scope = self._resolve(eqn.invars[0], scope)
        if pred_scope is None:
            return None
        pred_def = pred_scope.def_of.get(pred_atom)
        if pred_def is None or pred_def.primitive.name not in ("eq", "ne"):
            return None
        case_idx = 0 if pred_def.primitive.name == "eq" else 1
        lhs = self._resolve(pred_def.invars[0], pred_scope)
        rhs = self._resolve(pred_def.invars[1], pred_scope)
        case_src = self._resolve(eqn.invars[1 + case_idx], scope)

        def lit_zero(res) -> bool:
            a = res[0]
            return isinstance(a, jax.extend.core.Literal) and bool(
                np.all(np.asarray(a.val) == 0)
            )

        for var_side, lit_side in ((lhs, rhs), (rhs, lhs)):
            if (
                lit_zero(lit_side)
                and case_src[0] is var_side[0]
                and case_src[1] is var_side[1]
            ):
                return case_idx
        return None

    # -- scan fixpoint ------------------------------------------------------

    def _eval_scan(self, eqn, ins: List[AVal], path: str) -> List[AVal]:
        body = eqn.params["jaxpr"]
        n_const = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        consts = list(ins[:n_const])
        carry = list(ins[n_const:n_const + n_carry])
        xs = list(ins[n_const + n_carry:])

        prev_record = self._record
        self._record = False  # findings only on the final, sound pass
        try:
            for it in range(self.MAX_FIXPOINT_ITERS):
                outs = self.run_closed(body, consts + carry + xs)
                new_carry = [join(c, o) for c, o in zip(outs[:n_carry], carry)]
                if new_carry == carry:
                    break
                if it >= self.WIDEN_AFTER:
                    new_carry = [
                        widen(c, n) for c, n in zip(carry, new_carry)
                    ]
                carry = new_carry
            else:
                carry = [
                    top(c.kind) if c.kind != "b" else _bool_out()
                    for c in carry
                ]
        finally:
            self._record = prev_record

        env_map: Dict = {}
        outs = self.run_closed(body, consts + carry + xs,
                               path=f"{path}/scan/", env_out=env_map)
        if self._record:
            self._check_commit_carry(eqn, env_map, f"{path}/scan")
        final_carry = [join(c, o) for c, o in zip(outs[:n_carry], carry)]
        return final_carry + outs[n_carry:]

    # -- commit-carry non-negativity (guarded-decrement matcher) ------------

    def _check_commit_carry(self, eqn, env: Dict, path: str) -> None:
        """Classify every float carry slot of one scan body. See module
        docstring: structural recognition of the commit idiom, with the
        amount bound on *guarded* slots discharged by ``simon prove``."""
        import jax
        import jax.extend.core

        body = eqn.params["jaxpr"].jaxpr
        n_const = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        defs: Dict = {}
        for q in body.eqns:
            for v in q.outvars:
                defs[v] = q
        carry_vars = list(body.invars[n_const:n_const + n_carry])

        dropvar_t = getattr(jax.core, "DropVar", ())
        for slot, (cv, ov) in enumerate(zip(carry_vars,
                                            body.outvars[:n_carry])):
            if kind_of(cv.aval.dtype) != "f":
                continue
            verdict, detail = self._classify_carry_slot(
                defs, env, cv, ov, jax.extend.core.Literal
            )
            if verdict == CARRY_UNGUARDED and isinstance(
                eqn.outvars[slot], dropvar_t
            ):
                # the final carry never escapes the scan: this is a
                # virtual-commit replay (build_trajectory's onehot ≡ 1
                # evolution), not committed cluster state. Negative values
                # are reachable by design and gated by the feasibility
                # masks recorded alongside them.
                verdict = CARRY_VIRTUAL
                detail = ("unconditional decrement, but the final carry is "
                          "dropped — a virtual replay carry whose rows are "
                          "gated by recorded feasibility masks downstream")
            self.carry_proofs.append(CommitCarryReport(
                path, slot, cv.aval.str_short(), verdict, detail
            ))
            if verdict == CARRY_UNGUARDED:
                self.finding(
                    "commit-carry-nonneg", "scan", f"{path}/carry{slot}",
                    f"carry slot {slot} ({cv.aval.str_short()}): {detail}",
                )

    def _classify_carry_slot(self, defs, env, cv, ov, literal_t
                             ) -> Tuple[str, str]:
        out_atom = _chase(defs, ov, literal_t)
        if out_atom is cv:
            return CARRY_UNCHANGED, "carry slot is threaded through unchanged"
        q = defs.get(out_atom)
        if q is None:
            return (CARRY_UNRECOGNIZED,
                    "carry output rebinds a different input; not the commit "
                    "idiom")

        if q.primitive.name == "add":
            sides = [_chase(defs, a, literal_t) for a in q.invars]
            if cv not in sides:
                return (CARRY_UNRECOGNIZED,
                        f"update is add() but neither operand is the carry "
                        f"slot")
            inc = q.invars[1 - sides.index(cv)]
            av = _aval_of(env, inc, literal_t)
            if av.lo >= 0 and not av.neg_inf and not av.nan:
                return (CARRY_NON_DECREASING,
                        f"update adds a provably non-negative increment "
                        f"({av.describe()})")
            return (CARRY_UNRECOGNIZED,
                    f"update adds an increment the domain cannot bound "
                    f"below 0 ({av.describe()})")

        if q.primitive.name != "sub":
            return (CARRY_UNRECOGNIZED,
                    f"update primitive '{q.primitive.name}' is outside the "
                    f"guarded-decrement idiom")
        if _chase(defs, q.invars[0], literal_t) is not cv:
            return (CARRY_UNRECOGNIZED,
                    "sub() minuend is not the carry slot itself")

        dec = q.invars[1]
        factors = _mul_factors(defs, dec, literal_t)
        guards, amounts = [], []
        for f in factors:
            g = _guard_origin(defs, f, literal_t)
            (guards if g is not None else amounts).append(
                g if g is not None else f
            )
        if not guards:
            av = _aval_of(env, dec, literal_t)
            if av.hi <= 0 and not av.pos_inf and not av.nan:
                return (CARRY_NON_DECREASING,
                        f"unconditional sub of a non-positive amount "
                        f"({av.describe()})")
            return (CARRY_UNGUARDED,
                    "decrement has no {0,1} bool-derived guard factor; the "
                    "slot can go negative whenever the amount exceeds it")

        # the guard's backward slice: does a feasibility comparison tie the
        # guard to this carry slot (and, ideally, to the decrement amount)?
        tied_to_slot = False
        tied_to_amount = False
        for comp in _comparisons_in_slice(defs, guards, literal_t):
            sides = [_chase_eps(defs, a, literal_t) for a in comp.invars]
            for i in (0, 1):
                if sides[i] is cv:
                    tied_to_slot = True
                    other = sides[1 - i]
                    if any(other is a for a in amounts):
                        tied_to_amount = True
        if tied_to_slot and tied_to_amount:
            return (CARRY_PROVED,
                    "guard ⇒ decrement amount ≤ slot + ε (feasibility "
                    "comparison on this slot vs the amount is in the "
                    "guard's slice): slot ≥ -ε by induction")
        if tied_to_slot:
            return (CARRY_GUARDED,
                    "bool guard's slice compares this slot against a bound, "
                    "but the decrement amount is not syntactically the "
                    "compared quantity; residual discharged by simon prove")
        return (CARRY_GUARDED,
                "decrement is {0,1}-guarded but no comparison on this slot "
                "was found in the guard's slice; non-negativity rests on "
                "the small-scope exhaustive check (simon prove)")


# ---------------------------------------------------------------------------
# Rule table
# ---------------------------------------------------------------------------

def _binary(fn: Callable[[AVal, AVal], AVal]):
    return lambda interp, eqn, ins: [fn(ins[0], ins[1])]


def _unary(fn: Callable[[AVal], AVal]):
    return lambda interp, eqn, ins: [fn(ins[0])]


def _identity(interp, eqn, ins):
    return [ins[0]]


def _join_all(interp, eqn, ins):
    out = ins[0]
    for a in ins[1:]:
        out = join(out, a)
    return [out]


def _bool_rule(interp, eqn, ins):
    return [_bool_out()]


def _logic_rule(interp, eqn, ins):
    if all(a.kind == "b" for a in ins):
        return [_bool_out()]
    return [top("i")]  # bitwise on ints: no precision needed here


def _r_convert(interp, eqn, ins):
    return [_convert(ins[0], kind_of(eqn.params["new_dtype"]))]


def _r_iota(interp, eqn, ins):
    n = eqn.params["shape"][eqn.params["dimension"]]
    return [AVal(0.0, float(max(n - 1, 0)),
                 kind=kind_of(eqn.params["dtype"]))]


def _reduced_count(eqn) -> int:
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in eqn.params["axes"]:
        n *= shape[ax]
    return n


def _r_reduce_sum(interp, eqn, ins):
    return [_sum_of(ins[0], _reduced_count(eqn))]


def _r_reduce_minmax(keep: str):
    def rule(interp, eqn, ins):
        a = ins[0]
        if _reduced_count(eqn) == 0:
            # reduce over an empty axis yields the monoid identity
            ident = NEG if keep == "max" else POS
            return [AVal(0.0, 0.0, pos_inf=(ident == POS),
                         neg_inf=(ident == NEG), kind=a.kind)]
        return [dataclasses.replace(a, nonzero=False)]

    return rule


def _r_cumsum(interp, eqn, ins):
    n = eqn.invars[0].aval.shape[eqn.params["axis"]]
    a = ins[0]
    s = _sum_of(a, max(n, 1))
    # a prefix sum of k<=n terms: bounds include the 1-term case too
    return [AVal(min(s.lo, a.lo, 0.0) if n > 1 else s.lo,
                 max(s.hi, a.hi, 0.0) if n > 1 else s.hi,
                 s.pos_inf, s.neg_inf, s.nan, False, a.kind)]


def _r_dot_general(interp, eqn, ins):
    (lc, _), _ = eqn.params["dimension_numbers"]
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in lc:
        n *= shape[ax]
    return [_sum_of(_r_mul(ins[0], ins[1]), n)]


def _r_argminmax(interp, eqn, ins):
    axes = eqn.params["axes"]
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in axes:
        n *= shape[ax]
    return [AVal(0.0, float(max(n - 1, 0)),
                 kind=kind_of(eqn.params["index_dtype"]))]


def _r_sort(interp, eqn, ins):
    return [dataclasses.replace(a, nonzero=a.nonzero) for a in ins]


def _r_gather(interp, eqn, ins):
    out = ins[0]
    if "FILL" in str(eqn.params.get("mode", "")).upper():
        out = join(out, const(0.0, out.kind))
    return [out]


def _r_scatter(interp, eqn, ins):
    return [join(ins[0], ins[2])]


def _r_scatter_add(interp, eqn, ins):
    op, upd = ins[0], ins[2]
    # an unknown number of updates may hit one slot: only the direction
    # updates cannot push survives as a bound
    lo = NEG if may_neg(upd) else op.lo
    hi = POS if may_pos(upd) else op.hi
    nan = op.nan or upd.nan or (
        (op.pos_inf or upd.pos_inf) and (op.neg_inf or upd.neg_inf)
    )
    return [AVal(lo, hi, op.pos_inf or upd.pos_inf,
                 op.neg_inf or upd.neg_inf, nan, False, op.kind)]


def _r_clamp(interp, eqn, ins):
    mn, x, mx = ins
    return [_r_min(_r_max(x, mn), mx)]


def _r_dynamic_update_slice(interp, eqn, ins):
    return [join(ins[0], ins[1])]


def _r_is_finite(interp, eqn, ins):
    return [_bool_out()]


def _r_bitcast(interp, eqn, ins):
    """Reinterpreting bits severs every numeric relationship between input
    and output (an f32 in [0,1] bitcast to u32 spans almost the whole u32
    range), so the only sound transfer is TOP of the OUTPUT kind. That stays
    precise where it matters: a bitcast to an integer kind cannot introduce
    inf/NaN, which is exactly what ops.delta's digest_fold relies on."""
    return [top(kind_of(eqn.params["new_dtype"]))]


_RULES: Dict[str, Callable] = {
    "add": _binary(_r_add),
    "sub": _binary(_r_sub),
    "mul": _binary(_r_mul),
    "div": _binary(_r_div),
    "rem": _binary(_r_rem),
    "max": _binary(_r_max),
    "min": _binary(_r_min),
    "neg": _unary(_r_neg),
    "abs": _unary(_r_abs),
    "sign": _unary(_r_sign),
    "floor": _unary(_r_floor),
    "clamp": _r_clamp,
    "eq": _bool_rule,
    "ne": _bool_rule,
    "ge": _bool_rule,
    "gt": _bool_rule,
    "le": _bool_rule,
    "lt": _bool_rule,
    "is_finite": _r_is_finite,
    "and": _logic_rule,
    "or": _logic_rule,
    "xor": _logic_rule,
    "not": _logic_rule,
    "reduce_and": _bool_rule,
    "reduce_or": _bool_rule,
    "reduce_sum": _r_reduce_sum,
    "reduce_max": _r_reduce_minmax("max"),
    "reduce_min": _r_reduce_minmax("min"),
    "cumsum": _r_cumsum,
    "dot_general": _r_dot_general,
    "argmax": _r_argminmax,
    "argmin": _r_argminmax,
    "sort": _r_sort,
    "iota": _r_iota,
    "convert_element_type": _r_convert,
    "bitcast_convert_type": _r_bitcast,
    "broadcast_in_dim": _identity,
    "reshape": _identity,
    "transpose": _identity,
    "squeeze": _identity,
    "slice": _identity,
    "rev": _identity,
    "copy": _identity,
    "stop_gradient": _identity,
    "dynamic_slice": lambda interp, eqn, ins: [ins[0]],
    "dynamic_update_slice": _r_dynamic_update_slice,
    "concatenate": _join_all,
    "gather": _r_gather,
    "scatter": _r_scatter,
    "scatter-add": _r_scatter_add,
    "pad": _join_all,  # pad value is the last operand; join covers it
}


# ---------------------------------------------------------------------------
# Entry-tier audit: every registered jit entry on canonical shapes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EntryInvariantReport:
    entry: str
    bool_outputs: int
    float_outputs: int
    outputs: List[str]
    findings: List[InvariantFinding]
    commit_carry: List[CommitCarryReport] = dataclasses.field(
        default_factory=list
    )

    @property
    def ok(self) -> bool:
        return not self.findings

    def carry_verdict_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for p in self.commit_carry:
            out[p.verdict] = out.get(p.verdict, 0) + 1
        return out

    def to_dict(self) -> dict:
        return {
            "entry": self.entry,
            "ok": self.ok,
            "bool_outputs": self.bool_outputs,
            "float_outputs": self.float_outputs,
            "outputs": self.outputs,
            "commit_carry": [p.to_dict() for p in sorted(self.commit_carry)],
            "findings": [f.to_dict() for f in self.findings],
        }


def _dynamic_leaves(fn, args: tuple, kwargs: dict) -> list:
    """The jit's flat dynamic args, in invar order: (args, kwargs) leaves
    with the static args (``__osim_static_argnames__`` from @sanitizable)
    dropped, as jit itself drops them before flattening."""
    import inspect

    import jax

    static = set(getattr(fn, "__osim_static_argnames__", ()) or ())
    if static:
        params = list(inspect.signature(fn).parameters)
        args = tuple(
            a for i, a in enumerate(args)
            if i >= len(params) or params[i] not in static
        )
        kwargs = {k: v for k, v in kwargs.items() if k not in static}
    return jax.tree_util.tree_leaves((args, kwargs))


def check_traceable(entry: str, fn, args, kwargs=None) -> EntryInvariantReport:
    """Abstractly interpret one traceable callable on concrete args.

    Uses `.trace()` when `fn` is a jit Function (invars are its dynamic
    args, see _dynamic_leaves) and `jax.make_jaxpr` otherwise.
    """
    import jax

    kwargs = kwargs or {}
    if hasattr(fn, "trace"):
        traced = fn.trace(*args, **kwargs)
        closed = traced.jaxpr
        flat = _dynamic_leaves(fn, args, kwargs)
        if len(flat) != len(closed.jaxpr.invars):
            raise ValueError(
                f"{entry}: {len(flat)} dynamic args for "
                f"{len(closed.jaxpr.invars)} invars (static args undeclared?)"
            )
    else:
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
        flat = jax.tree_util.tree_leaves((args, kwargs))
    in_avals = [from_concrete(x) for x in flat]
    interp = Interpreter(entry)
    outs = interp.run_closed(closed, in_avals)

    bool_outputs = 0
    float_outputs = 0
    rendered = []
    for i, (out, var) in enumerate(zip(outs, closed.jaxpr.outvars)):
        rendered.append(out.describe())
        if out.kind == "b":
            bool_outputs += 1
            continue
        if out.kind == "f":
            float_outputs += 1
            if out.nan:
                interp.finding(
                    "nan-output", "output", f"out{i}",
                    f"float output {i} may be NaN ({out.describe()})",
                )
    return EntryInvariantReport(
        entry, bool_outputs, float_outputs, rendered, interp.findings,
        list(interp.carry_proofs),
    )


# ---------------------------------------------------------------------------
# Plugin-tier audit: each score kernel proves [0, 100]
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PluginInvariantReport:
    plugin: str
    lo: float
    hi: float
    flags: List[str]
    findings: List[InvariantFinding]

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "plugin": self.plugin,
            "ok": self.ok,
            "range": [self.lo, self.hi],
            "flags": self.flags,
            "findings": [f.to_dict() for f in self.findings],
        }


def check_score_plugin(name: str, fn, args) -> PluginInvariantReport:
    """Prove a score kernel's output is NaN-free, inf-free and in [0,100]."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    in_avals = [from_concrete(x) for x in jax.tree_util.tree_leaves(args)]
    interp = Interpreter(f"plugin:{name}")
    out = interp.run_closed(closed, in_avals)[0]

    findings = list(interp.findings)
    problems = []
    if out.nan:
        problems.append("may be NaN")
    if inf_any(out):
        problems.append("may be infinite")
    if out.lo < 0.0 or out.hi > 100.0:
        problems.append(f"range [{out.lo:g}, {out.hi:g}] escapes [0, 100]")
    if problems:
        findings.append(
            InvariantFinding(
                f"plugin:{name}", "score-range", "output", "out0",
                f"score {'; '.join(problems)} ({out.describe()})",
            )
        )
    return PluginInvariantReport(
        name, out.lo, out.hi, out.flags(), sorted(set(findings))
    )


def _plugin_specs():
    from ..ops import kernels as k

    return {
        "balanced_allocation": lambda ns, carry, pod: k.score_balanced(ns, carry, pod),
        "least_allocated": lambda ns, carry, pod: k.score_least_allocated(ns, carry, pod),
        "node_affinity": lambda ns, carry, pod: k.score_node_affinity(ns, pod),
        "taint_toleration": lambda ns, carry, pod: k.score_taint_toleration(ns, pod),
        "topology_spread": lambda ns, carry, pod: k.score_topology_spread(ns, carry, pod),
        "inter_pod_affinity": lambda ns, carry, pod: k.score_inter_pod_affinity(ns, carry, pod),
        "prefer_avoid_pods": lambda ns, carry, pod: k.score_prefer_avoid(ns, pod),
        "simon": lambda ns, carry, pod: k.score_simon(ns, carry, pod),
        "gpu_share": lambda ns, carry, pod: k.score_gpu_share(ns, carry, pod),
        "open_local": lambda ns, carry, pod: k.score_open_local(ns, carry, pod),
    }


# ---------------------------------------------------------------------------
# Top-level driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class InvariantAudit:
    entries: List[EntryInvariantReport]
    plugins: List[PluginInvariantReport]

    @property
    def ok(self) -> bool:
        return all(e.ok for e in self.entries) and all(
            p.ok for p in self.plugins
        )

    @property
    def findings(self) -> List[InvariantFinding]:
        out: List[InvariantFinding] = []
        for e in self.entries:
            out.extend(e.findings)
        for p in self.plugins:
            out.extend(p.findings)
        return sorted(out)

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "entries": [e.to_dict() for e in sorted(
                self.entries, key=lambda e: e.entry
            )],
            "plugins": [p.to_dict() for p in sorted(
                self.plugins, key=lambda p: p.plugin
            )],
        }

    def render_text(self) -> str:
        lines = [
            f"invariants: {'ok' if self.ok else 'FAILED'} — "
            f"{len(self.entries)} jit entries, {len(self.plugins)} score "
            f"plugins, {len(self.findings)} finding(s)"
        ]
        for e in sorted(self.entries, key=lambda e: e.entry):
            mark = "ok " if e.ok else "FAIL"
            lines.append(
                f"  [{mark}] {e.entry}: {e.bool_outputs} mask output(s) "
                f"proved {{0,1}}, {e.float_outputs} float output(s) NaN-free"
                if e.ok
                else f"  [{mark}] {e.entry}"
            )
            if e.commit_carry:
                counts = e.carry_verdict_counts()
                summary = ", ".join(
                    f"{counts[v]} {v}" for v in (
                        CARRY_PROVED, CARRY_GUARDED, CARRY_NON_DECREASING,
                        CARRY_UNCHANGED, CARRY_VIRTUAL, CARRY_UNRECOGNIZED,
                        CARRY_UNGUARDED,
                    ) if v in counts
                )
                lines.append(
                    f"        commit-carry: {len(e.commit_carry)} float "
                    f"slot(s) — {summary}"
                )
            for f in e.findings:
                lines.append(f"        {f.kind} @ {f.path}: {f.message}")
        for p in sorted(self.plugins, key=lambda p: p.plugin):
            mark = "ok " if p.ok else "FAIL"
            lines.append(
                f"  [{mark}] plugin {p.plugin}: score in "
                f"[{p.lo:g}, {p.hi:g}]"
            )
            for f in p.findings:
                lines.append(f"        {f.kind} @ {f.path}: {f.message}")
        return "\n".join(lines)


def run_invariants() -> InvariantAudit:
    """Retrace every canonical jit entry (jaxpr_audit.AUDIT_TARGETS) + the
    10 score plugins and abstractly interpret every jaxpr. Deterministic
    given the canonical state (the same one the jaxpr auditor uses)."""
    from . import jaxpr_audit as ja

    captured = ja._capture_calls()
    # one representative call per entry: the capture order is deterministic,
    # keep the first occurrence
    seen: Dict[str, object] = {}
    for cap in captured:
        seen.setdefault(cap.name, cap)

    entries = [
        check_traceable(name, cap.fn, cap.args, cap.kwargs)
        for name, cap in sorted(seen.items())
    ]

    probe = seen.get("ops.kernels:probe_step")
    plugins: List[PluginInvariantReport] = []
    if probe is not None:
        ns, carry, pod = probe.args[0], probe.args[1], probe.args[2]
        for pname, pfn in sorted(_plugin_specs().items()):
            plugins.append(check_score_plugin(pname, pfn, (ns, carry, pod)))
    return InvariantAudit(entries, plugins)
