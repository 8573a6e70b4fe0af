"""Runtime sanitizer mode for the jit entry points (OSIM_SANITIZE=1).

`@sanitizable(name, ...)` stacks ABOVE the `jax.jit` decorator on every
production entry point (ops/fast.py, ops/grouped.py, ops/kernels.py,
ops/delta.py). With the env knob off the wrapper is a single dict
lookup + call-through to the jitted function, so the fast path stays the
fast path. With `OSIM_SANITIZE=1` the same entry runs under
`jax.experimental.checkify` with NaN, out-of-bounds-index and
division-by-zero checks: fuzz and chaos runs execute with lane-level
assertions armed, and any violation increments
`osim_sanitizer_violations_total{entry=}` and raises SanitizerViolation
with checkify's first-failure message.

The decorator deliberately does NOT replace the `jax.jit` spelling —
analysis/lint.py detects jit roots syntactically, and the jaxpr auditor
calls `.trace()` on the module attribute — so the wrapper delegates
`trace`/`lower` to the underlying jit Function and keeps the original
decorator line intact underneath.

Checkify errors caught (the ISSUE's "NaN/OOB/div" set):

  * checkify.nan_checks   — a primitive *produced* a NaN. Note this does
    not flag infinities, so the deliberate -inf sentinels in fast.py's
    score lanes pass; only a genuine -inf * 0.0 style poisoning trips it.
  * checkify.index_checks — out-of-bounds gather/scatter/dynamic-slice.
  * checkify.div_checks   — integer division by zero.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Callable, Sequence

from ..utils import metrics

SANITIZE_ENV = "OSIM_SANITIZE"


class SanitizerViolation(RuntimeError):
    """A checkify error (NaN/OOB/div) fired inside a sanitized jit entry."""

    def __init__(self, entry: str, message: str) -> None:
        super().__init__(f"{entry}: {message}")
        self.entry = entry
        self.check_message = message


def sanitize_enabled() -> bool:
    """True when OSIM_SANITIZE is set to anything but ''/'0'/'false'/'no'.
    Read per call, so tests and chaos runs can flip it without reimports.

    Lint sees this as jit-reachable only through the decorator expression
    on the entry points; it runs on the host before dispatch, never inside
    a trace."""
    return os.environ.get(SANITIZE_ENV, "").strip().lower() not in (  # osim: lint-ok[impure-read]
        "",
        "0",
        "false",
        "no",
    )


def _errors():
    from jax.experimental import checkify

    return checkify.nan_checks | checkify.index_checks | checkify.div_checks


def _has_tracer(args: tuple, kwargs: dict) -> bool:
    import jax

    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def sanitizable(
    name: str,
    static_argnames: Sequence[str] = (),
    donate_argnums: Sequence[int] = (),
) -> Callable:
    """Wrap a jitted entry point with an opt-in checkify layer.

    `name` keys the osim_sanitizer_violations_total{entry=} counter and
    matches jaxpr_audit's entry naming ("ops.fast:light_scan").
    `static_argnames` must repeat the underlying jit's static args so the
    checkified re-jit treats them identically.

    `donate_argnums` must repeat the underlying jit's donated positional
    args. It is declarative: the jaxpr auditor reads it (as
    ``__osim_donate_argnums__``) to prove no donated arg aliases another
    arg of the same call, and callers/tests use it to know which inputs a
    call consumes. The checkified re-jit deliberately does NOT donate —
    sanitize mode trades the buffer reuse for intact inputs in checkify's
    failure reports; results are bit-identical either way.
    """
    static = tuple(static_argnames)
    donated = tuple(donate_argnums)

    def deco(jitted: Callable) -> Callable:
        import inspect

        cache: dict = {}
        cache_lock = threading.Lock()

        def _checked() -> Callable:
            fn = cache.get("fn")
            if fn is None:
                with cache_lock:
                    fn = cache.get("fn")
                    if fn is None:
                        import jax
                        from jax.experimental import checkify

                        # checkify the raw function beneath the jit, not the
                        # jit Function: nesting jits would hand the inner one
                        # tracers for its static args. wraps() restores the
                        # original signature so static_argnames still binds
                        # args passed positionally (checkify's own wrapper
                        # is (*args, **kwargs)-opaque).
                        inner = inspect.unwrap(jitted)
                        checked = functools.wraps(inner)(
                            checkify.checkify(inner, errors=_errors())
                        )
                        fn = cache["fn"] = jax.jit(
                            checked, static_argnames=static
                        )
            return fn

        @functools.wraps(jitted)
        def wrapper(*args: Any, **kwargs: Any):
            if not sanitize_enabled():
                return jitted(*args, **kwargs)
            if _has_tracer(args, kwargs):
                # already inside someone else's trace: the outer entry owns
                # the checkify scope
                return jitted(*args, **kwargs)
            err, out = _checked()(*args, **kwargs)
            msg = err.get()
            if msg:
                metrics.SANITIZER_VIOLATIONS.inc(entry=name)
                raise SanitizerViolation(name, msg)
            return out

        # jaxpr_audit captures the module attribute and calls .trace()/.lower()
        wrapper.trace = jitted.trace  # type: ignore[attr-defined]
        wrapper.lower = jitted.lower  # type: ignore[attr-defined]
        wrapper.__osim_sanitizable__ = name  # type: ignore[attr-defined]
        wrapper.__osim_donate_argnums__ = donated  # type: ignore[attr-defined]
        wrapper.__osim_static_argnames__ = static  # type: ignore[attr-defined]
        return wrapper

    return deco


def sanitized_entries(*modules) -> dict:
    """name -> wrapper for every @sanitizable attribute in `modules`
    (test/bench helper; mirrors jaxpr_audit.AUDIT_TARGETS coverage)."""
    out = {}
    for mod in modules:
        for attr in dir(mod):
            fn = getattr(mod, attr)
            tag = getattr(fn, "__osim_sanitizable__", None)
            if tag is not None:
                out[tag] = fn
    return out
