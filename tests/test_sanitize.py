"""OSIM_SANITIZE=1 checkify mode: off-path passthrough, violation
raising + metric, entry coverage, and plain-vs-sanitized result parity."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.ops.sanitize import (
    SANITIZE_ENV,
    SanitizerViolation,
    sanitizable,
    sanitize_enabled,
    sanitized_entries,
)
from open_simulator_tpu.utils import metrics


@sanitizable("test:log_entry")
@jax.jit
def _log_entry(x):
    return jnp.log(x)


@sanitizable("test:static_entry", static_argnames=("n",))
@functools.partial(jax.jit, static_argnames=("n",))
def _pad_entry(x, n):
    return jnp.pad(x, (0, n))


def test_env_parsing(monkeypatch):
    for off in ("", "0", "false", "no", " NO "):
        monkeypatch.setenv(SANITIZE_ENV, off)
        assert not sanitize_enabled()
    for on in ("1", "true", "yes", "on"):
        monkeypatch.setenv(SANITIZE_ENV, on)
        assert sanitize_enabled()
    monkeypatch.delenv(SANITIZE_ENV)
    assert not sanitize_enabled()


def test_disabled_passthrough_keeps_nan_silent(monkeypatch):
    monkeypatch.delenv(SANITIZE_ENV, raising=False)
    out = _log_entry(jnp.float32(-1.0))
    assert np.isnan(out)  # plain jit semantics, no raise


def test_violation_raises_and_increments_metric(monkeypatch):
    monkeypatch.setenv(SANITIZE_ENV, "1")
    before = metrics.SANITIZER_VIOLATIONS.value(entry="test:log_entry")
    with pytest.raises(SanitizerViolation) as ei:
        _log_entry(jnp.float32(-1.0))
    assert ei.value.entry == "test:log_entry"
    assert "nan" in ei.value.check_message.lower()
    after = metrics.SANITIZER_VIOLATIONS.value(entry="test:log_entry")
    assert after == before + 1


def test_clean_call_returns_plain_value(monkeypatch):
    monkeypatch.setenv(SANITIZE_ENV, "1")
    out = _log_entry(jnp.float32(1.0))
    assert float(out) == 0.0


def test_positional_static_args_survive_sanitizing(monkeypatch):
    """Regression: the checkified re-jit must bind static_argnames for
    positionally-passed args (grouped.py calls _group_jit positionally)."""
    monkeypatch.setenv(SANITIZE_ENV, "1")
    out = _pad_entry(jnp.ones(3, jnp.float32), 2)
    assert out.shape == (5,)


def test_nested_trace_falls_through(monkeypatch):
    """Inside someone else's jit trace the outer entry owns the checkify
    scope — the wrapper must not try to re-jit concrete-side."""
    monkeypatch.setenv(SANITIZE_ENV, "1")

    @jax.jit
    def outer(x):
        return _log_entry(x)

    assert np.isnan(outer(jnp.float32(-1.0)))  # no raise


def test_all_audited_entries_are_sanitizable():
    from open_simulator_tpu.analysis.jaxpr_audit import (
        AUDIT_TARGETS,
        REQUIRED_COVERAGE,
    )
    from open_simulator_tpu.ops import delta, fast, grouped, kernels

    entries = sanitized_entries(delta, fast, grouped, kernels)
    assert set(REQUIRED_COVERAGE) <= set(entries)
    expected = sum(len(attrs) for attrs in AUDIT_TARGETS.values())
    assert len([e for e in entries if not e.startswith("test:")]) == expected


def test_trace_delegation_for_jaxpr_audit():
    """The jaxpr auditor calls .trace() on captured entries; the wrapper
    must delegate to the underlying jit Function."""
    traced = _log_entry.trace(jnp.zeros(4, jnp.float32))
    assert len(traced.jaxpr.jaxpr.invars) == 1


def test_simulation_parity_plain_vs_sanitized(monkeypatch):
    """A real end-to-end sweep places identically with the sanitizer armed
    (observational mode): same scheduled/unscheduled counts."""
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )
    from tests.factories import make_deployment, make_node

    def placed_unsched():
        nodes = [make_node(f"n-{i}", "16", "32Gi") for i in range(8)]
        deploy = make_deployment("web", 24, cpu="500m", memory="1Gi")
        result = simulate(
            ClusterResource(nodes=nodes),
            [AppResource(name="web", objects=[deploy])],
        )
        placed = sum(len(st.pods) for st in result.node_status)
        return placed, len(result.unscheduled)

    monkeypatch.delenv(SANITIZE_ENV, raising=False)
    plain_placed, plain_unsched = placed_unsched()
    monkeypatch.setenv(SANITIZE_ENV, "1")
    san_placed, san_unsched = placed_unsched()
    assert (san_placed, san_unsched) == (plain_placed, plain_unsched)
    assert plain_placed == 24
