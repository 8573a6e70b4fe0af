"""jaxpr auditor + recompile guard.

Fast tests drive the jaxpr walker against small deliberately-broken
kernels (host callback, wide avals, nesting); the slow-marked tests run
the full canonical audit and the capacity-sweep recompile guard — the
same checks the CI lint job enforces through `simon lint`.
"""

import numpy as np
import pytest

from open_simulator_tpu.analysis.jaxpr_audit import (
    FORBIDDEN_PRIMITIVES,
    RECOMPILE_BUDGET,
    _audit_one,
    _Captured,
    run_audit,
    run_recompile_guard,
)


def test_forbidden_primitive_set_nonempty():
    assert FORBIDDEN_PRIMITIVES, "an empty forbidden set passes vacuously"
    assert "pure_callback" in FORBIDDEN_PRIMITIVES
    assert "device_put" in FORBIDDEN_PRIMITIVES


def test_audit_flags_host_callback():
    """A deliberately impure kernel — host callback in the middle of the
    computation — must fail the audit."""
    import jax
    import jax.numpy as jnp

    def impure(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            x,
        )
        return y + 1.0

    fn = jax.jit(impure)
    rep = _audit_one(_Captured("test:impure", fn, (jnp.ones(4, jnp.float32),), {}))
    assert rep.traced
    assert "pure_callback" in rep.forbidden
    assert not rep.ok


def test_audit_flags_callback_inside_scan():
    """The walker must recurse into scan/cond sub-jaxprs — hiding the host
    round trip inside a loop body is the realistic failure mode."""
    import jax
    import jax.numpy as jnp

    def step(c, x):
        y = jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((), jnp.float32), x
        )
        return c + y, y

    def kern(xs):
        out, _ = jax.lax.scan(step, jnp.float32(0), xs)
        return out

    fn = jax.jit(kern)
    rep = _audit_one(_Captured("test:scan", fn, (jnp.ones(8, jnp.float32),), {}))
    assert "pure_callback" in rep.forbidden


def test_audit_flags_wide_avals():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):

        def wide(x):
            return x.astype(jnp.float64) * 2.0

        fn = jax.jit(wide)
        rep = _audit_one(
            _Captured("test:wide", fn, (jnp.ones(4, jnp.float32),), {})
        )
    assert rep.traced
    assert rep.wide_avals and not rep.ok


def test_audit_clean_kernel_passes():
    import jax
    import jax.numpy as jnp

    def clean(x):
        return jnp.cumsum(x * 2.0).astype(jnp.int32)

    fn = jax.jit(clean)
    rep = _audit_one(_Captured("test:clean", fn, (jnp.ones(4, jnp.float32),), {}))
    assert rep.ok and rep.n_eqns > 0 and rep.primitives


def test_full_audit_covers_all_entry_points():
    """fast/grouped/kernels jit entries all traced on canonical bucketed
    shapes, with clean jaxprs (compile-heavy: runs the real dispatchers)."""
    report = run_audit()
    assert report.ok, report.render_text()
    assert not report.required_missing
    names = {t.name for t in report.targets}
    for required in (
        "ops.fast:build_trajectory",
        "ops.fast:sort_select",
        "ops.fast:light_scan",
        "ops.fast:domain_select",
        "ops.grouped:_group_jit",
        "ops.kernels:schedule_batch",
    ):
        assert required in names


@pytest.mark.slow
def test_recompile_guard_within_budget():
    """The capacity sweep must stay within the declared shape-family compile
    budget, and the jax.monitoring count must agree with the
    osim_compile_cache_total{event="backend_compile"} metric.

    slow-marked: the guard needs a cold jit cache for its `compiles > 0`
    liveness check, which a shared tier-1 process can't guarantee (earlier
    tests may have compiled the same kernel family). The CI lint job runs
    it in a fresh process on every PR via `simon lint`."""
    result = run_recompile_guard()
    assert result.ok, result.render_text()
    assert 0 < result.compiles <= RECOMPILE_BUDGET
    assert result.compiles == result.metric_compiles
    assert result.nodes_added > 0
