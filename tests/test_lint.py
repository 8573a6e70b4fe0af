"""AST lint engine + rules: per-rule true positive and near-miss fixtures.

Each rule gets (at least) one fixture snippet that MUST be flagged and one
superficially similar snippet that MUST NOT be (the near-miss false
positive). Fixture packages are written to tmp_path and only parsed —
never imported — so snippets are free to reference jax without tracing
anything.
"""

import json
import textwrap

import pytest

from open_simulator_tpu.analysis import iter_rules, run_lint
from open_simulator_tpu.analysis.lint import build_context


def _lint(tmp_path, source, extra_modules=None, only_rules=None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    for name, src in (extra_modules or {}).items():
        (pkg / f"{name}.py").write_text(textwrap.dedent(src))
    return run_lint(
        package_root=str(pkg), report_root=str(tmp_path), only_rules=only_rules
    )


def _rules_hit(report):
    return {(f.rule, f.line) for f in report.active}


def _rule_ids(report):
    return {f.rule for f in report.active}


# ---------------------------------------------------------------------------
# tracer-coercion


def test_tracer_coercion_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def kern(x):
            v = float(x)
            w = x.item()
            return v + w
        """,
    )
    assert sum(f.rule == "tracer-coercion" for f in r.active) == 2


def test_tracer_coercion_near_miss_static_and_host(tmp_path):
    """float() of a shape (static) and float() in host-only code are fine."""
    r = _lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def kern(x):
            return x * float(x.shape[0])

        def host(x):
            return float(x)
        """,
    )
    assert "tracer-coercion" not in _rule_ids(r)


def test_tracer_coercion_np_asarray(tmp_path):
    r = _lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            return np.asarray(x)

        @jax.jit
        def kern_ok(x):
            return x + np.zeros(4)[0]
        """,
    )
    hits = [f for f in r.active if f.rule == "tracer-coercion"]
    assert len(hits) == 1 and "asarray" in hits[0].message


# ---------------------------------------------------------------------------
# impure-read


def test_impure_read_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import os
        import time
        import random
        import jax

        @jax.jit
        def kern(x):
            t = time.time()
            e = os.environ.get("K")
            z = random.random()
            return x + t + z
        """,
    )
    assert sum(f.rule == "impure-read" for f in r.active) == 3


def test_impure_read_near_miss_host_only(tmp_path):
    """The same reads outside jit-reachable code are host configuration."""
    r = _lint(
        tmp_path,
        """
        import os
        import time
        import jax

        def configure():
            return float(os.environ.get("K", "1")) + time.time()

        @jax.jit
        def kern(x):
            return x * 2
        """,
    )
    assert "impure-read" not in _rule_ids(r)


# ---------------------------------------------------------------------------
# unhashable-static-default


def test_unhashable_static_default_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def kern(x, opts=[]):
            return x
        """,
    )
    assert "unhashable-static-default" in _rule_ids(r)


def test_unhashable_static_default_near_miss(tmp_path):
    """Tuple defaults on static args and list defaults on TRACED args are
    both fine (only static args become cache keys)."""
    r = _lint(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def kern(x, opts=(), scales=None):
            return x
        """,
    )
    assert "unhashable-static-default" not in _rule_ids(r)


def test_unhashable_static_default_jit_alias_form(tmp_path):
    """`name = jax.jit(fn, static_argnames=...)` marks fn as an entry too."""
    r = _lint(
        tmp_path,
        """
        import jax

        def kern(x, opts=[]):
            return x

        kern_jit = jax.jit(kern, static_argnames=("opts",))
        """,
    )
    assert "unhashable-static-default" in _rule_ids(r)


# ---------------------------------------------------------------------------
# import-time-jnp


def test_import_time_jnp_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import jax.numpy as jnp

        TABLE = jnp.arange(16)
        """,
    )
    assert "import-time-jnp" in _rule_ids(r)


def test_import_time_jnp_near_miss(tmp_path):
    """jnp inside functions and module-level *numpy* constants are fine."""
    r = _lint(
        tmp_path,
        """
        import jax.numpy as jnp
        import numpy as np

        TABLE = np.arange(16)

        def build():
            return jnp.arange(16)
        """,
    )
    assert "import-time-jnp" not in _rule_ids(r)


# ---------------------------------------------------------------------------
# f64-literal (scoped to ops/ modules)


def test_f64_literal_true_positive(tmp_path):
    pkg = tmp_path / "pkg"
    ops = pkg / "ops"
    ops.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (ops / "__init__.py").write_text("")
    (ops / "k.py").write_text(
        textwrap.dedent(
            """
            import numpy as np

            def f(x):
                return np.zeros(4, np.float64), x.astype(float)
            """
        )
    )
    r = run_lint(package_root=str(pkg), report_root=str(tmp_path))
    assert sum(f.rule == "f64-literal" for f in r.active) == 2


def test_f64_literal_near_miss_outside_ops(tmp_path):
    """float64 outside ops/ (report layer etc.) is out of scope; float32
    inside ops/ is the blessed dtype."""
    pkg = tmp_path / "pkg"
    ops = pkg / "ops"
    ops.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (ops / "__init__.py").write_text("")
    (pkg / "report.py").write_text("import numpy as np\nX = np.float64(0)\n")
    (ops / "k.py").write_text("import numpy as np\nY = np.zeros(4, np.float32)\n")
    r = run_lint(package_root=str(pkg), report_root=str(tmp_path))
    assert "f64-literal" not in _rule_ids(r)


# ---------------------------------------------------------------------------
# unbucketed-jit-shape


_SHAPE_PKG = {
    "encode": """
        def round_up(n, minimum=8):
            m = minimum
            while m < n:
                m *= 2
            return m
        """,
}


def test_unbucketed_shape_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("out_size",))
        def sized(x, out_size):
            return x[:out_size]

        def host(xs):
            n = len(xs)
            return sized(xs, n)
        """,
        extra_modules=_SHAPE_PKG,
    )
    assert "unbucketed-jit-shape" in _rule_ids(r)


def test_unbucketed_shape_near_miss_bucketed(tmp_path):
    """Sizes that provably flow through round_up (directly, via a local, or
    via min/max composition) are the blessed pattern."""
    r = _lint(
        tmp_path,
        """
        import functools
        import jax

        from .encode import round_up

        @functools.partial(jax.jit, static_argnames=("out_size",))
        def sized(x, out_size):
            return x[:out_size]

        def host(xs):
            g = round_up(len(xs))
            return sized(xs, g), sized(xs, min(round_up(4), 64))
        """,
        extra_modules=_SHAPE_PKG,
    )
    assert "unbucketed-jit-shape" not in _rule_ids(r)


def test_unbucketed_shape_wrapper_propagation(tmp_path):
    """A thin wrapper forwarding its own param into a jit shape arg moves
    the obligation to the wrapper's call sites (the _group_call pattern)."""
    r = _lint(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("group_size",))
        def kern(x, group_size):
            return x[:group_size]

        def wrapper(x, group_size):
            return kern(x, group_size=group_size)

        def host_bad(xs):
            return wrapper(xs, len(xs))
        """,
        extra_modules=_SHAPE_PKG,
    )
    hits = [f for f in r.active if f.rule == "unbucketed-jit-shape"]
    assert len(hits) == 1  # the wrapper call site, not the wrapper body


# ---------------------------------------------------------------------------
# device-sync-in-loop


def test_device_sync_in_loop_true_positive(tmp_path):
    r = _lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            return x * 2

        def drive(xs):
            acc = []
            for x in xs:
                y = kern(x)
                acc.append(np.asarray(y))
                y.block_until_ready()
            return acc
        """,
    )
    hits = [f for f in r.active if f.rule == "device-sync-in-loop"]
    assert len(hits) == 2
    assert any("np.asarray" in f.message for f in hits)
    assert any("block_until_ready" in f.message for f in hits)


def test_device_sync_near_miss_host_numpy_and_epilogue(tmp_path):
    """Coercing genuine numpy state in the loop is host arithmetic, and a
    one-shot sync after the loop is the blessed shape — neither flags."""
    r = _lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            return x * 2

        def drive(xs, hosts):
            outs = []
            for x, h in zip(xs, hosts):
                outs.append(kern(x))
                total = float(np.sum(h))  # host state, not a jit result
            return np.asarray(outs[-1]), total
        """,
    )
    assert "device-sync-in-loop" not in _rule_ids(r)


def test_device_sync_near_miss_consolidated_device_get(tmp_path):
    """One jax.device_get over the batch is the idiom the rule pushes
    toward; a host-returning wrapper that fetches internally is likewise
    not jit-ish, so loops around it are free to coerce its results."""
    r = _lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            return x * 2

        def kern_host(x):
            return jax.device_get(kern(x))

        def drive(xs):
            outs = [kern(x) for x in xs]
            fetched = []
            for x in xs:
                y = kern_host(x)
                fetched.append(float(np.sum(y)))
            return jax.device_get(outs), fetched
        """,
    )
    assert "device-sync-in-loop" not in _rule_ids(r)


_TRACING_FETCH = {
    "tracing": """
        import jax

        def fetch(tree, site):
            return jax.device_get(tree)
        """,
}


@pytest.mark.parametrize("wrapper,flagged", [
    # the tracing fetch makes a wrapper host-returning, like device_get
    ("return fetch(kern(x), 'site')", False),
    ("return jax.device_get(kern(x))", False),
    # control: a wrapper that hands back the jit result is jit-ish
    ("return kern(x)", True),
])
def test_device_sync_tracing_fetch_is_a_consolidated_fetch(
    tmp_path, wrapper, flagged
):
    r = _lint(
        tmp_path,
        f"""
        import jax
        import numpy as np

        from .tracing import fetch

        @jax.jit
        def kern(x):
            return x * 2

        def kern_host(x):
            {wrapper}

        def drive(xs):
            out = []
            for x in xs:
                y = kern_host(x)
                out.append(np.asarray(y))
                z = fetch(kern(x), "in-loop")
                out.append(np.asarray(z))
            return out
        """,
        extra_modules=_TRACING_FETCH,
    )
    hits = [f for f in r.active if f.rule == "device-sync-in-loop"]
    assert len(hits) == int(flagged)


def test_device_sync_suppression_escape(tmp_path):
    """A deliberate per-iteration sync takes the standard comment escape."""
    r = _lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            return x * 2

        def drive(xs):
            for x in xs:
                y = kern(x)
                # the mask gates the next dispatch; the sync is the point
                m = np.asarray(y)  # osim: lint-ok[device-sync-in-loop]
                if not m.any():
                    break
        """,
    )
    assert "device-sync-in-loop" not in _rule_ids(r)
    assert sum(f.suppressed for f in r.findings) == 1


# ---------------------------------------------------------------------------
# engine machinery


def test_reachability_through_helpers_and_scan(tmp_path):
    """Violations in helpers are attributed to the jit root that reaches
    them, including scan-body functions passed to jax.lax.scan."""
    r = _lint(
        tmp_path,
        """
        import time
        import jax

        def step(c, x):
            return c + time.time(), x

        def helper(x):
            return float(x)

        @jax.jit
        def kern(xs):
            out, _ = jax.lax.scan(step, 0.0, xs)
            return helper(out)

        def unreached(x):
            return float(x)
        """,
    )
    assert ("impure-read" in _rule_ids(r)) and ("tracer-coercion" in _rule_ids(r))
    roots = {f.jit_root for f in r.active if f.jit_root}
    assert roots == {"pkg.mod:kern"}
    flagged_lines = {f.line for f in r.active}
    assert not any(
        f.line > 17 for f in r.active
    ), f"unreached host fn must not be flagged: {flagged_lines}"


def test_suppression_comment(tmp_path):
    r = _lint(
        tmp_path,
        """
        import time
        import jax

        @jax.jit
        def kern(x):
            # trace-time constant is intentional here (test fixture)
            t = time.time()  # osim: lint-ok[impure-read]
            return x + t
        """,
    )
    assert not r.active
    assert sum(f.suppressed for f in r.findings) == 1


def test_suppression_is_rule_specific(tmp_path):
    """A lint-ok for one rule must not swallow a different rule's finding
    on the same line."""
    r = _lint(
        tmp_path,
        """
        import time
        import jax

        @jax.jit
        def kern(x):
            t = float(time.time())  # osim: lint-ok[impure-read]
            return x + t
        """,
    )
    assert _rule_ids(r) == {"tracer-coercion"}


def test_json_output_schema(tmp_path):
    r = _lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def kern(x):
            return float(x)
        """,
    )
    doc = json.loads(r.to_json())
    assert doc["version"] == 1
    assert doc["files_scanned"] >= 2
    assert doc["rules"] == sorted(rid for rid, _ in iter_rules())
    (finding,) = doc["findings"]
    assert finding["rule"] == "tracer-coercion"
    assert finding["path"].endswith("mod.py")
    assert finding["line"] > 0 and "message" in finding


def test_rule_filter(tmp_path):
    r = _lint(
        tmp_path,
        """
        import time
        import jax

        @jax.jit
        def kern(x):
            return float(x) + time.time()
        """,
        only_rules=["impure-read"],
    )
    assert _rule_ids(r) == {"impure-read"}


def test_repo_package_is_lint_clean():
    """The acceptance gate: `simon lint` exits 0 on the repository, and
    every surviving suppression is justified (non-empty neighbour comment)."""
    report = run_lint()
    assert not report.active, report.render_text()
    ctx = build_context()
    for mod in ctx.modules.values():
        for line_no in mod.suppressions:
            window = mod.lines[max(0, line_no - 3): line_no]
            assert any(
                "#" in line for line in window
            ), f"{mod.path}:{line_no}: suppression lacks a justification comment"


def test_repo_jit_roots_discovered():
    """The engine must keep seeing the real kernels — an import refactor
    that silently drops reachability would make every purity rule vacuous."""
    ctx = build_context()
    roots = set(ctx.reachable.values())
    for expected in (
        "open_simulator_tpu.ops.fast:build_trajectory",
        "open_simulator_tpu.ops.fast:sort_select",
        "open_simulator_tpu.ops.fast:light_scan",
        "open_simulator_tpu.ops.fast:domain_select",
        "open_simulator_tpu.ops.grouped:schedule_group",
        "open_simulator_tpu.ops.kernels:schedule_batch",
        "open_simulator_tpu.ops.kernels:probe_step",
        "open_simulator_tpu.ops.kernels:commit_step",
    ):
        assert expected in roots, f"missing jit root {expected}"


# ---------------------------------------------------------------------------
# lock-in-hot-path


HOT_PREAMBLE = """
    import threading
"""


def test_lock_in_hot_path_true_positive(tmp_path):
    """A per-call Lock on a thread target (and in everything it calls)
    synchronizes nothing and must be flagged."""
    r = _lint(
        tmp_path,
        HOT_PREAMBLE + """
    def helper():
        guard = threading.RLock()
        return guard

    def worker():
        lock = threading.Lock()
        with lock:
            helper()

    threading.Thread(target=worker).start()
    """,
        only_rules=["lock-in-hot-path"],
    )
    assert sum(f.rule == "lock-in-hot-path" for f in r.active) == 2


def test_lock_in_hot_path_instance_and_module_lifetime_ok(tmp_path):
    """Module-level locks and instance publishes (self._lock = Lock(),
    Condition(Lock()) wrappers included) are the sanctioned shapes."""
    r = _lint(
        tmp_path,
        HOT_PREAMBLE + """
    _lock = threading.Lock()

    class Pool:
        def worker(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(threading.Lock())
            with _lock:
                pass

    threading.Thread(target=Pool().worker).start()
    """,
        only_rules=["lock-in-hot-path"],
    )
    assert not r.active, [f.render() for f in r.active]


def test_lock_in_hot_path_cold_code_not_flagged(tmp_path):
    """A local lock in code no thread root reaches is out of scope (the
    module-hosts expansion the race pass uses does NOT apply here)."""
    r = _lint(
        tmp_path,
        HOT_PREAMBLE + """
    def setup_once():
        lock = threading.Lock()
        return lock

    def worker():
        pass

    threading.Thread(target=worker).start()
    """,
        only_rules=["lock-in-hot-path"],
    )
    assert not r.active, [f.render() for f in r.active]


def test_lock_in_hot_path_suppression(tmp_path):
    r = _lint(
        tmp_path,
        HOT_PREAMBLE + """
    def worker():
        lock = threading.Lock()  # osim: lint-ok[lock-in-hot-path]
        with lock:
            pass

    threading.Thread(target=worker).start()
    """,
        only_rules=["lock-in-hot-path"],
    )
    assert not r.active
    assert sum(f.suppressed for f in r.findings) == 1


def test_repo_clean_against_lock_in_hot_path():
    from open_simulator_tpu.analysis import run_lint

    r = run_lint(only_rules=["lock-in-hot-path"])
    assert not r.active, [f.render() for f in r.active]
