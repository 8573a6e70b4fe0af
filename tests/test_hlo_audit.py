"""Preflight auditor: budget book round-trip/diff, collective census,
replication + transfer-guard fixtures, and the ladder×mesh matrix on the
conftest's 8 forced host devices.

The capture pass (warmup_registry) executes every entry once (~30 s), so
it is module-scoped and shared; matrix tests filter it down to a couple
of entries rather than re-lowering all 18.
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.analysis import hlo_audit as H
from open_simulator_tpu.analysis.budget import (
    BudgetBook,
    ProgramBudget,
    estimate_bytes_by_device,
    program_key,
)
from open_simulator_tpu.parallel import mesh as pmesh

# ---------------------------------------------------------------------------
# shared captures (one ~30 s capture run for the whole module)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def caps():
    from open_simulator_tpu.engine.warmup import registry_captures

    return registry_captures()


@pytest.fixture(scope="module")
def tables():
    return H._axis_tables()


def _only(caps, *names):
    wanted = set(names)
    return [c for c in caps if c.name in wanted]


# ---------------------------------------------------------------------------
# budget book: round-trip + diff semantics (no jax compile involved)
# ---------------------------------------------------------------------------


def _budget(**over):
    base = dict(
        peak_bytes=1_000_000, argument_bytes=600_000, output_bytes=300_000,
        temp_bytes=100_000, alias_bytes=0,
        collectives={"all-reduce": 2}, collective_bytes=4096,
    )
    base.update(over)
    return ProgramBudget(**base)


def test_budget_book_round_trip(tmp_path):
    key = program_key("ops.fast:schedule_scenarios", 128, "2x2")
    book = BudgetBook(
        programs={key: _budget()},
        verdicts={"plan_1m_100k": {"ok": True, "peak_gib": 1.7}},
    )
    path = str(tmp_path / "budgets" / "preflight.json")
    book.save(path)
    loaded = BudgetBook.load(path)
    assert loaded.to_dict() == book.to_dict()
    # the on-disk form is plain sorted json (reviewable in a PR diff)
    doc = json.loads((tmp_path / "budgets" / "preflight.json").read_text())
    assert key in doc["programs"]
    assert doc["verdicts"]["plan_1m_100k"]["ok"] is True


def test_budget_diff_violation_kinds():
    key = program_key("e", 64, "1")
    book = BudgetBook(programs={key: _budget()}, slack_bytes=0, tolerance=0.05)

    # within tolerance + shrinking: clean
    assert book.diff({key: _budget(peak_bytes=1_040_000)}) == []
    assert book.diff({key: _budget(peak_bytes=10, argument_bytes=10,
                                   output_bytes=10, temp_bytes=10)}) == []

    # memory: any byte field over budget*(1+tol)+slack
    v = book.diff({key: _budget(peak_bytes=1_100_000)})
    assert [x.kind for x in v] == ["memory"]
    assert v[0].field == "peak_bytes"

    # new-collective: count above budget (absent kind counts as 0)
    v = book.diff({key: _budget(collectives={"all-reduce": 2, "all-gather": 1})})
    assert [(x.kind, x.field) for x in v] == [("new-collective", "all-gather")]

    # collective-bytes: same counts, fatter operands
    v = book.diff({key: _budget(collective_bytes=1 << 20)})
    assert [x.kind for x in v] == [("collective-bytes")]

    # unbudgeted: measured program with no book entry
    v = book.diff({program_key("e", 128, "1"): _budget()})
    assert [x.kind for x in v] == ["unbudgeted"]

    # book entries absent from measured are NOT violations (partial runs)
    assert book.diff({}) == []


# ---------------------------------------------------------------------------
# collective census + replication detector (pure text parsing)
# ---------------------------------------------------------------------------

_HLO_FIXTURE = """\
ENTRY %main (p0: f32[128,8]) -> f32[128,8] {
  %ag = f32[128,8]{1,0} all-gather(f32[64,8]{1,0} %p0), dimensions={0}
  %ar.1 = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%sum
  %rs = f32[64,8]{1,0} reduce-scatter(f32[128,8]{1,0} %ag), dimensions={0}
  %ag2-start = (f32[4,2]) all-gather-start(f32[2,2]{1,0} %y), dimensions={0}
}
"""


def test_collective_census_counts_kinds_and_bytes():
    kinds, total, ops = H.collective_census(_HLO_FIXTURE)
    assert kinds == {"all-gather": 2, "all-reduce": 1, "reduce-scatter": 1}
    assert [k for k, _s in ops] == [
        "all-gather", "all-reduce", "reduce-scatter", "all-gather",
    ]
    # 128*8*4 + 128*4 + 64*8*4 + 4*2*4
    assert total == 4096 + 512 + 2048 + 32


def test_node_table_gathers_flags_full_rung_dims():
    _k, _t, ops = H.collective_census(_HLO_FIXTURE)
    assert H.node_table_gathers(ops, 128) == ["f32[128,8]{1,0}"]
    # reductions and lane-scalar gathers never carry the rung dim
    assert H.node_table_gathers(ops, 999) == []


def test_parse_mesh():
    assert H.parse_mesh("1") == (1, 1)
    assert H.parse_mesh("2x1") == (2, 1)
    assert H.parse_mesh("1x4") == (1, 4)
    with pytest.raises(ValueError):
        H.parse_mesh("weird")


# ---------------------------------------------------------------------------
# seeded fixtures: replication flagged, clean program passes
# ---------------------------------------------------------------------------


def _fixture_cap(name, fn, *args):
    return types.SimpleNamespace(name=name, fn=jax.jit(fn), args=args, kwargs={})


def test_seeded_replication_fixture_is_flagged(tables):
    """A program that de-shards its node-sharded input back to every
    device must trip the replication detector at a rescaled rung."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pmesh.product_mesh_2d(1, 2)
    rep = NamedSharding(mesh, P())

    def replicate(x):
        return jax.lax.with_sharding_constraint(x + 1.0, rep)

    cap = _fixture_cap(
        "fixture:replicate", replicate, np.zeros((64, 4), np.float32)
    )
    pa = H.audit_program(cap, 128, "1x2", tables=tables)
    assert not pa.error, pa.error
    assert pa.collectives.get("all-gather", 0) >= 1
    assert pa.node_gathers, pa.to_dict()
    assert not pa.ok


def test_clean_sharded_fixture_passes(tables):
    """The same shape kept node-sharded compiles collective-free."""
    cap = _fixture_cap(
        "fixture:scale", lambda x: x * 2.0, np.zeros((64, 4), np.float32)
    )
    pa = H.audit_program(cap, 128, "1x2", tables=tables)
    assert not pa.error, pa.error
    assert pa.collectives == {}
    assert pa.node_gathers == []
    assert pa.estimate_ok, pa.to_dict()
    assert pa.ok


def test_replication_detector_mute_at_canonical_rung(tables):
    """At rung == N_CANON every fixed 64-wide dim matches the node dim,
    so the detector deliberately reports nothing there."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pmesh.product_mesh_2d(1, 2)
    rep = NamedSharding(mesh, P())

    def replicate(x):
        return jax.lax.with_sharding_constraint(x + 1.0, rep)

    cap = _fixture_cap(
        "fixture:replicate64", replicate, np.zeros((64, 4), np.float32)
    )
    pa = H.audit_program(cap, H.N_CANON, "1x2", tables=tables)
    assert pa.node_gathers == []


# ---------------------------------------------------------------------------
# estimator vs materialized placement (hbm_bytes_per_device's twin)
# ---------------------------------------------------------------------------


def test_estimator_matches_materialized_placement():
    """The static estimate of an unmaterialized sharded aval must equal
    hbm_bytes_per_device of the same tree actually placed on a 2-device
    mesh — the pre-materialization twin contract of satellite fix 3."""
    mesh = pmesh.product_mesh_2d(1, 2)
    x = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
    placed = jax.device_put(x, pmesh.node_sharding(mesh).alloc)
    real = pmesh.hbm_bytes_per_device(placed)
    aval = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=placed.sharding)
    est = estimate_bytes_by_device(aval)
    assert est == real
    # and hbm_bytes_per_device itself accepts the unplaced aval
    assert pmesh.hbm_bytes_per_device(aval) == real


def test_estimator_mismatch_fails_the_audit(tables, monkeypatch):
    """If the shape arithmetic under-counts, estimate_ok must go false —
    the cross-check is a real gate, not advisory."""
    cap = _fixture_cap(
        "fixture:big", lambda x: x + 1.0, np.zeros((256, 256), np.float32)
    )
    monkeypatch.setattr(
        H.budget_mod, "estimate_max_bytes_per_device",
        lambda *a, **k: 0,
    )
    pa = H.audit_program(cap, 64, "1", tables=tables)
    assert not pa.estimate_ok
    assert not pa.ok


# ---------------------------------------------------------------------------
# transfer guard
# ---------------------------------------------------------------------------


def test_transfer_guard_fixture_violation():
    """An entry that rebuilds a host operand and feeds it to the device
    every call pays a per-call h2d transfer — exactly what the guard must
    catch once the warm call has landed the one-time constants. (On the
    CPU backend only host->device transfers are guarded; d2h is
    zero-copy, which is why the fixture leaks in this direction.)"""
    add = jax.jit(jnp.add)

    def leaky(x):
        bias = np.arange(8, dtype=np.float32)  # host-built, every call
        return add(x, bias)

    chk = H.guarded_steady_state_check(
        leaky, (np.ones((8,), np.float32),), {}
    )
    assert not chk.ok
    assert "transfer" in chk.error.lower() or "disallow" in chk.error.lower()


def test_transfer_guard_clean_jit_entry_passes():
    fn = jax.jit(lambda x: x * 2.0)
    chk = H.guarded_steady_state_check(fn, (np.ones((8,), np.float32),), {})
    assert chk.ok, chk.error


# ---------------------------------------------------------------------------
# the matrix + verdict on real captured entries (8 forced devices)
# ---------------------------------------------------------------------------


def test_matrix_on_ladder_and_meshes(caps):
    subset = _only(
        caps, "ops.fast:schedule_scenarios", "ops.kernels:schedule_batch"
    )
    assert len(subset) == 2
    report = H.run_preflight(
        rungs=(64, 128), meshes=("1", "2x1", "2x2"), caps=subset,
        transfers=False, verdict=False,
    )
    assert report.meshes_skipped == []
    assert len(report.programs) == 12
    assert all(p.ok for p in report.programs), report.render_text()
    # lane parallelism: schedule_scenarios must stay collective-free on
    # meshes that do not shard the node axis
    for p in report.programs:
        if p.entry == "ops.fast:schedule_scenarios" and p.mesh in ("1", "2x1"):
            assert p.collectives == {}, p.to_dict()
    # the rescaled rung really reshaped the programs
    assert {p.rung for p in report.programs} == {64, 128}


def test_scenario_only_entry_skips_node_sharded_meshes(caps):
    subset = _only(caps, "ops.fast:light_scan")
    report = H.run_preflight(
        rungs=(64,), meshes=("1", "2x2"), caps=subset,
        transfers=False, verdict=False,
    )
    assert [p.mesh for p in report.programs] == ["1"]
    assert report.programs_skipped == [
        program_key("ops.fast:light_scan", 64, "2x2")
    ]
    assert report.ok, report.render_text()


def test_fixed_shape_entry_is_never_rung_resized():
    # the prover engine stacks EVERY leaf on the scenario axis at the
    # small-scope pads; rung-rescaling such a capture corrupts the vmap
    # axis. FIXED_SHAPE entries keep their captured shapes, unsharded.
    assert "ops.fast:schedule_universes" in H.FIXED_SHAPE
    cap = types.SimpleNamespace(
        name="ops.fast:schedule_universes",
        fn=None,
        args=(np.ones((8, 64, 4), np.float32), np.ones((8, 4), np.int32)),
        kwargs={"n_valid": 5},
    )
    args, kwargs = H.abstract_args(cap, rung=128, mesh=None, resize=False)
    assert [a.shape for a in args] == [(8, 64, 4), (8, 4)]
    assert all(a.sharding is None for a in args)
    assert kwargs == {"n_valid": 5}


def test_budget_write_and_diff_flow(caps, tmp_path):
    subset = _only(caps, "ops.kernels:probe_step")
    report = H.run_preflight(
        rungs=(64,), meshes=("1",), caps=subset,
        transfers=False, verdict=False,
    )
    assert report.ok

    path = str(tmp_path / "preflight.json")
    report.to_book().save(path)
    book = BudgetBook.load(path)

    # re-diffing the same measurements against the fresh book is clean
    assert book.diff(report.measured()) == []

    # a regression (node table suddenly 10x bigger) trips `memory`
    key = report.programs[0].key
    fat = dataclasses.replace(
        report.measured()[key],
        peak_bytes=report.measured()[key].peak_bytes * 10 + (64 << 20),
    )
    v = book.diff({key: fat})
    assert [x.kind for x in v] == ["memory"]

    # a brand-new (entry, rung, mesh) must be consciously admitted
    v = book.diff({program_key("ops.kernels:probe_step", 256, "1"):
                   report.measured()[key]})
    assert [x.kind for x in v] == ["unbudgeted"]


def test_plan_verdict_fits(caps, tables):
    # on the CPU the budget is the target chip's: one v5e, 16 GB
    v = H.plan_verdict(caps, tables=tables)
    assert v["config"] == "plan_1m_100k"
    assert v["mesh"] == "1x4"
    assert v["rung"] == 102400
    assert v["hbm_gib"] == round(16e9 / 1024 ** 3, 3)
    assert not v.get("error"), v
    assert v["fits"] is True
    assert v["node_table_sharded"] is True
    assert v["peak_gib"] < v["hbm_gib"]
    assert v["ok"] is True


class _FakeDevice:
    def __init__(self, platform, kind, stats):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize(
    "device, want",
    [
        (None, 16 * 10 ** 9),  # this process's CPU -> the target chip
        (_FakeDevice("tpu", "TPU v5 lite", {"bytes_limit": 123}), 123),
        (_FakeDevice("tpu", "TPU v5 lite", None), 16 * 10 ** 9),
        (_FakeDevice("tpu", "TPU v99", {}), KeyError),
    ],
    ids=["cpu-target", "chip-bytes-limit", "chip-table", "unknown-kind"],
)
def test_device_hbm_bytes(device, want):
    if want is KeyError:
        with pytest.raises(KeyError, match="TPU v99"):
            H.device_hbm_bytes(device)
    else:
        assert H.device_hbm_bytes(device) == want


def test_plan_verdict_without_entry_reports_error():
    v = H.plan_verdict([], hbm_gib=32.0)
    assert v["ok"] is False
    assert "schedule_scenarios" in v["error"]
