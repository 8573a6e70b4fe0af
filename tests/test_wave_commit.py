"""Conflict-parallel wave commit (ops/wave.py + the ops.fast wave entries).

The contract under test, end to end on a small plan (N=8, 24 pods,
3 live scenarios):

  - the wave driver (OSIM_WAVE_COMMIT=1) is byte-identical to the serial
    scan — carry and every output, across seeds, scenario lanes, 2/4
    device meshes, non-divisor wave sizes, and a warm (already-loaded)
    carry;
  - a wave that exhausts OSIM_WAVE_ROUNDS falls back to the serial
    chunked kernel (counted in osim_wave_fallbacks_total) and the plan
    stays byte-identical — the fallback is the oracle, not an
    approximation;
  - wave plans checkpoint one `plan_chunk` record per wave with the same
    digest chain a serial chunked run of chunk = wave would journal, so
    crash->resume is byte-identical in BOTH directions (wave plan resumed
    serially, serial plan resumed by the wave driver);
  - device_lost faults roll back to the last committed wave and replay
    in place (in-flight rounds mutate nothing);
  - the fixpoint loop both drivers share stops at the first repeated
    round, counts conflicts from round 2 on inside the live slice only,
    and falls back at its round limit; the universes driver equals
    schedule_universes bit for bit, and its overrun guard replays
    through it;
  - auto mode routes to the wave driver only on a parallel backend and a
    plan big enough to amortize the rounds — tier-1 CPU runs stay serial
    unless a test forces the engine on.

Everything here runs on the conftest's 8 virtual CPU devices. Wave size
is 4 everywhere it can be (24 pods bucket to 32; one compiled program
per (N, W) pair, shared across tests).
"""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.analysis import sarif, semantics
from open_simulator_tpu.durable import RunJournal, replay
from open_simulator_tpu.durable.checkpoint import (
    OUTPUT_NAMES,
    PlanCheckpointer,
    installed,
)
from open_simulator_tpu.ops import fast
from open_simulator_tpu.ops import state as state_mod
from open_simulator_tpu.ops import wave as wave_mod
from open_simulator_tpu.ops.kernels import Carry, weights_array
from open_simulator_tpu.parallel import mesh as pmesh
from open_simulator_tpu.resilience import faults
from open_simulator_tpu.utils import metrics

S_REAL = 3
N_PODS = 24  # buckets to batch.p = 32 pod rows (trailing rows invalid)
WAVE = 4  # shares the (N=8, W=4) program across tests


@pytest.fixture(scope="module")
def plan_state():
    from tests.factories import build_state

    ns, carry, batch = build_state(8, 24)
    s_pad = fast.scenario_bucket(S_REAL)
    weights = np.stack([np.asarray(weights_array())] * s_pad)
    return ns, carry, batch, weights, s_pad


def _valid_lanes(ns, s_pad, seed):
    """[s_pad, N] validity: lane 0 = the real cluster, lanes 1..S_REAL-1
    knock out a seeded fraction of nodes, pad lanes copy lane 0."""
    base = np.asarray(ns.valid)
    v = np.stack([base.copy() for _ in range(s_pad)])
    rng = np.random.RandomState(seed)
    for lane in range(1, S_REAL):
        v[lane] = base & ~(rng.rand(base.shape[0]) < 0.25)
    return v


def _to_host(out):
    return (fast.carry_to_host(out[0]),) + tuple(
        np.asarray(a) for a in out[1:]
    )


def _dispatch(plan_state, valid, ndev=0, carry=None):
    """One schedule_scenarios_host call, optionally sharded over the
    first `ndev` devices, optionally from a warm host-carry snapshot."""
    ns, carry0, batch, weights, s_pad = plan_state
    carry_s = state_mod.stack_carry(carry0, s_pad)
    if carry is not None:
        carry_s = fast.carry_from_host(carry_s, carry)
    w_s = jnp.asarray(weights)
    v_s = jnp.asarray(valid)
    if ndev:
        m = pmesh.scenario_mesh(pmesh.make_mesh(jax.devices()[:ndev]))
        ns, carry_s, v_s, w_s = pmesh.shard_scenarios(m, ns, carry_s, v_s, w_s)
    return _to_host(
        fast.schedule_scenarios_host(ns, carry_s, batch, w_s, v_s, S_REAL)
    )


def _assert_identical(got, want):
    for f in Carry._fields:
        np.testing.assert_array_equal(
            got[0][f], want[0][f], err_msg=f"carry.{f}"
        )
    for k, name in enumerate(OUTPUT_NAMES):
        np.testing.assert_array_equal(got[1 + k], want[1 + k], err_msg=name)


def _serial_ref(plan_state, valid, monkeypatch, **kw):
    monkeypatch.setenv("OSIM_WAVE_COMMIT", "0")
    monkeypatch.delenv("OSIM_COMMIT_CHUNK", raising=False)
    return _dispatch(plan_state, valid, **kw)


def _wave_on(monkeypatch, wave=WAVE, rounds=None):
    monkeypatch.setenv("OSIM_WAVE_COMMIT", "1")
    monkeypatch.setenv("OSIM_WAVE_SIZE", str(wave))
    monkeypatch.delenv("OSIM_COMMIT_CHUNK", raising=False)
    if rounds is None:
        monkeypatch.delenv("OSIM_WAVE_ROUNDS", raising=False)
    else:
        monkeypatch.setenv("OSIM_WAVE_ROUNDS", str(rounds))


def _hist_count(h):
    samples = h.snapshot()["samples"]
    return int(samples[0]["count"]) if samples else 0


# ---------------------------------------------------------------------------
# Byte-identity: wave fixpoint == serial scan
# ---------------------------------------------------------------------------

def test_wave_matches_serial_across_seeds(plan_state, monkeypatch):
    ns, _, batch, _, s_pad = plan_state
    for seed in (0, 1, 2):
        valid = _valid_lanes(ns, s_pad, seed)
        ref = _serial_ref(plan_state, valid, monkeypatch)
        _wave_on(monkeypatch)
        rounds0 = _hist_count(metrics.COMMIT_ROUNDS)
        got = _dispatch(plan_state, valid)
        _assert_identical(got, ref)
        assert fast.scenario_carry_digest_host(
            got[0]
        ) == fast.scenario_carry_digest_host(ref[0])
        # one osim_commit_rounds observation per wave
        n_waves = -(-int(batch.p) // WAVE)
        assert _hist_count(metrics.COMMIT_ROUNDS) == rounds0 + n_waves


def test_wave_matches_serial_non_divisor_wave_sizes(plan_state, monkeypatch):
    # W=5 and W=7 do not divide 24: the driver pads the pod axis and the
    # final wave runs count-gated (dead steps pin their choice to -1)
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    for wave in (5, 7):
        _wave_on(monkeypatch, wave=wave)
        _assert_identical(_dispatch(plan_state, valid), ref)


def test_wave_whole_plan_as_one_wave(plan_state, monkeypatch):
    # W >= P: a single wave, no count gate ever bites mid-plan
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 1)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    _wave_on(monkeypatch, wave=int(batch.p) + 8)
    _assert_identical(_dispatch(plan_state, valid), ref)


@pytest.mark.parametrize("ndev", [2, 4])
def test_wave_matches_serial_on_mesh(plan_state, monkeypatch, ndev):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 2)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    _wave_on(monkeypatch)
    _assert_identical(_dispatch(plan_state, valid, ndev=ndev), ref)


def test_wave_matches_serial_on_warm_carry(plan_state, monkeypatch):
    """The preemption/warm-start shape: a second sweep of the same pods
    lands on an already-loaded carry (some pods now unschedulable, some
    repacked), and the wave fixpoint must still reproduce the serial
    scan bit-for-bit."""
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    warm = _serial_ref(plan_state, valid, monkeypatch)[0]
    ref = _serial_ref(plan_state, valid, monkeypatch, carry=warm)
    _wave_on(monkeypatch)
    got = _dispatch(plan_state, valid, carry=warm)
    _assert_identical(got, ref)
    # the warm sweep genuinely differs from the cold one (capacity bit)
    cold = _serial_ref(plan_state, valid, monkeypatch)
    assert not np.array_equal(got[1], cold[1])


# ---------------------------------------------------------------------------
# Round budget: the serial fallback is the oracle path
# ---------------------------------------------------------------------------

def test_wave_max_rounds_fallback_stays_identical(plan_state, monkeypatch):
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    _wave_on(monkeypatch, rounds=1)  # a live wave cannot confirm in 1 round
    fb0 = metrics.WAVE_FALLBACKS.value(reason="max_rounds")
    _assert_identical(_dispatch(plan_state, valid), ref)
    # only waves holding live pods burn the budget: all-pad waves probe
    # straight to all -1 choices and converge on round 1
    n_live_waves = -(-N_PODS // WAVE)
    assert metrics.WAVE_FALLBACKS.value(
        reason="max_rounds"
    ) == fb0 + n_live_waves


# ---------------------------------------------------------------------------
# The shared fixpoint loop (fast._wave_fixpoint), driven by host-only rounds
# ---------------------------------------------------------------------------

def _scripted_rounds(script):
    """A host-only round function: round r returns script[r - 1] as its
    choices (the last entry repeats) and records the choices it was fed."""
    fed = []

    def round_fn(choices, r):
        fed.append(np.asarray(choices).copy())
        ch = np.asarray(script[min(r, len(script)) - 1], np.int32)
        return ("carry", ch, "outputs")

    return round_fn, fed


@pytest.mark.parametrize("script,want_rounds", [
    # an all-pad wave probes straight to all -1: round 1 is its own input
    ([[[-1, -1], [-1, -1]]], 1),
    ([[[0, 1], [1, 1]], [[0, 0], [1, 1]], [[0, 0], [1, 1]]], 3),
])
def test_wave_fixpoint_stops_at_first_repeated_round(script, want_rounds):
    round_fn, fed = _scripted_rounds(script)
    rounds, out, ch = fast._wave_fixpoint(
        round_fn, (2, 2), 0, ..., "wave-round", "max_rounds"
    )
    assert rounds == want_rounds
    assert out[0] == "carry" and out[2] == "outputs"
    np.testing.assert_array_equal(ch, script[want_rounds - 1])
    # round 1 starts cold; every later round is fed its predecessor
    assert len(fed) == want_rounds
    np.testing.assert_array_equal(fed[0], np.full((2, 2), -1))
    for r in range(1, want_rounds):
        np.testing.assert_array_equal(fed[r], script[r - 1])


def test_wave_fixpoint_counts_conflicts_from_round_two_in_live_slice():
    script = [
        # round 1 differs from the cold -1 everywhere: not a conflict
        [[0, 1, 2, 3], [0, 1, 2, 3]],
        # round 2: two changes inside the live slice [:1, :3], three
        # outside it (the pad lane and the dead column)
        [[1, 1, 0, 0], [1, 0, 0, 3]],
        [[1, 1, 0, 0], [1, 0, 0, 3]],
    ]
    round_fn, _ = _scripted_rounds(script)
    c0 = metrics.WAVE_CONFLICTS.value()
    rounds, out, _ = fast._wave_fixpoint(
        round_fn, (2, 4), 0, np.s_[:1, :3], "wave-round", "max_rounds"
    )
    assert rounds == 3 and out is not None
    assert metrics.WAVE_CONFLICTS.value() == c0 + 2


@pytest.mark.parametrize("reason", ["max_rounds", "universes_max_rounds"])
def test_wave_fixpoint_falls_back_at_round_limit(reason):
    # choices that flip every round never reach a fixpoint
    round_fn, fed = _scripted_rounds([[[r % 2]] for r in range(1, 10)])
    fb0 = metrics.WAVE_FALLBACKS.value(reason=reason)
    rounds, out, _ = fast._wave_fixpoint(
        round_fn, (1, 1), 4, ..., "wave-round", reason
    )
    assert (rounds, out) == (4, None)
    assert len(fed) == 4
    assert metrics.WAVE_FALLBACKS.value(reason=reason) == fb0 + 1


# ---------------------------------------------------------------------------
# The universes driver (`simon prove --engine wave`) against the serial scan
# ---------------------------------------------------------------------------

UNIVERSES = [
    # contended (pods pile onto one roomy node), selector-pinned,
    # GPU-share, cordoned, and a mixed universe
    ("AA--", "ppppp"), ("AB--", "qqqqq"), ("C---", "rrrrr"),
    ("DA--", "pqrpq"), ("ABCD", "rqprq"),
]


def _universes_inputs():
    scope = semantics.SmallScope()
    chunk = [semantics.Universe(n, p) for n, p in UNIVERSES]
    return semantics._pack_chunk(
        scope, chunk, fast.scenario_bucket(len(chunk))
    )


def _universes_host(out):
    return (
        {f: np.asarray(getattr(out[0], f)) for f in Carry._fields},
    ) + tuple(np.asarray(a) for a in out[1:])


def test_universes_wave_host_matches_schedule_universes():
    # fresh inputs per engine: schedule_universes donates its carry
    ref = _universes_host(fast.schedule_universes(*_universes_inputs()))
    rounds0 = _hist_count(metrics.COMMIT_ROUNDS)
    got = _universes_host(
        fast.schedule_universes_wave_host(*_universes_inputs())
    )
    _assert_identical(got, ref)
    assert _hist_count(metrics.COMMIT_ROUNDS) == rounds0 + 1
    # the live pods both place and run out of room
    live = ref[1][: len(UNIVERSES), : semantics.SmallScope.POD_SLOTS]
    assert (live >= 0).any() and (live < 0).any()


def test_universes_wave_host_overrun_falls_back_to_serial(monkeypatch):
    ref = _universes_host(fast.schedule_universes(*_universes_inputs()))
    calls = []

    def never_converges(ns_s, carry_s, pods_s, weights_s, choices, f=None):
        calls.append(1)
        return (carry_s, np.asarray(choices) + 1)

    monkeypatch.setattr(fast, "schedule_universes_wave", never_converges)
    fb0 = metrics.WAVE_FALLBACKS.value(reason="universes_max_rounds")
    got = _universes_host(
        fast.schedule_universes_wave_host(*_universes_inputs())
    )
    _assert_identical(got, ref)
    # the guard fires at round P + 2, one past the W + 1 guarantee
    assert len(calls) == semantics.SmallScope.P_PAD + 2
    assert metrics.WAVE_FALLBACKS.value(
        reason="universes_max_rounds"
    ) == fb0 + 1


# ---------------------------------------------------------------------------
# Routing policy (wave_enabled)
# ---------------------------------------------------------------------------

def test_wave_enabled_policy(monkeypatch):
    big = 10 * wave_mod.WAVE_AUTO_MIN_PODS
    monkeypatch.setenv("OSIM_WAVE_COMMIT", "0")
    assert not wave_mod.wave_enabled(big)
    monkeypatch.setenv("OSIM_WAVE_COMMIT", "1")
    assert wave_mod.wave_enabled(1)
    # auto: needs BOTH a parallel backend and an amortizing plan size
    monkeypatch.delenv("OSIM_WAVE_COMMIT", raising=False)
    monkeypatch.setattr(wave_mod, "_parallel_backend", lambda: True)
    assert wave_mod.wave_enabled(big)
    assert not wave_mod.wave_enabled(wave_mod.WAVE_AUTO_MIN_PODS - 1)
    monkeypatch.setattr(wave_mod, "_parallel_backend", lambda: False)
    assert not wave_mod.wave_enabled(big)


# ---------------------------------------------------------------------------
# Device-loss rollback (no checkpointer: the in-memory last-good wave)
# ---------------------------------------------------------------------------

def _device_lost_plan(chunk, times):
    faults.install_plan(
        faults.FaultPlan(
            rules=[
                faults.FaultRule(
                    target="device",
                    kind="device_lost",
                    op=f"commit-chunk:{chunk}",
                    times=times,
                )
            ]
        )
    )


def test_wave_device_lost_recovers_in_place(plan_state, monkeypatch):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    _wave_on(monkeypatch)
    yes0 = metrics.DEVICE_LOST.value(handled="yes")
    _device_lost_plan(chunk=2, times=1)
    try:
        got = _dispatch(plan_state, valid)
    finally:
        faults.uninstall_plan()
    _assert_identical(got, ref)
    assert metrics.DEVICE_LOST.value(handled="yes") == yes0 + 1


def test_wave_device_lost_strikes_out_after_three(plan_state, monkeypatch):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    _wave_on(monkeypatch)
    no0 = metrics.DEVICE_LOST.value(handled="no")
    _device_lost_plan(chunk=1, times=3)
    try:
        with pytest.raises(faults.DeviceLostError):
            _dispatch(plan_state, valid)
    finally:
        faults.uninstall_plan()
    assert metrics.DEVICE_LOST.value(handled="no") == no0 + 1


# ---------------------------------------------------------------------------
# Crash -> resume: wave and serial chunked runs share one digest chain
# ---------------------------------------------------------------------------

def _crash_run(plan_state, valid, run_dir, kill_chunk=4):
    """Run under a checkpointer and a 3-strike device_lost rule: two
    in-place recoveries, then the third strike aborts the plan with waves
    0..kill_chunk-1 journaled and a snapshot on disk."""
    journal = RunJournal.open(run_dir)
    cp = PlanCheckpointer(journal, every=2)
    _device_lost_plan(kill_chunk, times=3)
    try:
        with installed(cp):
            with pytest.raises(faults.DeviceLostError):
                _dispatch(plan_state, valid)
    finally:
        faults.uninstall_plan()
        journal.close()


def _resume_run(plan_state, valid, run_dir):
    journal = RunJournal.open(run_dir)
    cp = PlanCheckpointer(journal, resume=True, every=2)
    try:
        with installed(cp):
            return _dispatch(plan_state, valid)
    finally:
        journal.close()


def test_wave_crash_then_resume_byte_identical(
    plan_state, monkeypatch, tmp_path
):
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 1)
    ref = _serial_ref(plan_state, valid, monkeypatch)
    _wave_on(monkeypatch)
    run_dir = str(tmp_path / "run")

    _crash_run(plan_state, valid, run_dir, kill_chunk=4)
    events = replay(run_dir)
    chunks = [e for e in events if e["event"] == "plan_chunk"]
    assert [e["chunk"] for e in chunks] == [0, 1, 2, 3]

    skipped0 = metrics.RESUME_CHUNKS_SKIPPED.value()
    got = _resume_run(plan_state, valid, run_dir)
    _assert_identical(got, ref)
    # the newest snapshot covers waves 0..3 (every=2): all four skipped
    assert metrics.RESUME_CHUNKS_SKIPPED.value() == skipped0 + 4

    events = replay(run_dir)
    chunks = [e for e in events if e["event"] == "plan_chunk"]
    n_waves = -(-int(batch.p) // WAVE)
    assert [e["chunk"] for e in chunks] == list(range(n_waves))
    done = [e for e in events if e["event"] == "plan_done"]
    assert len(done) == 1 and done[0]["chunks"] == n_waves


@pytest.mark.slow
def test_wave_serial_resume_interop(plan_state, monkeypatch, tmp_path):
    """One wave = one checkpoint chunk with the SAME plan key and digest
    chain: a plan crashed under the wave driver resumes byte-identically
    through the serial chunked driver, and vice versa."""
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 2)
    ref = _serial_ref(plan_state, valid, monkeypatch)

    # wave crash -> serial resume
    run_dir = str(tmp_path / "wave-then-serial")
    _wave_on(monkeypatch)
    _crash_run(plan_state, valid, run_dir, kill_chunk=4)
    monkeypatch.setenv("OSIM_WAVE_COMMIT", "0")
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(WAVE))
    _assert_identical(_resume_run(plan_state, valid, run_dir), ref)

    # serial crash -> wave resume
    run_dir = str(tmp_path / "serial-then-wave")
    _crash_run(plan_state, valid, run_dir, kill_chunk=4)
    _wave_on(monkeypatch)
    _assert_identical(_resume_run(plan_state, valid, run_dir), ref)


# ---------------------------------------------------------------------------
# Static-analysis surface: the wave entries are first-class programs
# ---------------------------------------------------------------------------

def test_preflight_budget_book_names_wave_entries():
    import json

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "budgets", "preflight.json",
    )
    with open(path) as fh:
        book = json.load(fh)
    keys = " ".join(book.get("programs", {}))
    for entry in (
        "ops.fast:schedule_wave",
        "ops.fast:schedule_universes_wave",
        "ops.kernels:commit_wave",
    ):
        assert entry in keys, f"{entry} missing from the preflight budgets"


def test_sarif_preflight_run_lists_covered_programs():
    """A clean preflight run still NAMES every covered program in its
    SARIF property bag — dropping a wave entry from the budget book shows
    up as an inventory diff, not a silently absent annotation."""
    report = types.SimpleNamespace(
        violations=[],
        programs=[
            types.SimpleNamespace(
                key="ops.fast:schedule_wave", error=None, estimate_ok=True
            ),
            types.SimpleNamespace(
                key="ops.fast:schedule_universes_wave", error=None,
                estimate_ok=True,
            ),
        ],
        transfers=[],
        verdict=None,
        budgets_path="budgets/preflight.json",
    )
    run = sarif.preflight_run(report)
    assert run["results"] == []
    assert run["properties"]["programs"] == [
        "ops.fast:schedule_universes_wave", "ops.fast:schedule_wave",
    ]
