"""Chunked commit checkpoints (durable/checkpoint.py + ops/fast.py).

The contract under test, end to end on a small plan (N=8, 24 pods,
3 live scenarios):

  - chunked dispatch (OSIM_COMMIT_CHUNK) is byte-identical to the
    monolithic scan — carry and every output, across seeds and for
    non-divisor chunk sizes;
  - a plan killed mid-chunk resumes byte-identically from its journal +
    newest verified snapshot, including onto a SMALLER mesh (4-dev ->
    2-dev -> single-device elastic resume);
  - a torn or content-corrupted snapshot is detected by its embedded
    digest and skipped in favor of the previous one (or a from-scratch
    replay), never trusted;
  - a re-executed chunk whose digest contradicts the journaled
    `plan_chunk` record refuses to continue (CheckpointError);
  - `device_lost` faults roll back to the last good carry and replay in
    place (degraded, not failed), with a flight-recorder artifact naming
    the last good chunk and carry digest.

Everything here runs on the conftest's 8 virtual CPU devices. The chunk
size is 4 everywhere (one compiled program per (N, C) pair, shared
across tests); the true-SIGKILL subprocess test is `slow`.
"""

import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from open_simulator_tpu.durable import RunJournal, replay
from open_simulator_tpu.durable.checkpoint import (
    OUTPUT_NAMES,
    CheckpointError,
    PlanCheckpointer,
    checkpoint_every,
    installed,
)
from open_simulator_tpu.ops import fast
from open_simulator_tpu.ops import state as state_mod
from open_simulator_tpu.ops.kernels import Carry, weights_array
from open_simulator_tpu.parallel import mesh as pmesh
from open_simulator_tpu.resilience import faults
from open_simulator_tpu.utils import metrics

S_REAL = 3
CHUNK = 4  # 24 pods bucket to 32 -> 8 chunks; one shared program per test


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


def _dispatch(plan_state, valid, ndev=0):
    """One schedule_scenarios_host call on a fresh stacked carry,
    optionally sharded over the first `ndev` devices."""
    ns, carry, batch, weights, s_pad = plan_state
    carry_s = state_mod.stack_carry(carry, s_pad)
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


def _mono_ref(plan_state, valid, monkeypatch):
    monkeypatch.delenv("OSIM_COMMIT_CHUNK", raising=False)
    return _dispatch(plan_state, valid)


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


def _crash_run(plan_state, valid, run_dir, ndev=0, kill_chunk=4):
    """Run chunked under a checkpointer and a 3-strike device_lost rule:
    two in-place recoveries, then the third strike aborts the plan with
    chunks 0..kill_chunk-1 journaled and a snapshot on disk."""
    journal = RunJournal.open(run_dir)
    cp = PlanCheckpointer(journal, every=2)
    _device_lost_plan(kill_chunk, times=3)
    try:
        with installed(cp):
            with pytest.raises(faults.DeviceLostError):
                _dispatch(plan_state, valid, ndev=ndev)
    finally:
        faults.uninstall_plan()
        journal.close()


def _resume_run(plan_state, valid, run_dir, ndev=0):
    journal = RunJournal.open(run_dir)
    cp = PlanCheckpointer(journal, resume=True, every=2)
    try:
        with installed(cp):
            return _dispatch(plan_state, valid, ndev=ndev)
    finally:
        journal.close()


def _snapshot_paths(run_dir):
    return sorted(glob.glob(os.path.join(run_dir, "ckpt", "plan-*.npz")))


# ---------------------------------------------------------------------------
# Byte-identity: chunked == monolithic
# ---------------------------------------------------------------------------

def test_chunked_matches_monolithic_across_seeds(plan_state, monkeypatch):
    ns, _, _, _, s_pad = plan_state
    for seed in (0, 1, 2):
        valid = _valid_lanes(ns, s_pad, seed)
        ref = _mono_ref(plan_state, valid, monkeypatch)
        monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
        got = _dispatch(plan_state, valid)
        _assert_identical(got, ref)
        assert fast.scenario_carry_digest_host(
            got[0]
        ) == fast.scenario_carry_digest_host(ref[0])


def test_chunked_matches_monolithic_non_divisor_chunk(plan_state, monkeypatch):
    # C=5 does not divide the padded pod count: the final chunk runs with
    # trailing pad rows whose carry writes the count gate must mask exactly
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", "5")
    _assert_identical(_dispatch(plan_state, valid), ref)


def test_chunk_at_least_plan_size_stays_monolithic(plan_state, monkeypatch):
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(int(batch.p)))
    before = metrics.PLAN_CHUNKS.value()
    _assert_identical(_dispatch(plan_state, valid), ref)
    assert metrics.PLAN_CHUNKS.value() == before  # single-scan path taken


def test_carry_digest_device_host_twins_agree(plan_state):
    _, carry, _, _, s_pad = plan_state
    carry_s = state_mod.stack_carry(carry, s_pad)
    dev = fast.scenario_carry_digest(carry_s)
    host = fast.scenario_carry_digest_host(fast.carry_to_host(carry_s))
    assert dev == host


# ---------------------------------------------------------------------------
# Device-loss rollback (no checkpointer: the in-memory last_good path)
# ---------------------------------------------------------------------------

def test_device_lost_recovers_in_place(plan_state, monkeypatch, tmp_path):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    monkeypatch.setenv("OSIM_FLIGHT_DIR", str(tmp_path))
    yes0 = metrics.DEVICE_LOST.value(handled="yes")
    _device_lost_plan(chunk=2, times=1)
    try:
        got = _dispatch(plan_state, valid)
    finally:
        faults.uninstall_plan()
    _assert_identical(got, ref)
    assert metrics.DEVICE_LOST.value(handled="yes") == yes0 + 1
    # the flight-recorder artifact names the last good chunk + carry digest
    arts = sorted(glob.glob(str(tmp_path / "flightrec-device-lost-*.json")))
    assert arts
    with open(arts[-1]) as fh:
        events = json.load(fh)["events"]
    lost = [e for e in events if e.get("kind") == "device-lost"]
    assert lost and lost[-1]["chunk"] == 2
    assert "restored_to" in lost[-1]
    int(lost[-1]["digest"], 16)  # well-formed carry digest


def test_device_lost_strikes_out_after_three(plan_state, monkeypatch):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    no0 = metrics.DEVICE_LOST.value(handled="no")
    yes0 = metrics.DEVICE_LOST.value(handled="yes")
    _device_lost_plan(chunk=1, times=3)
    try:
        with pytest.raises(faults.DeviceLostError):
            _dispatch(plan_state, valid)
    finally:
        faults.uninstall_plan()
    assert metrics.DEVICE_LOST.value(handled="yes") == yes0 + 2
    assert metrics.DEVICE_LOST.value(handled="no") == no0 + 1


# ---------------------------------------------------------------------------
# Crash -> resume byte-identity (journal + snapshot)
# ---------------------------------------------------------------------------

def test_crash_then_resume_byte_identical(plan_state, monkeypatch, tmp_path):
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 1)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    run_dir = str(tmp_path / "run")

    _crash_run(plan_state, valid, run_dir, kill_chunk=4)
    events = replay(run_dir)
    chunks = [e for e in events if e["event"] == "plan_chunk"]
    assert [e["chunk"] for e in chunks] == [0, 1, 2, 3]
    assert _snapshot_paths(run_dir)  # at least one on-disk snapshot

    skipped0 = metrics.RESUME_CHUNKS_SKIPPED.value()
    got = _resume_run(plan_state, valid, run_dir)
    _assert_identical(got, ref)
    # the newest snapshot covers chunks 0..3 (every=2): all four skipped
    assert metrics.RESUME_CHUNKS_SKIPPED.value() == skipped0 + 4

    events = replay(run_dir)
    chunks = [e for e in events if e["event"] == "plan_chunk"]
    n_chunks = -(-int(batch.p) // CHUNK)
    # no duplicate records: the resumed run journals only the tail chunks
    assert [e["chunk"] for e in chunks] == list(range(n_chunks))
    done = [e for e in events if e["event"] == "plan_done"]
    assert len(done) == 1 and done[0]["chunks"] == n_chunks


def test_elastic_resume_on_smaller_mesh(plan_state, monkeypatch, tmp_path):
    """A plan snapshotted on a 4-device mesh resumes byte-identically on
    2 devices, and a 2-device snapshot resumes on a single device."""
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 2)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))

    run_dir = str(tmp_path / "run-4dev")
    _crash_run(plan_state, valid, run_dir, ndev=4, kill_chunk=4)
    _assert_identical(_resume_run(plan_state, valid, run_dir, ndev=2), ref)

    run_dir = str(tmp_path / "run-2dev")
    _crash_run(plan_state, valid, run_dir, ndev=2, kill_chunk=4)
    _assert_identical(_resume_run(plan_state, valid, run_dir, ndev=0), ref)


# ---------------------------------------------------------------------------
# Snapshot corruption: torn files and digest mismatches are never trusted
# ---------------------------------------------------------------------------

def test_torn_snapshot_falls_back_to_previous(plan_state, monkeypatch, tmp_path):
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 1)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    run_dir = str(tmp_path / "run")
    _crash_run(plan_state, valid, run_dir, kill_chunk=4)

    snaps = _snapshot_paths(run_dir)
    assert len(snaps) == 2  # every=2 -> snapshots after chunks 1 and 3
    with open(snaps[-1], "rb+") as fh:  # tear the newest one in half
        fh.truncate(os.path.getsize(snaps[-1]) // 2)

    skipped0 = metrics.RESUME_CHUNKS_SKIPPED.value()
    got = _resume_run(plan_state, valid, run_dir)
    _assert_identical(got, ref)
    # fell back to the chunks 0..1 snapshot: only two chunks skipped
    assert metrics.RESUME_CHUNKS_SKIPPED.value() == skipped0 + 2


def test_corrupt_snapshot_digest_detected(plan_state, monkeypatch, tmp_path):
    """A snapshot with silently flipped carry bytes is a valid .npz whose
    embedded digest no longer matches its leaves: resume must skip it."""
    ns, _, _, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 1)
    ref = _mono_ref(plan_state, valid, monkeypatch)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    run_dir = str(tmp_path / "run")
    _crash_run(plan_state, valid, run_dir, kill_chunk=4)

    for path in _snapshot_paths(run_dir):  # corrupt BOTH snapshots
        with np.load(path) as z:
            arrays = {k: z[k].copy() for k in z.files}
        leaf = f"carry_{Carry._fields[0]}"
        flat = arrays[leaf].reshape(-1)
        flat[0] = flat[0] + 1
        with open(path, "wb") as fh:
            np.savez(fh, **arrays)

    skipped0 = metrics.RESUME_CHUNKS_SKIPPED.value()
    got = _resume_run(plan_state, valid, run_dir)
    _assert_identical(got, ref)
    # no trustworthy snapshot: full from-scratch replay, nothing skipped,
    # with every re-executed chunk digest-checked against the journal
    assert metrics.RESUME_CHUNKS_SKIPPED.value() == skipped0
    _, _, batch, _, _ = plan_state
    chunks = [
        e["chunk"] for e in replay(run_dir) if e["event"] == "plan_chunk"
    ]
    # tail re-journaled once, no dupes
    assert chunks == list(range(-(-int(batch.p) // CHUNK)))


def test_resume_refuses_divergent_replay(plan_state, monkeypatch, tmp_path):
    """A journaled plan_chunk digest that contradicts the re-executed
    chunk is journal corruption or non-determinism: hard refusal."""
    ns, _, batch, _, s_pad = plan_state
    valid = _valid_lanes(ns, s_pad, 0)
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", str(CHUNK))
    run_dir = str(tmp_path / "run")
    journal = RunJournal.open(run_dir)
    key = f"0:{int(ns.valid.shape[0])}x{int(batch.p)}x{s_pad}c{CHUNK}"
    journal.append("plan_chunk", plan=key, chunk=0, pods=CHUNK,
                   digest="deadbeef")
    journal.close()

    journal = RunJournal.open(run_dir)
    cp = PlanCheckpointer(journal, resume=True, every=2)
    try:
        with installed(cp):
            with pytest.raises(CheckpointError, match="not .*byte-identical|refusing"):
                _dispatch(plan_state, valid)
    finally:
        journal.close()


# ---------------------------------------------------------------------------
# Knob parsing
# ---------------------------------------------------------------------------

def test_knob_parsing(monkeypatch):
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", "garbage")
    assert fast.commit_chunk_size() == 0
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", "-3")
    assert fast.commit_chunk_size() == 0
    monkeypatch.setenv("OSIM_COMMIT_CHUNK", "256")
    assert fast.commit_chunk_size() == 256
    monkeypatch.setenv("OSIM_CKPT_EVERY", "0")
    assert checkpoint_every() == 1
    monkeypatch.setenv("OSIM_CKPT_EVERY", "nope")
    assert checkpoint_every() == 4
    monkeypatch.delenv("OSIM_CKPT_EVERY")
    assert checkpoint_every() == 4


# ---------------------------------------------------------------------------
# True SIGKILL: a real sweep subprocess killed mid-chunk, resumed by the
# CLI into byte-identical placements (the crash_resume_smoke.sh scenario)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sigkill_mid_chunk_then_cli_resume(tmp_path):
    import random

    cfg = os.path.join(
        os.path.dirname(__file__), "fixtures", "sweep", "simon-config.yaml"
    )
    kill_chunk = random.Random(0xC0FFEE).randrange(1, 4)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        OSIM_COMMIT_CHUNK="8",
        OSIM_CKPT_EVERY="2",
    )
    env.pop("OSIM_FAULT_PLAN", None)

    def sweep(run_dir, fault_plan=None):
        e = dict(env)
        if fault_plan:
            e["OSIM_FAULT_PLAN"] = fault_plan
        return subprocess.run(
            [sys.executable, "-m", "open_simulator_tpu.cli.main", "sweep",
             "--capacity", "-f", cfg, "--run-dir", run_dir],
            env=e, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ).returncode

    ref_dir = str(tmp_path / "ref")
    assert sweep(ref_dir) == 0

    run_dir = str(tmp_path / "run")
    plan = (
        "rules:\n"
        "  - target: device\n"
        f"    op: \"commit-chunk:{kill_chunk}\"\n"
        "    kind: chunk_kill\n"
        "    times: 1\n"
    )
    rc = sweep(run_dir, fault_plan=plan)
    assert rc in (137, -9), f"expected SIGKILL, got rc={rc}"
    assert any(
        e["event"] == "plan_chunk" for e in replay(run_dir)
    ), "child died before journaling any chunk"

    rc = subprocess.run(
        [sys.executable, "-m", "open_simulator_tpu.cli.main", "runs",
         "resume", run_dir],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    ).returncode
    assert rc == 0

    with open(os.path.join(ref_dir, "outcome.json")) as fh:
        want = json.load(fh)["placement_digest"]
    with open(os.path.join(run_dir, "outcome.json")) as fh:
        got = json.load(fh)["placement_digest"]
    assert got == want


def test_chaos_capacity_runs_every_leg_in_a_child(tmp_path, capsys,
                                                  monkeypatch):
    """`simon chaos --capacity`: the clean reference, the faulted sweep and
    the resume each run in a child (one process per chip); the resumed plan
    byte-matches the reference."""
    from open_simulator_tpu.cli.main import main

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    calls = []
    real_run = subprocess.run

    def spy(cmd, *a, **k):
        calls.append(list(cmd))
        return real_run(cmd, *a, **k)

    monkeypatch.setattr(subprocess, "run", spy)
    cfg = os.path.join(
        os.path.dirname(__file__), "fixtures", "sweep", "simon-config.yaml"
    )
    plan = os.path.join(
        os.path.dirname(__file__), "..", "example", "fault-plan-device.yaml"
    )
    rc = main(["chaos", "--capacity", "-f", cfg, "--fault-plan", plan,
               "--run-dir", str(tmp_path / "run")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "killed mid-plan (SIGKILL), resumed from checkpoint" in out
    assert "placement digest vs clean reference: match" in out
    sweeps = [c for c in calls if "sweep" in c]
    assert len(sweeps) == 3 and "--resume" in sweeps[2]
