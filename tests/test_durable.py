"""Durable-runs layer: journal WAL semantics (append / replay / torn-tail
truncation), checkpoint/resume of the capacity bisection (zero re-run
trials, identical plans), the backend-acquisition watchdog (sleep-free fake
clocks), and the honest-provenance TPU→CPU degradation ladder.

No test here sleeps for real: guarded_call takes an injectable clock and
poll interval, and the crash is simulated by truncating a journal rather
than killing a process (the cross-process SIGKILL path is exercised by
scripts/crash_resume_smoke.sh in CI)."""

import io
import json
import os
import threading

import pytest

from open_simulator_tpu.durable import (
    BackendUnavailable,
    DeadlineExceeded,
    RunJournal,
    acquire_backend,
    atomic_write,
    completed_segments,
    guarded_call,
    list_runs,
    replay,
    summarize_run,
)
from open_simulator_tpu.durable.journal import JOURNAL_NAME
from open_simulator_tpu.resilience import faults
from open_simulator_tpu.utils import metrics

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CONFIG = os.path.join(FIXTURES, "simon-config.yaml")


def _counter_total(counter) -> int:
    return int(sum(s["value"] for s in counter.snapshot()["samples"]))


# ---------------------------------------------------------------------------
# Journal WAL semantics.
# ---------------------------------------------------------------------------

def test_journal_append_replay_roundtrip(tmp_path):
    d = str(tmp_path / "run")
    with RunJournal.open(d) as j:
        j.append("run_start", kind="test")
        j.append("trial", node_count=0, good=False)
        j.append("trial", node_count=4, good=True)
    events = replay(d)
    assert [e["event"] for e in events] == ["run_start", "trial", "trial"]
    assert [e["seq"] for e in events] == [0, 1, 2]
    assert all(isinstance(e["ts"], float) for e in events)
    # reopen continues the sequence — the journal is append-only
    with RunJournal.open(d) as j:
        assert [e["node_count"] for e in j.events("trial")] == [0, 4]
        assert j.has("run_start") and not j.has("run_end")
        j.append("run_end", outcome="ok")
    assert replay(d)[-1]["seq"] == 3


def test_journal_direct_construction_rejected(tmp_path):
    with pytest.raises(TypeError):
        RunJournal(str(tmp_path))


def test_journal_torn_tail_truncated_on_open(tmp_path):
    d = str(tmp_path / "run")
    with RunJournal.open(d) as j:
        j.append("run_start", kind="test")
        j.append("trial", node_count=1, good=True)
    path = os.path.join(d, JOURNAL_NAME)
    good_size = os.path.getsize(path)
    # a crash mid-write leaves a torn (partial, unterminated) record
    with open(path, "ab") as fh:
        fh.write(b'{"seq": 2, "event": "tri')
    with RunJournal.open(d) as j:
        assert [e["event"] for e in j.events()] == ["run_start", "trial"]
        j.append("trial", node_count=2, good=True)
        assert j.events()[-1]["seq"] == 2
    # the torn bytes were physically truncated, not just skipped: every
    # line on disk parses, and the post-crash append starts where the good
    # prefix ended
    raw = open(path, "rb").read()
    lines = raw.decode().splitlines()
    assert len(lines) == 3 and all(json.loads(ln) for ln in lines)
    assert json.loads(raw[good_size:])["node_count"] == 2
    assert len(replay(d)) == 3


def test_journal_record_without_newline_not_committed(tmp_path):
    # the fsync'd newline is the commit point: a parseable record that never
    # got its terminator on disk is a torn write and must not replay
    d = str(tmp_path / "run")
    os.makedirs(d)
    path = os.path.join(d, JOURNAL_NAME)
    with open(path, "wb") as fh:
        fh.write(b'{"seq": 0, "ts": 1.0, "event": "run_start"}\n')
        fh.write(b'{"seq": 1, "ts": 2.0, "event": "trial", "good": true}')
    assert [e["event"] for e in replay(d)] == ["run_start"]
    with RunJournal.open(d) as j:
        j.append("resumed")
        assert [e["seq"] for e in j.events()] == [0, 1]


def test_journal_replay_is_deterministic(tmp_path):
    d = str(tmp_path / "run")
    with RunJournal.open(d) as j:
        for i in range(20):
            j.append("trial", node_count=i, good=i % 2 == 0)
    assert replay(d) == replay(d)
    assert replay(d) == RunJournal.open(d).events()


def test_atomic_write_replaces_without_litter(tmp_path):
    path = str(tmp_path / "out.json")
    atomic_write(path, '{"v": 1}\n')
    atomic_write(path, '{"v": 2}\n')
    assert open(path).read() == '{"v": 2}\n'
    assert os.listdir(str(tmp_path)) == ["out.json"]


def test_completed_segments_last_write_wins():
    events = [
        {"event": "segment", "segment": "canary", "result": {"v": 1}},
        {"event": "segment", "segment": "headline", "result": {"v": 2}},
        {"event": "segment", "segment": "canary", "result": {"v": 3}},
        {"event": "trial", "node_count": 0},
    ]
    segs = completed_segments(events)
    assert segs == {"canary": {"v": 3}, "headline": {"v": 2}}


def test_summarize_and_list_runs(tmp_path):
    a = str(tmp_path / "a")
    with RunJournal.open(a) as j:
        j.append("run_start", kind="apply", simon_config="x.yaml")
        j.append("backend", device="TFRT_CPU_0")
        j.append("trial", node_count=0, good=True)
        j.append("run_end", outcome="ok")
    b = str(tmp_path / "b")
    with RunJournal.open(b) as j:
        j.append("run_start", kind="bench")
        j.append("backend_error", platform="tpu", error="timed out")
    sa = summarize_run(a)
    assert sa["kind"] == "apply" and sa["status"] == "completed"
    assert sa["outcome"] == "ok" and sa["trials"] == 1
    assert sa["device"] == "TFRT_CPU_0" and sa["backend_error"] == ""
    sb = summarize_run(b)
    assert sb["status"] == "in-flight/crashed"
    # no device was acquired; the error is what the summary shows
    assert sb["device"] == "" and sb["backend_error"] == "timed out"
    rows = list_runs(str(tmp_path))
    assert [r["name"] for r in rows] == ["b", "a"]  # newest first


# ---------------------------------------------------------------------------
# Watchdog (sleep-free: fake clocks, tiny poll intervals).
# ---------------------------------------------------------------------------

def test_guarded_call_inline_when_deadline_zero():
    calls = []

    def fn():
        calls.append(threading.current_thread())
        return 42

    assert guarded_call("t", fn, 0) == 42
    assert calls == [threading.main_thread()]  # no worker thread spawned


def test_guarded_call_returns_result_within_deadline():
    assert guarded_call("t", lambda: "ok", 60, poll_s=0.001) == "ok"


def test_guarded_call_propagates_worker_error():
    def boom():
        raise ValueError("from worker")

    with pytest.raises(ValueError, match="from worker"):
        guarded_call("t", boom, 60, poll_s=0.001)


def test_watchdog_fires_on_deadline_with_fake_clock(tmp_path):
    before = _counter_total(metrics.WATCHDOG_FIRED)
    release = threading.Event()
    ticks = iter([0.0] + [1000.0] * 100)
    journal = RunJournal.open(str(tmp_path / "run"))
    try:
        with pytest.raises(DeadlineExceeded) as exc:
            guarded_call(
                "hung-stage", release.wait, 5.0,
                clock=lambda: next(ticks), poll_s=0.001, journal=journal,
            )
    finally:
        release.set()  # unblock the abandoned worker thread
    assert exc.value.stage == "hung-stage"
    assert _counter_total(metrics.WATCHDOG_FIRED) == before + 1
    wd = journal.events("watchdog")
    assert len(wd) == 1 and wd[0]["stage"] == "hung-stage"
    journal.close()


def test_acquire_backend_happy_path(tmp_path):
    journal = RunJournal.open(str(tmp_path / "run"))
    info = acquire_backend(
        deadline_s=60, journal=journal, probe=lambda: "FAKE_DEV_0",
        poll_s=0.001,
    )
    assert info == {"device": "FAKE_DEV_0"}
    assert [e["event"] for e in journal.events()] == ["backend"]
    journal.close()


def test_acquire_backend_failure_raises_without_switching(tmp_path):
    """A failing probe is journaled and raised, naming the platform; the
    process stays on the platform it asked for."""
    import jax

    calls = []

    def bad_probe():
        calls.append(1)
        raise RuntimeError("device init failed")

    before = os.environ.get("JAX_PLATFORMS")
    journal = RunJournal.open(str(tmp_path / "run"))
    with pytest.raises(BackendUnavailable) as exc:
        acquire_backend(
            deadline_s=60, journal=journal, probe=bad_probe, poll_s=0.001
        )
    assert len(calls) == 1
    assert exc.value.platform == "cpu"
    assert "'cpu'" in str(exc.value) and "device init failed" in str(exc.value)
    assert [e["event"] for e in journal.events()] == ["backend_error"]
    err = journal.events("backend_error")[0]
    assert err["platform"] == "cpu" and "device init failed" in err["error"]
    assert os.environ.get("JAX_PLATFORMS") == before
    assert jax.config.jax_platforms == "cpu"
    journal.close()


def test_apply_with_a_hung_backend_exits_naming_the_platform(
    tmp_path, monkeypatch, capsys
):
    """`simon apply` against a backend whose init outlives the deadline
    exits 1 with the platform in the error; it never runs elsewhere."""
    from open_simulator_tpu.cli.main import main

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    monkeypatch.setenv("OSIM_BACKEND_DEADLINE_S", "0.2")
    monkeypatch.setenv(
        "OSIM_FAULT_PLAN",
        "rules:\n  - target: backend\n    op: acquire\n"
        "    kind: latency\n    latency_s: 2\n",
    )
    rc = main(["apply", "-f", CONFIG, "--run-dir", str(tmp_path / "run")])
    assert rc == 1
    assert "backend 'cpu' unavailable" in capsys.readouterr().err
    events = [e["event"] for e in replay(str(tmp_path / "run"))]
    assert "watchdog" in events and "backend_error" in events
    assert "run_end" not in events


def test_backend_fault_injection_fails_acquisition(tmp_path):
    # OSIM_FAULT_PLAN-style plan against the backend-acquire injection point
    plan = faults.FaultPlan.from_dict({
        "rules": [{"target": "backend", "op": "acquire", "kind": "error"}],
    })
    from open_simulator_tpu.durable.watchdog import _default_probe

    with faults.injected(plan):
        journal = RunJournal.open(str(tmp_path / "run"))
        with pytest.raises(BackendUnavailable, match="injected by fault plan"):
            acquire_backend(
                deadline_s=60, journal=journal, probe=_default_probe,
                poll_s=0.001,
            )
    assert [e["event"] for e in journal.events()] == ["backend_error"]
    journal.close()


# ---------------------------------------------------------------------------
# Checkpoint/resume of the capacity bisection.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def overloaded():
    from open_simulator_tpu.api.config import SimonConfig
    from open_simulator_tpu.engine.apply import (
        build_apps,
        build_cluster,
        load_new_node,
    )

    cfg = SimonConfig.load(CONFIG)
    cluster = build_cluster(cfg)
    apps = build_apps(cfg)
    for app in apps:
        for obj in app.objects:
            if obj.get("kind") == "Deployment":
                obj["spec"]["replicas"] = 20
    return cluster, apps, load_new_node(cfg)


def _plan_counting(monkeypatch, cluster, apps, new_node, journal, resume):
    """plan_capacity with `simulate` wrapped to count live probe runs."""
    from open_simulator_tpu.engine import capacity

    real = capacity.simulate
    calls = []

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(capacity, "simulate", counting)
    plan = capacity.plan_capacity(
        cluster, apps, new_node, journal=journal, resume=resume
    )
    monkeypatch.setattr(capacity, "simulate", real)
    return plan, len(calls)


def _seed_journal_with_trials(src_dir, dst_dir, n_trials):
    """Simulate a crash: the dst run dir gets only the first n journaled
    trial verdicts from the src run (the crash happened before the rest
    were committed)."""
    trials = [e for e in replay(src_dir) if e["event"] == "trial"]
    os.makedirs(dst_dir, exist_ok=True)
    with open(os.path.join(dst_dir, JOURNAL_NAME), "w") as fh:
        for e in trials[:n_trials]:
            fh.write(json.dumps(e, sort_keys=True) + "\n")


def test_capacity_resume_skips_all_journaled_trials(
    tmp_path, monkeypatch, overloaded
):
    cluster, apps, new_node = overloaded
    d1 = str(tmp_path / "fresh")
    j1 = RunJournal.open(d1)
    fresh_plan, fresh_calls = _plan_counting(
        monkeypatch, cluster, apps, new_node, j1, resume=False
    )
    j1.close()
    assert fresh_plan is not None and fresh_plan.nodes_added >= 1
    n_trials = len([e for e in replay(d1) if e["event"] == "trial"])
    assert n_trials >= 2  # the sweep actually bisected

    # crash after ALL trials committed (but before the outcome landed):
    # the resume re-runs ZERO trials — only the one `final` materializing
    # replay that turns the winning verdict back into a SimulateResult
    d2 = str(tmp_path / "resumed")
    _seed_journal_with_trials(d1, d2, n_trials)
    j2 = RunJournal.open(d2)
    resumed_plan, resumed_calls = _plan_counting(
        monkeypatch, cluster, apps, new_node, j2, resume=True
    )
    j2.close()
    assert resumed_calls == 1
    assert resumed_plan.nodes_added == fresh_plan.nodes_added
    assert resumed_plan.attempts == fresh_plan.attempts
    assert resumed_plan.retries == fresh_plan.retries
    # the replayed final is journaled as `final`, never as a new trial
    ev2 = replay(d2)
    assert len([e for e in ev2 if e["event"] == "trial"]) == n_trials
    assert [e["event"] for e in ev2][-1] == "final"

    # identical placements, not just identical counts
    from open_simulator_tpu.engine.apply import placement_digest

    assert placement_digest(resumed_plan.result) == placement_digest(
        fresh_plan.result
    )


def test_capacity_resume_reruns_only_missing_trials(
    tmp_path, monkeypatch, overloaded
):
    cluster, apps, new_node = overloaded
    d1 = str(tmp_path / "fresh")
    j1 = RunJournal.open(d1)
    fresh_plan, _ = _plan_counting(
        monkeypatch, cluster, apps, new_node, j1, resume=False
    )
    j1.close()
    n_trials = len([e for e in replay(d1) if e["event"] == "trial"])

    # crash one trial earlier: exactly that trial re-runs, plus the final
    d2 = str(tmp_path / "resumed")
    _seed_journal_with_trials(d1, d2, n_trials - 1)
    j2 = RunJournal.open(d2)
    resumed_plan, resumed_calls = _plan_counting(
        monkeypatch, cluster, apps, new_node, j2, resume=True
    )
    j2.close()
    assert resumed_calls <= 2  # 1 re-run trial (+1 final unless it was last)
    assert resumed_plan.nodes_added == fresh_plan.nodes_added
    assert resumed_plan.attempts == fresh_plan.attempts


# ---------------------------------------------------------------------------
# Batched capacity sweep: journaled `sweep` records + resume.
# ---------------------------------------------------------------------------

HOSTNAME_ANTI = {
    "podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {
                "labelSelector": {"matchLabels": {"app": "lonely"}},
                "topologyKey": "kubernetes.io/hostname",
            }
        ]
    }
}


@pytest.fixture(scope="module")
def batch_overloaded():
    """Batch-eligible fixture (no DaemonSets/priority/greed — the yaml
    fixtures all carry a DaemonSet, which forces the serial fallback) whose
    hostname anti-affinity defeats the demand/supply estimate, so the
    batched search issues several `sweep` records worth resuming."""
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
    )
    from tests.factories import make_deployment, make_node

    cluster = ClusterResource(
        nodes=[make_node(f"base-{i}", cpu="32", memory="64Gi")
               for i in range(2)]
    )
    apps = [
        AppResource(
            name="app",
            objects=[
                make_deployment(
                    "lonely", replicas=24, cpu="500m", memory="1Gi",
                    with_affinity=HOSTNAME_ANTI,
                )
            ],
        )
    ]
    return cluster, apps, make_node("clone", cpu="32", memory="64Gi")


def _plan_counting_batched(monkeypatch, cluster, apps, new_node, journal,
                           resume):
    """plan_capacity(sweep_mode=auto) with both live-work channels counted:
    `simulate` (serial probes + the final materialize) and
    `Simulator.run_scenarios` (batched device calls)."""
    from open_simulator_tpu.engine import capacity

    real_simulate = capacity.simulate
    real_sim_cls = capacity.Simulator
    serial_calls = []
    batched_live = []

    def counting(*a, **kw):
        serial_calls.append(1)
        return real_simulate(*a, **kw)

    class CountingSimulator(real_sim_cls):
        def run_scenarios(self, *a, **kw):
            batched_live.append(1)
            return super().run_scenarios(*a, **kw)

    monkeypatch.setattr(capacity, "simulate", counting)
    monkeypatch.setattr(capacity, "Simulator", CountingSimulator)
    plan = capacity.plan_capacity(
        cluster, apps, new_node, journal=journal, resume=resume
    )
    monkeypatch.setattr(capacity, "simulate", real_simulate)
    monkeypatch.setattr(capacity, "Simulator", real_sim_cls)
    return plan, len(serial_calls), len(batched_live)


def _seed_journal_with_events(src_dir, dst_dir, events):
    """Simulate a crash: the dst run dir gets exactly `events` from the src
    run's journal (the crash happened before anything else committed)."""
    os.makedirs(dst_dir, exist_ok=True)
    with open(os.path.join(dst_dir, JOURNAL_NAME), "w") as fh:
        for e in events:
            fh.write(json.dumps(e, sort_keys=True) + "\n")


def test_batched_sweep_journals_all_lane_verdicts(
    tmp_path, monkeypatch, batch_overloaded
):
    from open_simulator_tpu.core.workloads import reset_name_rng

    cluster, apps, new_node = batch_overloaded
    d = str(tmp_path / "fresh")
    reset_name_rng()
    j = RunJournal.open(d)
    plan, serial_calls, batched_live = _plan_counting_batched(
        monkeypatch, cluster, apps, new_node, j, resume=False
    )
    j.close()
    assert plan is not None and plan.nodes_added >= 1
    sweeps = [e for e in replay(d) if e["event"] == "sweep"]
    # one committed `sweep` record per live batched device call, each
    # carrying ALL lane verdicts for that call
    assert len(sweeps) == plan.batched_calls == batched_live >= 2
    for e in sweeps:
        assert e["phase"] in ("ladder", "refine")
        assert len(e["counts"]) == len(e["good"]) >= 1
        assert e["n_pad"] >= len(cluster.nodes)
    # attempts = the base trial + every lane verdict of every sweep
    assert plan.attempts == 1 + sum(len(e["counts"]) for e in sweeps)
    assert serial_calls == 2  # base trial + final materialize


def test_batched_sweep_resume_reruns_zero_scenarios(
    tmp_path, monkeypatch, batch_overloaded
):
    from open_simulator_tpu.core.workloads import reset_name_rng
    from open_simulator_tpu.engine.apply import placement_digest

    cluster, apps, new_node = batch_overloaded
    d1 = str(tmp_path / "fresh")
    reset_name_rng()
    j1 = RunJournal.open(d1)
    fresh_plan, _, fresh_batched = _plan_counting_batched(
        monkeypatch, cluster, apps, new_node, j1, resume=False
    )
    j1.close()
    assert fresh_plan is not None and fresh_batched >= 2

    # crash after the base trial + ALL sweep records committed (before the
    # final landed): the resume replays every verdict from the journal —
    # ZERO live scenarios — and only re-runs the materializing final
    d2 = str(tmp_path / "resumed")
    _seed_journal_with_events(
        d1, d2,
        [e for e in replay(d1) if e["event"] in ("trial", "sweep")],
    )
    j2 = RunJournal.open(d2)
    resumed_plan, resumed_serial, resumed_batched = _plan_counting_batched(
        monkeypatch, cluster, apps, new_node, j2, resume=True
    )
    j2.close()
    assert resumed_batched == 0  # zero re-run scenarios
    assert resumed_serial == 1  # only the final materialize
    assert resumed_plan.nodes_added == fresh_plan.nodes_added
    assert resumed_plan.attempts == fresh_plan.attempts
    assert resumed_plan.batched_calls == fresh_plan.batched_calls
    assert resumed_plan.retries == fresh_plan.retries
    assert placement_digest(resumed_plan.result) == placement_digest(
        fresh_plan.result
    )
    ev2 = replay(d2)
    assert len([e for e in ev2 if e["event"] == "sweep"]) == fresh_batched
    assert [e["event"] for e in ev2][-1] == "final"


def test_batched_sweep_resume_reruns_only_missing_sweeps(
    tmp_path, monkeypatch, batch_overloaded
):
    from open_simulator_tpu.core.workloads import reset_name_rng

    cluster, apps, new_node = batch_overloaded
    d1 = str(tmp_path / "fresh")
    reset_name_rng()
    j1 = RunJournal.open(d1)
    fresh_plan, _, fresh_batched = _plan_counting_batched(
        monkeypatch, cluster, apps, new_node, j1, resume=False
    )
    j1.close()

    # crash one sweep earlier: exactly that device call re-runs live
    events = [e for e in replay(d1) if e["event"] in ("trial", "sweep")]
    sweep_idx = [i for i, e in enumerate(events) if e["event"] == "sweep"]
    d2 = str(tmp_path / "resumed")
    _seed_journal_with_events(d1, d2, events[: sweep_idx[-1]])
    j2 = RunJournal.open(d2)
    resumed_plan, resumed_serial, resumed_batched = _plan_counting_batched(
        monkeypatch, cluster, apps, new_node, j2, resume=True
    )
    j2.close()
    assert resumed_batched == 1
    assert resumed_serial == 1
    assert resumed_plan.nodes_added == fresh_plan.nodes_added
    assert resumed_plan.attempts == fresh_plan.attempts
    assert resumed_plan.batched_calls == fresh_plan.batched_calls


def test_sweep_cli_resume_outcome_byte_identical(tmp_path, monkeypatch):
    """`simon sweep --capacity` end-to-end: a crashed-then-resumed run's
    outcome.json is byte-identical to an uninterrupted one (the in-process
    twin of scripts/crash_resume_smoke.sh's batched leg)."""
    from open_simulator_tpu.cli.main import main as cli_main
    from open_simulator_tpu.core.workloads import reset_name_rng

    cfg = os.path.join(FIXTURES, "sweep", "simon-config.yaml")
    ref = str(tmp_path / "ref")
    reset_name_rng()
    rc = cli_main([
        "sweep", "-f", cfg, "--capacity", "--run-dir", ref,
    ])
    assert rc == 0
    ref_bytes = open(os.path.join(ref, "outcome.json"), "rb").read()
    doc = json.loads(ref_bytes)
    assert doc["kind"] == "sweep" and doc["batched_calls"] >= 1
    assert doc["placement_digest"]

    # "crash" before the final/run_end committed, then resume via the CLI
    crash = str(tmp_path / "crash")
    _seed_journal_with_events(
        ref, crash,
        [e for e in replay(ref)
         if e["event"] in ("run_start", "trial", "sweep")],
    )
    assert not os.path.exists(os.path.join(crash, "outcome.json"))
    reset_name_rng()
    rc = cli_main([
        "sweep", "-f", cfg, "--capacity", "--run-dir", crash, "--resume",
    ])
    assert rc == 0
    crash_bytes = open(os.path.join(crash, "outcome.json"), "rb").read()
    assert crash_bytes == ref_bytes
    ev = replay(crash)
    assert "run_resume" in [e["event"] for e in ev]
    assert [e["event"] for e in ev][-1] == "run_end"


# ---------------------------------------------------------------------------
# run_apply end-to-end: journaled outcome, resume identity, provenance.
# ---------------------------------------------------------------------------

def test_run_apply_journals_and_resumes_identically(tmp_path):
    from open_simulator_tpu.api.config import SimonConfig
    from open_simulator_tpu.engine.apply import run_apply

    cfg = SimonConfig.load(CONFIG)
    d = str(tmp_path / "run")
    out = io.StringIO()
    outcome = run_apply(cfg, out=out, run_dir=d, config_path=CONFIG)
    assert outcome.device  # provenance always stamped
    first = open(os.path.join(d, "outcome.json"), "rb").read()
    doc = json.loads(first)
    for key in ("device", "placement_digest"):
        assert key in doc  # TOP-LEVEL provenance fields
    events = [e["event"] for e in replay(d)]
    assert events[0] == "run_start" and "run_end" in events

    before = _counter_total(metrics.RUN_RESUMED)
    outcome2 = run_apply(
        cfg, out=io.StringIO(), run_dir=d, resume=True, config_path=CONFIG
    )
    assert _counter_total(metrics.RUN_RESUMED) == before + 1
    second = open(os.path.join(d, "outcome.json"), "rb").read()
    assert first == second  # byte-identical outcome after resume
    assert outcome2.result.unscheduled == outcome.result.unscheduled
    assert "run_resume" in [e["event"] for e in replay(d)]


def test_run_apply_output_reports_device(tmp_path):
    from open_simulator_tpu.api.config import SimonConfig
    from open_simulator_tpu.engine.apply import run_apply

    cfg = SimonConfig.load(CONFIG)
    out = io.StringIO()
    run_apply(cfg, out=out)
    assert "device:" in out.getvalue()
