"""Resilience layer: retry/backoff determinism, circuit-breaker transitions,
deterministic fault injection, and the degraded-mode e2e paths (extender
retry-then-schedule, ignorable skip on open breaker, clean aggregate failure,
stale-snapshot serving, slow-loris 408, SIGTERM drain, `simon chaos`).

No test here sleeps for real: RetryPolicy takes injectable rng/clock/sleep,
CircuitBreaker takes an injectable clock, and the e2e retry tests pin
OSIM_RETRY_BASE_S=0 so every backoff is zero.
"""

import json
import random
import socket
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest
import yaml

from open_simulator_tpu.core.objects import Node
from open_simulator_tpu.engine.simulator import (
    AppResource,
    ClusterResource,
    simulate,
)
from open_simulator_tpu.models.profiles import ExtenderConfig
from open_simulator_tpu.resilience import faults
from open_simulator_tpu.resilience.faults import (
    FaultInjectionError,
    FaultInjector,
    FaultPlan,
    FaultRule,
)
from open_simulator_tpu.resilience.policy import (
    CircuitBreaker,
    RetryExhaustedError,
    RetryPolicy,
    breaker_for,
)
from open_simulator_tpu.utils import metrics

_NODE = {
    "kind": "Node",
    "metadata": {
        "name": "n0",
        "labels": {"kubernetes.io/hostname": "n0"},
    },
    "status": {"allocatable": {"cpu": "16", "memory": "32Gi", "pods": "110"}},
}


def _nodes(n, cpu="16"):
    return [
        Node.from_dict(
            {
                "metadata": {
                    "name": f"n{i}",
                    "labels": {"kubernetes.io/hostname": f"n{i}"},
                },
                "status": {
                    "allocatable": {"cpu": cpu, "memory": "32Gi", "pods": "110"}
                },
            }
        )
        for i in range(n)
    ]


def _deploy(replicas=1, cpu="1", name="d"):
    return {
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": "x"},
        "spec": {
            "replicas": replicas,
            "template": {
                "metadata": {"labels": {"app": name}},
                "spec": {
                    "containers": [
                        {
                            "name": "c",
                            "image": "img",
                            "resources": {"requests": {"cpu": cpu}},
                        }
                    ]
                },
            },
        },
    }


def _ext(url, **kw):
    return ExtenderConfig(
        url_prefix=url, filter_verb="filter", prioritize_verb="prioritize",
        **kw,
    )


# ---------------------------------------------------------------------------
# RetryPolicy: jitter determinism, exhaustion, deadline budget
# ---------------------------------------------------------------------------

def test_decorrelated_jitter_deterministic_and_bounded():
    def run():
        delays = []
        p = RetryPolicy(
            max_attempts=6, base_s=0.05, cap_s=2.0,
            rng=random.Random(42), sleep=delays.append,
        )
        calls = [0]

        def fn(_timeout):
            calls[0] += 1
            if calls[0] < 6:
                raise ValueError("blip")
            return "ok"

        assert p.execute(fn, retryable=(ValueError,)) == "ok"
        assert calls[0] == 6
        return delays

    a, b = run(), run()
    assert a == b                    # same seed -> identical schedule
    assert len(a) == 5               # one backoff per retry
    for d in a:
        assert 0.05 <= d <= 2.0      # decorrelated jitter stays in [base, cap]
    assert len(set(a)) > 1           # and actually jitters


def test_retry_counts_metric_and_wraps_last_error():
    before = metrics.RETRY_ATTEMPTS.value(target="unit")
    p = RetryPolicy(max_attempts=3, base_s=0.0, rng=random.Random(0))

    def fn(_timeout):
        raise ValueError("still down")

    with pytest.raises(RetryExhaustedError) as ei:
        p.execute(fn, retryable=(ValueError,), target="unit")
    assert ei.value.attempts == 3
    assert "still down" in str(ei.value)
    assert "(after 3 attempt(s))" in str(ei.value)
    assert metrics.RETRY_ATTEMPTS.value(target="unit") == before + 2


def test_non_retryable_error_propagates_immediately():
    calls = [0]
    p = RetryPolicy(max_attempts=5, base_s=0.0, rng=random.Random(0))

    def fn(_timeout):
        calls[0] += 1
        raise KeyError("permanent")

    with pytest.raises(KeyError):
        p.execute(fn, retryable=(ValueError,))
    assert calls[0] == 1


def test_deadline_budget_aborts_instead_of_oversleeping():
    now = [0.0]
    slept = []

    def sleep(s):
        slept.append(s)
        now[0] += s

    p = RetryPolicy(
        max_attempts=100, base_s=1.0, cap_s=5.0, deadline_s=2.5,
        rng=random.Random(0), clock=lambda: now[0], sleep=sleep,
    )

    def fn(_timeout):
        raise ValueError("down")

    with pytest.raises(RetryExhaustedError) as ei:
        p.execute(fn, retryable=(ValueError,))
    assert ei.value.attempts < 100            # gave up on the budget
    assert sum(slept) <= 2.5                  # never slept past the deadline


def test_per_attempt_timeout_clamped_to_remaining_deadline():
    # Regression: per_attempt_timeout_s used to be handed to fn untouched,
    # so one attempt could overshoot the whole deadline (a transport given
    # timeout=10 against a 4s deadline hangs for 10).
    now = [0.0]
    budgets = []

    def fn(timeout):
        budgets.append(timeout)
        now[0] += timeout  # the attempt burns its entire budget
        raise ValueError("slow")

    p = RetryPolicy(
        max_attempts=5, base_s=1.0, cap_s=1.0, per_attempt_timeout_s=10.0,
        deadline_s=4.0, rng=random.Random(0), clock=lambda: now[0],
        sleep=lambda s: now.__setitem__(0, now[0] + s),
    )
    with pytest.raises(RetryExhaustedError):
        p.execute(fn, retryable=(ValueError,))
    assert budgets[0] == 4.0                  # min(10, remaining 4), not 10
    assert all(0 < b <= 4.0 for b in budgets)
    assert now[0] <= p.deadline_s + p.cap_s   # no attempt overshot the budget


def test_blown_deadline_refuses_to_launch_attempt():
    # Regression: with the deadline exactly consumed and a zero backoff, the
    # next attempt used to launch with a clamped timeout of 0 — which most
    # transports treat as *unbounded*. It must be refused instead.
    now = [0.0]
    calls = []

    def fn(timeout):
        calls.append(timeout)
        now[0] += 2.0  # consumes the whole deadline
        raise ValueError("hang")

    p = RetryPolicy(
        max_attempts=3, base_s=0.0, cap_s=0.0, deadline_s=2.0,
        rng=random.Random(0), clock=lambda: now[0], sleep=lambda s: None,
    )
    with pytest.raises(RetryExhaustedError) as ei:
        p.execute(fn, retryable=(ValueError,))
    assert calls == [2.0]                     # exactly one attempt launched
    assert ei.value.attempts == 1
    assert isinstance(ei.value.last_exc, ValueError)


def test_from_env_knobs(monkeypatch):
    monkeypatch.setenv("OSIM_RETRY_MAX_ATTEMPTS", "5")
    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0.01")
    monkeypatch.setenv("OSIM_RETRY_CAP_S", "0.5")
    monkeypatch.setenv("OSIM_RETRY_JITTER_SEED", "7")
    p = RetryPolicy.from_env()
    assert p.max_attempts == 5
    assert p.base_s == 0.01 and p.cap_s == 0.5
    assert p.rng.random() == random.Random(7).random()
    # caller defaults hold when a knob is unset; a set knob overrides them
    monkeypatch.delenv("OSIM_RETRY_MAX_ATTEMPTS")
    assert RetryPolicy.from_env(max_attempts=2).max_attempts == 2
    assert RetryPolicy.from_env(deadline_s=60.0).deadline_s == 60.0
    monkeypatch.setenv("OSIM_RETRY_DEADLINE_S", "0")
    assert RetryPolicy.from_env(deadline_s=60.0).deadline_s is None
    monkeypatch.setenv("OSIM_RETRY_DEADLINE_S", "9")
    assert RetryPolicy.from_env().deadline_s == 9.0


# ---------------------------------------------------------------------------
# CircuitBreaker: closed -> open -> half-open -> closed, no real sleeps
# ---------------------------------------------------------------------------

def test_circuit_breaker_transitions():
    now = [0.0]
    b = CircuitBreaker(
        "http://e", failure_threshold=3, cooldown_s=10.0,
        clock=lambda: now[0],
    )
    assert b.state == b.CLOSED and b.allow()
    b.record_failure("boom")
    b.record_failure("boom")
    assert b.state == b.CLOSED and b.allow()   # under the threshold
    b.record_failure("boom")
    assert b.state == b.OPEN and not b.allow()
    assert metrics.CIRCUIT_STATE.value(endpoint="http://e") == 1.0

    now[0] = 9.9
    assert not b.allow()                       # cooldown not yet elapsed
    now[0] = 10.0
    assert b.allow()                           # the single half-open probe
    assert b.state == b.HALF_OPEN
    assert metrics.CIRCUIT_STATE.value(endpoint="http://e") == 2.0
    assert not b.allow()                       # probe already in flight

    b.record_failure("still down")             # failed probe -> reopen
    assert b.state == b.OPEN and not b.allow()
    now[0] = 25.0
    assert b.allow()
    b.record_success()                         # healed probe -> closed
    assert b.state == b.CLOSED and b.allow()
    assert b.consecutive_failures == 0
    assert metrics.CIRCUIT_STATE.value(endpoint="http://e") == 0.0


def test_breaker_registry_shared_and_described():
    a = breaker_for("http://x")
    assert breaker_for("http://x") is a        # endpoint-keyed singleton
    assert breaker_for("http://y") is not a
    a.force_open("hard down")
    assert "circuit open" in a.describe()
    assert "hard down" in a.describe()


# ---------------------------------------------------------------------------
# Fault plans: validation, deterministic schedule, gating
# ---------------------------------------------------------------------------

def test_fault_plan_validation():
    with pytest.raises(FaultInjectionError, match="unknown target"):
        FaultRule(target="dns", kind="latency")
    with pytest.raises(FaultInjectionError, match="unknown kind"):
        FaultRule(target="extender", kind="kaboom")
    with pytest.raises(FaultInjectionError, match="unknown key"):
        FaultRule.from_dict({"target": "extender", "kind": "latency", "lag": 1})
    with pytest.raises(FaultInjectionError, match="non-empty list"):
        FaultPlan.from_dict({"seed": 1})
    with pytest.raises(FaultInjectionError, match="not in \\[0, 1\\]"):
        FaultRule(target="chart", kind="error", probability=1.5)


def test_fault_schedule_is_seed_deterministic():
    doc = {
        "seed": 123,
        "rules": [
            {"target": "extender", "kind": "connection_error",
             "probability": 0.5},
        ],
    }

    def run():
        inj = FaultInjector(FaultPlan.from_dict(doc))
        return [
            inj.intercept("extender", "filter") is not None for _ in range(50)
        ]

    a, b = run(), run()
    assert a == b                # same seed -> same schedule
    assert any(a) and not all(a)  # the coin actually flips both ways


def test_fault_rule_after_times_and_op_gating():
    plan = FaultPlan.from_dict(
        {
            "rules": [
                {"target": "kubeclient", "op": "/nodes",
                 "kind": "http_error", "after": 1, "times": 2},
            ]
        }
    )
    inj = FaultInjector(plan)
    assert inj.intercept("kubeclient", "/api/v1/nodes") is None   # after=1
    assert inj.intercept("kubeclient", "/api/v1/pods") is None    # op mismatch
    assert inj.intercept("extender", "/api/v1/nodes") is None     # target
    assert inj.intercept("kubeclient", "/api/v1/nodes") is not None
    assert inj.intercept("kubeclient", "/api/v1/nodes") is not None
    assert inj.intercept("kubeclient", "/api/v1/nodes") is None   # exhausted
    (row,) = inj.summary()
    assert row["injected"] == 2 and row["matched"] == 4


def test_fault_plan_from_env_inline_and_path(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "OSIM_FAULT_PLAN",
        "{seed: 3, rules: [{target: chart, kind: error}]}",
    )
    plan = FaultPlan.from_env()
    assert plan.seed == 3 and plan.rules[0].target == "chart"
    path = tmp_path / "plan.yaml"
    path.write_text("seed: 4\nrules:\n  - target: extender\n    kind: latency\n")
    monkeypatch.setenv("OSIM_FAULT_PLAN", str(path))
    assert FaultPlan.from_env().seed == 4
    monkeypatch.setenv("OSIM_FAULT_PLAN", "")
    assert FaultPlan.from_env() is None


# ---------------------------------------------------------------------------
# e2e: extender transport under faults (acceptance criteria a/b and the
# non-ignorable aggregate failure)
# ---------------------------------------------------------------------------

def test_transient_faults_retry_then_schedule(stub_factory, monkeypatch):
    """Acceptance (a): a filter call failing twice then succeeding schedules
    the pod, with osim_retry_attempts_total == 2 — and zero real sleeps."""
    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    stub = stub_factory({})                     # healthy pass-through
    plan = FaultPlan.from_dict(
        {
            "seed": 0,
            "rules": [
                {"target": "extender", "op": "filter",
                 "kind": "connection_error", "times": 2},
            ],
        }
    )
    before = metrics.RETRY_ATTEMPTS.value(target="extender")
    with faults.injected(plan) as inj:
        res = simulate(
            ClusterResource(nodes=_nodes(2)),
            [AppResource(name="a", objects=[_deploy(replicas=1)])],
            extenders=[_ext(stub.url)],
        )
    assert not res.unscheduled
    assert metrics.RETRY_ATTEMPTS.value(target="extender") == before + 2
    (row,) = inj.summary()
    assert row["injected"] == 2
    assert stub.calls                           # the third attempt went through


def test_open_breaker_ignorable_extender_skipped(stub_factory):
    """Acceptance (b): an ignorable extender behind an open breaker is
    skipped — the simulation completes and the skip metric increments —
    without a single network round trip."""
    stub = stub_factory({"allow": set()})       # would veto every node
    breaker_for(stub.url).force_open("chaos: backend hard down")
    before = metrics.EXTENDER_SKIPPED.value(endpoint=stub.url)
    res = simulate(
        ClusterResource(nodes=_nodes(2)),
        [AppResource(name="a", objects=[_deploy(replicas=1)])],
        extenders=[_ext(stub.url, ignorable=True)],
    )
    assert not res.unscheduled
    assert metrics.EXTENDER_SKIPPED.value(endpoint=stub.url) >= before + 1
    assert stub.calls == []                     # failed fast, no round trips


def test_open_breaker_non_ignorable_fails_fast(stub_factory):
    stub = stub_factory({})
    breaker_for(stub.url).force_open("backend hard down")
    res = simulate(
        ClusterResource(nodes=_nodes(2)),
        [AppResource(name="a", objects=[_deploy(replicas=1)])],
        extenders=[_ext(stub.url)],
    )
    assert len(res.unscheduled) == 1
    reason = res.unscheduled[0].reason
    assert "circuit open" in reason and "failing fast" in reason
    assert "backend hard down" in reason
    assert stub.calls == []


def test_hard_down_non_ignorable_aggregate_message(monkeypatch):
    """A dead non-ignorable extender fails the pod with a clear aggregate
    message naming the attempt count."""
    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    res = simulate(
        ClusterResource(nodes=_nodes(2)),
        [AppResource(name="a", objects=[_deploy(replicas=1)])],
        extenders=[_ext("http://127.0.0.1:9", http_timeout_s=0.5)],
    )
    assert len(res.unscheduled) == 1
    reason = res.unscheduled[0].reason
    assert "extender" in reason
    assert "(after 3 attempt(s))" in reason
    assert res.unscheduled[0].transient          # blip, not a verdict


def test_http_error_body_snippet_bounded(stub_factory, monkeypatch):
    """Satellite: urlopen raises HTTPError on non-2xx, so the error body —
    where real extenders put the failure reason — must be read from the
    exception, bounded, and quoted in the pod's failure message."""
    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    body = b'{"reason": "quota exhausted"}' + b"x" * 400
    stub = stub_factory({"http_error": 503, "http_error_body": body})
    res = simulate(
        ClusterResource(nodes=_nodes(2)),
        [AppResource(name="a", objects=[_deploy(replicas=1)])],
        extenders=[_ext(stub.url)],
    )
    assert len(res.unscheduled) == 1
    reason = res.unscheduled[0].reason
    assert "HTTP 503" in reason
    assert "quota exhausted" in reason           # body snippet surfaced
    assert "x" * 250 not in reason               # ...but bounded


def test_flaky_extender_recovers_via_stub(stub_factory, monkeypatch):
    """Same acceptance path driven by a flaky endpoint (503, 503, then
    healthy) instead of the fault plan: the transport itself retries."""
    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    stub = stub_factory({"fail_first": 2})
    res = simulate(
        ClusterResource(nodes=_nodes(2)),
        [AppResource(name="a", objects=[_deploy(replicas=1)])],
        extenders=[_ext(stub.url)],
    )
    assert not res.unscheduled
    assert len(stub.calls) >= 3                  # 2 failures + the success


# ---------------------------------------------------------------------------
# kubeclient: transient retry + clean surfacing
# ---------------------------------------------------------------------------

@pytest.fixture
def stub_apiserver():
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            out = json.dumps({"items": []}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(out)))
            self.end_headers()
            self.wfile.write(out)

    server = HTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    server.server_close()


def test_kubeclient_retries_malformed_json(stub_apiserver, monkeypatch):
    from open_simulator_tpu.utils.kubeclient import KubeClient, KubeConfig

    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    client = KubeClient(KubeConfig(server=stub_apiserver))
    plan = FaultPlan.from_dict(
        {"rules": [{"target": "kubeclient", "kind": "malformed_json",
                    "times": 1}]}
    )
    with faults.injected(plan):
        doc = client.get("/api/v1/nodes")
    assert doc == {"items": []}                  # retry healed the truncation


def test_kubeclient_exhausted_retries_surface_aggregate(
    stub_apiserver, monkeypatch
):
    from open_simulator_tpu.utils.kubeclient import (
        KubeClient,
        KubeClientError,
        KubeConfig,
    )

    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    client = KubeClient(KubeConfig(server=stub_apiserver))
    plan = FaultPlan.from_dict(
        {"rules": [{"target": "kubeclient", "kind": "connection_error"}]}
    )
    with faults.injected(plan):
        with pytest.raises(KubeClientError, match=r"after 3 attempt"):
            client.get("/api/v1/nodes")


# ---------------------------------------------------------------------------
# capacity planner: a transient-extender trial is retried, not trusted
# ---------------------------------------------------------------------------

def test_capacity_trial_retried_on_transient_extender_error(
    stub_factory, monkeypatch
):
    from open_simulator_tpu.engine.capacity import plan_capacity

    monkeypatch.setenv("OSIM_RETRY_BASE_S", "0")
    stub = stub_factory({})                      # healthy pass-through
    # 3 injected connection errors exhaust the first probe's 3 transport
    # attempts; the planner re-runs that trial once and it heals
    plan_doc = FaultPlan.from_dict(
        {
            "rules": [
                {"target": "extender", "op": "filter",
                 "kind": "connection_error", "times": 3},
            ]
        }
    )
    before = metrics.RETRY_ATTEMPTS.value(target="capacity-probe")
    with faults.injected(plan_doc):
        plan = plan_capacity(
            ClusterResource(nodes=_nodes(2)),
            [AppResource(name="a", objects=[_deploy(replicas=1)])],
            _nodes(1)[0],
            extenders=[_ext(stub.url)],
        )
    assert plan is not None
    assert plan.nodes_added == 0                 # fits without new nodes
    assert not plan.result.unscheduled
    assert plan.retries == 1                     # the blipped trial re-ran
    assert plan.attempts == 2                    # original + retry
    assert metrics.RETRY_ATTEMPTS.value(target="capacity-probe") == before + 1


# ---------------------------------------------------------------------------
# server: stale-snapshot degradation, slow-loris 408, SIGTERM drain
# ---------------------------------------------------------------------------

def test_live_snapshot_degrades_to_stale_cache(monkeypatch):
    from open_simulator_tpu.server import server as server_mod
    from open_simulator_tpu.utils import kubeclient as kc

    cached = ClusterResource(nodes=[Node.from_dict(_NODE)])
    monkeypatch.setattr(server_mod, "_kubeconfig", "/nonexistent")
    monkeypatch.setattr(server_mod, "_master", "")
    monkeypatch.setattr(server_mod, "_snapshot", cached)
    monkeypatch.setattr(server_mod, "_snapshot_at", -1.0e9)  # long stale

    def boom(path, context=None, master=""):
        raise kc.KubeClientError("apiserver down")

    monkeypatch.setattr(kc, "create_cluster_resource_from_kubeconfig", boom)
    before = metrics.SNAPSHOT_STALE.value()
    c = server_mod._live_snapshot()
    assert [n.name for n in c.nodes] == ["n0"]   # served from the stale cache
    assert metrics.SNAPSHOT_STALE.value() == before + 1
    # _snapshot_at untouched -> the next request retries the refresh
    assert server_mod._snapshot_at == -1.0e9

    # with nothing cached there is nothing to degrade to: the error surfaces
    monkeypatch.setattr(server_mod, "_snapshot", None)
    with pytest.raises(kc.KubeClientError, match="apiserver down"):
        server_mod._live_snapshot()


def test_slow_loris_body_read_times_out(monkeypatch):
    from open_simulator_tpu.server import server as server_mod

    monkeypatch.setattr(server_mod, "REQUEST_TIMEOUT_S", 0.2)
    httpd = server_mod.make_server(0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        s.settimeout(10)
        # headers promise a body that never arrives (slow loris)
        s.sendall(
            b"POST /api/deploy-apps HTTP/1.1\r\n"
            b"Host: localhost\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: 100\r\n\r\n"
        )
        chunks = []
        while True:
            piece = s.recv(65536)
            if not piece:
                break                # the 408 closes the connection
            chunks.append(piece)
        s.close()
        data = b"".join(chunks)
    finally:
        httpd.shutdown()
        httpd.server_close()
    status = data.split(b"\r\n", 1)[0]
    assert b"408" in status
    assert b"request body read timed out" in data


def test_sigterm_drains_in_flight_request(monkeypatch):
    """Acceptance (c): SIGTERM while a request is in flight lets that request
    complete (200 delivered) before serve() returns."""
    import signal as _signal

    from open_simulator_tpu.server import server as server_mod

    started = threading.Event()
    release = threading.Event()

    def fake_sim(body):
        started.set()
        assert release.wait(timeout=30)
        return {"placements": {}, "unscheduled": []}

    monkeypatch.setattr(server_mod, "_simulate_request", fake_sim)

    ready = threading.Event()
    rc = {}

    def run_server():
        rc["code"] = server_mod.serve(port=0, ready=ready)

    server_thread = threading.Thread(target=run_server, daemon=True)
    server_thread.start()
    assert ready.wait(10)
    port = server_mod._current_server.server_address[1]

    resp = {}

    def post():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps",
            data=b"{}",
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            resp["status"] = r.status
            resp["body"] = json.loads(r.read())

    poster = threading.Thread(target=post, daemon=True)
    poster.start()
    assert started.wait(10)                     # request is mid-simulation

    # the signal handler path (called directly: signals only reach the main
    # thread, and serve() runs on a worker thread in this test)
    server_mod._graceful_shutdown(_signal.SIGTERM, None)
    server_thread.join(timeout=0.5)
    assert server_thread.is_alive()             # draining, not dead

    release.set()
    poster.join(timeout=60)
    server_thread.join(timeout=60)
    assert not server_thread.is_alive()
    assert resp.get("status") == 200            # the in-flight request won
    assert rc.get("code") == 0


# ---------------------------------------------------------------------------
# simon chaos: deterministic end-to-end degraded-mode report (acceptance d)
# ---------------------------------------------------------------------------

def _chaos_fixture(tmp_path):
    cluster_dir = tmp_path / "cluster"
    cluster_dir.mkdir()
    (cluster_dir / "node.yaml").write_text(yaml.safe_dump(_NODE))
    app_dir = tmp_path / "app"
    app_dir.mkdir()
    (app_dir / "deploy.yaml").write_text(yaml.safe_dump(_deploy(replicas=2)))
    chart_dir = tmp_path / "chart"
    (chart_dir / "templates").mkdir(parents=True)
    (chart_dir / "Chart.yaml").write_text(
        "apiVersion: v2\nname: web\nversion: 0.1.0\n"
    )
    (chart_dir / "values.yaml").write_text("")
    (chart_dir / "templates" / "deploy.yaml").write_text(
        yaml.safe_dump(_deploy(replicas=1, name="web"))
    )
    cfg = {
        "apiVersion": "simon/v1alpha1",
        "kind": "Config",
        "metadata": {"name": "chaos-e2e"},
        "spec": {
            "cluster": {"customConfig": str(cluster_dir)},
            "appList": [
                {"name": "ok", "path": str(app_dir)},
                {"name": "web", "path": str(chart_dir), "chart": True},
            ],
        },
    }
    cfg_path = tmp_path / "simon.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))
    plan_path = tmp_path / "plan.yaml"
    plan_path.write_text(
        "seed: 7\nrules:\n"
        "  - target: chart\n    op: web\n    kind: error\n    times: 1\n"
    )
    return cfg_path, plan_path


def test_chaos_report_deterministic_and_degraded(tmp_path, capsys, monkeypatch):
    """Acceptance (d): the same fault-plan seed yields byte-identical chaos
    reports across two runs; an injected chart fault degrades (exit 0)."""
    from open_simulator_tpu.cli.main import main

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    cfg_path, plan_path = _chaos_fixture(tmp_path)
    argv = ["chaos", "-f", str(cfg_path), "--fault-plan", str(plan_path)]

    rc1 = main(argv)
    out1 = capsys.readouterr().out
    rc2 = main(argv)
    out2 = capsys.readouterr().out

    assert rc1 == 0 and rc2 == 0                # degraded is still exit 0
    assert out1 == out2                          # byte-identical reports
    assert "simon chaos report" in out1
    assert "target=chart" in out1 and "injected 1 of 1" in out1
    assert "apps failed to render: 1 (web)" in out1
    assert "unscheduled pods: 0" in out1
    assert "outcome: degraded" in out1


def test_chaos_requires_a_plan(capsys):
    from open_simulator_tpu.cli.main import main

    rc = main(["chaos", "-f", "/nonexistent.yaml"])
    assert rc == 1
    assert "no fault plan" in capsys.readouterr().err
