"""Metrics subsystem: registry semantics, Prometheus rendering, the
/metrics endpoint, and the OSIM_TRACE_FILE Chrome-trace export."""

import json
import logging
import re
import threading
import urllib.request

import pytest

from open_simulator_tpu.utils import metrics, tracing
from open_simulator_tpu.utils.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# Prometheus text-format validator (shape only; values checked separately)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(\\.|[^\"\\])*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(\\.|[^\"\\])*\")*\})?"  # labels
    r" (\+Inf|-Inf|NaN|-?[0-9.e+-]+)$"      # value
)


def assert_valid_prometheus_text(text: str) -> None:
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_inc_and_value():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", labelnames=("k",))
    c.inc(k="a")
    c.inc(2.5, k="a")
    c.inc(k="b")
    assert c.value(k="a") == 3.5
    assert c.value(k="b") == 1.0
    assert c.value(k="never") == 0.0


def test_counter_rejects_decrease_and_label_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "", labelnames=("k",))
    with pytest.raises(ValueError):
        c.inc(-1, k="a")
    with pytest.raises(ValueError):
        c.inc()  # missing label
    with pytest.raises(ValueError):
        c.inc(k="a", extra="b")


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("t_gauge", "")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value() == 13
    g.set(-4)  # gauges may go negative
    assert g.value() == -4


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", "", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    cum, total, count = h.child_state()
    assert cum == [1, 2, 3, 4]  # +Inf bucket appended automatically
    assert count == 4
    assert abs(total - 55.55) < 1e-9
    text = h.render()
    assert 't_seconds_bucket{le="0.1"} 1' in text
    assert 't_seconds_bucket{le="+Inf"} 4' in text
    assert "t_seconds_count 4" in text
    assert_valid_prometheus_text(text)


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("t_total", "", labelnames=("k",))
    assert reg.counter("t_total", "", labelnames=("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("t_total", "", labelnames=("k",))
    with pytest.raises(ValueError):
        reg.counter("t_total", "", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.counter("bad name", "")


def test_label_value_escaping():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "", labelnames=("k",))
    c.inc(k='a"b\\c\nd')
    text = reg.render()
    assert 'k="a\\"b\\\\c\\nd"' in text
    assert_valid_prometheus_text(text)


def test_render_unlabeled_counter_reports_zero():
    reg = MetricsRegistry()
    reg.counter("never_fired_total", "h")
    text = reg.render()
    assert "# TYPE never_fired_total counter" in text
    assert "never_fired_total 0" in text


def test_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "")
    h = reg.histogram("t_seconds", "", buckets=(1.0,))
    n_threads, per_thread = 8, 1000

    def work():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == n_threads * per_thread
    cum, _, count = h.child_state()
    assert count == n_threads * per_thread
    assert cum[-1] == count


def test_snapshot_shape_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "h", labelnames=("k",))
    h = reg.histogram("t_seconds", "h", buckets=(1.0,))
    c.inc(3, k="x")
    h.observe(0.5)
    snap = reg.snapshot()
    assert snap["t_total"]["samples"] == [{"labels": {"k": "x"}, "value": 3.0}]
    hs = snap["t_seconds"]["samples"][0]
    assert hs["count"] == 1 and hs["buckets"]["1"] == 1
    # empty families are omitted unless asked for
    reg.counter("quiet_total", "h")
    assert "quiet_total" not in reg.snapshot()
    assert "quiet_total" in reg.snapshot(include_empty=True)
    reg.reset()
    assert c.value(k="x") == 0.0
    assert reg.snapshot() == {}


def test_default_registry_renders_valid_text():
    assert_valid_prometheus_text(metrics.REGISTRY.render())


def test_observe_span_routes_to_parity_histograms():
    _, _, before_e2e = metrics.E2E_SCHEDULING.child_state()
    _, _, before_enc = metrics.ENCODE_DURATION.child_state()
    with tracing.span("simulate"):
        with tracing.span("encode"):
            pass
    _, _, after_e2e = metrics.E2E_SCHEDULING.child_state()
    _, _, after_enc = metrics.ENCODE_DURATION.child_state()
    assert after_e2e == before_e2e + 1
    assert after_enc == before_enc + 1
    _, _, n = metrics.SPAN_DURATION.child_state(span="encode")
    assert n >= 1


# ---------------------------------------------------------------------------
# /metrics endpoint + one simulated request (acceptance criterion)
# ---------------------------------------------------------------------------

_NODE = {
    "kind": "Node",
    "metadata": {"name": "n0", "labels": {"kubernetes.io/hostname": "n0"}},
    "status": {"allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}},
}
_DEPLOY = {
    "kind": "Deployment",
    "metadata": {"name": "d", "namespace": "x"},
    "spec": {
        "replicas": 2,
        "template": {
            "metadata": {"labels": {"app": "d"}},
            "spec": {
                "containers": [
                    {"name": "c", "image": "i",
                     "resources": {"requests": {"cpu": "1"}}}
                ]
            },
        },
    },
}


def test_metrics_endpoint_after_simulated_request():
    from open_simulator_tpu.server.server import make_server

    httpd = make_server(0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        body = json.dumps(
            {
                "cluster": {"objects": [_NODE]},
                "apps": [{"name": "a", "objects": [_DEPLOY]}],
            }
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/deploy-apps",
            data=body,
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert len(out["placements"]) == 2

        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
    finally:
        httpd.shutdown()
        httpd.server_close()

    assert_valid_prometheus_text(text)
    assert "# TYPE osim_e2e_scheduling_duration_seconds histogram" in text
    assert 'osim_e2e_scheduling_duration_seconds_bucket{le="+Inf"}' in text
    m = re.search(
        r'^osim_schedule_result_total\{result="scheduled"\} (\d+)$',
        text, re.M,
    )
    assert m and int(m.group(1)) >= 2
    m = re.search(r"^osim_pod_scheduling_attempts_total (\d+)$", text, re.M)
    assert m and int(m.group(1)) >= 2
    # the handler counts its own traffic too
    assert 'path="/api/deploy-apps"' in text


# ---------------------------------------------------------------------------
# OSIM_TRACE_FILE round trip (acceptance criterion)
# ---------------------------------------------------------------------------

def test_trace_file_round_trip(monkeypatch, tmp_path):
    from open_simulator_tpu.core.objects import Node
    from open_simulator_tpu.engine.simulator import (
        AppResource,
        ClusterResource,
        simulate,
    )

    path = tmp_path / "trace.json"
    monkeypatch.setenv("OSIM_TRACE_FILE", str(path))
    tracing.reset_trace_events()
    try:
        simulate(
            ClusterResource(nodes=[Node.from_dict(_NODE)]),
            [AppResource(name="a", objects=[_DEPLOY])],
        )
    finally:
        monkeypatch.delenv("OSIM_TRACE_FILE")
        payload = json.loads(path.read_text())
        tracing.reset_trace_events()

    events = payload["traceEvents"]
    names = [e["name"] for e in events]
    for expected in ("simulate", "expand-workloads", "encode-cluster",
                     "encode", "schedule", "decode-result"):
        assert expected in names
    roots = [e for e in events if e["name"] == "simulate"]
    assert len(roots) == 1
    root = roots[0]
    for e in events:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and e["ts"] > 0
        assert isinstance(e["dur"], float) and e["dur"] >= 0
        assert e["pid"] and e["tid"]
        # children nest inside the root's window (1ms slack for rounding)
        assert e["ts"] >= root["ts"] - 1e3
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e3
    # root meta rides along as Chrome trace args
    assert root["args"]["nodes"] == 1


def test_trace_file_not_written_when_env_unset(tmp_path, monkeypatch):
    monkeypatch.delenv("OSIM_TRACE_FILE", raising=False)
    tracing.reset_trace_events()
    with tracing.span("no-export"):
        pass
    assert list(tmp_path.iterdir()) == []


def test_metrics_file_cli_flag(tmp_path, monkeypatch):
    """`simon apply --metrics-file` dumps the JSON snapshot."""
    import yaml

    from open_simulator_tpu.cli.main import main

    # keep the CLI entry point's compilation cache out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))

    cfg_dir = tmp_path / "cluster"
    cfg_dir.mkdir()
    (cfg_dir / "node.yaml").write_text(yaml.safe_dump(_NODE))
    app_dir = tmp_path / "app"
    app_dir.mkdir()
    (app_dir / "deploy.yaml").write_text(yaml.safe_dump(_DEPLOY))
    cfg = {
        "apiVersion": "simon/v1alpha1",
        "kind": "Config",
        "metadata": {"name": "t"},
        "spec": {
            "cluster": {"customConfig": str(cfg_dir)},
            "appList": [{"name": "a", "path": str(app_dir)}],
        },
    }
    cfg_path = tmp_path / "simon.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))
    metrics_path = tmp_path / "metrics.json"
    rc = main([
        "apply", "-f", str(cfg_path), "--output-file",
        str(tmp_path / "report.txt"), "--metrics-file", str(metrics_path),
    ])
    assert rc == 0
    snap = json.loads(metrics_path.read_text())
    assert "osim_schedule_result_total" in snap
    assert "osim_apply_total" in snap


def test_init_logging_idempotent_and_honors_loglevel(monkeypatch):
    monkeypatch.setenv("LogLevel", "warn")
    tracing.init_logging()
    handler = tracing._log_handler
    assert handler is not None
    assert tracing.log.handlers.count(handler) == 1
    assert handler.level == logging.WARNING
    # second call must not duplicate the handler and must re-read LogLevel
    monkeypatch.setenv("LogLevel", "debug")
    tracing.init_logging()
    assert tracing._log_handler is handler
    assert tracing.log.handlers.count(handler) == 1
    assert handler.level == logging.DEBUG
    assert tracing.log.level == logging.DEBUG


# ---------------------------------------------------------------------------
# /metrics exposition-format compliance + trace-event cap rotation
# ---------------------------------------------------------------------------

def test_metrics_exposition_format_compliance():
    """Prometheus text format 0.0.4: exact Content-Type (with charset),
    EOF-safe trailing newline, every line a comment or a parseable sample."""
    from open_simulator_tpu.server.server import make_server

    httpd = make_server(0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            assert (
                resp.headers["Content-Type"]
                == "text/plain; version=0.0.4; charset=utf-8"
            )
            text = resp.read().decode()
    finally:
        httpd.shutdown()
        httpd.server_close()
    # scrapers treat a missing final newline as a truncated exposition
    assert text.endswith("\n") and not text.endswith("\n\n")
    assert_valid_prometheus_text(text)
    # every sample family is preceded by its HELP/TYPE comments
    seen_type = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            seen_type.add(line.split()[2])
        elif line and not line.startswith("#"):
            fam = re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*", line).group(0)
            base = re.sub(r"_(bucket|count|sum)$", "", fam)
            assert fam in seen_type or base in seen_type, line


def test_render_always_ends_with_single_newline():
    reg = MetricsRegistry()
    assert reg.render().endswith("\n")  # even with zero families
    reg.counter("fmt_probe_total", "h").inc()
    out = reg.render()
    assert out.endswith("\n") and not out.endswith("\n\n")


def test_trace_file_event_cap_rotates_oldest(monkeypatch, tmp_path, caplog):
    path = tmp_path / "trace.json"
    monkeypatch.setenv("OSIM_TRACE_FILE", str(path))
    monkeypatch.setenv("OSIM_TRACE_MAX_EVENTS", "5")
    tracing.reset_trace_events()
    try:
        with caplog.at_level(logging.WARNING, logger=tracing.log.name):
            for i in range(9):
                with tracing.span(f"rotate-{i}"):
                    pass
        payload = json.loads(path.read_text())
    finally:
        tracing.reset_trace_events()
    names = [e["name"] for e in payload["traceEvents"]]
    # oldest-first rotation at the cap: only the newest 5 roots survive
    assert names == [f"rotate-{i}" for i in range(4, 9)]
    warnings = [
        r for r in caplog.records if "event cap 5 reached" in r.getMessage()
    ]
    assert len(warnings) == 1  # one-time warning, not once per export


def test_trace_event_cap_bad_value_falls_back(monkeypatch, tmp_path):
    path = tmp_path / "trace.json"
    monkeypatch.setenv("OSIM_TRACE_FILE", str(path))
    monkeypatch.setenv("OSIM_TRACE_MAX_EVENTS", "not-a-number")
    tracing.reset_trace_events()
    try:
        with tracing.span("cap-fallback"):
            pass
        payload = json.loads(path.read_text())
    finally:
        tracing.reset_trace_events()
    assert [e["name"] for e in payload["traceEvents"]] == ["cap-fallback"]
