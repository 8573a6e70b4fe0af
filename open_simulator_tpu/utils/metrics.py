"""Aggregated scheduler metrics: counters, gauges, histograms + Prometheus text.

The reference embeds the real kube-scheduler, whose `metrics` package is what
operators tune against (e2e scheduling duration, attempt counts, schedule
results).  This module is the TPU-port equivalent: a small, dependency-free,
thread-safe registry with the kube-scheduler metric names carried over under
the `osim_` prefix.

Parity table (ours -> kube-scheduler):

    osim_e2e_scheduling_duration_seconds  -> scheduler_e2e_scheduling_duration_seconds
    osim_pod_scheduling_attempts_total    -> scheduler_pod_scheduling_attempts
    osim_schedule_result_total{result=}   -> scheduler_schedule_attempts_total{result=}
    osim_filter_failure_total{reason=}    -> (per-plugin UnschedulableAndUnresolvable counts)
    osim_compile_cache_total{event=}      -> (no analogue: XLA compile requests, cache hits)
    osim_victim_search_total{path=}       -> (no analogue: DefaultPreemption searches by path)
    osim_encode_duration_seconds          -> (no analogue: cluster/pod -> device-array encode)

Exposure paths:
  * `GET /metrics` on the HTTP server (Prometheus text format 0.0.4);
  * `snapshot()` as JSON (`simon apply --metrics-file`, flight-recorder
    dumps, the benchmark's per-layer counters);
  * every `tracing.span()` observes into a histogram via `observe_span()`.

Hand-rolled on purpose: the image pins jax/numpy/pyyaml only, and the subset
of prometheus_client we need (labeled counter/gauge/histogram + text render)
is ~300 lines.
"""

from __future__ import annotations

import math
import re
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "observe_span",
]

_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# kube-scheduler's e2e duration buckets: exponential from 1ms, factor 2,
# 15 buckets (1ms .. ~16s).
DEFAULT_BUCKETS: Tuple[float, ...] = tuple(0.001 * 2 ** i for i in range(15))


def _escape_label_value(value: str) -> str:
    return (
        value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _format_value(value: float) -> str:
    if value == math.inf:
        return "+Inf"
    if value == -math.inf:
        return "-Inf"
    if isinstance(value, float) and value.is_integer() and abs(value) < 2 ** 53:
        return str(int(value))
    return repr(float(value))


def _format_labels(
    labelnames: Sequence[str], labelvalues: Sequence[str], extra: str = ""
) -> str:
    """Render `{a="x",b="y"}` (or "" when there are no labels)."""
    parts = [
        '%s="%s"' % (n, _escape_label_value(v))
        for n, v in zip(labelnames, labelvalues)
    ]
    if extra:
        parts.append(extra)
    if not parts:
        return ""
    return "{" + ",".join(parts) + "}"


class _Metric:
    """Base: one metric family; children keyed by label-value tuples."""

    kind = "untyped"

    def __init__(
        self,
        name: str,
        help: str,
        labelnames: Sequence[str] = (),
        lock: Optional[threading.RLock] = None,
    ) -> None:
        if not _METRIC_NAME_RE.match(name):
            raise ValueError(f"invalid metric name: {name!r}")
        for ln in labelnames:
            if not _LABEL_NAME_RE.match(ln) or ln.startswith("__"):
                raise ValueError(f"invalid label name: {ln!r}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = lock if lock is not None else threading.RLock()
        self._children: Dict[Tuple[str, ...], object] = {}
        if not self.labelnames:
            # Label-less metrics expose a sample immediately (a counter that
            # has never fired still renders as `name 0`).
            self._child(())

    # -- child management ---------------------------------------------------

    def _new_child(self) -> object:
        raise NotImplementedError

    def _child(self, key: Tuple[str, ...]) -> object:
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = self._new_child()
        return child

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(labels)} != "
                f"declared {sorted(self.labelnames)}"
            )
        return tuple(str(labels[n]) for n in self.labelnames)

    # -- rendering ----------------------------------------------------------

    def _sample_lines(self) -> Iterable[str]:
        raise NotImplementedError

    def render(self) -> str:
        with self._lock:
            lines = [
                f"# HELP {self.name} {_escape_help(self.help)}",
                f"# TYPE {self.name} {self.kind}",
            ]
            lines.extend(self._sample_lines())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        raise NotImplementedError


class Counter(_Metric):
    kind = "counter"

    def _new_child(self) -> list:
        return [0.0]

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        if amount < 0:
            raise ValueError(f"{self.name}: counters cannot decrease")
        key = self._key(labels)
        with self._lock:
            self._child(key)[0] += amount

    def value(self, **labels: str) -> float:
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            return child[0] if child is not None else 0.0

    def _sample_lines(self) -> Iterable[str]:
        for key in sorted(self._children):
            yield "%s%s %s" % (
                self.name,
                _format_labels(self.labelnames, key),
                _format_value(self._children[key][0]),
            )

    def snapshot(self) -> dict:
        with self._lock:
            samples = [
                {"labels": dict(zip(self.labelnames, key)), "value": val[0]}
                for key, val in sorted(self._children.items())
            ]
        return {"type": self.kind, "help": self.help, "samples": samples}


class Gauge(Counter):
    kind = "gauge"

    def set(self, value: float, **labels: str) -> None:
        key = self._key(labels)
        with self._lock:
            self._child(key)[0] = float(value)

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = self._key(labels)
        with self._lock:
            self._child(key)[0] += amount

    def dec(self, amount: float = 1.0, **labels: str) -> None:
        self.inc(-amount, **labels)


class _HistChild:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int) -> None:
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative) counts
        self.sum = 0.0
        self.count = 0


class Histogram(_Metric):
    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str,
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
        lock: Optional[threading.RLock] = None,
    ) -> None:
        ordered = sorted(float(b) for b in buckets)
        if not ordered:
            raise ValueError(f"{name}: histogram needs at least one bucket")
        if ordered[-1] != math.inf:
            ordered.append(math.inf)
        self.buckets = tuple(ordered)
        super().__init__(name, help, labelnames, lock=lock)

    def _new_child(self) -> _HistChild:
        return _HistChild(len(self.buckets))

    def observe(self, value: float, **labels: str) -> None:
        key = self._key(labels)
        value = float(value)
        # leftmost bucket whose upper bound contains the value
        idx = len(self.buckets) - 1
        for i, ub in enumerate(self.buckets):
            if value <= ub:
                idx = i
                break
        with self._lock:
            child = self._child(key)
            child.counts[idx] += 1
            child.sum += value
            child.count += 1

    def child_state(self, **labels: str) -> Tuple[List[int], float, int]:
        """(cumulative bucket counts, sum, count) — test/snapshot helper."""
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                return [0] * len(self.buckets), 0.0, 0
            cum, running = [], 0
            for c in child.counts:
                running += c
                cum.append(running)
            return cum, child.sum, child.count

    def _sample_lines(self) -> Iterable[str]:
        for key in sorted(self._children):
            child = self._children[key]
            running = 0
            for ub, c in zip(self.buckets, child.counts):
                running += c
                le = _format_labels(
                    self.labelnames, key, extra='le="%s"' % _format_value(ub)
                )
                yield "%s_bucket%s %d" % (self.name, le, running)
            plain = _format_labels(self.labelnames, key)
            yield "%s_sum%s %s" % (self.name, plain, _format_value(child.sum))
            yield "%s_count%s %d" % (self.name, plain, child.count)

    def snapshot(self) -> dict:
        with self._lock:
            samples = []
            for key, child in sorted(self._children.items()):
                running, cum = 0, []
                for c in child.counts:
                    running += c
                    cum.append(running)
                samples.append(
                    {
                        "labels": dict(zip(self.labelnames, key)),
                        "buckets": {
                            _format_value(ub): n
                            for ub, n in zip(self.buckets, cum)
                        },
                        "sum": child.sum,
                        "count": child.count,
                    }
                )
        return {"type": self.kind, "help": self.help, "samples": samples}


class MetricsRegistry:
    """Get-or-create registry; re-registering a name returns the existing
    metric (and raises if the kind or label set changed)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._metrics: Dict[str, _Metric] = {}

    def _register(self, cls, name, help, labelnames, **kwargs) -> _Metric:
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if type(existing) is not cls or existing.labelnames != tuple(
                    labelnames
                ):
                    raise ValueError(
                        f"metric {name!r} already registered with a "
                        f"different kind or label set"
                    )
                return existing
            metric = cls(name, help, labelnames, lock=self._lock, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(
        self, name: str, help: str = "", labelnames: Sequence[str] = ()
    ) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(
        self, name: str, help: str = "", labelnames: Sequence[str] = ()
    ) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(
        self,
        name: str,
        help: str = "",
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> Histogram:
        return self._register(
            Histogram, name, help, labelnames, buckets=buckets
        )

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4. The payload always ends
        with exactly one trailing newline (each family render is
        newline-terminated; an empty registry still yields "\\n") — the
        EOF-safety the text format requires of scrapable exports."""
        with self._lock:
            families = [self._metrics[n] for n in sorted(self._metrics)]
        out = "".join(f.render() for f in families)
        if not out.endswith("\n"):
            out += "\n"
        return out

    def snapshot(self, include_empty: bool = False) -> Dict[str, dict]:
        """JSON-friendly dump of every metric family."""
        with self._lock:
            families = sorted(self._metrics.items())
        out = {}
        for name, metric in families:
            snap = metric.snapshot()
            if not include_empty and not any(
                s.get("value") or s.get("count") for s in snap["samples"]
            ):
                continue
            out[name] = snap
        return out

    def reset(self) -> None:
        """Zero all samples, keep registrations (test isolation helper)."""
        with self._lock:
            for metric in self._metrics.values():
                metric._children.clear()
                if not metric.labelnames:
                    metric._child(())


REGISTRY = MetricsRegistry()

# ---------------------------------------------------------------------------
# Well-known scheduler metrics (kube-scheduler name parity where an analogue
# exists — see the parity table in the module docstring).
# ---------------------------------------------------------------------------

E2E_SCHEDULING = REGISTRY.histogram(
    "osim_e2e_scheduling_duration_seconds",
    "End-to-end simulate() duration (root span), seconds.",
)
ENCODE_DURATION = REGISTRY.histogram(
    "osim_encode_duration_seconds",
    "Pod/cluster -> device-array encode duration, seconds.",
)
SPAN_DURATION = REGISTRY.histogram(
    "osim_span_duration_seconds",
    "Duration of every tracing span, by span name, seconds.",
    labelnames=("span",),
)
SCHEDULING_ATTEMPTS = REGISTRY.counter(
    "osim_pod_scheduling_attempts_total",
    "Pods entering a scheduling pass (preemption retries count again).",
)
SCHEDULE_RESULT = REGISTRY.counter(
    "osim_schedule_result_total",
    "Final scheduling outcomes: scheduled, unscheduled, or preempted "
    "(victims evicted by a committed preemption).",
    labelnames=("result",),
)
COMPILE_CACHE = REGISTRY.counter(
    "osim_compile_cache_total",
    "XLA compile requests (backend_compile) and those the persistent "
    "cache served (persistent_hit).",
    labelnames=("event",),
)
EXPAND_CACHE = REGISTRY.counter(
    "osim_expand_cache_total",
    "Workload expand-cache lookups inside simulate().",
    labelnames=("event",),
)
FILTER_FAILURE = REGISTRY.counter(
    "osim_filter_failure_total",
    "Per-(pod,node) filter rejections for pods that failed to schedule, "
    "by kube failure-reason string.",
    labelnames=("reason",),
)
FAST_PATH = REGISTRY.counter(
    "osim_fast_path_total",
    "schedule_batch_fast strategy selections, by path.",
    labelnames=("path",),
)
PREEMPTION_ATTEMPTS = REGISTRY.counter(
    "osim_preemption_attempts_total",
    "Preemption attempts for unscheduled pods, by outcome.",
    labelnames=("outcome",),
)
VICTIM_SEARCH = REGISTRY.counter(
    "osim_victim_search_total",
    "DefaultPreemption victim searches, by path: device (every filter "
    "evaluated per node on the device), whole_cluster (out-of-tree filters: "
    "each node's lane runs every filter over the whole cluster), extenders "
    "(every candidate's victims go to preemption-supporting extenders).",
    labelnames=("path",),
)
PREEMPTION_RETRY = REGISTRY.counter(
    "osim_preemption_retry_total",
    "Preemptors rescheduled after their eviction, by path: device (the "
    "eviction and one scheduling step as one device program), batch (the "
    "eviction, then a one-pod batch through the commit engine: an extender "
    "is interested in the pod, or its profile is missing).",
    labelnames=("path",),
)
BOUND_POD_COPIES = REGISTRY.counter(
    "osim_bound_pod_copies_total",
    "Pre-bound cluster pods copied because preemption evicted them, "
    "rolled-back evictions included (simulate borrows the caller's bound "
    "pods and copies only these).",
)
EXTENDER_REQUESTS = REGISTRY.counter(
    "osim_extender_requests_total",
    "HTTP scheduler-extender round trips, by verb and outcome.",
    labelnames=("verb", "outcome"),
)
EXTENDER_DURATION = REGISTRY.histogram(
    "osim_extender_duration_seconds",
    "HTTP scheduler-extender round-trip duration, seconds, by verb and "
    "outcome (ok / error / circuit_open) — error and fail-fast paths cost "
    "real wall time too.",
    labelnames=("verb", "outcome"),
)
EXTENDER_INFLIGHT = REGISTRY.gauge(
    "osim_extender_inflight",
    "Per-pod extender HTTP chains currently in flight in the wave engine.",
)
EXTENDER_WAVE_SIZE = REGISTRY.histogram(
    "osim_extender_wave_size",
    "Pods per dispatched extender wave (real lanes, excluding bucket pad).",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
EXTENDER_WAVE_RESPILL = REGISTRY.counter(
    "osim_extender_wave_respill_total",
    "Wave pods respilled to the next wave after the commit-time feasibility "
    "recheck saw a mask changed by earlier commits.",
)
HTTP_REQUESTS = REGISTRY.counter(
    "osim_http_requests_total",
    "Simulator HTTP server responses, by path and status code.",
    labelnames=("path", "code"),
)
CAPACITY_PROBES = REGISTRY.counter(
    "osim_capacity_probe_total",
    "Capacity-planner simulate() probes (bracket + bisection).",
)
CAPACITY_NODES_ADDED = REGISTRY.gauge(
    "osim_capacity_plan_nodes_added",
    "Nodes added by the most recent capacity plan.",
)
APPLY_RUNS = REGISTRY.counter(
    "osim_apply_total",
    "simon-apply runs, by outcome.",
    labelnames=("outcome",),
)
RETRY_ATTEMPTS = REGISTRY.counter(
    "osim_retry_attempts_total",
    "Retries performed by resilience.RetryPolicy, by call target.",
    labelnames=("target",),
)
CIRCUIT_STATE = REGISTRY.gauge(
    "osim_circuit_state",
    "Per-endpoint circuit-breaker state (0=closed, 1=open, 2=half-open).",
    labelnames=("endpoint",),
)
EXTENDER_SKIPPED = REGISTRY.counter(
    "osim_extender_skipped_total",
    "Ignorable extenders skipped after an error or an open circuit breaker.",
    labelnames=("endpoint",),
)
SNAPSHOT_STALE = REGISTRY.counter(
    "osim_snapshot_stale_total",
    "Server requests served from a stale cluster snapshot after a refresh "
    "failure.",
)
FAULTS_INJECTED = REGISTRY.counter(
    "osim_faults_injected_total",
    "Faults injected by the resilience fault-injection harness.",
    labelnames=("target", "kind"),
)
SANITIZER_VIOLATIONS = REGISTRY.counter(
    "osim_sanitizer_violations_total",
    "checkify violations (NaN/OOB/div) caught by OSIM_SANITIZE=1 runs, by "
    "jit entry point.",
    labelnames=("entry",),
)
WATCHDOG_FIRED = REGISTRY.counter(
    "osim_watchdog_fired_total",
    "Watchdog deadlines that fired on a guarded call (backend acquisition, "
    "compile/execute), by stage.",
    labelnames=("stage",),
)
RUN_RESUMED = REGISTRY.counter(
    "osim_run_resumed_total",
    "Runs resumed from a journal (apply/bench --resume).",
)
PLAN_CHUNKS = REGISTRY.counter(
    "osim_plan_chunks_total",
    "Commit chunks executed by the chunked scenario driver "
    "(OSIM_COMMIT_CHUNK > 0).",
)
CHECKPOINT_BYTES = REGISTRY.counter(
    "osim_checkpoint_bytes",
    "Bytes atomically persisted in mid-plan carry snapshots.",
)
RESUME_CHUNKS_SKIPPED = REGISTRY.counter(
    "osim_resume_chunks_skipped_total",
    "Commit chunks a resumed plan restored from a snapshot instead of "
    "re-executing.",
)
COMMIT_ROUNDS = REGISTRY.histogram(
    "osim_commit_rounds",
    "Rounds to fixpoint per wave in the conflict-parallel wave commit "
    "engine (ops/wave.py). 2 is the floor: one round to decide, one to "
    "confirm; a wave that exhausts its round budget records the budget "
    "it burned before the serial fallback.",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
)
WAVE_CONFLICTS = REGISTRY.counter(
    "osim_wave_conflicts_total",
    "Pod decisions revised between wave rounds (choice changes observed "
    "in rounds >= 2): each count is one pod whose tentative placement was "
    "disturbed by an earlier pod's commit and re-decided.",
)
WAVE_FALLBACKS = REGISTRY.counter(
    "osim_wave_fallbacks_total",
    "Waves re-run through the serial chunked kernel after failing to "
    "reach the fixpoint within the round budget (OSIM_WAVE_ROUNDS), by "
    "reason. The fallback is the oracle path: results stay byte-identical.",
    labelnames=("reason",),
)
DEVICE_LOST = REGISTRY.counter(
    "osim_device_lost_total",
    "Device-loss events seen by the chunked commit driver; handled=yes "
    "means the carry was restored from the last good snapshot and the plan "
    "continued.",
    labelnames=("handled",),
)
JOURNAL_EVENTS = REGISTRY.counter(
    "osim_journal_events_total",
    "Records durably committed to run journals, by event type.",
    labelnames=("event",),
)
ADMISSION_QUEUE_DEPTH = REGISTRY.gauge(
    "osim_admission_queue_depth",
    "Requests currently waiting in the server admission queue.",
)
REQUESTS_SHED = REGISTRY.counter(
    "osim_requests_shed_total",
    "Requests shed by admission control with a definite response "
    "(429/503 + Retry-After), by reason.",
    labelnames=("reason",),
)
REQUESTS_DROPPED = REGISTRY.counter(
    "osim_requests_dropped_total",
    "Requests dropped without a simulated or shed response (scheduler "
    "worker death) — any nonzero value is a failure, not degradation.",
)
COALESCED_BATCH = REGISTRY.histogram(
    "osim_coalesced_batch_size",
    "Requests answered by one coalesced simulate pass: mode=fanout counts "
    "identical-body waiters fanned out from one result (per coalesce key), "
    "mode=scenarios counts distinct-scenario bodies merged into one batched "
    "device call.",
    labelnames=("mode",),
    buckets=(1, 2, 4, 8, 16, 32, 64),
)
SCENARIOS_PER_CALL = REGISTRY.histogram(
    "osim_scenarios_per_call",
    "Scenarios evaluated by one batched (vmapped) device call; the sample "
    "count is the number of batched calls issued.",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
BATCH_SWEEP_DURATION = REGISTRY.histogram(
    "osim_batch_sweep_duration_seconds",
    "Wall-clock duration of one batched multi-scenario sweep call "
    "(capacity ladder/refinement or coalesced serving batch), seconds.",
)
REQUEST_LATENCY = REGISTRY.histogram(
    "osim_server_request_duration_seconds",
    "Admission-to-response latency of POST simulation requests, seconds.",
)
RESIDENT_DRIFT_REPAIRS = REGISTRY.counter(
    "osim_resident_drift_repairs_total",
    "Anti-entropy repairs (full re-encode) of the resident cluster state, by "
    "trigger: digest_mismatch (drift detector), torn_delta (partial apply), "
    "delta_budget (too many deltas since last full encode), disabled "
    "(OSIM_RESIDENT=0 forced degrade).",
    labelnames=("reason",),
)
RESIDENT_DELTAS = REGISTRY.counter(
    "osim_resident_deltas_total",
    "Deltas applied to the resident cluster state without a full re-encode, "
    "by kind (pod_usage = bind/unbind changed a node's free planes, "
    "node_row = a node object changed, node_added).",
    labelnames=("kind",),
)
RESIDENT_FALLBACKS = REGISTRY.counter(
    "osim_resident_fallbacks_total",
    "Requests or syncs that declined the resident fast path and re-encoded "
    "from scratch for a structural reason (node_removed, node_order, "
    "bucket_overflow, shape_growth, not_covering, disabled).",
    labelnames=("reason",),
)
RESIDENT_VERIFICATIONS = REGISTRY.counter(
    "osim_resident_verifications_total",
    "Drift-detector digest cross-checks of the resident state against a full "
    "re-encode, by outcome (ok | mismatch).",
    labelnames=("outcome",),
)
RESIDENT_EPOCH = REGISTRY.gauge(
    "osim_resident_epoch",
    "Current generation of the resident cluster state; bumps on every delta "
    "apply and every repair. Globally monotonic across re-serves.",
)
ADMISSION_FENCE = REGISTRY.counter(
    "osim_admission_fence_total",
    "Generation-fence decisions at admission dequeue: current = ticket ran "
    "against the epoch it was submitted under, rekeyed = the resident epoch "
    "moved between submit and dequeue so the ticket was re-keyed to prevent "
    "cross-generation coalescing.",
    labelnames=("outcome",),
)
LOOP_ITERATION = REGISTRY.histogram(
    "osim_loop_iteration_seconds",
    "Wall-clock duration of one continuous-batching scheduler-loop "
    "iteration (pack assembly + the device call + fan-out); the EWMA of "
    "this feeds Retry-After hints.",
)
PACK_LATENCY = REGISTRY.histogram(
    "osim_pack_latency_seconds",
    "Per-ticket time between admission and the moment its pack was taken "
    "by the scheduler loop — the queueing cost of continuous batching, "
    "excluding the device call itself.",
)
LANE_OCCUPANCY = REGISTRY.histogram(
    "osim_lane_occupancy_ratio",
    "Real scenario lanes over padded lanes (s_real / s_pad) per batched "
    "device call — how full the SCENARIO_BUCKET-padded shape ran.",
    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
)
LOOP_FALLBACKS = REGISTRY.counter(
    "osim_loop_fallbacks_total",
    "Requests served per-request on the handler thread because the "
    "scheduler loop thread was not alive (degradation ladder, "
    "docs/serving.md) — correctness is preserved, batching is lost.",
)
JOBS = REGISTRY.counter(
    "osim_jobs_total",
    "Async jobs (POST /v1/jobs), by terminal outcome "
    "(completed | failed | rejected).",
    labelnames=("outcome",),
)
NODE_BUCKET = REGISTRY.gauge(
    "osim_node_bucket",
    "Node-axis ladder rung (padded node count, ops.encode.node_bucket) of "
    "the most recent encode or capacity-sweep device call — the shape the "
    "jit family compiled for.",
)
ENCODE_STAMPED_ROWS = REGISTRY.counter(
    "osim_encode_stamped_rows_total",
    "Node rows materialized by the template-stamping encode fast path (row "
    "broadcast of a previously-encoded identical node spec plus per-row "
    "name fixups) instead of the per-node Python encode loop.",
)
HBM_BYTES_PER_DEVICE = REGISTRY.gauge(
    "osim_hbm_bytes_per_device",
    "Bytes of cluster-state shards resident on each device after the most "
    "recent sharded placement (parallel.mesh.hbm_bytes_per_device) — under "
    "the 2-D (scenarios, nodes) mesh this stays ~1/node_devices of the "
    "replicated node-table footprint.",
    labelnames=("device",),
)

# Span names that map onto a dedicated kube-parity histogram; everything
# else lands only in osim_span_duration_seconds{span=...}.
_SPAN_HISTOGRAMS: Dict[str, Histogram] = {
    "simulate": E2E_SCHEDULING,
    "encode": ENCODE_DURATION,
}


def observe_span(name: str, seconds: float) -> None:
    """Feed one finished tracing span into the histograms.

    Called from tracing.span()'s finally block for *every* span, so the
    import direction is tracing -> metrics (metrics must never import
    tracing).
    """
    dedicated = _SPAN_HISTOGRAMS.get(name)
    if dedicated is not None:
        dedicated.observe(seconds)
    SPAN_DURATION.observe(seconds, span=name)
