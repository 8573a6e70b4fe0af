"""Timing spans + logging: the observability subsystem.

Parity targets:
  - `utils.LogIfLong` / the 1 s slow-Simulate trace threshold
    (`/root/reference/pkg/simulator/core.go:72-73`, `simulator.go:511-521`):
    here every root span that exceeds OSIM_SLOW_TRACE (default 1.0 s) logs its
    whole subtree at WARNING.
  - the `LogLevel` env handling (`cmd/simon/simon.go:46-66`): init_logging()
    maps LogLevel ∈ {debug, info, warn, error} onto the stdlib logger.
  - per-pod progress output (`simulator.go:311-321`): the engine emits a
    per-batch progress line at DEBUG (per-pod printing would serialize the
    batched device path — the batch line carries the same information).
  - pprof on the server (`pkg/server/server.go:152`): the /debug/timings
    endpoint serves recent span trees as JSON.

Spans nest via a thread-local stack; finished roots are kept in a bounded
ring buffer for the server endpoint (size: OSIM_SPAN_HISTORY, default 64).
Every finished span also feeds the metrics histograms (utils/metrics.py),
and when OSIM_TRACE_FILE is set, finished root trees are exported as Chrome
trace events (load the file in Perfetto / chrome://tracing). A span costs
10–20 µs of host time — safe to leave in hot host paths (device time is
measured as host wall time around blocking calls, which is what a user can
act on).

Device-trace twin: every span also opens `jax.profiler.TraceAnnotation(name)`
for its duration, so a `jax.profiler` capture (`simon profile -- <cmd>`, the
benchmark's `--trace 1`) shows each span on the calling thread's host line,
on the same clock as the device's operations: an idle stretch of the device
is named by the innermost span open over it. Only the name goes to the
profiler, never the meta (JAX would fold it into the event name and split
one span into many labels); with no capture running it costs ~0.5 µs.

Host syncs: `fetch(tree, site)` is the one device->host read of the
simulate path — `jax.device_get` inside a `host-sync` span with `site` in
its meta — so a call's `host-sync` count is its host round trips and their
time is what the host waited on the device.

Cross-thread propagation: every span carries explicit trace/span IDs. A
`TraceContext` snapshot of the active span (`current_context()`) can cross a
queue or a thread-pool boundary and be re-activated on the far side with
`activate(ctx)` — the next root span opened there becomes a *child by ID*
of the captured span, so one request's work stays one connected trace even
though each thread keeps its own span stack. The wire form is the W3C
`traceparent` header (`TraceContext.to_traceparent` /
`TraceContext.from_traceparent`); packed/coalesced lanes that share one
execution record their relationship as span *links* (`Span.add_link`)
instead of a parent edge. All IDs ride the Chrome-trace export as event
args, so an exported file is reconnectable offline.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import re
import threading
import time
from contextlib import contextmanager
from typing import List, Optional

from . import metrics

log = logging.getLogger("osim")

SLOW_TRACE_S = float(os.environ.get("OSIM_SLOW_TRACE", "1.0"))
_HISTORY_DEFAULT = 64


def _history_max() -> int:
    """Ring-buffer size for /debug/timings; OSIM_SPAN_HISTORY overrides the
    default of 64 so long bench runs can keep full histories. Read per root
    close (cheap) so tests and long-lived servers can change it on the fly."""
    raw = os.environ.get("OSIM_SPAN_HISTORY", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            log.warning("ignoring non-integer OSIM_SPAN_HISTORY=%r", raw)
    return _HISTORY_DEFAULT


def _new_trace_id() -> str:
    return os.urandom(16).hex()


def _new_span_id() -> str:
    return os.urandom(8).hex()


class TraceContext:
    """Immutable (trace_id, span_id) snapshot — the part of a span's
    identity that can cross a thread, a queue, or a process boundary.
    Captured at enqueue (`current_context()`), re-activated at dequeue
    (`activate(ctx)`), and serialized on the wire as a W3C traceparent."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, TraceContext)
            and self.trace_id == other.trace_id
            and self.span_id == other.span_id
        )

    def __hash__(self) -> int:
        return hash((self.trace_id, self.span_id))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"TraceContext({self.trace_id}, {self.span_id})"

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    # -- W3C traceparent (version 00, sampled flag always set) --------------

    _TRACEPARENT_RE = re.compile(
        r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
    )

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, header: Optional[str]) -> Optional["TraceContext"]:
        """Parse a traceparent header; malformed/absent/all-zero IDs return
        None (the request simply starts a fresh trace)."""
        if not header:
            return None
        m = cls._TRACEPARENT_RE.match(header.strip().lower())
        if m is None:
            return None
        version, trace_id, span_id = m.group(1), m.group(2), m.group(3)
        if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
            return None
        return cls(trace_id, span_id)


class Span:
    __slots__ = (
        "name", "start", "end", "children", "meta",
        "trace_id", "span_id", "parent_id", "links",
    )

    def __init__(self, name: str) -> None:
        self.name = name
        self.start = time.time()
        self.end: Optional[float] = None
        self.children: List["Span"] = []
        self.meta: dict = {}
        self.trace_id: str = ""
        self.span_id: str = _new_span_id()
        self.parent_id: Optional[str] = None
        self.links: List[dict] = []

    @property
    def duration(self) -> float:
        return (self.end or time.time()) - self.start

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    def add_link(self, ctx) -> None:
        """Record a non-parent relationship to another span (a packed lane
        pointing at its pack's execution span and vice versa). Accepts a
        TraceContext or another Span."""
        self.links.append(
            {"trace_id": ctx.trace_id, "span_id": ctx.span_id}
        )

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "start": round(self.start, 6),
            "duration_s": round(self.duration, 4),
            "trace_id": self.trace_id,
            "span_id": self.span_id,
        }
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.links:
            d["links"] = [dict(ln) for ln in self.links]
        if self.meta:
            d["meta"] = self.meta
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def render(self, indent: int = 0) -> str:
        lines = [f"{'  ' * indent}{self.name}: {self.duration * 1e3:.1f} ms"
                 + (f" {self.meta}" if self.meta else "")]
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)


class _Tracer(threading.local):
    def __init__(self) -> None:
        self.stack: List[Span] = []
        # remote parent context re-activated on this thread (activate());
        # the next ROOT span opened here becomes its child by ID
        self.remote: Optional[TraceContext] = None


_tracer = _Tracer()
_history: List[dict] = []
_history_lock = threading.Lock()


@functools.cache
def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported on first use so that importing
    this module does not import jax."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


@contextmanager
def span(name: str, **meta):
    """Time a phase. Nested spans build a tree; every finished span observes
    into the metrics histograms, and when a ROOT span closes it is recorded
    for /debug/timings, exported to OSIM_TRACE_FILE (if set), logged at
    DEBUG, and escalated to WARNING with its full subtree when slower than
    OSIM_SLOW_TRACE seconds (the LogIfLong analog). For its duration the
    span is also a profiler TraceAnnotation of the same name (no meta)."""
    s = Span(name)
    if meta:
        s.meta.update(meta)
    parent = _tracer.stack[-1] if _tracer.stack else None
    if parent is not None:
        parent.children.append(s)
        s.trace_id = parent.trace_id
        s.parent_id = parent.span_id
    elif _tracer.remote is not None:
        # cross-thread continuation: a local root, but a child by ID of the
        # context captured on the submitting thread
        s.trace_id = _tracer.remote.trace_id
        s.parent_id = _tracer.remote.span_id
    else:
        s.trace_id = _new_trace_id()
    _tracer.stack.append(s)
    try:
        with _trace_annotation()(name):
            yield s
    finally:
        s.end = time.time()
        _tracer.stack.pop()
        metrics.observe_span(s.name, s.end - s.start)
        if parent is None:
            root_dict = s.to_dict()
            with _history_lock:
                _history.append(root_dict)
                del _history[:-_history_max()]
            _record_flight(root_dict)
            _maybe_export_trace(s)
            if s.duration > SLOW_TRACE_S:
                log.warning("slow trace (> %.1fs):\n%s", SLOW_TRACE_S, s.render())
            else:
                log.debug("trace:\n%s", s.render())


def fetch(tree, site: str):
    """`jax.device_get(tree)` inside a `host-sync` span naming `site`: the
    one way the simulate path reads device values on the host. Fetch
    everything one step needs in one call — each call is a round trip."""
    import jax

    with span("host-sync", site=site):
        return jax.device_get(tree)


@contextmanager
def activate(ctx: Optional[TraceContext]):
    """Re-activate a captured TraceContext on the current thread: root spans
    opened inside become children by ID of the captured span. `None` is a
    no-op, so call sites can pass an optional context unconditionally."""
    if ctx is None:
        yield
        return
    prev = _tracer.remote
    _tracer.remote = ctx
    try:
        yield
    finally:
        _tracer.remote = prev


def current_span() -> Optional[Span]:
    """The innermost open span on this thread, if any."""
    return _tracer.stack[-1] if _tracer.stack else None


def current_context() -> Optional[TraceContext]:
    """Snapshot of the active span (or the re-activated remote context when
    no span is open) for crossing a thread/queue boundary; None when this
    thread is not inside any trace."""
    if _tracer.stack:
        return _tracer.stack[-1].context()
    return _tracer.remote


def current_trace_id() -> Optional[str]:
    ctx = current_context()
    return ctx.trace_id if ctx is not None else None


def current_traceparent() -> Optional[str]:
    """The W3C traceparent header for outbound HTTP, or None when the
    calling thread is not inside any trace (never mints a fresh ID — a
    header nobody can correlate is noise)."""
    ctx = current_context()
    return ctx.to_traceparent() if ctx is not None else None


def _record_flight(root_dict: dict) -> None:
    """Feed the finished root into the crash flight recorder (always-on
    bounded ring, utils/flightrec.py). Lazy import: flightrec reads trace
    IDs back through this module, so neither imports the other at top."""
    try:
        from . import flightrec

        flightrec.record_span(root_dict)
    except Exception:  # pragma: no cover - recorder must never break tracing
        pass


def recent_timings() -> List[dict]:
    """Recent root span trees, oldest first (the /debug/timings payload)."""
    with _history_lock:
        return list(_history)


# ---------------------------------------------------------------------------
# Chrome trace-event export (OSIM_TRACE_FILE)
# ---------------------------------------------------------------------------
#
# Each finished root span tree is flattened into "X" (complete) events with
# epoch-microsecond `ts` and `dur`, and the whole accumulated event list is
# rewritten to the file — roots are rare (one per simulate call), so the
# rewrite is cheap and the file is valid JSON after every root, even if the
# process dies mid-run. Epoch microseconds stay below 2^53, so `ts` survives
# the JSON double round trip. Every event carries its span's trace/span/
# parent IDs (and links) as args, so the exported file stays one connected,
# offline-reconnectable tree per request.

_trace_lock = threading.Lock()
_trace_events: List[dict] = []
_TRACE_MAX_EVENTS = 250_000  # default backstop for long-lived servers
_trace_overflow_logged = False


def _trace_max_events() -> int:
    """OSIM_TRACE_MAX_EVENTS overrides the 250k default event cap; read per
    export so long-lived servers can be resized without a restart."""
    raw = os.environ.get("OSIM_TRACE_MAX_EVENTS", "").strip()
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            log.warning("ignoring non-integer OSIM_TRACE_MAX_EVENTS=%r", raw)
    return _TRACE_MAX_EVENTS


def _span_events(s: Span, pid: int, tid: int, out: List[dict]) -> None:
    args = dict(s.meta) if s.meta else {}
    args["trace_id"] = s.trace_id
    args["span_id"] = s.span_id
    if s.parent_id:
        args["parent_id"] = s.parent_id
    if s.links:
        args["links"] = [dict(ln) for ln in s.links]
    ev = {
        "name": s.name,
        "cat": "osim",
        "ph": "X",
        "ts": s.start * 1e6,
        "dur": max(s.duration, 0.0) * 1e6,
        "pid": pid,
        "tid": tid,
        "args": args,
    }
    out.append(ev)
    for c in s.children:
        _span_events(c, pid, tid, out)


def _maybe_export_trace(root: Span) -> None:
    path = os.environ.get("OSIM_TRACE_FILE", "").strip()
    if not path:
        return
    global _trace_overflow_logged
    events: List[dict] = []
    _span_events(root, os.getpid(), threading.get_ident(), events)
    with _trace_lock:
        cap = _trace_max_events()
        _trace_events.extend(events)
        overflow = len(_trace_events) - cap
        if overflow > 0:
            # oldest-first rotation: the newest spans are the ones a live
            # incident needs; the rotated-out prefix is already on disk in
            # the previous rewrite anyway
            del _trace_events[:overflow]
            if not _trace_overflow_logged:
                _trace_overflow_logged = True
                log.warning(
                    "OSIM_TRACE_FILE: event cap %d reached; rotating oldest "
                    "events out (set OSIM_TRACE_MAX_EVENTS to resize)",
                    cap,
                )
        payload = {"traceEvents": list(_trace_events),
                   "displayTimeUnit": "ms"}
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError as exc:
            log.warning("OSIM_TRACE_FILE write failed: %s", exc)


def reset_trace_events() -> None:
    """Drop accumulated trace events (test isolation / manual truncation)."""
    global _trace_overflow_logged
    with _trace_lock:
        _trace_events.clear()
        _trace_overflow_logged = False


def progress(fmt: str, *args) -> None:
    """Per-batch progress line (the reference's per-pod report.Progress,
    simulator.go:311-321, lifted to batch granularity)."""
    log.debug(fmt, *args)


_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warn": logging.WARNING,
    "warning": logging.WARNING,
    "error": logging.ERROR,
}

_FORMAT = "%(asctime)s %(levelname)-7s %(name)s: %(message)s"
_log_handler: Optional[logging.Handler] = None


def init_logging(default: str = "info") -> None:
    """Honor the LogLevel env exactly like cmd/simon/simon.go:46-66 (invalid
    values fall back to the default, case-insensitive).

    Idempotent: `logging.basicConfig` is a no-op once any root handler
    exists (e.g. under pytest, or on a second serve() call), which used to
    silently ignore LogLevel changes. The `osim` logger now owns a single
    dedicated stderr handler whose level tracks LogLevel on every call;
    propagation stays on so root-level capture (pytest caplog) still works.
    """
    level = _LEVELS.get(os.environ.get("LogLevel", default).strip().lower())
    if level is None:
        level = _LEVELS[default]
    global _log_handler
    if _log_handler is None:
        _log_handler = logging.StreamHandler()
        _log_handler.setFormatter(logging.Formatter(_FORMAT))
        log.addHandler(_log_handler)
    _log_handler.setLevel(level)
    log.setLevel(level)
