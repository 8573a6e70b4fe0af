"""Watchdogs: hard deadlines around the calls that can hang.

A device call that blocks forever leaves a silent process and loses every
completed trial. The host-side watchdog runs the blocking call in a worker
thread, polls a monotonic clock, and when the deadline passes raises
`DeadlineExceeded` in the *caller*, so the run fails loudly, with the
stage named, instead of hanging.

Two deadlines, both env-tunable (see docs/durability.md):

    OSIM_BACKEND_DEADLINE_S  (default 90)  backend acquisition / first
                                           device contact
    OSIM_CALL_DEADLINE_S     (default 0)   any guarded compile/execute
                                           call; 0 disables

`acquire_backend` probes the platform that ``JAX_PLATFORMS`` (or JAX's
default) names under the backend deadline. A probe that fails or times
out is journaled as `backend_error` and raised as `BackendUnavailable`,
naming the platform: no run ever continues on another platform than the
one it asked for.

Caveat shared by every host-side watchdog: an abandoned worker thread may
still hold the GIL-released blocking call (XLA compile, device RPC). We
cannot kill it — we *can* stop waiting and record the timeout durably.
The daemon flag keeps the zombie from blocking interpreter exit.

Tests inject `clock`/`poll_s` (and a fake probe) so deadline behavior is
provable without sleeping — same idiom as resilience/policy.py.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..resilience import faults
from ..utils import metrics
from ..utils.platform import enable_compilation_cache
from ..utils.tracing import log, span

DEFAULT_BACKEND_DEADLINE_S = 90.0


class DeadlineExceeded(Exception):
    """A guarded call outlived its deadline. The worker may still be
    running (blocking native code is unkillable from the host); the caller
    must treat the backend/call as lost and fail."""

    def __init__(self, stage: str, deadline_s: float) -> None:
        super().__init__(f"{stage} exceeded {deadline_s:g}s deadline")
        self.stage = stage
        self.deadline_s = deadline_s


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        log.warning("%s=%r is not a number; using %g", name, raw, default)
        return default


def backend_deadline_s() -> float:
    return _env_float("OSIM_BACKEND_DEADLINE_S", DEFAULT_BACKEND_DEADLINE_S)


def call_deadline_s() -> float:
    """Deadline for guarded compile/execute calls; 0 = watchdog off."""
    return _env_float("OSIM_CALL_DEADLINE_S", 0.0)


def guarded_call(
    stage: str,
    fn: Callable[[], Any],
    deadline_s: float,
    *,
    clock: Callable[[], float] = time.monotonic,
    poll_s: float = 0.25,
    journal: Any = None,
) -> Any:
    """Run `fn()` in a watchdog-guarded worker; raise DeadlineExceeded if it
    doesn't finish within `deadline_s` (<=0 runs `fn` inline, unguarded).

    The heartbeat is the poll loop itself: the host wakes every `poll_s`,
    re-reads the clock, and decides liveness — so a wedged native call
    can't take the supervising thread down with it."""
    if deadline_s <= 0:
        return fn()

    result: List[Any] = []
    error: List[BaseException] = []
    done = threading.Event()

    def _worker() -> None:
        try:
            result.append(fn())
        except BaseException as e:  # noqa: B036 - must forward KeyboardInterrupt etc.
            error.append(e)
        finally:
            done.set()

    with span("watchdog", stage=stage, deadline_s=deadline_s):
        t = threading.Thread(target=_worker, name=f"osim-guarded-{stage}", daemon=True)
        start = clock()
        t.start()
        while not done.is_set():
            remaining = deadline_s - (clock() - start)
            if remaining <= 0 and not done.is_set():
                metrics.WATCHDOG_FIRED.inc(stage=stage)
                log.error("watchdog: %s exceeded %gs deadline", stage, deadline_s)
                if journal is not None:
                    journal.append("watchdog", stage=stage, deadline_s=deadline_s)
                try:
                    # flight recorder (utils/flightrec.py): dump the
                    # recent-span/metric/journal ring next to the journal —
                    # the wedge evidence a post-mortem needs, captured at
                    # the moment of the fire, never able to worsen it
                    from ..utils import flightrec

                    flightrec.dump(
                        "watchdog",
                        run_dir=getattr(journal, "run_dir", None),
                        error=f"{stage} exceeded {deadline_s:g}s deadline",
                    )
                except Exception:
                    pass
                raise DeadlineExceeded(stage, deadline_s)
            done.wait(min(poll_s, max(remaining, 0.001)))
    if error:
        raise error[0]
    return result[0]


# ---------------------------------------------------------------------------
# Backend acquisition.
# ---------------------------------------------------------------------------

def requested_platform() -> str:
    """The platform this process asked for: ``JAX_PLATFORMS`` as JAX read
    it, or "(jax default)"."""
    import jax

    return jax.config.jax_platforms or "(jax default)"


class BackendUnavailable(RuntimeError):
    """The requested platform failed to initialize or answer in time."""

    def __init__(self, platform: str, error: BaseException) -> None:
        super().__init__(
            f"backend {platform!r} unavailable: "
            f"{type(error).__name__}: {error}"
        )
        self.platform = platform


def _default_probe() -> str:
    """First device contact: touch a device on the requested platform and
    return its name. This is the fault-injection point for backend hangs
    (target=backend, op=acquire)."""
    rule = faults.maybe_inject("backend", "acquire")
    if rule is not None:
        faults.apply_backend_fault(rule)
    import jax
    import jax.numpy as jnp

    jnp.zeros(4).block_until_ready()
    return str(jax.devices()[0])


def warmup_requested() -> bool:
    """OSIM_WARMUP=1 opts runs into the pre-acquisition warmup phase."""
    return os.environ.get("OSIM_WARMUP", "").strip().lower() in (
        "1", "true", "yes", "on",
    )


def _warm_phase(deadline_s: float, journal: Any, info: Dict[str, Any]) -> None:
    """Best-effort AOT warmup right after first device contact: bank every
    audited jit entry + the sweep rehearsal into the persistent compile
    cache while nothing is being timed, and journal the outcome so a warm
    cache is recorded provenance, not luck. A timeout or error is journaled
    and swallowed — the run proceeds cold rather than dying here (the
    watchdog still guards every later compile)."""
    from ..engine.warmup import run_warmup

    try:
        report = guarded_call(
            "warmup", run_warmup, deadline_s, journal=journal
        )
    except Exception as e:
        log.warning("warmup phase failed (%s); continuing cold", e)
        info["warmup"] = {"ok": False, "error": str(e)}
        if journal is not None:
            journal.append("warmup_error", error=str(e))
        return
    info["warmup"] = {
        "ok": report.ok,
        "entries": len(report.entries),
        "seconds": round(report.seconds, 3),
        "cold_compiles": report.cold_compiles,
        "cache_dir": report.cache_dir,
    }
    if journal is not None:
        journal.append(
            "warmup",
            ok=report.ok,
            entries=len(report.entries),
            seconds=round(report.seconds, 3),
            cold_compiles=report.cold_compiles,
            cache_dir=report.cache_dir,
        )


def acquire_backend(
    deadline_s: Optional[float] = None,
    journal: Any = None,
    *,
    probe: Optional[Callable[[], str]] = None,
    clock: Callable[[], float] = time.monotonic,
    poll_s: float = 0.25,
    warmup: Optional[bool] = None,
) -> Dict[str, Any]:
    """Acquire the requested JAX backend under a hard deadline.

    Configures the persistent compile cache first: JAX sets it up at the
    first compile (the probe's device touch), and a cache configured after
    that never serves hits in this process.

    `warmup` (default: OSIM_WARMUP env) runs the AOT warmup phase
    (engine/warmup.run_warmup) right after first device contact, under its
    own watchdog deadline, journaling a `warmup` event — so downstream
    capture windows open against a provably banked compile cache.

    Returns ``{"device": ...}`` (plus ``{"warmup": ...}`` when the phase
    ran). Raises BackendUnavailable, after journaling `backend_error`,
    when the probe fails or outlives the deadline."""
    if deadline_s is None:
        deadline_s = backend_deadline_s()
    if warmup is None:
        warmup = warmup_requested()
    enable_compilation_cache()
    info: Dict[str, Any] = {}
    try:
        device = guarded_call(
            "backend-acquire", probe or _default_probe, deadline_s,
            clock=clock, poll_s=poll_s, journal=journal,
        )
    except Exception as e:  # DeadlineExceeded or a real probe error
        platform = requested_platform()
        log.error("backend %r unavailable: %s", platform, e)
        if journal is not None:
            journal.append("backend_error", platform=platform, error=str(e))
        raise BackendUnavailable(platform, e) from e
    info["device"] = device
    if journal is not None:
        journal.append("backend", device=device)
    if warmup:
        _warm_phase(deadline_s, journal, info)
    return info
