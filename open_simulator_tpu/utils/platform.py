"""Persistent XLA compile cache and compile-event accounting.

The platform is whatever ``JAX_PLATFORMS`` or JAX's default says; nothing
here picks or changes it.
"""

from __future__ import annotations

import os
from typing import Optional

#: the checkout root (the directory holding the ``open_simulator_tpu``
#: package); the default compile cache lives inside it
CHECKOUT_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compilation_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` exactly when set, else the fixed
    ``<checkout>/.jax_cache`` (git-ignored): a cache directory that moves
    between runs never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() or (
        os.path.join(CHECKOUT_DIR, ".jax_cache")
    )


def enable_compilation_cache() -> Optional[str]:
    """Persist XLA executables across processes (parity concern: the
    reference binary re-simulates a tweaked cluster interactively in seconds,
    apply.go:203-216 — repeat `simon apply` runs must not re-pay 30s+ of
    compilation). Must run before the process's first compile: JAX sets up
    its persistent cache once. Returns the directory, or None when it
    cannot be created (the run then compiles cold)."""
    path = compilation_cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, however fast the compile looked
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


#: jax.monitoring event fired once per compile *request* — it wraps
#: compile_or_get_cached, so it fires whether XLA compiled or the persistent
#: cache served the executable.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: fired exactly once per persistent-cache hit, *inside* the window the
#: duration event wraps. cold compiles = duration events - hit events.
PERSISTENT_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_listener_installed = False


def install_compile_listener() -> bool:
    """Mirror XLA backend compiles into the metrics registry.

    Registers jax.monitoring listeners that bump
    ``osim_compile_cache_total{event="backend_compile"}`` every time a
    compile request reaches XLA and ``{event="persistent_hit"}`` when the
    persistent cache served it (the duration event fires in both cases —
    only in-process jit cache hits skip it). One counter therefore tells
    the whole compile-cache story: ``hit``/``miss`` from the engine's own
    jit lookup caches, ``backend_compile``/``persistent_hit`` from XLA; a
    cold-compile regression shows up as backend_compile growing faster
    than persistent_hit. Idempotent; returns False when jax.monitoring is
    unavailable."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return True
    try:
        from jax import monitoring
    except ImportError:
        return False

    from . import metrics

    def _on_event(event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            metrics.COMPILE_CACHE.inc(event="backend_compile")

    def _on_hit(event: str, **kwargs) -> None:
        if event == PERSISTENT_HIT_EVENT:
            metrics.COMPILE_CACHE.inc(event="persistent_hit")

    monitoring.register_event_duration_secs_listener(_on_event)
    monitoring.register_event_listener(_on_hit)
    # idempotence flag, set once during single-threaded platform init (or
    # inside a watchdog-guarded warmup whose supervisor blocks in
    # done.wait); a lost update would only double-register a counter
    # listener for the same monotonic metric
    _compile_listener_installed = True  # osim: audit-ok[race]
    return True


class CompileCounter:
    """Context manager counting XLA compile requests and persistent-cache
    hits over a code region via local jax.monitoring listeners.

    ``cold_compiles`` is the honest recompile metric: compile requests that
    the persistent cache did NOT absorb — the quantity ``simon warmup`` is
    meant to drive to zero for a warmed workload. Unregistration uses the
    private jax.monitoring helpers when present and degrades to a disarm
    flag otherwise (the listener list has no public remove API)."""

    def __init__(self) -> None:
        self.backend_compiles = 0
        self.persistent_hits = 0
        #: seconds inside compile requests (cold compiles and cache loads)
        self.compile_seconds = 0.0
        self._armed = False

    @property
    def cold_compiles(self) -> int:
        return max(0, self.backend_compiles - self.persistent_hits)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if self._armed and event == BACKEND_COMPILE_EVENT:
            self.backend_compiles += 1
            self.compile_seconds += duration

    def _on_event(self, event: str, **kwargs) -> None:
        if self._armed and event == PERSISTENT_HIT_EVENT:
            self.persistent_hits += 1

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self._armed = True
        return self

    def __exit__(self, *exc) -> None:
        self._armed = False
        try:
            from jax import monitoring

            monitoring._unregister_event_duration_listener_by_callback(
                self._on_duration
            )
        except Exception:
            pass
        try:
            from jax._src import monitoring as _mon

            _mon._unregister_event_listener_by_callback(self._on_event)
        except Exception:
            pass
