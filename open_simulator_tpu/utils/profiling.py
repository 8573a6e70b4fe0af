"""Device-time profiling: a jax.profiler capture around any simon command.

The span layer (utils/tracing.py) times host phases and marks each span in
the profiler's host timeline (a TraceAnnotation of the same name), so a
capture shows what the device did and, where it sat idle, which span the
host was in. `capture_device_trace` is a thin wrapper over
`jax.profiler.start_trace`/`stop_trace` writing a Perfetto-loadable trace
into a run directory, exposed as `simon profile -- <cmd>` and
`GET /debug/profile?ms=` on the server. Failures degrade to an
`{"ok": false}` report — profiling must never take the run down.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from .tracing import span

__all__ = ["capture_device_trace"]


def capture_device_trace(
    out_dir: str, duration_ms: float = 1000.0, fn=None
) -> Dict[str, Any]:
    """Capture a jax.profiler device trace into `out_dir` — around `fn()`
    when given, else for `duration_ms` of wall time. Returns a report dict
    ({"ok": bool, "trace_dir": ..., "seconds": ...}, plus "error" on
    failure); never raises."""
    import jax

    report: Dict[str, Any] = {"ok": False, "trace_dir": out_dir}
    t0 = time.perf_counter()
    try:
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
    except Exception as e:
        report["error"] = str(e)
        return report
    err: Optional[str] = None
    try:
        with span("device-profile", out_dir=out_dir):
            if fn is not None:
                fn()
            else:
                time.sleep(max(float(duration_ms), 0.0) / 1000.0)
    except Exception as e:
        # the workload blew up, not the profiler — still stop the trace
        # (below) so the partial capture is readable, and report not raise
        err = f"{type(e).__name__}: {e}"
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            report["error"] = err or str(e)
            return report
    if err is not None:
        report["error"] = err
        return report
    report["ok"] = True
    report["seconds"] = round(time.perf_counter() - t0, 4)
    return report
