#!/usr/bin/env python
"""CI observability smoke (docs/observability.md).

Runs a small apply under OSIM_TRACE_FILE inside ONE root span, then proves
the exported Chrome trace is a single connected tree:

  * every event carries the same trace_id (one request = one trace);
  * exactly one root event (no parent_id) — the smoke's own root span;
  * every parent_id resolves to a span_id present in the file (no
    orphans);
  * the simulate phases are present, the host's device reads among them
    (`host-sync`, nested in `schedule`).

Publishes span counts and times to the GitHub job summary when
GITHUB_STEP_SUMMARY is set. Exits nonzero on any violation.
"""

import io
import json
import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "example", "simon-config.yaml",
)


def main() -> int:
    out_dir = tempfile.mkdtemp(prefix="osim-obs-smoke-")
    trace_path = os.path.join(out_dir, "trace.json")
    os.environ["OSIM_TRACE_FILE"] = trace_path
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from open_simulator_tpu.api.config import SimonConfig
    from open_simulator_tpu.engine.apply import run_apply
    from open_simulator_tpu.utils.tracing import span

    cfg = SimonConfig.load(CONFIG)
    with span("observability-smoke"):
        run_apply(cfg, out=io.StringIO())

    with open(trace_path) as fh:
        events = json.load(fh)["traceEvents"]
    assert events, "trace export produced no events"

    trace_ids = {e["args"]["trace_id"] for e in events}
    assert len(trace_ids) == 1, (
        f"expected one connected trace, got {len(trace_ids)}: "
        f"{sorted(trace_ids)}"
    )
    roots = [e for e in events if "parent_id" not in e["args"]]
    assert len(roots) == 1, (
        f"expected exactly one root span, got "
        f"{[r['name'] for r in roots]}"
    )
    assert roots[0]["name"] == "observability-smoke", roots[0]["name"]
    span_ids = {e["args"]["span_id"] for e in events}
    orphans = [
        e["name"] for e in events
        if e["args"].get("parent_id") not in span_ids | {None}
    ]
    assert not orphans, f"orphaned spans (unresolvable parent_id): {orphans}"

    by_name = {}
    for e in events:
        n, ms = by_name.get(e["name"], (0, 0.0))
        by_name[e["name"]] = (n + 1, ms + e["dur"] / 1e3)
    for phase in ("simulate", "prepare-cluster", "schedule",
                  "bind-placements", "host-sync"):
        assert phase in by_name, f"no {phase} span in the trace"

    lines = [
        "### observability smoke",
        "",
        f"- one connected trace: `{trace_ids.pop()}` "
        f"({len(events)} spans, root `{roots[0]['name']}`)",
        "",
        "| span | count | ms |",
        "|---|---|---|",
    ]
    for name, (n, ms) in sorted(by_name.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"| {name} | {n} | {ms:.3f} |")
    summary = "\n".join(lines)
    print(summary)
    step_summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if step_summary:
        with open(step_summary, "a") as fh:
            fh.write(summary + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
