"""simon CLI: apply / server / lint / audit / preflight / prove / version /
gen-doc.

Parity: `/root/reference/cmd/` (cobra commands → argparse subcommands):
  apply   -f/--simon-config, --output-file, -i/--interactive, --use-greed,
          --extended-resources (cmd/apply/apply.go:27-32)
  server  --port (cmd/server/*; the reference binds a real cluster via
          kubeconfig — ours serves simulations over snapshots)
  version (cmd/version/version.go)
  gen-doc (cmd/doc/generate_markdown.go)
"""

from __future__ import annotations

import argparse
import os
import sys

VERSION = "0.1.0"


def _add_apply(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "apply", help="simulate deploying applications",
        description="simulate deploying applications",
    )
    p.add_argument("-f", "--simon-config", required=True, help="path of simon config")
    p.add_argument(
        "--default-scheduler-config", default="",
        help="KubeSchedulerConfiguration YAML merged with simon's plugin set",
    )
    p.add_argument("--output-file", default="", help="write the report to a file")
    p.add_argument(
        "-i", "--interactive", action="store_true",
        help="reference-style interactive add-node loop",
    )
    p.add_argument(
        "--no-auto-plan", action="store_true",
        help="disable the automatic add-node capacity search",
    )
    p.add_argument(
        "--use-greed", action="store_true",
        help="order pods by descending dominant resource share before "
        "scheduling (GreedQueue; the reference declares this flag but never "
        "wires it — here it works)",
    )
    p.add_argument(
        "--extended-resources", default="",
        help="comma list: gpu,open-local (extended report views)",
    )
    p.add_argument(
        "--devices", type=int, default=1,
        help="shard the node axis across this many JAX devices "
        "(0 = all visible devices; 1 = single-device, the default)",
    )
    p.add_argument(
        "--metrics-file", default="",
        help="after the run, write the scheduler metrics snapshot "
        "(counters/histograms, see docs/observability.md) as JSON here",
    )
    p.add_argument(
        "--run-dir", default="",
        help="journal the run into this directory (durable checkpoint: "
        "every capacity trial is committed as it completes, see "
        "docs/durability.md)",
    )
    p.add_argument(
        "--resume", nargs="?", const=True, default=False, metavar="RUN_DIR",
        help="resume a journaled run: completed trials replay from the "
        "journal instead of re-simulating (RUN_DIR defaults to --run-dir)",
    )


def _add_runs(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "runs",
        help="list, inspect, and resume journaled runs",
        description=(
            "Operate on durable run journals (docs/durability.md). "
            "`list` shows every journaled run under the runs root "
            "(OSIM_RUNS_DIR or ~/.cache/open-simulator-tpu/runs, or --root); "
            "`show` prints one run's summary and journal; `resume` re-runs "
            "an interrupted apply from its journal, re-simulating only "
            "trials the crashed run never committed."
        ),
    )
    p.add_argument(
        "action", choices=("list", "show", "resume"),
        help="list all runs / show one run / resume an interrupted apply",
    )
    p.add_argument(
        "run_dir", nargs="?", default="",
        help="run directory (required for show/resume)",
    )
    p.add_argument(
        "--root", default="",
        help="runs root for `list` (default: OSIM_RUNS_DIR or "
        "~/.cache/open-simulator-tpu/runs)",
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format",
    )
    p.add_argument(
        "-f", "--simon-config", default="",
        help="resume: config path override (default: the journaled one)",
    )


def _run_runs(args) -> int:
    import json as _json

    from ..durable import default_runs_root, list_runs, replay, summarize_run

    if args.action == "list":
        rows = list_runs(args.root or default_runs_root())
        if args.format == "json":
            print(_json.dumps(rows, indent=2, sort_keys=True))
            return 0
        if not rows:
            print(f"no journaled runs under {args.root or default_runs_root()}")
            return 0
        hdr = f"{'RUN':<28} {'KIND':<6} {'STATUS':<18} {'TRIALS':>6} {'SEGS':>4} {'DEVICE':<14} PATH"
        print(hdr)
        for r in rows:
            flag = " (backend error)" if r["backend_error"] else ""
            print(
                f"{r['name']:<28} {r['kind']:<6} {r['status']:<18} "
                f"{r['trials']:>6} {r['segments']:>4} "
                f"{(r['device'] or '?'):<14} {r['run_dir']}{flag}"
            )
        return 0

    if not args.run_dir:
        print(f"error: `runs {args.action}` needs a run directory", file=sys.stderr)
        return 1
    summary = summarize_run(args.run_dir)
    if not summary["events"]:
        print(f"error: no journal found in {args.run_dir}", file=sys.stderr)
        return 1

    if args.action == "show":
        events = replay(args.run_dir)
        if args.format == "json":
            print(_json.dumps({"summary": summary, "events": events},
                              indent=2, sort_keys=True))
            return 0
        for k in ("run_dir", "kind", "config", "status", "outcome", "device",
                  "backend_error", "events", "trials", "segments", "resumes",
                  "watchdogs"):
            print(f"{k:>10}: {summary[k]}")
        print("journal:")
        for e in events:
            extra = {k: v for k, v in e.items() if k not in ("seq", "ts", "event")}
            print(f"  [{e['seq']:>4}] {e['event']:<18} {_json.dumps(extra, sort_keys=True)}")
        return 0

    # resume: apply and sweep runs are the resumable kinds
    if summary["kind"] not in ("apply", "sweep"):
        print(
            f"error: run {args.run_dir} is kind={summary['kind'] or '?'}; "
            "`simon runs resume` handles apply and sweep runs only",
            file=sys.stderr,
        )
        return 1
    config_path = args.simon_config or summary["config"]
    if not config_path:
        print(
            "error: the journal records no config path; pass -f/--simon-config",
            file=sys.stderr,
        )
        return 1
    if summary["kind"] == "sweep":
        import argparse as _argparse

        start = (replay(args.run_dir) or [{}])[0]
        return _run_sweep(_argparse.Namespace(
            simon_config=config_path, capacity=True, node_counts="",
            use_greed=bool(start.get("use_greed")), format="text",
            run_dir=args.run_dir, resume=True,
        ))
    from ..api.config import SimonConfig
    from ..engine.apply import ApplyError, run_apply

    try:
        cfg = SimonConfig.load(config_path)
        outcome = run_apply(
            cfg, run_dir=args.run_dir, resume=True, config_path=config_path
        )
    except (ApplyError, ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    return 0 if not outcome.result.unscheduled else 2


def _add_sweep(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "sweep",
        help="batched multi-scenario simulation (one vmapped device call)",
        description=(
            "Evaluate many what-if scenarios of one simon config through "
            "the batched scenario engine (docs/batching.md): every lane "
            "shares the encoded cluster and one compiled program, so a "
            "whole sweep costs one (or log-few) device calls instead of "
            "one simulation per scenario. `--node-counts` compares cluster "
            "sizes (each lane keeps only the first N nodes); `--capacity` "
            "runs the batched minimum-node capacity search against the "
            "config's newNode candidate, with the same journal/resume "
            "contract as `simon apply` (docs/durability.md)."
        ),
    )
    p.add_argument(
        "-f", "--simon-config", required=True, help="path of simon config"
    )
    p.add_argument(
        "--node-counts", default="",
        help="comma list of node counts; one scenario per count, each "
        "keeping only the first N cluster nodes (e.g. 4,8,16)",
    )
    p.add_argument(
        "--capacity", action="store_true",
        help="batched capacity search: minimum clones of the config's "
        "newNode so everything schedules (plan_capacity sweep_mode=batched)",
    )
    p.add_argument(
        "--use-greed", action="store_true",
        help="order pods by descending dominant resource share "
        "(forces the serial fallback for node-count sweeps: greed ordering "
        "depends on the lane's node set)",
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format",
    )
    p.add_argument(
        "--run-dir", default="",
        help="journal a --capacity sweep into this directory (each batched "
        "call commits a `sweep` record with all lane verdicts)",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="resume a journaled --capacity sweep: journaled sweep records "
        "replay with zero re-run scenarios",
    )


def _run_sweep(args) -> int:
    import json as _json
    import time as _time

    from ..api.config import SimonConfig
    from ..engine.apply import (
        ApplyError,
        build_apps,
        build_cluster,
        load_new_node,
    )
    from ..engine.simulator import Scenario, simulate_batch

    try:
        cfg = SimonConfig.load(args.simon_config)
        cluster = build_cluster(cfg)
        apps = build_apps(cfg)
    except (ApplyError, ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1

    if args.capacity:
        from ..engine.capacity import plan_capacity

        new_node = load_new_node(cfg)
        if new_node is None:
            print(
                "error: --capacity needs a newNode candidate in the config",
                file=sys.stderr,
            )
            return 1
        journal = None
        if args.run_dir:
            from ..durable import RunJournal

            journal = RunJournal.open(args.run_dir)
            if args.resume:
                journal.append("run_resume")
            else:
                journal.append(
                    "run_start", kind="sweep",
                    simon_config=args.simon_config,
                    use_greed=bool(args.use_greed),
                )
        elif args.resume:
            print("error: --resume needs --run-dir", file=sys.stderr)
            return 1
        t0 = _time.monotonic()
        plan = plan_capacity(
            cluster, apps, new_node, use_greed=args.use_greed,
            journal=journal, resume=args.resume, sweep_mode="batched",
        )
        wall = _time.monotonic() - t0
        if journal is not None:
            import os as _os

            from ..durable import atomic_write
            from ..engine.apply import placement_digest

            journal.append(
                "run_end",
                outcome="ok" if plan is not None else "does_not_fit",
                nodes_added=plan.nodes_added if plan else -1,
            )
            # timestamp-free snapshot (mirrors run_apply's outcome.json):
            # a SIGKILL'd-then-resumed sweep must byte-match an
            # uninterrupted one — the crash-resume smoke `cmp`s these
            atomic_write(
                _os.path.join(journal.run_dir, "outcome.json"),
                _json.dumps(
                    {
                        "outcome": "ok" if plan else "does_not_fit",
                        "kind": "sweep",
                        "nodes_added": plan.nodes_added if plan else -1,
                        "attempts": plan.attempts if plan else 0,
                        "batched_calls": plan.batched_calls if plan else 0,
                        "retries": plan.retries if plan else 0,
                        "unscheduled": (
                            len(plan.result.unscheduled) if plan else -1
                        ),
                        "placement_digest": (
                            placement_digest(plan.result) if plan else ""
                        ),
                    },
                    indent=2,
                    sort_keys=True,
                )
                + "\n",
            )
            journal.close()
        if plan is None:
            print("capacity sweep failed: workload does not fit", file=sys.stderr)
            return 2
        doc = {
            "nodes_added": plan.nodes_added,
            "attempts": plan.attempts,
            "batched_calls": plan.batched_calls,
            "retries": plan.retries,
            "wall_s": round(wall, 3),
        }
        if args.format == "json":
            print(_json.dumps(doc, indent=2, sort_keys=True))
        else:
            print(
                f"capacity sweep: add {plan.nodes_added} x {new_node.name} "
                f"({plan.attempts} scenario verdicts in "
                f"{plan.batched_calls} batched call(s), {wall:.2f}s)"
            )
        return 0

    try:
        counts = [
            int(s) for s in args.node_counts.split(",") if s.strip()
        ]
    except ValueError:
        print(
            f"error: --node-counts must be a comma list of integers, got "
            f"{args.node_counts!r}", file=sys.stderr,
        )
        return 1
    if not counts:
        print(
            "error: pass --node-counts or --capacity (nothing to sweep)",
            file=sys.stderr,
        )
        return 1
    scenarios = [Scenario(name=f"nodes-{k}", node_count=k) for k in counts]
    t0 = _time.monotonic()
    try:
        results = simulate_batch(
            cluster, apps, scenarios, use_greed=args.use_greed
        )
    except ValueError as e:  # e.g. a count outside [0, n_nodes]
        print(f"error: {e}", file=sys.stderr)
        return 1
    wall = _time.monotonic() - t0
    rows = []
    for sc, res in zip(scenarios, results):
        placed = sum(len(st.pods) for st in res.node_status)
        rows.append({
            "scenario": sc.name,
            "nodes": sc.node_count,
            "pods_placed": placed,
            "unscheduled": len(res.unscheduled),
        })
    if args.format == "json":
        print(_json.dumps(
            {"scenarios": rows, "wall_s": round(wall, 3)},
            indent=2, sort_keys=True,
        ))
    else:
        print(f"{'SCENARIO':<16} {'NODES':>6} {'PLACED':>8} {'UNSCHEDULED':>12}")
        for r in rows:
            print(
                f"{r['scenario']:<16} {r['nodes']:>6} {r['pods_placed']:>8} "
                f"{r['unscheduled']:>12}"
            )
        print(f"{len(rows)} scenario(s) in {wall:.2f}s (one batched sweep)")
    return 0 if all(r["unscheduled"] == 0 for r in rows) else 2


def _add_lint(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "lint",
        help="static analysis: AST purity/shape/dtype rules + jaxpr audit",
        description=(
            "Run the static-analysis subsystem over the installed package: "
            "the AST lint rules (tracer coercions, impure reads, dtype "
            "drift, unbucketed jit shapes) and, unless --no-jaxpr, the "
            "jaxpr auditor + recompile guard that trace the fast-path "
            "kernels on canonical bucketed shapes. Exit 0 = clean. See "
            "docs/static-analysis.md."
        ),
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--rules", default="",
        help="comma list of AST rule ids to run (default: all); "
        "see `simon lint --list-rules`",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalogue and exit",
    )
    p.add_argument(
        "--no-jaxpr", action="store_true",
        help="skip the jaxpr auditor (pure-AST mode: no jax import, "
        "suitable for pre-commit hooks)",
    )
    p.add_argument(
        "--no-recompile-guard", action="store_true",
        help="skip the capacity-sweep recompile guard (the slowest stage)",
    )


def _add_chaos(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "chaos",
        help="run an apply under a deterministic fault plan and report "
        "degraded vs failed behavior",
        description=(
            "Install a fault-injection plan (docs/resilience.md), run the "
            "same simulation as `simon apply`, and print a deterministic "
            "report: which faults fired, what degraded (retries, skipped "
            "ignorable extenders, stale snapshots, failed app renders), and "
            "what failed outright (unscheduled pods, aborted runs). The "
            "report is byte-identical across runs with the same plan seed. "
            "Exit 0 when the simulation completed — even degraded; 1 when "
            "it aborted."
        ),
    )
    p.add_argument("-f", "--simon-config", required=True, help="path of simon config")
    p.add_argument(
        "--fault-plan", default="",
        help="fault plan YAML path (default: the OSIM_FAULT_PLAN env var)",
    )
    p.add_argument(
        "--default-scheduler-config", default="",
        help="KubeSchedulerConfiguration YAML merged with simon's plugin set",
    )
    p.add_argument(
        "--capacity", action="store_true",
        help="mid-plan-kill scenario: run a chunked capacity sweep under "
        "the plan's device faults (chunk_kill SIGKILLs a subprocess "
        "mid-chunk, device_lost is recovered in place), resume it, and "
        "prove the resumed placements byte-match a clean reference "
        "(docs/durability.md)",
    )
    p.add_argument(
        "--run-dir", default="",
        help="--capacity: journal the faulted sweep here (default: a "
        "temporary directory, removed afterwards)",
    )


def _run_chaos(args) -> int:
    import io as _io

    from ..api.config import SimonConfig
    from ..engine.apply import ApplyError, run_apply
    from ..resilience import faults
    from ..resilience.policy import breaker_states, reset_breakers
    from ..utils import metrics

    try:
        plan = (
            faults.FaultPlan.load(args.fault_plan)
            if args.fault_plan
            else faults.FaultPlan.from_env()
        )
    except faults.FaultInjectionError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if plan is None:
        print(
            "error: no fault plan (pass --fault-plan or set OSIM_FAULT_PLAN)",
            file=sys.stderr,
        )
        return 1

    if args.capacity:
        return _run_chaos_capacity(args, plan)

    # A clean slate makes the report a pure function of (config, plan seed):
    # same seed in -> byte-identical report out.
    metrics.REGISTRY.reset()
    reset_breakers()
    injector = faults.install_plan(plan)
    aborted = ""
    outcome = None
    try:
        cfg = SimonConfig.load(args.simon_config)
        outcome = run_apply(
            cfg,
            out=_io.StringIO(),  # the chaos report replaces the apply report
            scheduler_config=args.default_scheduler_config,
        )
    except (ApplyError, ValueError, OSError) as e:
        aborted = str(e)
        # chaos abort = the run died under injected faults: dump the flight
        # recorder so the abort leaves the same post-mortem artifact a real
        # crash would (utils/flightrec.py)
        try:
            from ..utils import flightrec

            flightrec.dump("chaos-abort", error=aborted)
        except Exception:
            pass
    finally:
        faults.uninstall_plan()

    def total(counter) -> int:
        snap = counter.snapshot()
        return int(sum(s["value"] for s in snap["samples"]))

    lines = ["simon chaos report", "=================="]
    lines.append(f"fault plan: seed={plan.seed}, {len(plan.rules)} rule(s)")
    for i, r in enumerate(injector.summary(), 1):
        lines.append(
            f"  rule {i}: target={r['target']} op={r['op'] or '*'} "
            f"kind={r['kind']} -> injected {r['injected']} of "
            f"{r['matched']} matched call(s)"
        )
    if aborted:
        lines.append(f"outcome: failed — apply aborted: {aborted}")
        print("\n".join(lines))
        return 1

    retries = total(metrics.RETRY_ATTEMPTS)
    skips = total(metrics.EXTENDER_SKIPPED)
    stale = total(metrics.SNAPSHOT_STALE)
    # Overload accounting (docs/serving.md): shedding is the admission
    # queue WORKING — every shed client got a definite 429/503 with a
    # Retry-After, so it is degradation; a drop (no response at all) is the
    # failure mode admission control exists to prevent.
    shed = total(metrics.REQUESTS_SHED)
    dropped = total(metrics.REQUESTS_DROPPED)
    # Resident-state self-healing (engine/resident.py): a repair is the
    # anti-entropy loop WORKING — the drifted/torn state was re-encoded from
    # the source of truth before answering, so it counts as degradation,
    # never as failure.
    repairs = total(metrics.RESIDENT_DRIFT_REPAIRS)
    failed_apps = sorted(fa.name for fa in outcome.failed_apps)
    not_closed = sorted(
        ep for ep, state in breaker_states().items() if state != "closed"
    )
    unscheduled = outcome.result.unscheduled
    degraded = bool(
        retries or skips or stale or failed_apps or not_closed or shed
        or repairs
    )

    lines.append("degraded:")
    lines.append(
        "  apps failed to render: "
        + (f"{len(failed_apps)} ({', '.join(failed_apps)})" if failed_apps else "0")
    )
    lines.append(f"  retries performed: {retries}")
    lines.append(f"  ignorable extenders skipped: {skips}")
    lines.append(f"  stale snapshots served: {stale}")
    lines.append(f"  requests shed with Retry-After: {shed}")
    lines.append(f"  resident drift repairs: {repairs}")
    lines.append(
        "  circuit breakers not closed: "
        + (", ".join(not_closed) if not_closed else "none")
    )
    lines.append("failed:")
    lines.append(f"  unscheduled pods: {len(unscheduled)}")
    for reason in sorted({u.reason for u in unscheduled}):
        lines.append(f"    reason: {reason}")
    lines.append(f"  requests dropped without response: {dropped}")
    if unscheduled:
        lines.append(
            "outcome: failed — pods went unscheduled under the fault plan"
        )
    elif dropped:
        lines.append(
            "outcome: failed — requests were dropped without a response"
        )
    elif degraded:
        lines.append("outcome: degraded — simulation completed under faults")
    else:
        lines.append("outcome: clean — no degradation observed")
    print("\n".join(lines))
    return 0


def _fault_plan_doc(plan) -> dict:
    """Serialize a FaultPlan back to its YAML schema (only non-default
    fields), so chaos can hand the exact plan to a subprocess via
    OSIM_FAULT_PLAN."""
    rules = []
    for r in plan.rules:
        doc: dict = {"target": r.target, "kind": r.kind}
        if r.op:
            doc["op"] = r.op
        if r.times is not None:
            doc["times"] = r.times
        if r.after:
            doc["after"] = r.after
        if r.probability != 1.0:
            doc["probability"] = r.probability
        if r.latency_s:
            doc["latency_s"] = r.latency_s
        if r.status != 503:
            doc["status"] = r.status
        if r.body:
            doc["body"] = r.body
        rules.append(doc)
    return {"seed": plan.seed, "rules": rules}


def _run_chaos_capacity(args, plan) -> int:
    """`simon chaos --capacity`: the mid-plan-kill scenario.

    Three legs, each a `simon sweep --capacity` child, so that the parent
    never touches the device and each child can hold the chip: (1) a clean
    journaled sweep banks the reference placement digest; (2) the same
    sweep runs under the fault plan — `chunk_kill` SIGKILLs it mid-chunk
    (the child cannot report anything; its journal and snapshots are the
    evidence), `device_lost` is recovered inside the child from its last
    good carry; (3) a killed run is resumed with faults OFF (resume must
    work on a healthy host) and the final placement digest is compared
    byte-for-byte with the reference. Degraded-not-failed means: faults
    fired, the plan still landed, and the digests match (exit 0)."""
    import json as _json
    import os as _os
    import shutil as _shutil
    import subprocess as _sp
    import tempfile as _tf

    import yaml as _yaml

    from ..durable import replay

    chunk = _os.environ.get("OSIM_COMMIT_CHUNK", "").strip() or "8"
    every = _os.environ.get("OSIM_CKPT_EVERY", "").strip() or "2"
    # the device-loss leg runs against the wave engine by default (the
    # new hot path): one wave per chunk record, rollback to the last
    # good wave, and the resumed digest still byte-matches the clean
    # reference. OSIM_WAVE_COMMIT=0 in the environment keeps the serial
    # chunked driver for comparison.
    wave = _os.environ.get("OSIM_WAVE_COMMIT", "").strip() or "1"

    run_dir = args.run_dir or _tf.mkdtemp(prefix="simon-chaos-capacity-")
    cleanup = not args.run_dir
    ref_dir = _tf.mkdtemp(prefix="simon-chaos-reference-")
    env = dict(_os.environ)
    env.pop("OSIM_FAULT_PLAN", None)
    env.update(
        OSIM_COMMIT_CHUNK=chunk, OSIM_CKPT_EVERY=every,
        OSIM_WAVE_COMMIT=wave,
    )

    def sweep(out_dir: str, *extra: str, fault_plan: str = "") -> int:
        e = dict(env, OSIM_FLIGHT_DIR=out_dir)
        if fault_plan:
            e["OSIM_FAULT_PLAN"] = fault_plan
        return _sp.run(
            [sys.executable, "-m", "open_simulator_tpu.cli.main", "sweep",
             "--capacity", "-f", args.simon_config, "--run-dir", out_dir,
             *extra],
            env=e, stdout=_sp.DEVNULL, stderr=_sp.DEVNULL,
        ).returncode

    def digest_of(out_dir: str) -> str:
        try:
            with open(_os.path.join(out_dir, "outcome.json")) as fh:
                return str(_json.load(fh).get("placement_digest", ""))
        except (OSError, ValueError):
            return ""

    try:
        if not _os.path.exists(args.simon_config):
            print(f"error: config not found: {args.simon_config}",
                  file=sys.stderr)
            return 1
        rc = sweep(ref_dir)
        ref_digest = digest_of(ref_dir)
        if rc != 0 or not ref_digest:
            print(
                f"error: reference capacity sweep failed (rc {rc}); it "
                "needs a newNode candidate and a plan that fits",
                file=sys.stderr,
            )
            return 1

        _os.makedirs(run_dir, exist_ok=True)
        plan_path = _os.path.join(run_dir, "fault-plan.yaml")
        with open(plan_path, "w") as fh:
            _yaml.safe_dump(_fault_plan_doc(plan), fh, sort_keys=True)
        rc = sweep(run_dir, fault_plan=plan_path)
        killed = rc in (137, -9)
        if killed:
            rc = sweep(run_dir, "--resume")
            if rc != 0:
                print(
                    f"error: resume of the killed sweep failed (rc {rc})",
                    file=sys.stderr,
                )
                return 1
        elif rc != 0:
            print(
                f"error: faulted sweep exited rc {rc} "
                "(expected 0, or SIGKILL from a chunk_kill rule)",
                file=sys.stderr,
            )
            return 1
        got_digest = digest_of(run_dir)

        events = replay(run_dir)
        n_chunk_records = sum(
            1 for e in events if e.get("event") == "plan_chunk"
        )

        skipped = 0
        art_kinds: dict = {}
        last_note = None
        for name in sorted(_os.listdir(run_dir)):
            if not name.startswith("flightrec-"):
                continue
            try:
                with open(_os.path.join(run_dir, name)) as fh:
                    doc = _json.load(fh)
            except (OSError, ValueError):
                continue
            reason = str(doc.get("reason", "?"))
            art_kinds[reason] = art_kinds.get(reason, 0) + 1
            restored = None
            for ev in doc.get("events", []):
                if ev.get("kind") in ("plan-restore", "device-lost"):
                    last_note = ev
                if ev.get("kind") == "plan-restore":
                    restored = ev
            # each resume restore dumps one artifact right after noting the
            # last chunk its snapshot covers
            if reason == "chunk-restore" and restored is not None:
                skipped += int(restored.get("chunk", -1)) + 1

        lines = ["simon chaos report", "=================="]
        lines.append(f"fault plan: seed={plan.seed}, {len(plan.rules)} rule(s)")
        for i, r in enumerate(plan.rules, 1):
            lines.append(
                f"  rule {i}: target={r.target} op={r.op or '*'} "
                f"kind={r.kind}"
            )
        lines.append(
            "scenario: chunked capacity sweep "
            f"(OSIM_COMMIT_CHUNK={chunk}, snapshot every {every} chunk(s), "
            f"engine={'wave' if wave != '0' else 'serial'})"
        )
        lines.append("degraded:")
        lines.append(
            "  faulted run: "
            + ("killed mid-plan (SIGKILL), resumed from checkpoint"
               if killed else
               "completed — device faults recovered in place")
        )
        lines.append(f"  plan_chunk records journaled: {n_chunk_records}")
        lines.append(f"  chunks restored from snapshot on resume: {skipped}")
        lines.append(
            f"  device_lost recoveries: {art_kinds.get('device-lost', 0)}"
        )
        if last_note is not None:
            where = last_note.get("restored_to", last_note.get("chunk"))
            lines.append(
                f"  last good chunk: {where} "
                f"(carry digest {last_note.get('digest')})"
            )
        lines.append(
            "  flight artifacts: "
            + (", ".join(f"{k}:{v}" for k, v in sorted(art_kinds.items()))
               or "none")
        )
        lines.append("failed:")
        match = bool(got_digest) and got_digest == ref_digest
        lines.append(
            "  placement digest vs clean reference: "
            + ("match" if match else "MISMATCH")
        )
        if not n_chunk_records:
            lines.append(
                "outcome: failed — the chunked commit driver never engaged "
                "(workload too small for OSIM_COMMIT_CHUNK?)"
            )
            print("\n".join(lines))
            return 1
        if not match:
            lines.append(
                "outcome: failed — resumed placements diverge from the "
                "clean reference"
            )
            print("\n".join(lines))
            return 1
        lines.append(
            "outcome: degraded — plan survived the device fault(s); "
            "placements byte-identical to the clean run"
        )
        print("\n".join(lines))
        return 0
    finally:
        _shutil.rmtree(ref_dir, ignore_errors=True)
        if cleanup:
            _shutil.rmtree(run_dir, ignore_errors=True)


def _add_audit(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "audit",
        help="semantic verification: concurrency race detector + jaxpr "
        "numeric-invariant prover",
        description=(
            "Run the semantic audit passes: the lock-discipline race "
            "detector over thread-reachable code (server handlers, thread "
            "targets, signal handlers) and the abstract interpreter that "
            "re-traces every registered jit entry point, proving mask "
            "outputs stay in {0,1}, score plugins stay in [0,100], and no "
            "NaN can reach a selection primitive. Deterministic output; "
            "exit 0 = clean. The runtime companion is OSIM_SANITIZE=1. "
            "See docs/static-analysis.md."
        ),
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--no-races", action="store_true",
        help="skip the concurrency race detector",
    )
    p.add_argument(
        "--no-invariants", action="store_true",
        help="skip the jaxpr invariant prover (pure-AST mode: no jax "
        "import, suitable for pre-commit hooks)",
    )
    p.add_argument(
        "--memory", action="store_true",
        help="also run the compact memory/collective slice of the "
        "preflight matrix (canonical rung, host-available meshes); the "
        "full matrix with budget diff lives under `simon preflight`",
    )


def _run_audit(args) -> int:
    from ..analysis.audit import run_semantic_audit

    if not args.no_invariants or args.memory:
        from ..utils.tracing import init_logging

        init_logging()
    report = run_semantic_audit(
        races=not args.no_races,
        invariants=not args.no_invariants,
        memory=args.memory,
    )
    if args.format == "json":
        print(report.to_json())
    else:
        print(report.render_text())
    return 0 if report.ok else 1


def _add_interleave(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "interleave",
        help="deterministic concurrency model checker over the serving & "
        "durability protocols",
        description=(
            "Stateless model checker: run the real AdmissionQueue / "
            "SchedulerLoop / session-LRU / RunJournal / CircuitBreaker "
            "code under cooperative shim sync primitives (one runnable "
            "thread at a time, a yield at every acquire/release/wait/"
            "journal append) and exhaustively explore every interleaving "
            "of each small-scope protocol scenario within a context-"
            "switch bound, pruned by sleep-set DPOR. Safety invariants "
            "(no lost/double-dispatched ticket, fence-epoch monotonicity, "
            "no double session checkout, journal prefix-closure under "
            "crash, breaker state-machine legality) and semantic-deadlock "
            "freedom are checked on every schedule; a violation exits 1 "
            "with a ddmin-minimized, replayable schedule. "
            "See docs/static-analysis.md."
        ),
    )
    p.add_argument(
        "scenario", nargs="*", metavar="SCENARIO",
        help="scenarios to explore (default: all; see --format=json "
        "output or docs/static-analysis.md for the catalog: admission, "
        "fence, session, journal, breaker)",
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--quick", action="store_true",
        help="CI quick mode: preemption bound 1 and a smaller run budget "
        "(exhaustive within those bounds, still deterministic)",
    )
    p.add_argument(
        "--seed", type=int, default=0,
        help="exploration-order seed; same seed => byte-identical report "
        "(default: 0)",
    )
    p.add_argument(
        # keep in sync with analysis.interleave.MUTATIONS (validated
        # there too; static here so the parser stays import-light)
        "--mutate",
        choices=("double-checkout", "double-probe", "fence-regression",
                 "lost-ticket", "torn-checkpoint"),
        default=None,
        help="seeded protocol-bug injection: run the mutation's scenario "
        "with a deliberately-broken protocol; the checker must catch and "
        "minimize it (proves the checker)",
    )
    p.add_argument(
        "--replay", default=None, metavar="PATH",
        help="execute exactly one schedule from a violation's JSON "
        "schedule file instead of exploring (the concurrency-fix "
        "regression vehicle)",
    )
    p.add_argument(
        "--schedule-out", default=None, metavar="PATH",
        help="write the first violation's minimized schedule JSON here "
        "(replayable via --replay)",
    )
    p.add_argument(
        "--preemptions", type=int, default=None, metavar="N",
        help="context-switch bound override (default: 2; --quick: 1)",
    )
    p.add_argument(
        "--max-runs", type=int, default=None, metavar="N",
        help="per-scenario interleaving budget override (default: 60000; "
        "--quick: 8000)",
    )
    p.add_argument(
        "--max-steps", type=int, default=None, metavar="N",
        help="per-run scheduling-decision cap (default: 500)",
    )
    p.add_argument(
        "--no-dpor", action="store_true",
        help="disable sleep-set partial-order reduction (cross-check "
        "mode: slower, must reach the same verdicts)",
    )


def _run_interleave(args) -> int:
    import json as _json

    from ..analysis import interleave

    replay = None
    if args.replay:
        with open(args.replay) as fh:
            replay = _json.load(fh)
    try:
        report = interleave.run_interleave(
            args.scenario or None,
            seed=args.seed,
            quick=args.quick,
            mutate=args.mutate,
            preemptions=args.preemptions,
            max_runs=args.max_runs,
            max_steps=args.max_steps,
            use_dpor=not args.no_dpor,
            replay=replay,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if args.schedule_out:
        for sc in report.scenarios:
            if sc.violations:
                sched = interleave._schedule_dict(
                    sc.violations[0], report.seed, report.mutate
                )
                with open(args.schedule_out, "w") as fh:
                    _json.dump(sched, fh, indent=2, sort_keys=True)
                    fh.write("\n")
                break
    if args.format == "json":
        print(_json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(report.render_text())
    return 0 if report.ok else 1


def _add_check(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "check",
        help="umbrella static gate: lint + audit + preflight + interleave "
        "in one SARIF 2.1.0 report",
        description=(
            "Run every static pass the repo ships — `simon lint` "
            "(syntactic contracts), `simon audit` (race detector + jaxpr "
            "invariant prover), `simon preflight` (HBM/collective budget "
            "diff), `simon interleave` (concurrency model checker) — and "
            "emit one SARIF 2.1.0 document with a run per producer, "
            "ready for a CI annotation step (e.g. "
            "github/codeql-action/upload-sarif). Exit 1 if any pass "
            "fails. Individual passes can be skipped; `--no-invariants "
            "--no-preflight` keeps the gate pure-AST + model checking "
            "(no jax import, no compiles)."
        ),
    )
    p.add_argument(
        "--format", choices=("sarif", "json", "text"), default="sarif",
        help="sarif (default) = one SARIF 2.1.0 document; json/text = "
        "the concatenated native reports",
    )
    p.add_argument(
        "--output", default=None, metavar="PATH",
        help="write the report here instead of stdout",
    )
    p.add_argument(
        "--quick", action="store_true",
        help="bound the interleave pass to its CI quick budget",
    )
    p.add_argument("--no-lint", action="store_true",
                   help="skip the lint pass")
    p.add_argument("--no-races", action="store_true",
                   help="skip the race-detector pass")
    p.add_argument("--no-invariants", action="store_true",
                   help="skip the jaxpr invariant prover (no jax import)")
    p.add_argument("--no-preflight", action="store_true",
                   help="skip the preflight budget diff (no compiles)")
    p.add_argument("--no-interleave", action="store_true",
                   help="skip the concurrency model checker")


def _run_check(args) -> int:
    import json as _json
    import os as _os

    from ..analysis import sarif as sarif_mod

    if not args.no_invariants or not args.no_preflight:
        from ..utils.tracing import init_logging

        init_logging()

    runs = []
    texts = []
    native = {}
    ok = True

    if not args.no_lint:
        from ..analysis.lint import run_lint

        lint_report = run_lint()
        ok = ok and not lint_report.active
        runs.append(sarif_mod.lint_run(lint_report))
        native["lint"] = _json.loads(lint_report.to_json())
        texts.append(lint_report.render_text())

    if not (args.no_races and args.no_invariants):
        from ..analysis.audit import run_semantic_audit

        audit_report = run_semantic_audit(
            races=not args.no_races,
            invariants=not args.no_invariants,
            memory=False,
        )
        ok = ok and audit_report.ok
        runs.append(sarif_mod.audit_run(audit_report))
        native["audit"] = audit_report.to_dict()
        texts.append(audit_report.render_text())

    if not args.no_preflight:
        from ..analysis.budget import BudgetBook
        from ..analysis.hlo_audit import run_preflight

        budgets = "budgets/preflight.json"
        book = BudgetBook.load(budgets) if _os.path.exists(budgets) else None
        pf_report = run_preflight(book=book)
        pf_report.budgets_path = budgets
        ok = ok and pf_report.ok
        runs.append(sarif_mod.preflight_run(pf_report))
        native["preflight"] = pf_report.to_dict()
        texts.append(pf_report.render_text())

    if not args.no_interleave:
        from ..analysis import interleave

        il_report = interleave.run_interleave(quick=args.quick)
        ok = ok and il_report.ok
        runs.append(sarif_mod.interleave_run(il_report))
        native["interleave"] = il_report.to_dict()
        texts.append(il_report.render_text())

    if args.format == "sarif":
        out = _json.dumps(
            sarif_mod.sarif_document(runs), indent=2, sort_keys=True
        )
    elif args.format == "json":
        out = _json.dumps(
            {"ok": ok, "passes": native}, indent=2, sort_keys=True
        )
    else:
        texts.append(f"check: {'ok' if ok else 'FAILED'}")
        out = "\n".join(texts)
    if args.output:
        with open(args.output, "w") as fh:
            fh.write(out + "\n")
        print(f"wrote {args.output}", file=sys.stderr)
    else:
        print(out)
    return 0 if ok else 1


def _add_preflight(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "preflight",
        help="static HBM budgets + collective census over lowered programs",
        description=(
            "Pre-flight program auditor: lower-and-compile every audited "
            "jit entry at each node-ladder rung x mesh shape (on forced "
            "host devices), extract per-device argument/output/temp/peak "
            "bytes from compiled.memory_analysis() cross-checked against "
            "the shape-arithmetic estimator, census the HLO collectives "
            "(failing on node-table replication or collectives in lane-"
            "parallel programs), re-run entries under jax.transfer_guard, "
            "and diff everything against the checked-in budget book. The "
            "plan_1m_100k configuration gets a machine-checked fits-in-"
            "HBM verdict at mesh 1x4 — all without executing a single "
            "lowered program. See docs/static-analysis.md."
        ),
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--budgets", default="budgets/preflight.json",
        help="budget book to diff against (default: budgets/preflight.json)",
    )
    p.add_argument(
        "--write-budgets", action="store_true",
        help="rewrite the budget book from this run's measurements instead "
        "of diffing — the only sanctioned way to admit a memory or "
        "collective change",
    )
    p.add_argument(
        "--rungs", default="",
        help="comma-separated node-ladder rungs (default: 64,128)",
    )
    p.add_argument(
        "--meshes", default="",
        help="comma-separated mesh tags like 1,2x1,2x2 (default); meshes "
        "needing more devices than available are skipped and reported",
    )
    p.add_argument(
        "--entries", default="",
        help="comma-separated audit names (e.g. ops.fast:schedule_scenarios)"
        " to restrict the matrix; default: every captured entry",
    )
    p.add_argument(
        "--no-transfers", action="store_true",
        help="skip the transfer-guard audit (the one pass that executes "
        "programs; without it the preflight is fully static)",
    )
    p.add_argument(
        "--no-verdict", action="store_true",
        help="skip the plan_1m_100k fits-in-HBM verdict compile",
    )
    p.add_argument(
        "--hbm-gib", type=float, default=None,
        help="per-device HBM budget for the verdict (default: the chip's "
        "own bytes_limit; on the CPU, a TPU v5e chip's 16 GB)",
    )


def _run_preflight(args) -> int:
    import json as _json
    import os as _os

    from ..analysis.budget import BudgetBook
    from ..analysis.hlo_audit import run_preflight

    book = None
    if not args.write_budgets and _os.path.exists(args.budgets):
        book = BudgetBook.load(args.budgets)
    rungs = [int(r) for r in args.rungs.split(",") if r.strip()] or None
    meshes = [m.strip() for m in args.meshes.split(",") if m.strip()] or None
    entries = [e.strip() for e in args.entries.split(",") if e.strip()] or None
    report = run_preflight(
        rungs=rungs, meshes=meshes, entries=entries, book=book,
        transfers=not args.no_transfers, verdict=not args.no_verdict,
        hbm_gib=args.hbm_gib,
    )
    report.budgets_path = args.budgets
    if args.write_budgets:
        base = (
            BudgetBook.load(args.budgets)
            if _os.path.exists(args.budgets) else None
        )
        report.to_book(base).save(args.budgets)
        print(f"wrote {args.budgets}", file=sys.stderr)
    if args.format == "json":
        print(_json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(report.render_text())
    return 0 if report.ok else 1


def _add_prove(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "prove",
        help="exhaustive small-scope semantics check against the pure "
        "oracle + commit-order contract verification",
        description=(
            "Small-scope semantics prover: enumerate EVERY scheduling "
            "universe in a bounded family (4 node slots x 5 pod slots "
            "drawn from a quantized catalog — 151,875 distinct universes), "
            "run the real ops.fast:schedule_universes engine over all of "
            "them in a handful of identically-shaped vmapped device calls, "
            "and diff every placement, reason code, GPU assignment and "
            "final carry against the independent pure-numpy oracle "
            "(analysis/oracle.py). Full runs also verify the canonical "
            "commit-order contract (budgets/commit_contract.json) that "
            "the conflict-parallel wave commit must reproduce; any "
            "divergence exits 1 with a minimized counterexample universe. "
            "See docs/static-analysis.md."
        ),
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--contract", default=None, metavar="PATH",
        help="contract artifact to verify "
        "(default: budgets/commit_contract.json)",
    )
    p.add_argument(
        "--write-contract", action="store_true",
        help="bank this run's placement digest as the canonical contract "
        "instead of verifying — the only sanctioned way to admit a "
        "commit-order change (refused over a diverging corpus)",
    )
    p.add_argument(
        "--smoke", type=int, default=None, metavar="N",
        help="check only N universes strided across the corpus (engine vs "
        "oracle only; the digest is sample-dependent, so no contract "
        "verdict)",
    )
    p.add_argument(
        "--chunk", type=int, default=None, metavar="S",
        help="universes per device call (default: 25608 — six calls, one "
        "compile for the full corpus)",
    )
    p.add_argument(
        "--mutate", choices=("tiebreak", "nocommit"), default=None,
        help="seeded commit-rule fault injection: run a deliberately-wrong "
        "engine variant; the checker must exit nonzero with a minimized "
        "counterexample (proves the prover)",
    )
    p.add_argument(
        "--engine", choices=("serial", "wave"), default="serial",
        help="scheduling engine to prove: the serial scan "
        "(ops.fast:schedule_universes, default) or the conflict-parallel "
        "wave engine (ops/wave.py) — both must reproduce the SAME banked "
        "placement digest; a passing wave run is its admission proof "
        "under the commit-order contract",
    )


def _run_prove(args) -> int:
    import json as _json

    from ..analysis import semantics

    report = semantics.run_prove(
        contract_path=args.contract or semantics.CONTRACT_PATH,
        write=args.write_contract,
        smoke=args.smoke,
        chunk=args.chunk or semantics.DEFAULT_CHUNK,
        mutate=args.mutate,
        engine=args.engine,
        progress=(
            (lambda done, total: print(
                f"prove: {done}/{total} universes", file=sys.stderr
            ))
            if args.format == "text" else None
        ),
    )
    if args.format == "json":
        print(_json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(report.render_text())
    return 0 if report.ok else 1


def _add_warmup(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "warmup",
        help="AOT-compile every audited jit entry into the persistent "
        "compilation cache",
        description=(
            "Compile lifecycle as a phase, not a side effect: enumerate "
            "the audited jit entries (the same set the jaxpr audit proves "
            "over) at their canonical bucketed shapes, drive each through "
            "trace().lower().compile(), and rehearse the full capacity "
            "sweep so every program the engine needs lands in the "
            "persistent compilation cache (JAX_COMPILATION_CACHE_DIR, "
            "else <checkout>/.jax_cache) before "
            "anything is being timed or deadlined. A later process "
            "sharing the cache then pays zero cold compiles — "
            "`simon warmup --check` asserts exactly that and exits "
            "nonzero otherwise. See docs/performance.md."
        ),
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable CI artifact)",
    )
    p.add_argument(
        "--no-sweep", action="store_true",
        help="skip the capacity-sweep rehearsal (registry entries only; "
        "the zero-cold-compile guarantee then covers only the audited "
        "registry programs)",
    )
    p.add_argument(
        "--check", action="store_true",
        help="run the warm-start check instead of warming: re-run the "
        "full capacity sweep and demand ZERO cold compiles (exit 1 "
        "otherwise); run after `simon warmup` in a process sharing "
        "its compilation cache directory",
    )


def _run_warmup(args) -> int:
    import json as _json

    if args.check:
        from ..analysis.jaxpr_audit import warm_start_check

        result = warm_start_check()
    else:
        from ..engine.warmup import run_warmup

        result = run_warmup(include_sweep=not args.no_sweep)
    if args.format == "json":
        print(_json.dumps(result.to_dict(), indent=2, sort_keys=True))
    else:
        print(result.render_text())
    return 0 if result.ok else 1


def _add_profile(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "profile",
        help="run a simon command under a jax.profiler device trace",
        description=(
            "Device-time profiling (docs/observability.md): run a simon "
            "command (`simon profile -- apply -f cfg.yaml`) under a "
            "jax.profiler device trace written to --out "
            "(Perfetto/TensorBoard-loadable). Every program span appears on "
            "the host timeline, so an idle stretch of the device is named "
            "by the span the host was in."
        ),
    )
    p.add_argument(
        "--out", default="",
        help="device-trace output directory (default: "
        "<runs root>/device-profile)",
    )
    p.add_argument(
        "--format", choices=("text", "json"), default="text",
        help="output format (json is the machine-readable artifact)",
    )
    p.add_argument(
        "cmd", nargs=argparse.REMAINDER, metavar="command",
        help="simon command to run under the device trace, e.g. "
        "`simon profile -- apply -f cfg.yaml`",
    )


def _run_profile(args) -> int:
    import json as _json

    from ..durable import default_runs_root
    from ..utils.profiling import capture_device_trace

    cmd = [c for c in args.cmd if c != "--"]
    if not cmd:
        print("error: simon profile needs a command to trace, e.g. "
              "`simon profile -- apply -f cfg.yaml`", file=sys.stderr)
        return 2
    out_dir = args.out or os.path.join(default_runs_root(), "device-profile")
    rc_box: list = []
    trace = capture_device_trace(out_dir, fn=lambda: rc_box.append(main(cmd)))
    rc = rc_box[0] if rc_box else 1
    if not trace.get("ok"):
        rc = rc or 1
    if args.format == "json":
        print(_json.dumps({"trace": trace}, indent=2, sort_keys=True))
    else:
        state = "ok" if trace.get("ok") else f"failed: {trace.get('error')}"
        print(f"device trace ({state}): {trace.get('trace_dir')}")
    return rc


def _run_lint(args) -> int:
    import json as _json

    from ..analysis import iter_rules, run_lint

    if args.list_rules:
        for rid, doc in iter_rules():
            print(f"{rid}: {doc}")
        return 0
    only = [r.strip() for r in args.rules.split(",") if r.strip()] or None
    known = {rid for rid, _ in iter_rules()}
    unknown = set(only or ()) - known
    if unknown:
        print(f"error: unknown rule(s) {sorted(unknown)}", file=sys.stderr)
        return 1
    report = run_lint(only_rules=only)
    audit = guard = None
    if not args.no_jaxpr:
        from ..analysis.jaxpr_audit import run_audit, run_recompile_guard

        audit = run_audit()
        if not args.no_recompile_guard:
            guard = run_recompile_guard()
    ok = (
        not report.active
        and (audit is None or audit.ok)
        and (guard is None or guard.ok)
    )
    if args.format == "json":
        doc = _json.loads(report.to_json())
        doc["jaxpr_audit"] = audit.to_dict() if audit is not None else None
        doc["recompile_guard"] = guard.to_dict() if guard is not None else None
        doc["ok"] = ok
        print(_json.dumps(doc, indent=2, sort_keys=True))
    else:
        print(report.render_text())
        if audit is not None:
            print(audit.render_text())
        if guard is not None:
            print(guard.render_text())
    return 0 if ok else 1


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(
        prog="simon",
        description="TPU-native cluster scheduling simulator (open-simulator capabilities)",
    )
    sub = parser.add_subparsers(dest="command")
    _add_apply(sub)
    _add_audit(sub)
    _add_chaos(sub)
    _add_check(sub)
    _add_interleave(sub)
    _add_lint(sub)
    _add_preflight(sub)
    _add_profile(sub)
    _add_prove(sub)
    _add_runs(sub)
    _add_sweep(sub)
    _add_warmup(sub)
    ps = sub.add_parser(
        "server", help="run the REST simulation service",
        description="run the REST simulation service",
    )
    ps.add_argument("--port", type=int, default=9998)
    ps.add_argument(
        "--kubeconfig", default="",
        help="snapshot this cluster per request when the request body carries "
        "no cluster spec",
    )
    ps.add_argument(
        "--master", default="",
        help="apiserver URL overriding the kubeconfig's server "
        "(cmd/server/options.go parity)",
    )
    ps.add_argument(
        "--queue-depth", type=int, default=None,
        help="admission queue depth before 429 shedding "
        "(default: OSIM_SERVER_QUEUE_DEPTH or 16; docs/serving.md)",
    )
    ps.add_argument(
        "--pack-window-ms", type=float, default=None,
        help="upper bound on how long the scheduler loop holds a PARTIAL "
        "pack open for stragglers; lone requests and full packs always "
        "dispatch immediately (default: OSIM_SERVER_PACK_WINDOW_MS or 0)",
    )
    ps.add_argument(
        "--coalesce-ms", type=float, default=None,
        help="DEPRECATED alias for --pack-window-ms (the fixed coalescing "
        "window became the pack-window upper bound of the continuous-"
        "batching loop; OSIM_SERVER_COALESCE_MS still works, with a "
        "warning — see docs/serving.md migration note)",
    )
    ps.add_argument(
        "--default-deadline-ms", type=float, default=None,
        help="deadline applied to requests without an X-Osim-Deadline-Ms "
        "header (default: OSIM_SERVER_DEFAULT_DEADLINE_MS or 0 = none)",
    )
    sub.add_parser(
        "version", help="print version", description="print version"
    )
    pd = sub.add_parser(
        "gen-doc", help="generate CLI markdown docs",
        description="generate CLI markdown docs",
    )
    pd.add_argument("--output-dir", default="./docs/commandline")

    args = parser.parse_args(argv)
    if args.command == "preflight" or (
        args.command == "audit" and getattr(args, "memory", False)
    ) or (
        args.command == "check" and not getattr(args, "no_preflight", False)
    ):
        # the mesh matrix (2x1/2x2) and the 1x4 verdict need multiple
        # devices; force host devices BEFORE jax initializes (no-op when
        # the caller already set the flag or runs on real hardware)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if args.command in (
        "apply", "chaos", "server", "runs", "sweep", "warmup", "preflight",
        "profile", "prove",
    ):
        from ..utils.platform import (
            enable_compilation_cache,
            install_compile_listener,
        )
        from ..utils.tracing import init_logging

        init_logging()  # LogLevel env, parity: cmd/simon/simon.go:46-66
        enable_compilation_cache()
        install_compile_listener()
        # crash flight recorder: any unhandled crash of a device-touching
        # command dumps the recent-span/metric/journal ring first
        # (utils/flightrec.py; idempotent, safe under nested main() calls)
        from ..utils import flightrec

        flightrec.install_crash_hook()
    if args.command in ("apply", "server", "runs", "sweep"):
        # honor OSIM_FAULT_PLAN for non-chaos entry points too (chaos does
        # its own install): docs/resilience.md promises env-driven plans,
        # and the crash-resume smoke injects its deterministic SIGKILL into
        # a plain `simon apply` this way
        from ..resilience import faults

        try:
            plan = faults.FaultPlan.from_env()
        except faults.FaultInjectionError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        if plan is not None:
            faults.install_plan(plan)
    if args.command == "version":
        print(f"simon-tpu version {VERSION}")
        return 0
    if args.command == "chaos":
        return _run_chaos(args)
    if args.command == "runs":
        return _run_runs(args)
    if args.command == "audit":
        return _run_audit(args)
    if args.command == "check":
        return _run_check(args)
    if args.command == "interleave":
        return _run_interleave(args)
    if args.command == "preflight":
        return _run_preflight(args)
    if args.command == "lint":
        return _run_lint(args)
    if args.command == "sweep":
        return _run_sweep(args)
    if args.command == "warmup":
        return _run_warmup(args)
    if args.command == "profile":
        return _run_profile(args)
    if args.command == "prove":
        return _run_prove(args)
    if args.command == "gen-doc":
        return _gen_doc(parser, args.output_dir)
    if args.command == "server":
        from ..server.server import serve

        return serve(
            port=args.port,
            kubeconfig=args.kubeconfig,
            master=args.master,
            queue_depth=args.queue_depth,
            coalesce_ms=args.coalesce_ms,
            pack_window_ms=args.pack_window_ms,
            default_deadline_ms=args.default_deadline_ms,
        )
    if args.command == "apply":
        from ..api.config import SimonConfig
        from ..durable import BackendUnavailable, DeadlineExceeded
        from ..engine.apply import ApplyError, run_apply

        try:
            cfg = SimonConfig.load(args.simon_config)
            out = open(args.output_file, "w") if args.output_file else None
            try:
                ext = (
                    [s.strip() for s in args.extended_resources.split(",") if s.strip()]
                    if args.extended_resources
                    else None
                )
                unknown = set(ext or ()) - {"gpu", "open-local"}
                if unknown:
                    raise ApplyError(
                        f"--extended-resources: unknown resource(s) "
                        f"{sorted(unknown)}; expected gpu, open-local"
                    )
                run_dir = args.run_dir or (
                    args.resume if isinstance(args.resume, str) else ""
                )
                if args.resume and not run_dir:
                    raise ApplyError(
                        "--resume needs a run dir (inline or --run-dir)"
                    )
                outcome = run_apply(
                    cfg,
                    interactive=args.interactive,
                    auto_plan=not args.no_auto_plan,
                    out=out,
                    scheduler_config=args.default_scheduler_config,
                    use_greed=args.use_greed,
                    devices=args.devices,
                    extended_resources=ext,
                    run_dir=run_dir,
                    resume=bool(args.resume),
                    config_path=args.simon_config,
                )
            finally:
                if out is not None:
                    out.close()
            if args.metrics_file:
                import json

                from ..utils.metrics import REGISTRY

                with open(args.metrics_file, "w") as fh:
                    json.dump(REGISTRY.snapshot(), fh, indent=2)
        except (
            ApplyError, ValueError, OSError, BackendUnavailable,
            DeadlineExceeded,
        ) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        return 0 if not outcome.result.unscheduled else 2
    parser.print_help()
    return 0


def _gen_doc(parser: argparse.ArgumentParser, output_dir: str) -> int:
    """Markdown docs, one file per command like cobra's doc generator
    (parity: cmd/doc/generate_markdown.go:38 — GenMarkdownTree emits
    simon.md + simon_<sub>.md with cross-links)."""
    os.makedirs(output_dir, exist_ok=True)
    sub_actions = [
        a for a in parser._actions
        if isinstance(a, argparse._SubParsersAction)
    ]
    commands = dict(sub_actions[0].choices) if sub_actions else {}

    written = []
    root = os.path.join(output_dir, "simon.md")
    with open(root, "w") as fh:
        fh.write("## simon\n\n")
        fh.write(f"{parser.description}\n\n")
        fh.write("```\n" + parser.format_help() + "```\n\n")
        if commands:
            fh.write("### SEE ALSO\n\n")
            for name, sp in commands.items():
                help_line = (sp.description or "").strip()
                fh.write(
                    f"* [simon {name}](simon_{name}.md)"
                    + (f" — {help_line}" if help_line else "")
                    + "\n"
                )
    written.append(root)

    for name, sp in commands.items():
        path = os.path.join(output_dir, f"simon_{name}.md")
        with open(path, "w") as fh:
            fh.write(f"## simon {name}\n\n")
            if sp.description:
                fh.write(f"{sp.description}\n\n")
            fh.write("```\n" + sp.format_help() + "```\n\n")
            fh.write("### SEE ALSO\n\n* [simon](simon.md)\n")
        written.append(path)
    for path in written:
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
