"""Durable runs: journaled checkpoint/resume + watchdog-guarded execution.

`journal` is the per-run write-ahead log (JSONL, fsync per record) that
lets a crashed/preempted capacity sweep or bench ladder resume from its
committed trials; `watchdog` puts hard deadlines around backend
acquisition and blocking device calls, so a backend that fails or hangs
fails the run loudly, naming the platform. See docs/durability.md.
"""

from .checkpoint import (  # noqa: F401
    CheckpointError,
    PlanCheckpointer,
    active_checkpointer,
    checkpoint_every,
)
from .journal import (  # noqa: F401
    JournalError,
    RunJournal,
    atomic_write,
    completed_segments,
    default_runs_root,
    list_runs,
    replay,
    summarize_run,
)
from .watchdog import (  # noqa: F401
    BackendUnavailable,
    DeadlineExceeded,
    acquire_backend,
    backend_deadline_s,
    call_deadline_s,
    guarded_call,
)
