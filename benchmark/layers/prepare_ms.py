"""simulate layer: `prepare-cluster` span (Simulator construction: pod
validation, copies of the bound and pending pods, DaemonSets, PDBs), mean
milliseconds per call."""


def read(ctx):
    return ctx.span_ms_per_call("prepare-cluster")
