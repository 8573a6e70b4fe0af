"""preemption layer: the `preemption` span (the DefaultPreemption pass over
a batch's failures: victim searches, evictions and the preemptors'
retries), mean milliseconds per call. None where no pod preempted."""


def read(ctx):
    return ctx.span_ms_per_call("preemption")
