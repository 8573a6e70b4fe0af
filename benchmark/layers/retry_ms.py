"""preemption layer: the `retry` span (a preemptor's eviction and one
scheduling step as one device program, its host read and the bind), mean
milliseconds per call. None where the program has no such span."""


def read(ctx):
    return ctx.span_ms_per_call("retry")
