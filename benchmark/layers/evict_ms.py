"""preemption layer: the `evict` span (the chosen victims leave the victim
table and the carry), mean milliseconds per call. None where the program
has no such span."""


def read(ctx):
    return ctx.span_ms_per_call("evict")
