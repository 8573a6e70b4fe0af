"""preemption layer: the `victim-search` span (one preemptor's victim search
on the device and its one host read), mean milliseconds per call. None
where the program has no such span."""


def read(ctx):
    return ctx.span_ms_per_call("victim-search")
