"""simulate layer: `bind-placements` spans (the per-pod bind of each
`schedule`'s placements on the host), mean milliseconds per call."""


def read(ctx):
    return ctx.span_ms_per_call("bind-placements")
