def read(ctx):
    """The commit engine's `schedule` span, ms per call."""
    return ctx.span_ms_per_call("schedule")
