def read(ctx):
    """`simulate` calls in the window."""
    return ctx.calls
