"""commit engine layer: `host-sync` spans per call: the host's device->host
round trips."""


def read(ctx):
    if "host-sync" not in ctx.spans or not ctx.calls:
        return None
    return ctx.spans["host-sync"][1] / ctx.calls
