"""commit engine layer: `host-sync` spans (each one device->host read,
`utils/tracing.fetch`), mean milliseconds per call: the time the host
waited on the device. Most of them nest in `schedule`."""


def read(ctx):
    return ctx.span_ms_per_call("host-sync")
