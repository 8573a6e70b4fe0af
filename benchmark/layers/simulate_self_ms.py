"""simulate layer: the root span's self time, mean milliseconds per call:
`simulate` less its direct children, which on the benchmark's path are
these seven (nothing in it nests them deeper). None where the program has
no `prepare-cluster` span, so the root does not cover construction."""

CHILDREN = ("prepare-cluster", "expand-workloads", "encode-cluster", "encode",
            "schedule", "bind-placements", "decode-result")


def read(ctx):
    if "prepare-cluster" not in ctx.spans:
        return None
    root = ctx.span_ms_per_call("simulate")
    if root is None:
        return None
    return root - ctx.span_ms_per_call(*CHILDREN)
