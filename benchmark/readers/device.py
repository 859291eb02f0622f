"""Device: share of the traced window in which no operation ran."""

from benchmark.harness import trace_reduce


def idle_share(ctx):
    if ctx.trace is None:
        return None
    return trace_reduce.idle_share(ctx.trace)
