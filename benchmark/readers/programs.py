"""Compiled programs: device time per execution, from the device trace."""

import statistics

from benchmark.harness import trace_reduce


def device_ms_per_call(ctx, program: str, divide: float = 1.0):
    if ctx.trace is None:
        return None
    times = trace_reduce.program_times(ctx.trace).get(program)
    if not times:
        return None
    return statistics.fmean(times) * 1e3 / float(divide)
