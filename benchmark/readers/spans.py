"""Engine metrics of the whole window, read from the program's own spans
after the run: the request clocks the runner kept (``ctx.records[i].clocks``),
the process-wide trace ring (each request's ``prefill`` / ``decode_block``
spans) and the phase profiler's timeline (one record a decode block and a
admission, the engine loop's seams), all on ``time.perf_counter`` and cut to
``[ctx.t0, ctx.t0 + ctx.window_s)``.

A program without these public calls (``PhaseProfiler.between``,
``TraceRing.rolled_past``: the commit before PR 26) gives None, and so does a
ring that no longer reaches back to the window's start — never a number of
part of the window."""

from benchmark.harness import stats

#: request spans at whose end tokens became visible to the caller
EMITTING = ("prefill", "decode_block", "verify_block")


def _window(ctx):
    if ctx.t0 is None:
        return None
    return ctx.t0, ctx.t0 + ctx.window_s


def _timeline(ctx):
    """The profiler's sums over the window, or None."""
    win = _window(ctx)
    if win is None:
        return None
    try:
        from deeplearning4j_tpu.observability import default_profiler
        sums = default_profiler().between(*win)
    except (ImportError, AttributeError):
        return None
    return None if sums["truncated"] else sums


def first_token_wait_p95(ctx):
    """p95 over the window's requests of first token minus admission: the
    prefill, and the decode block it queued behind."""
    vals = []
    for r in ctx.records or []:
        clocks = r.clocks or {}
        adm, first = clocks.get("admitted"), clocks.get("first_token")
        if adm is not None and first is not None:
            vals.append((first - adm) * 1e3)
    return stats.percentile(vals, 95)


def emit_gap_p95(ctx):
    """p95 over every pair of consecutive emissions of one request, over
    the requests created in the window: the ends of its ``prefill`` and
    ``decode_block`` spans in the trace ring (``GenerationRequest
    .emissions()`` holds the same stamps; the handles are gone by now)."""
    win = _window(ctx)
    if win is None:
        return None
    try:
        from deeplearning4j_tpu.observability import default_trace_ring
        ring = default_trace_ring()
        if ring.rolled_past(win[0]):
            return None
    except (ImportError, AttributeError):
        return None
    gaps = []
    for trace in ring.recent():
        if not win[0] <= trace.created_at < win[1]:
            continue
        if trace.dropped_spans:
            return None
        ends = sorted(s.t1 for s in trace.spans() if s.name in EMITTING
                      and s.attrs.get("tokens", 1) > 0)
        gaps += [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]
    return stats.percentile(gaps, 95)


def block_host_ms(ctx):
    """Mean per decode block of host + journal + publish: the engine's
    ``dl4j.engine.retire``, ``.journal`` and ``.publish`` seams."""
    sums = _timeline(ctx)
    block = (sums or {}).get("kinds", {}).get("block")
    if not block or not block["n"]:
        return None
    phases = block["phase_seconds"]
    return (phases["host"] + phases["journal"] + phases["publish"]) \
        * 1e3 / block["n"]


def _idle_seconds(acc):
    """Of dispatches with nothing else in flight: from the readback of the
    work dispatched last to the return of the dispatch call (the device
    cannot start what the host has not finished handing over)."""
    return acc["bubble_seconds"] + acc.get("dispatch_seconds", 0.0)


def bubble_share(ctx):
    """Seconds the device certainly sat idle waiting for the host, summed
    over the window's blocks and admissions, over the window (not over the
    device phases: with the double buffer consecutive blocks' dispatch to
    readback spans overlap), in percent. A lower bound of the device
    trace's idle share: the host learns of a completion only when its
    readback returns."""
    sums = _timeline(ctx)
    if not sums or not sums["kinds"]:
        return None
    idle = sum(_idle_seconds(a) for a in sums["bubble_after"].values())
    return 100.0 * idle / ctx.window_s


def admit_gap_ms(ctx):
    """Mean idle stretch that follows an admission: from the prefill's
    readback, through its bookkeeping and the retire of the block it queued
    behind, to the return of the next block's dispatch call (the record
    marked ``after: admission``)."""
    sums = _timeline(ctx)
    after = (sums or {}).get("bubble_after", {}).get("admission")
    if not after or not after["n"]:
        return None
    return _idle_seconds(after) * 1e3 / after["n"]
