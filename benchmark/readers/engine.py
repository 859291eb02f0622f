"""Engine / scheduler: queue wait, from the request clocks the engine stamps
(``_created_t``, ``_admitted_t``, ``_first_token_t``: program spans on
``time.perf_counter``; private today, public accessors are the tracing
issue's)."""

from benchmark.harness import stats


def queue_wait_p95(ctx):
    """Admission minus due time (the engine's admission clock against the
    benchmark's schedule)."""
    vals = []
    for r in ctx.records or []:
        adm = (r.clocks or {}).get("admitted")
        if adm is not None:
            vals.append((adm - ctx.t0 - r.due) * 1e3)
    return stats.percentile(vals, 95)
