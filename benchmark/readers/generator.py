"""Load generator: how late it sent each request."""

from benchmark.harness import loadgen, stats


def late_p95(ctx):
    recs = [r for r in (ctx.records or []) if r.sent is not None]
    if not recs:
        return None
    return stats.percentile(
        loadgen.lateness_ms([r.sent for r in recs], [r.due for r in recs]),
        95)
