"""Host runtime: what Python's collector held the interpreter for inside the
window (``serve.collector_watch``), load generator and the engine's host
loop alike."""


def gc_pause_ms(ctx):
    """Every collection's pause inside the window, all generations, summed."""
    if not ctx.gc_pauses:
        return None
    return sum(s for _, s in ctx.gc_pauses) * 1e3
