"""The whole step's share of the chip's bf16 peak, from operations counted
by ``flops.py`` (true lengths, nothing recomputed) and ``peaks.json``."""

from benchmark.harness import flops, serve, trace_reduce


def serve_window(ctx):
    """Operations of every prompt and new token of requests completed in
    the window, over the window's seconds."""
    if not ctx.records:
        return None
    total = serve.processed_flops(ctx)
    if total <= 0:
        return None
    return 100.0 * total / (ctx.window_s * ctx.peak["bf16_flops_per_s"])


def train_traced(ctx, program: str = "train_step"):
    """Forward + backward operations per token x tokens per second over the
    peak, the rate taken from the device trace: whole steps between the
    first and the last step's start in the traced window."""
    if ctx.trace is None or not ctx.train:
        return None
    starts = trace_reduce.program_starts(ctx.trace, program)
    if len(starts) < 3:
        return None
    rate = (len(starts) - 1) * ctx.train["tokens_per_step"] \
        / (starts[-1] - starts[0])
    per_token = flops.train_token_flops(ctx.sizes, ctx.train["seq_len"])
    return 100.0 * per_token * rate / ctx.peak["bf16_flops_per_s"]
