"""The whole step's share of the chip's bf16 peak, from model operations as
the cell's family counts them (``ctx.family``: true lengths, nothing
recomputed) and ``peaks.json``."""

from benchmark.harness import trace_reduce


def processed_flops(ctx) -> float:
    """Model operations of every prompt and new token of the requests that
    completed inside the window (true lengths, no padding)."""
    total = 0.0
    for r in ctx.records:
        if r.error is None and r.done is not None and r.done <= ctx.window_s:
            p, g = len(r.request.prompt), r.request.new_tokens
            total += ctx.family.prompt_flops(ctx.sizes, p) \
                + ctx.family.decode_flops(ctx.sizes, p, g)
    return total


def serve_window(ctx):
    """Operations of every prompt and new token of requests completed in
    the window, over the window's seconds."""
    if not ctx.records:
        return None
    total = processed_flops(ctx)
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
    per_token = ctx.family.train_token_flops(ctx.sizes,
                                             ctx.train["seq_len"])
    return 100.0 * per_token * rate / ctx.peak["bf16_flops_per_s"]
