"""The state-space mixers of a decode step: their device time from the trace,
the state update's share of its roofline, and how much of the state-update
work went to lanes a request holds.

The program counts, in ``engine.stats()`` (window deltas in
``ctx.engine_stats``), a decode step at a time: ``ssm_step_layers`` (alive
lanes x state-space layers) and ``ssm_lane_layers`` (every lane x those
layers: what the step computed). A trace's events carry an operation's HLO
text only (no scope), so the mixers' operations are found by what they
touch, inside the executions of the decode block: the state-update kernel by
NAME (``kernels/ssm_update.py`` names its ``pallas_call``
``ssm_decode_update``) or by the state [slots, H, P, N] among its operands,
and every operation with an operand or a result as wide as the mixer's own
widths (in_proj's output, the convolution's channels, the inner width) —
the projections, the convolution, the gate and its norm. What one update
call needs (``ssm_decode_need``) is the family's static count, so the share
reads the same work whatever implements it, and it takes no window counter:
the numerator and the denominator are of the same traced calls. A program
without the kernel or the counters gives None."""

import statistics
import sys

from benchmark.harness import flops, trace_reduce
from benchmark.readers.moe import ops_in_decode_blocks

KERNEL = "%ssm_decode_update"


def _widths(s):
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    conv = inner + 2 * s["ssm_state"]
    return {inner, conv, inner + conv + s["ssm_heads"]}


def _mixer_ops(ctx):
    """([(device ns, is the kernel)] of the mixers' operations inside
    decode blocks, decode blocks)."""
    ops, blocks = ops_in_decode_blocks(ctx.trace)
    s = ctx.sizes
    lanes = int(ctx.engine_options["num_slots"])
    state = (lanes, s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"])
    widths = _widths(s)
    out = []
    for _, dur, text in ops:
        shapes = trace_reduce.operand_shapes(text) \
            + trace_reduce.result_shapes(text)
        kernel = text.startswith(KERNEL)
        if kernel or state in shapes or any(
                sh and sh[-1] in widths for sh in shapes):
            out.append((dur, kernel))
    return out, blocks


def _traced(ctx):
    if ctx.trace is None or not ctx.sizes or "ssm_heads" not in ctx.sizes:
        return None
    ops, blocks = _mixer_ops(ctx)
    return (ops, blocks) if blocks and any(k for _, k in ops) else None


def token_ms(ctx):
    """Device ms a decoded step in the state-space mixers' operations."""
    got = _traced(ctx)
    if got is None:
        return None
    ops, blocks = got
    return sum(d for d, _ in ops) / 1e6 / (blocks * 4)


def roofline(ctx):
    """The least time the chip could take for one state-update call (the
    family's count of what it moves) over the mean device time of the
    traced calls."""
    got = _traced(ctx)
    if got is None:
        return None
    calls = [d for d, k in got[0] if k]
    need = ctx.family.flops.ssm_decode_need(
        ctx.sizes, int(ctx.engine_options["num_slots"]))
    least = flops.roofline_seconds(need["flops"], need["bytes"], ctx.peak)
    mean_s = statistics.fmean(calls) / 1e9
    print(f"[ssm] {len(calls)} update calls traced, {mean_s * 1e6:.1f} us "
          f"each; bound by {least['bound']} ({least['seconds'] * 1e6:.1f} "
          f"us)", file=sys.stderr)
    return 100.0 * least["seconds"] / mean_s


def alive_lane_share(ctx):
    """State updates of alive lanes, of all the updates the decode steps
    computed, over the window."""
    st = ctx.engine_stats or {}
    alive, every = st.get("ssm_step_layers"), st.get("ssm_lane_layers")
    if not alive or not every:
        return None
    return 100.0 * alive / every
