"""The shortcut-connected expert branch of a decode step, and the dense
FFNs beside it: device time from the trace, the branch's least time from the
engine's counters, and what the router's choices were.

The program counts, in ``engine.stats()`` (window deltas in
``ctx.engine_stats``), a decode step and an expert branch at a time, of alive
lanes only: ``moe_step_layers`` (step, layer) pairs, ``moe_assignments``
choices (a token's ``top_k``), of which ``moe_zero_assignments`` fell on
zero-compute experts and ``moe_held_assignments`` on experts held here (the
rest are held on other chips), and ``moe_experts_hit`` distinct held experts
chosen. A trace's events carry an operation's HLO text only (no scope), so
the branch's operations are found by what they touch, inside the executions
of the decode block: the routed-expert kernel by NAME
(``kernels/expert_ffn.py`` names its ``pallas_call`` ``moe_expert_ffn``) or
by the expert stack among its operands; and every operation with an operand
or a result as wide as the router (``experts + zero``: the router's product,
the softmax, the top-k), shaped [lanes, top_k(, d)] (the choices, the gates,
the gather back, the zero-compute term), or shaped as the kernel's own row
and tile operands are (the sort, the layout, the row gather). What the work
needs (``moe_decode_need``) is the family's count, so the share reads the
same work whatever implements it. A program without the counters or the
kernel gives None."""

import sys

from benchmark.harness import flops, trace_reduce
from benchmark.readers.moe import KERNEL, ops_in_decode_blocks

COUNTERS = ("moe_step_layers", "moe_assignments", "moe_held_assignments",
            "moe_zero_assignments", "moe_experts_hit")


def _counters(ctx):
    st = ctx.engine_stats or {}
    got = [st.get(k) for k in COUNTERS]
    return None if None in got or not got[0] or not got[1] else got


def zero_expert_share(ctx):
    """Alive lanes' choices that fell on zero-compute experts, of all their
    choices, over the window (even routing: zero / (experts + zero))."""
    got = _counters(ctx)
    return None if got is None else 100.0 * got[3] / got[1]


def held_experts_hit_share(ctx):
    """Distinct experts held here that an expert branch's decode step hits,
    of those held, over the window."""
    got = _counters(ctx)
    if got is None or not ctx.sizes or "experts_held" not in ctx.sizes:
        return None
    return 100.0 * got[4] / (ctx.sizes["experts_held"] * got[0])


def _shapes(text):
    return trace_reduce.operand_shapes(text) \
        + trace_reduce.result_shapes(text)


def _branch_ops(ctx):
    """(device ns of the expert branch's operations inside decode blocks,
    calls of the routed-expert kernel there, decode blocks)."""
    ops, blocks = ops_in_decode_blocks(ctx.trace)
    s = ctx.sizes
    d, width = s["d"], s["experts"] + s["zero"]
    stack = (s["experts_held"], d, s["expert_ffn"])
    lanes, k = int(ctx.engine_options["num_slots"]), s["top_k"]
    marks = {(lanes, k), (lanes, k, d), (lanes * k,)}
    for _, _, text in ops:            # the kernel's own rows and tiles
        if text.startswith(KERNEL):
            for sh in trace_reduce.operand_shapes(text):
                if len(sh) == 1 or (len(sh) == 2 and sh[1] == d):
                    marks.update({sh, sh[:1]})
            break
    total, calls = 0.0, 0
    for _, dur, text in ops:
        shapes = _shapes(text)
        kernel = text.startswith(KERNEL) or stack in shapes
        if kernel or any(sh in marks or width in sh for sh in shapes):
            total += dur
            calls += int(kernel)
    return total, calls, blocks


def _traced(ctx):
    if ctx.trace is None or not ctx.sizes or "zero" not in ctx.sizes:
        return None
    total, calls, blocks = _branch_ops(ctx)
    return (total, calls, blocks) if calls and blocks else None


def token_ms(ctx):
    """Device ms a decoded step in the expert branches' operations."""
    got = _traced(ctx)
    return None if got is None else got[0] / 1e6 / (got[2] * 4)


def roofline(ctx):
    """The least time the chip could take for an expert branch's decode
    step (the window's counters, the family's count of what they need),
    over the device time one took in the traced stretch."""
    got, traced = _counters(ctx), _traced(ctx)
    if got is None or traced is None:
        return None
    total, calls, _ = traced
    need = ctx.family.flops.moe_decode_need(ctx.sizes, *got)
    least = flops.roofline_seconds(need["flops"], need["bytes"], ctx.peak)
    print(f"[scmoe] {got[0]} step-layers in the window, {calls} traced; "
          f"bound by {least['bound']}", file=sys.stderr)
    return 100.0 * (least["seconds"] / got[0]) / (total / 1e9 / calls)


def dense_ffn_token_ms(ctx):
    """Device ms a decoded step in the operations that take a dense FFN's
    [d, ffn] / [ffn, d] weights."""
    s = ctx.sizes or {}
    if ctx.trace is None or "dense_ffn" not in s:
        return None
    marks = {(s["d"], s["dense_ffn"]), (s["dense_ffn"], s["d"])}
    ops, blocks = ops_in_decode_blocks(ctx.trace)
    total = sum(dur for _, dur, text in ops
                if marks & set(trace_reduce.operand_shapes(text)))
    if not blocks or total <= 0:
        return None
    return total / 1e6 / (blocks * 4)
