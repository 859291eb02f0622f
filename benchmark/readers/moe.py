"""Expert layers of a decode step: their device time from the trace, their
least time from the engine's counters, and how many experts a step hits.

The program counts, in ``engine.stats()`` (window deltas in
``ctx.engine_stats``), a decode step and an expert layer at a time, of alive
lanes only: ``moe_step_layers`` (step, layer) pairs, ``moe_assignments``
token-expert pairs and ``moe_experts_hit`` distinct experts chosen; and of
every lane a block computes, stopped ones too: ``moe_experts_read``. The
device time is that of the operations which take the experts' weights,
inside the executions of the decode block: the routed-expert kernel by NAME
(``kernels/expert_ffn.py`` names its ``pallas_call`` ``moe_expert_ffn``) or
by the expert stack among its operands ([experts, d, h] / [experts, h, d]),
and the router's and the shared expert's products by their weights' shapes.
What the work needs (``moe_decode_need``) is the family's count, so the
share reads the same work whatever implements it. A program without the
counters or the kernel gives None."""

import sys

from benchmark.harness import flops, trace_reduce

KERNEL = "%moe_expert_ffn"
BLOCK = "decode_block4_impl"


def _counters(ctx):
    st = ctx.engine_stats or {}
    got = [st.get(k) for k in ("moe_step_layers", "moe_assignments",
                               "moe_experts_hit")]
    return None if None in got or not got[0] else got


def experts_hit_share(ctx):
    """Distinct experts an expert layer's decode step hits, of all of them,
    over the window."""
    got = _counters(ctx)
    need = getattr(ctx.family, "flops", None)
    if got is None or not ctx.sizes or need is None:
        return None
    return 100.0 * got[2] / (ctx.sizes["experts"] * got[0])


def experts_read_share(ctx):
    """Distinct experts an expert layer's decode step computes and reads,
    of all of them, over the window: every lane of the block, whether a
    request holds it or not. What it reads above ``experts_hit_share`` is
    work for no request."""
    got = _counters(ctx)
    read = (ctx.engine_stats or {}).get("moe_experts_read")
    if got is None or read is None or not ctx.sizes:
        return None
    return 100.0 * read / (ctx.sizes["experts"] * got[0])


def ops_in_decode_blocks(trace):
    """(device operations that ran inside an execution of the decode block
    in the window, the number of those executions)."""
    lo, hi = trace.window
    blocks = [(s, s + d) for evs in trace.modules.values()
              for s, d, name in evs
              if trace_reduce.program_name(name) == BLOCK and s >= lo
              and s + d <= hi]
    # a loop's event spans its body's: the block's scan is left out, its
    # body's operations are what is counted
    return [e for evs in trace.ops.values() for e in evs
            if " while(" not in e[2]
            and any(a <= e[0] and e[0] + e[1] <= b for a, b in blocks)], \
        len(blocks)


def _weights(sizes):
    """Operand shapes that mark an operation as an expert layer's."""
    d, f, e = sizes["d"], sizes["expert_ffn"], sizes["experts"]
    held, fs = sizes["experts_held"], sizes["shared"] * sizes["expert_ffn"]
    return {(held, d, f), (held, f, d), (d, e), (d, fs), (fs, d)}


def _expert_ops(ctx):
    """(device ns of the expert layers' operations inside decode blocks,
    calls of the routed-expert kernel there, decode blocks)."""
    ops, blocks = ops_in_decode_blocks(ctx.trace)
    s = ctx.sizes
    marks = _weights(s)
    stack = (s["experts_held"], s["d"], s["expert_ffn"])
    total, calls = 0.0, 0
    for _, dur, text in ops:
        shapes = set(trace_reduce.operand_shapes(text))
        kernel = text.startswith(KERNEL) or stack in shapes
        if kernel or marks & shapes:
            total += dur
            calls += int(kernel)
    return total, calls, blocks


def token_ms(ctx):
    """Device ms a decoded step in the expert layers' operations."""
    if ctx.trace is None or not ctx.sizes or "experts" not in ctx.sizes:
        return None
    total, calls, blocks = _expert_ops(ctx)
    if not calls or not blocks:
        return None
    return total / 1e6 / (blocks * 4)


def roofline(ctx):
    """The least time the chip could take for an expert layer's decode
    step (the window's counters, the family's count of what they need),
    over the device time one took in the traced stretch."""
    got = _counters(ctx)
    if ctx.trace is None or got is None or not ctx.sizes:
        return None
    total, calls, _ = _expert_ops(ctx)
    if not calls:
        return None
    need = ctx.family.flops.moe_decode_need(ctx.sizes, *got)
    least = flops.roofline_seconds(need["flops"], need["bytes"], ctx.peak)
    print(f"[moe] {got[0]} step-layers in the window, {calls} traced; "
          f"bound by {least['bound']}", file=sys.stderr)
    return 100.0 * (least["seconds"] / got[0]) / (total / 1e9 / calls)
