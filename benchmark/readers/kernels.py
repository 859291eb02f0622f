"""Attention kernels (Mosaic custom calls in the device trace): the least
time the chip could take for what each call needs, from its operand shapes
(``flops.attention_kernel``), over the device time the calls took."""

import sys

from benchmark.harness import flops, trace_reduce

MOSAIC = 'custom_call_target="tpu_custom_call"'


def _call_need(hlo_text: str, peak):
    """Seconds at the roofline for one kernel call, or None where its text
    does not look like [batch*heads, T, D] attention operands."""
    operands = [s for s in trace_reduce.operand_shapes(hlo_text)
                if len(s) == 3]
    if len(operands) < 3:
        return None
    bh, t, d = operands[0]
    moved = [s for s in operands + trace_reduce.result_shapes(hlo_text)
             if s == (bh, t, d)]
    # every kernel of the family (forward; backward dq; backward dk and dv)
    # needs two causal products for what it returns
    need = flops.attention_kernel(bh, t, t, d, causal=True, products=2,
                                  tensors=len(moved))
    return flops.roofline_seconds(need["flops"], need["bytes"], peak)


def attention_roofline(ctx):
    if ctx.trace is None:
        return None
    calls = trace_reduce.ops_matching(ctx.trace, MOSAIC)
    need_s, took_s, bounds = 0.0, 0.0, {}
    for _, dur, text in calls:
        need = _call_need(text, ctx.peak)
        if need is None:
            continue
        need_s += need["seconds"]
        took_s += dur / 1e9
        bounds[need["bound"]] = bounds.get(need["bound"], 0) + 1
    if took_s <= 0:
        return None
    print(f"[kernels] {sum(bounds.values())} attention kernel calls, bound "
          f"by {bounds}", file=sys.stderr)
    return 100.0 * need_s / took_s
