"""Latent attention of a decode step: device time of the operations that
read or write the latent slab (an operand or a result whose last two
dimensions are the slab's ``[t_max, kv_rank + rope]``: the absorbed scores
and sums, and the row writes) inside the executions of the decode block. A
program with no such slab gives None."""

from benchmark.harness import trace_reduce
from benchmark.readers.moe import ops_in_decode_blocks


def token_ms(ctx):
    """Device ms a decoded step in attention over the latent slab."""
    s = ctx.sizes or {}
    if ctx.trace is None or "kv_rank" not in s:
        return None
    slab = (int(ctx.engine_options["t_max"]), s["kv_rank"] + s["rope"])
    ops, blocks = ops_in_decode_blocks(ctx.trace)
    total = sum(dur for _, dur, text in ops
                if any(tuple(sh[-2:]) == slab
                       for sh in trace_reduce.operand_shapes(text)
                       + trace_reduce.result_shapes(text)))
    if not blocks or total <= 0:
        return None
    return total / 1e6 / (blocks * 4)
