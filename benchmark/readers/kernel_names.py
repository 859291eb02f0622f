"""Attention kernels found by NAME in the device trace: each ``pallas_call``
of ``kernels/pallas_attention.py`` carries a ``name=`` (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``), which the compiler makes the name of
the kernel's instruction and so the start of its ``XLA Ops`` event's text
(``%flash_bwd_dq.3 = ...``). Device time a training step, forward and
backward apart; None where no event carries the name (a program before
PR 26 names them ``jvp__`` / ``transpose_jvp___``)."""

from benchmark.harness import trace_reduce


def device_ms_per_step(ctx, needle: str, program: str = "train_step"):
    """Summed device time of the operations whose text starts with
    ``%<needle>``, over the executions of ``program`` inside the traced
    window."""
    if ctx.trace is None:
        return None
    steps = len(trace_reduce.program_times(ctx.trace).get(program, ()))
    calls = [dur for _, dur, text in
             trace_reduce.ops_matching(ctx.trace, "%" + needle)
             if text.startswith("%" + needle)]
    if not steps or not calls:
        return None
    return sum(calls) / 1e6 / steps
