#!/usr/bin/env python3
"""Readings for the limits in ``limits/<cell>.json``, taken on the chip at
the cell's own size, many seeds in ONE process (set-up is paid once):

    python3 benchmark/calibrate.py --workload <cell> --seeds 201,202,... \\
        --seconds 12 --control-seeds 3 --fault-seeds 3

per seed the program's numbers (the lower reading is their largest); on the
first ``--control-seeds`` seeds also the control — the reference put in the
program's place in the nearest precision below the one the configuration
states; on the last ``--fault-seeds`` seeds the faults the cell can have.
Every reading goes through ``compare.judge`` with the cell's own limits, and
the line says how it was judged (``verdict``: a sound run has to read
correct, a control or a fault not correct). The benchmark's own runs never
run this; it prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

#: the nearest precision below the one a configuration computes in
CONTROL_OF = {"float32": "bfloat16", "bfloat16": "fp8"}


def _verdict(limits, numbers, over=None):
    """``correct`` as a run's last line would say it, of ``numbers`` with
    those in ``over`` put in their place (of the limits, those whose number
    these readings produce: a window's own, as the closing loss, are not)."""
    from benchmark.harness import compare
    got = dict(numbers, **(over or {}))
    return compare.judge(got, {k: v for k, v in limits.items()
                               if k in got})[0]


def _serve(ctx, seeds, seconds, n_control, n_fault, limits):
    from benchmark.harness import serve
    session = serve.Session(ctx)
    vocab = session.sizes["vocab"]
    control = CONTROL_OF[ctx.config["run"]["compute_dtype"]]
    for i, seed in enumerate(seeds):
        fault = i >= len(seeds) - n_fault
        ctx.control_precision = control if i < n_control else ""
        ctx.control_numbers = None
        session.install(seed)
        undo = _alter_tokens(vocab) if fault else None
        session.settle()
        try:
            records, compiles = session.window(seed, seconds)
        finally:
            if undo:
                undo()
        numbers = serve.check(ctx, session.sizes, seed, records, compiles)
        verdict = {"token_altered" if fault else "program":
                   _verdict(limits, numbers)}
        if ctx.control_numbers is not None:
            verdict["control"] = _verdict(limits, numbers,
                                          ctx.control_numbers)
        pauses = ctx.gc_pauses or []
        yield {"seed": seed, "fault": "token_altered" if fault else None,
               "numbers": numbers, "control": ctx.control_numbers,
               "verdict": verdict,
               "end_to_end": {m: serve.end_to_end(ctx, m) for m in (
                   "ttft_p95_ms", "tpot_p95_ms")},
               "gc": {"collections": len(pauses),
                      "full": sum(g == 2 for g, _ in pauses),
                      "ms": sum(s for _, s in pauses) * 1e3},
               "requests": len(records),
               "failed": sum(r.error is not None for r in records)}
    session.close()


def _sweep(ctx, rates, seed, seconds):
    """The knee, found once: the same mix offered at each of a few fixed
    rates from one engine; per rate the tails, the tokens completed inside
    the window and how much was still unfinished when it closed."""
    from benchmark.harness import serve
    session = serve.Session(ctx)
    for rate in rates:
        ctx.traffic["rate_per_s"] = float(rate)
        session.settle()
        records, _ = session.window(seed, seconds)
        done_in = [r for r in records if r.error is None
                   and r.done is not None and r.done <= seconds]
        yield {"rate_per_s": rate, "requests": len(records),
               "failed": sum(r.error is not None for r in records),
               "finished_in_window": len(done_in),
               "ttft_p95_ms": serve.end_to_end(ctx, "ttft_p95_ms"),
               "tpot_p95_ms": serve.end_to_end(ctx, "tpot_p95_ms"),
               "new_tokens_per_s": sum(r.request.new_tokens
                                       for r in done_in) / seconds,
               "last_done_s": max((r.done or 0.0) for r in records)}
        for r in records:
            r.handle = None
    session.close()


def _alter_tokens(vocab):
    """A token altered where it is produced: the last token of every
    request, as it completes."""
    from deeplearning4j_tpu.models.generation import GenerationRequest
    real = GenerationRequest._complete

    def altered(self):
        if self.generated:
            self.generated[-1] = (self.generated[-1] + 1) % vocab
        real(self)
    GenerationRequest._complete = altered

    def undo():
        GenerationRequest._complete = real
    return undo


def _train(ctx, seeds, seconds, n_control, n_fault, limits):
    from benchmark.harness import reference, train
    session = train.Session(ctx)
    config, traffic = ctx.config, ctx.traffic
    adam = config["run"]["optimizer"]
    control = CONTROL_OF[config["run"]["compute_dtype"]]
    rows = int(traffic.get("reference_rows", 2))
    for i, seed in enumerate(seeds):
        session.install(seed)
        prog = session.first_steps(seed)
        batches = session.host_batches[:train.CHECK_STEPS]
        session.release()
        ref = reference.train_steps(session.sizes, seed, batches, adam,
                                    rows_per_block=rows)
        ref.pop("params")
        out = {"seed": seed, "numbers": train.numbers_of(
            prog["losses"], prog["grad_norms"], prog["change_norms"], ref)}
        out["verdict"] = {"program": _verdict(limits, out["numbers"])}
        if i < n_control:
            low = reference.train_steps(session.sizes, seed, batches, adam,
                                        precision=control,
                                        rows_per_block=rows)
            out["control"] = train.numbers_of(
                low["losses"], low["grad_norms"], low["change_norms"], ref)
            out["verdict"]["control"] = _verdict(limits, out["numbers"],
                                                 out["control"])
        if i >= len(seeds) - n_fault:
            half = list(range(session.rows // 2))
            low = reference.train_steps(session.sizes, seed, batches, adam,
                                        rows_per_block=rows, keep=half)
            out["fault_half_batch"] = train.numbers_of(
                low["losses"], low["grad_norms"], low["change_norms"], ref)
            out["verdict"]["half_batch"] = _verdict(
                limits, out["numbers"], out["fault_half_batch"])
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--rates", default="",
                    help="open loop only: sweep these rates instead")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmark import run as bench_run
    from benchmark.harness import compare, manifest as mf
    manifest = mf.Manifest(_ROOT)
    cell = manifest.cell(args.workload)
    bench_run.configure_cache()
    device, peak = bench_run.find_chips(int(cell["chips"]))
    run_args = argparse.Namespace(workload=args.workload, seed=seeds[0],
                                  seconds=args.seconds, trace=0)
    ctx = bench_run.Context(manifest, cell, run_args, peak)
    if args.rates:
        lines = _sweep(ctx, [float(r) for r in args.rates.split(",")],
                       seeds[0], args.seconds)
    else:
        body = _train if ctx.traffic["kind"] == "train" else _serve
        limits = compare.load_limits(manifest.bench_dir, cell["name"])
        lines = body(ctx, seeds, args.seconds, args.control_seeds,
                     args.fault_seeds, limits)
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
