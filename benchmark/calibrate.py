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

The readings are the runner's own (``runners/<kind>.py``: ``calibrate``, and
``sweep`` for ``--rates``), the reference and its control the family's; a
runner that brings neither cannot be calibrated here, and the call says so.
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
    want = "sweep" if args.rates else "calibrate"
    body = getattr(ctx.runner, want, None)
    if body is None:
        raise SystemExit(f"calibrate: the runner of traffic kind "
                         f"{ctx.traffic['kind']!r} ({ctx.runner.__file__}) "
                         f"has no {want}()")
    if args.rates:
        lines = body(ctx, [float(r) for r in args.rates.split(",")],
                     seeds[0], args.seconds)
    else:
        limits = compare.load_limits(manifest.bench_dir, cell["name"])
        lines = body(ctx, seeds, args.seconds, args.control_seeds,
                     args.fault_seeds, limits,
                     CONTROL_OF[ctx.config["run"]["compute_dtype"]])
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
