#!/usr/bin/env python3
"""One new process, one cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

builds the cell's configuration, warms every shape its traffic uses (set-up),
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of standard
output. It fails, and prints no result, without a TPU that ``peaks.json``
knows or with fewer chips than the cell asks for.

Everything specific to a cell is files found by name, and nothing here or
under ``harness/`` knows a model: BENCHMARK.json names the cell, its
configuration (``configs/``) and its traffic mix (``traffic/``); the
configuration's ``family`` names the model's code (``families/<family>/``:
the program's builder, the seed's weights, the plain reference, the counts);
the mix's ``kind`` names its runner (``runners/<kind>.py``); the per-layer
metrics that list the cell name their readers (``metrics/`` + ``readers/``);
``limits/<cell>.json`` holds what ``correct`` compares. A new architecture
brings a configuration, a family, a traffic mix, limits and its entries (a
runner only for a new kind of traffic), and edits no file that is here
(``harness/manifest.py`` has the rules, ``families/README.md`` and
``runners/README.md`` the two protocols).
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


class Tracer:
    """With ``--trace 1``: a profiler trace bracketed by the ``bench.window``
    span, taken from a helper thread so that starting it never holds up the
    load. ``tick`` starts it ``trace_seconds`` before a window of ``seconds``
    closes (training); ``start`` starts it at once (serving traces a stretch
    of its traffic offered again after the window, see ``trace_replay`` in
    ``runners/open_loop.py``). ``finish`` stops it and reads it — a stop holds the interpreter for some tens of
    seconds per traced second, so it comes only when nothing timed is left."""

    def __init__(self, ctx, seconds: float):
        self.ctx = ctx
        self.enabled = bool(ctx.args.trace)
        self.seconds = float(ctx.traffic.get("trace_seconds", 4.0))
        self.start_at = max(0.0, seconds - min(self.seconds, seconds))
        self._thread = None
        self._stop = threading.Event()
        self._started = threading.Event()
        self.dir = None

    @property
    def started(self) -> bool:
        return self._thread is not None

    def tick(self, now: float) -> None:
        if self.enabled and not self.started and now >= self.start_at:
            self.start()

    def start(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")
        self._thread.start()
        self._started.wait(timeout=30)

    def _run(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                self._started.set()
                self._stop.wait()
        finally:
            jax.profiler.stop_trace()

    def finish(self) -> None:
        if self._thread is None:
            return
        t0 = time.perf_counter()
        self._stop.set()
        self._thread.join(timeout=300)
        t1 = time.perf_counter()
        from benchmark.harness import trace_reduce
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            if files:
                self.ctx.trace = trace_reduce.load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        calls = {} if self.ctx.trace is None else {
            k: len(v)
            for k, v in trace_reduce.program_times(self.ctx.trace).items()}
        print(f"[trace] stopped in {t1 - t0:.1f}s, read in "
              f"{time.perf_counter() - t1:.1f}s; executions inside the "
              f"window: {calls}", file=sys.stderr, flush=True)


class Context:
    """What a run knows, handed to the runner and then to every reader."""

    def __init__(self, manifest, cell, args, peak):
        self.manifest, self.cell, self.args = manifest, cell, args
        self.peak = peak
        self.config = manifest.config(cell["config"])
        self.traffic = manifest.traffic(cell["traffic"])
        self.family = manifest.family(self.config)
        self.runner = manifest.runner(self.traffic)
        self.t_start = _T_START
        self.trace = None
        self.control_precision = ""
        self.control_numbers = None
        self.records = None
        self.train = None
        self.sizes = None
        self.engine_stats = None
        self.window_s = float(args.seconds)
        self.t0 = None
        self.engine_options = None
        self.gc_pauses = None

    def span(self, name: str):
        if not self.args.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def tracer(self, seconds: float) -> Tracer:
        return Tracer(self, seconds)

    @staticmethod
    def memory_peak() -> int:
        import jax
        peak = 0
        for d in jax.local_devices():
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak


def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout,
    every program kept however quickly it compiled and nothing evicted, so
    that only a checkout's first run of a cell compiles. Set in code, over
    JAX_COMPILATION_CACHE_DIR / _MAX_SIZE, as the benchmark's contract has it
    (ISSUE 25 would follow the variable): the parent's checkout and the
    change's then share nothing, and a machine-wide cache with a size limit
    below what a cell's programs take (some 250 MB for the 25 admission
    programs of ``chat-open``) evicts in a cycle and never hits, so that
    every run would compile and pass its time limit (PERF.md, PR 25)."""
    import jax
    path = os.path.join(_ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(chips: int):
    """The device as JAX reports it; no TPU, an unknown kind or too few
    chips ends the run with no result."""
    import jax

    from benchmark.harness import manifest as mf
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"benchmark: jax found no TPU (platform "
                         f"{dev.platform!r}); a cell runs on the chip only")
    peak = mf.peaks(dev.device_kind)
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax "
                         f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import compare, manifest as mf
    manifest = mf.Manifest(_ROOT)
    cell = manifest.cell(args.workload)
    # every file the cell names, found before the chip is looked for: a cell
    # whose family, runner or limits are missing ends here, naming them
    manifest.family(manifest.config(cell["config"]))
    manifest.runner(manifest.traffic(cell["traffic"]))
    compare.load_limits(manifest.bench_dir, cell["name"])
    configure_cache()
    device, peak = find_chips(int(cell["chips"]))
    result = run_cell(manifest, cell, args, device, peak)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(manifest, cell, args, device, peak, prepare=None):
    """Everything of a run after the look for a chip: build, warm, measure,
    compare, read the metrics. ``prepare(ctx)`` lets a test or the
    calibration tool adjust the context before the runner starts."""
    from benchmark.harness import compare
    ctx = Context(manifest, cell, args, peak)
    if prepare is not None:
        prepare(ctx)
    limits = compare.load_limits(manifest.bench_dir, cell["name"])
    print(f"[run] {cell['name']} seed {args.seed} on {device}",
          file=sys.stderr, flush=True)

    runner = ctx.runner
    out = runner.run(ctx)
    correct, rows = compare.judge(out["numbers"], limits)

    metrics = {}
    if args.trace:
        for m in manifest.per_layer(cell["name"]):
            value = manifest.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in manifest.end_to_end(cell["name"]):
            value = out["setup_s"] if m["name"] == "setup_s" \
                else runner.end_to_end(ctx, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if args.trace and ctx.trace is not None:
        from benchmark.harness import trace_reduce
        device["busy_s"] = trace_reduce.busy_seconds(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx.trace, 10),
            "idle_gaps": trace_reduce.idle_gaps(ctx.trace, 10)}
    if ctx.control_numbers is not None:
        result["control"] = ctx.control_numbers
    result["compared"] = rows
    compare.print_rows(rows, correct)
    return result


if __name__ == "__main__":
    sys.exit(main())
