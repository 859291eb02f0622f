"""Runner for serving cells (traffic kind ``open_loop``): the window drives
``SlotGenerationEngine.start()/submit()/result()`` and nothing else of the
program. General over model families: the net with the seed's weights, the
ids the traffic may draw (``sizes["vocab"]``) and the plain reference come
from ``ctx.family`` (``families/README.md``), and nothing else does.

Set-up: weights from the seed, the engine, then a warm-up that sends one
admission batch per (count bucket x padded length) the mix can reach and a
few decode blocks, synchronously, so that the window compiles nothing.
Window: an open loop sends each request when it is due and times it from
then. The benchmark takes every end-to-end clock itself: it polls each
handle's ``generated`` list for the first token and stamps completion in a
done callback, both on ``time.perf_counter``.
After the window, with ``--trace 1``: the mix again from its beginning, the
same arrivals and lengths, a stretch of it under the profiler
(:func:`trace_replay`). Then the program's state is freed, and the plain
reference runs once over a seeded sample of finished requests (the longest
included). ``calibrate`` and ``sweep`` are ``benchmark/calibrate.py``'s
readings for this kind of traffic: many seeds from one engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.harness import compare, loadgen, stats

POLL_S = 0.002                 # first-token poll; TTFT is read to this grain
DRAIN_S = 60.0                 # wait this long past the close for answers


@dataclasses.dataclass
class Record:
    request: loadgen.Request
    due: float = 0.0           # seconds after the window opened
    sent: Optional[float] = None
    first: Optional[float] = None
    done: Optional[float] = None
    handle: object = None
    error: Optional[str] = None
    clocks: Optional[Dict] = None   # the engine's own request clocks


def _say(msg: str) -> None:
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ set-up
def warm_up(engine, traffic: Dict, vocab: int, seed: int) -> None:
    """The one warm-up: every (count bucket, padded length) the mix can
    reach is compiled or loaded from the cache, side by side
    (:func:`precompile`), and then run once as an admission batch through
    ``submit`` + ``run_until_drained`` (0.0-0.3 s each), before ``start``;
    then decode blocks from a host carry and from the device carry."""
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    dist = traffic["prompt_tokens"]
    t_max, slots = engine.t_max, engine.num_slots
    lengths = loadgen.length_buckets(dist, t_max)
    counts = loadgen.count_buckets(slots)
    precompile(engine, [(cb, tp) for tp in lengths for cb in counts],
               max(1, min(8, (os.cpu_count() or 2) - 1)))
    for tp in lengths:
        n = max(min(tp, int(dist["max"]), t_max - 2), int(dist["min"]))
        for cb in counts:
            t0 = time.perf_counter()
            hs = [engine.submit(rng.integers(0, vocab, n).astype(np.int32), 1)
                  for _ in range(cb)]
            engine.run_until_drained()
            for h in hs:
                h.result(timeout=0)
            _say(f"warm prefill count {cb} length {tp}: "
                 f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    n = int(dist["min"])
    new = 3 * engine.block_size + 1
    hs = [engine.submit(rng.integers(0, vocab, n).astype(np.int32), new)
          for _ in range(slots)]
    engine.run_until_drained()
    for h in hs:
        h.result(timeout=0)
    _say(f"warm decode: {time.perf_counter() - t0:.1f}s")


def precompile(engine, shapes: List, workers: int) -> None:
    """Compile the admission programs of ``shapes`` [(count, padded
    length)] side by side (a cold checkout otherwise compiles some thirty
    programs, each unrolled over 36 layers, one after another; a warm one
    loads them from the cache side by side). Ahead-of-time ``lower().compile()`` of the decoder's
    own jitted function on the engine's own arguments, so the warm-up's real
    calls then find every program in the cache. It reaches into the
    decoder's cost seam and the engine's cache and key, which are not
    public (the engine has no ``warm(shapes)`` of its own; PERF.md, Open
    questions). A later PR may move them and may not edit this file: then
    this step says so on standard error and the warm-up's calls compile one
    by one, a slower first run of a checkout and the same warm run."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    try:
        dec = engine.decoder
        dec._fn("prefill_slots")
        jitted = dec._cost_seam["prefill_slots_impl"][0]
        fixed = (dec._device_params(), dec.net._inference_state(),
                 engine._caches)
        key = jax.random.fold_in(engine._key, 1)
    except (AttributeError, KeyError, TypeError) as exc:
        _say(f"WARNING: the decoder's seam has moved ({type(exc).__name__}: "
             f"{exc}); {len(shapes)} admission programs compile one by one")
        return

    def one(shape):
        m, tp = shape
        jitted.lower(*fixed, jnp.zeros((m, tp), jnp.int32),
                     jnp.zeros(m, jnp.int32), jnp.zeros(m, jnp.int32),
                     jnp.zeros(m, jnp.float32), key).compile()

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(one, shapes))
    _say(f"{len(shapes)} admission programs ahead of time: "
         f"{time.perf_counter() - t0:.1f}s")


class CompileCounter:
    """Counts what compiles between ``mark`` and ``since``: jax's own
    monitoring events for a lowering and for a backend compile (a cache hit
    still lowers). The benchmark's own count, beside the program's
    ``CompileAudit``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1

    def mark(self) -> int:
        return self.count

    def since(self, mark: int) -> int:
        return self.count - mark


@contextlib.contextmanager
def collector_watch(ctx):
    """Around the window: every pause of Python's collector, as (generation,
    seconds), left in ``ctx.gc_pauses``. The collector itself is left as any
    serving process has it: on, with its thresholds. Set-up ends in one
    ``gc.collect()`` (:meth:`Session.settle`): tracing 25 admission programs
    leaves 2.4 M tracked objects, and the full collection they set off, 1.3 s
    with the interpreter held, otherwise lands at some moment of the window
    (three runs of six read ``ttft_p95_ms`` over a second for it; PERF.md,
    PR 25). What the collector still costs inside the window is the
    per-layer metric ``gc_pause_ms.chat``."""
    pauses: List = []
    started = [0.0]

    def watch(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((int(info["generation"]),
                           time.perf_counter() - started[0]))
    gc.callbacks.append(watch)
    try:
        yield
    finally:
        gc.callbacks.remove(watch)
        ctx.gc_pauses = pauses
        full = [s for g, s in pauses if g == 2]
        _say(f"collector: {len(pauses)} collections in the window, "
             f"{sum(s for _, s in pauses) * 1e3:.1f} ms together; "
             f"{len(full)} full, longest "
             f"{max(full, default=0.0) * 1e3:.1f} ms")


# ------------------------------------------------------------------ windows
def _submit(engine, rec: Record, t0: float, span) -> None:
    req = rec.request
    rec.sent = time.perf_counter() - t0
    with span("bench.submit"):
        h = engine.submit(req.prompt, req.new_tokens,
                          temperature=req.temperature)
    rec.handle = h

    def _done(handle, rec=rec):
        rec.done = time.perf_counter() - t0
    h.add_done_callback(_done)


def _error_of(handle) -> Optional[str]:
    try:
        handle.result(timeout=0)
        return None
    except Exception as exc:   # noqa: BLE001 — any failure is a failed request
        return f"{type(exc).__name__}: {exc}"


def _offer(engine, records: List[Record], seconds: float, span,
           tick=None, wait_first: bool = True) -> float:
    """Send each request when it is due and poll for first tokens, until
    ``seconds`` have passed (and, with ``wait_first``, every request sent
    has its first token or has ended). ``tick(now)``, where given, is called
    once a turn and returns the seconds it held the loop up, which the
    schedule's clock then skips. Returns the clock's zero."""
    waiting: List[Record] = []
    nxt, n = 0, len(records)
    t0 = time.perf_counter()
    while True:
        if tick is not None:
            t0 += tick(time.perf_counter() - t0)
        now = time.perf_counter() - t0
        while nxt < n and records[nxt].due <= now:
            _submit(engine, records[nxt], t0, span)
            waiting.append(records[nxt])
            nxt += 1
        if waiting:
            now = time.perf_counter() - t0
            still = []
            for rec in waiting:
                h = rec.handle
                if h.generated or h.done():
                    rec.first = now
                else:
                    still.append(rec)
            waiting = still
        now = time.perf_counter() - t0
        if now >= seconds and (not wait_first or (nxt >= n and not waiting)):
            break
        if now >= seconds + DRAIN_S:
            break
        wake = now + POLL_S
        if nxt < n:
            wake = min(wake, records[nxt].due)
        pause = wake - (time.perf_counter() - t0)
        if pause > 0:
            with span("bench.wait_next_arrival" if not waiting
                      else "bench.poll_first_token"):
                time.sleep(pause)
    return t0


def open_loop(engine, schedule: List[loadgen.Request], seconds: float,
              span):
    """Send each request when it is due; poll for first tokens; after the
    close wait up to DRAIN_S for every answer."""
    records = [Record(r, due=r.due_s) for r in schedule]
    t0 = _offer(engine, records, seconds, span)
    deadline = t0 + seconds + DRAIN_S
    with span("bench.drain"):
        for rec in records:
            left = deadline - time.perf_counter()
            try:
                rec.handle.result(timeout=max(left, 0.0))
            except Exception:   # noqa: BLE001 — recorded below
                pass
    for rec in records:
        if not rec.handle.done():
            rec.error = "never finished"
        else:
            rec.error = _error_of(rec.handle)
            if rec.done is None:
                rec.done = time.perf_counter() - t0
        if rec.error is not None:
            rec.first = None
    return records, t0


def replay_stretch(traffic: Dict, schedule: List[loadgen.Request],
                   length: float):
    """(start, end) of the traced stretch on the schedule's clock: after a
    lead-in of ``trace_lead_seconds`` (at most half the schedule), in which
    the engine fills up as in the window, the trace starts a tenth of a
    second before the next arrival, so that an admission and its prefill
    fall inside it whatever the seed, and lasts ``length`` seconds."""
    dues = [r.due_s for r in schedule]
    lead = min(float(traffic.get("trace_lead_seconds", 5.0)), 0.5 * dues[-1])
    margin = min(0.1, 0.2 * length)
    anchor = next((d for d in dues if d >= lead + margin), dues[-1])
    start = max(0.0, anchor - margin)
    return start, start + length


def trace_replay(ctx, engine, schedule: List[loadgen.Request]) -> None:
    """With ``--trace 1``: the cell's own traffic under the profiler. Once
    the window has closed and every answer is in, the same schedule is
    offered again from its beginning by the same loop: the same arrivals,
    prompts and answer lengths, first tokens polled as in the window. After
    the lead-in (:func:`replay_stretch`) the profiler runs for
    ``trace_seconds``: arrivals, admissions, prefills and decode blocks
    together. Then every replayed request is cancelled, and only then is the
    profiler stopped; the replayed requests are no part of the run's
    records. Not inside the window: the profiler's stop holds the
    interpreter, the engine's host loop with it, for some 45 s per traced
    second, and a trace that ran on to the end of the drain took 343 s to
    stop (PERF.md, PR 25). Starting the profiler holds the loop up too: the
    schedule's clock skips that time."""
    tracer = ctx.tracer(0.0)
    if not tracer.enabled:
        return
    start_at, end_at = replay_stretch(ctx.traffic, schedule, tracer.seconds)
    records = [Record(r, due=r.due_s) for r in schedule if r.due_s < end_at]

    def tick(now: float) -> float:
        if tracer.started or now < start_at:
            return 0.0
        t = time.perf_counter()
        tracer.start()
        return time.perf_counter() - t
    _offer(engine, records, end_at, ctx.span, tick, wait_first=False)
    sent = [rec.handle for rec in records if rec.handle is not None]
    for h in sent:
        h.cancel()
    tracer.finish()
    deadline = time.perf_counter() + DRAIN_S
    for h in sent:
        try:
            h.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except Exception:   # noqa: BLE001 — cancelled, as asked
            pass
    _say(f"replayed {len(sent)} requests, traced {start_at:.2f}-"
         f"{end_at:.2f}s of the schedule")


# ------------------------------------------------------------ the whole run
class Session:
    """One engine, set up once. ``run`` uses it for one seed and one window;
    the calibration tool keeps it across seeds, handing it new weights."""

    def __init__(self, ctx):
        from deeplearning4j_tpu.analysis.compile_audit import CompileAudit
        from deeplearning4j_tpu.models.generation import \
            SlotGenerationEngine
        self.ctx = ctx
        self.compiles = CompileCounter()
        self.audit = CompileAudit(ignore=())
        config, args = ctx.config, ctx.args
        self.net, self.sizes, self.shapes = ctx.family.make_net(config)
        ctx.family.install(self.net, config, self.sizes, self.shapes,
                           args.seed, train=False)
        ctx.sizes = self.sizes
        ctx.engine_options = dict(config["run"]["engine"])
        _say(f"weights on device: {time.perf_counter() - ctx.t_start:.1f}s")
        self.engine = SlotGenerationEngine(self.net, seed=0,
                                           **config["run"]["engine"])
        self.audit.__enter__()
        warm_up(self.engine, ctx.traffic, self.sizes["vocab"], args.seed)
        self.engine.start()

    def install(self, seed: int) -> None:
        """Hand the idle engine the weights of another seed."""
        self.ctx.family.install(self.net, self.ctx.config, self.sizes,
                                self.shapes, seed, train=False)

    def settle(self) -> None:
        """The last act of set-up: one collection of what set-up left (see
        :func:`collector_watch`)."""
        gc.collect()

    def window(self, seed: int, seconds: float):
        ctx, engine = self.ctx, self.engine
        schedule = loadgen.open_loop_schedule(ctx.traffic,
                                              self.sizes["vocab"], seed,
                                              seconds)
        stats0 = engine.stats()
        snap, mark = self.audit.snapshot(), self.compiles.mark()
        with collector_watch(ctx):
            records, ctx.t0 = open_loop(engine, schedule, seconds, ctx.span)
        stats1 = engine.stats()
        audit_delta = self.audit.delta(snap)
        window_compiles = max(self.compiles.since(mark),
                              sum(audit_delta.values()))
        if audit_delta:
            _say(f"compiled inside the window: {audit_delta}")
        trace_replay(ctx, engine, schedule)
        ctx.records, ctx.window_s = records, float(seconds)
        ctx.engine_stats = {k: stats1[k] - stats0[k] for k in stats1
                            if isinstance(stats1[k], int)
                            and isinstance(stats0.get(k), int)}
        return records, window_compiles

    def close(self) -> None:
        """Stop the engine and free the program's state."""
        self.engine.shutdown()
        self.audit.__exit__(None, None, None)
        self.engine = self.net = None
        gc.collect()


def check(ctx, sizes: Dict, seed: int, records: List[Record],
          window_compiles: int) -> Dict:
    """The numbers a serving cell compares: a seeded sample of finished
    requests (the longest among them) against the plain reference."""
    traffic = ctx.traffic
    finished = [r for r in records if r.error is None and r.done is not None]
    sample = _sample(finished, int(traffic.get("check_requests", 8)), seed)
    sequences = [np.asarray(r.handle.result(timeout=0)) for r in sample]
    prompt_lens = [len(r.request.prompt) for r in sample]
    wrong_echo = sum(
        int(not np.array_equal(s[:p], r.request.prompt)
            or len(s) != p + r.request.new_tokens
            or s.min() < 0 or s.max() >= sizes["vocab"])
        for s, p, r in zip(sequences, prompt_lens, sample))
    never = sum(r.error == "never finished" for r in records)
    for r in records:      # keep the engine's clocks, let go of its handles
        h = r.handle
        r.clocks = {"created": getattr(h, "_created_t", None),
                    "admitted": getattr(h, "_admitted_t", None),
                    "first_token": getattr(h, "_first_token_t", None)}
        r.handle = None
    t_ref = time.perf_counter()
    if sequences:
        gaps = ctx.family.served_token_gaps(
            sizes, seed, sequences, prompt_lens,
            control=ctx.control_precision)
    else:
        gaps = {"served_gap": float("inf"), "tokens": 0}
    _say(f"reference over {len(sequences)} requests, {gaps['tokens']} served "
         f"tokens: {time.perf_counter() - t_ref:.1f}s")
    if "control_gap" in gaps:
        ctx.control_numbers = {"served_gap": gaps["control_gap"]}
    return {"served_gap": gaps["served_gap"],
            "wrong_echo": float(wrong_echo),
            "never_finished": float(never),
            "window_compiles": float(window_compiles)}


def run(ctx) -> Dict:
    args = ctx.args
    session = Session(ctx)
    session.settle()
    setup_s = time.perf_counter() - ctx.t_start
    _say(f"set-up {setup_s:.1f}s; window opens")
    records, window_compiles = session.window(args.seed, args.seconds)
    memory_peak = ctx.memory_peak()
    _say("window closed: " + ", ".join(
        f"{m} {end_to_end(ctx, m) or float('nan'):.1f}" for m in (
            "ttft_p95_ms", "tpot_p95_ms")))
    sizes = session.sizes
    # the program's state is freed before the reference touches the chip;
    # the finished requests' token arrays are host memory and stay
    session.close()
    numbers = check(ctx, sizes, args.seed, records, window_compiles)
    failed = sum(r.error is not None for r in records)
    return {"numbers": numbers, "attempted": len(records), "failed": failed,
            "setup_s": setup_s, "memory_peak_bytes": memory_peak}


def _sample(finished: List[Record], k: int, seed: int) -> List[Record]:
    """k finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.request.prompt)
                  + r.request.new_tokens)
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(int(seed) ^ 0xC0FFEE)
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


# ------------------------------------------------- end-to-end metric values
def end_to_end(ctx, name: str) -> Optional[float]:
    """The serving end-to-end metrics, from the benchmark's own clocks."""
    recs = ctx.records
    if name == "ttft_p95_ms":
        vals = [((r.first - r.due) * 1e3 if r.first is not None
                 else float("inf")) for r in recs]
        return stats.percentile(vals, 95)
    if name == "tpot_p95_ms":
        vals = []
        for r in recs:
            n = r.request.new_tokens
            if r.error is not None or r.first is None or r.done is None:
                vals.append(float("inf"))
            elif n > 1:
                vals.append((r.done - r.first) * 1e3 / (n - 1))
        return stats.percentile(vals, 95)
    return None


# ------------------------------------------- readings for calibrate.py
def calibrate(ctx, seeds, seconds, n_control, n_fault, limits, control):
    """Per seed the program's numbers from one engine; on the first
    ``n_control`` seeds also the control (the reference in the precision
    ``control``), on the last ``n_fault`` seeds a token altered where it is
    produced. One line (a dict) per seed."""
    session = Session(ctx)
    vocab = session.sizes["vocab"]
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
        numbers = check(ctx, session.sizes, seed, records, compiles)
        verdict = {"token_altered" if fault else "program":
                   compare.verdict(limits, numbers)}
        if ctx.control_numbers is not None:
            verdict["control"] = compare.verdict(limits, numbers,
                                                 ctx.control_numbers)
        pauses = ctx.gc_pauses or []
        yield {"seed": seed, "fault": "token_altered" if fault else None,
               "numbers": numbers, "control": ctx.control_numbers,
               "verdict": verdict,
               "end_to_end": {m: end_to_end(ctx, m) for m in (
                   "ttft_p95_ms", "tpot_p95_ms")},
               "gc": {"collections": len(pauses),
                      "full": sum(g == 2 for g, _ in pauses),
                      "ms": sum(s for _, s in pauses) * 1e3},
               "requests": len(records),
               "failed": sum(r.error is not None for r in records)}
    session.close()


def sweep(ctx, rates, seed, seconds):
    """The knee, found once: the same mix offered at each of a few fixed
    rates from one engine; per rate the tails, the tokens completed inside
    the window and how much was still unfinished when it closed."""
    session = Session(ctx)
    for rate in rates:
        ctx.traffic["rate_per_s"] = float(rate)
        session.settle()
        records, _ = session.window(seed, seconds)
        done_in = [r for r in records if r.error is None
                   and r.done is not None and r.done <= seconds]
        yield {"rate_per_s": rate, "requests": len(records),
               "failed": sum(r.error is not None for r in records),
               "finished_in_window": len(done_in),
               "ttft_p95_ms": end_to_end(ctx, "ttft_p95_ms"),
               "tpot_p95_ms": end_to_end(ctx, "tpot_p95_ms"),
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
