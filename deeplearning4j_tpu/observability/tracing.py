"""Per-request tracing for the serving path.

One :class:`Trace` follows one generation request from the moment it
enters the system (route consume / engine submit) to the moment its
output leaves (publish), as a flat list of host-side wall-time
:class:`Span` records: ``consume`` → ``submit`` → ``queued`` →
``prefill`` → ``decode_block``×N → (``takeover`` on supervised
recovery) → ``publish``. The trace object rides ON the
GenerationRequest, so EngineSupervisor quarantine → ``requeue`` keeps
the SAME trace across an engine restart — a recovered request yields
exactly one trace, with a ``takeover`` span marking the seam, never two
half-traces.

Overhead rules (the ≤5% telemetry A/B bar and the zero-new-compiles
acceptance gate):

- spans carry host INTERVAL-clock times only (:func:`interval_now`,
  ``time.perf_counter`` — the one clock every duration in the
  observability layer derives from; an NTP wall-clock step can never
  produce a negative or garbage span, and each trace keeps exactly ONE
  ``time.time()`` anchor, ``wall_anchor``, for display) — recording a
  span never touches the device, never syncs beyond the serving path's
  existing ``device_fetch`` seam, and compiles nothing;
- recording is bounded: a trace keeps at most ``max_spans`` spans
  (oldest decode blocks are the ones that matter least; overflow is
  counted in ``dropped_spans``), and completed traces land in a fixed
  ring (:class:`TraceRing`) — memory is O(ring × max_spans) forever;
- nothing here may run under jit: graftlint GL008 flags trace/metric
  record calls in traced contexts.

Engine-loop and training-step seams (:class:`Seam`): ONE helper stamps
each seam of the serve loop and of ``fit_batch`` — ``interval_now()``
once at entry, once at exit — and the caller hands those two stamps to
the in-process sinks (``EngineChannel.record_*``, the requests'
``Trace.add_span``). The same interval is mirrored as a
``jax.profiler.TraceAnnotation`` under the seam's fixed ``dl4j.*`` name
(:data:`SEAMS`), so under a profiler session it lands on ``/host:CPU``
of the xplane, on the device events' clock, on the thread that did the
work. With no session active the mirror costs one ``is_enabled()`` call.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_TRACE_IDS = itertools.count(1)
_BLOCK_IDS = itertools.count(1)

# ---- the seams: a fixed list, not free text at call sites ---------------
ADMIT = "dl4j.engine.admit"                  # select, pack, dispatch prefill
PREFILL_READBACK = "dl4j.engine.prefill_readback"   # device_fetch of it
PREFILL_CHUNK = "dl4j.engine.prefill_chunk"  # one chunked-prefill window
DISPATCH_BLOCK = "dl4j.engine.dispatch_block"
BLOCK_READBACK = "dl4j.engine.block_readback"       # the device_fetch wait
RETIRE = "dl4j.engine.retire"                # host bookkeeping, engine lock
JOURNAL = "dl4j.engine.journal"
PUBLISH = "dl4j.engine.publish"              # completions, done-callbacks
IDLE_WAIT = "dl4j.engine.idle_wait"          # serve loop waiting for work
SPEC_DRAFT = "dl4j.engine.spec_draft"
SPEC_REWIND = "dl4j.engine.spec_rewind"
TRAIN_STEP = "dl4j.train.step"               # a StepTraceAnnotation
TRAIN_STAGE = "dl4j.train.stage"
TRAIN_READBACK = "dl4j.train.readback"

SEAMS = (ADMIT, PREFILL_READBACK, PREFILL_CHUNK, DISPATCH_BLOCK,
         BLOCK_READBACK, RETIRE, JOURNAL, PUBLISH, IDLE_WAIT, SPEC_DRAFT,
         SPEC_REWIND, TRAIN_STEP, TRAIN_STAGE, TRAIN_READBACK)


def interval_now() -> float:
    """The ONE interval clock for every observability duration (spans,
    SLO clocks, request deadlines, profiler phase stamps):
    ``time.perf_counter`` — monotonic, NTP-step-immune, and the highest
    resolution clock the host offers. Durations are only ever computed
    between two ``interval_now()`` anchors; wall-clock time
    (``time.time``) appears exactly once per trace (``wall_anchor``),
    for human display, and NEVER in interval math — a backwards
    wall-clock step cannot corrupt a histogram (regression-tested)."""
    return time.perf_counter()


def next_block_id() -> int:
    """Process-wide, monotonically increasing id of one engine dispatch
    (a decode block, a verify block, an admission's prefill, a prefill
    window). The engine's seam spans and the profiler's timeline entry
    carry it, and each request span the dispatch produced names it in
    ``attrs["block"]``: request span -> engine block -> (through the
    dispatch order) the device trace's ``XLA Modules`` event."""
    return next(_BLOCK_IDS)


class Seam:
    """``with Seam(DISPATCH_BLOCK, block=b, lanes=n, k=4) as s:`` — the one
    way the engine loop and ``fit_batch`` take a stamp. ``name`` is one of
    :data:`SEAMS`; ``t0``/``t1`` are THE two ``interval_now()`` stamps of
    the interval, and the caller passes them on to the in-process sinks.
    Host-only: graftlint GL008/GL015/GL016 reject it under jit, like the
    sinks it feeds. Under an active ``jax.profiler``
    session the same interval is written as a ``TraceAnnotation`` named
    ``name`` with ``block``/``lanes``/``k`` as its stats (``step`` makes
    it a ``StepTraceAnnotation`` numbered ``block``); without one, no
    annotation object and no attrs dict is built."""

    __slots__ = ("name", "block", "lanes", "k", "t0", "t1", "_mirror",
                 "_step", "_ann")

    def __init__(self, name: str, block: Optional[int] = None,
                 lanes: Optional[int] = None, k: Optional[int] = None,
                 mirror: bool = True, step: bool = False):
        self.name = name
        self.block, self.lanes, self.k = block, lanes, k
        self._mirror, self._step = mirror, step
        self._ann = None
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Seam":
        if self._mirror and TraceAnnotation.is_enabled():
            if self._step:
                ann = StepTraceAnnotation(self.name, step_num=self.block)
            else:
                stats = {key: val for key, val in (
                    ("block", self.block), ("lanes", self.lanes),
                    ("k", self.k)) if val is not None}
                ann = TraceAnnotation(self.name, **stats)
            ann.__enter__()
            self._ann = ann
        self.t0 = interval_now()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = interval_now()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None


class Span:
    """One closed interval on a trace's timeline (host wall clock)."""

    __slots__ = ("name", "t0", "t1", "attrs")

    def __init__(self, name: str, t0: float, t1: float,
                 attrs: Optional[dict] = None):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs or {}

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        d = {"name": self.name, "t0": round(self.t0, 6),
             "t1": round(self.t1, 6),
             "duration_ms": round((self.t1 - self.t0) * 1e3, 3)}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class Trace:
    """Timeline of one request. Thread-safe: the route's consumer thread,
    the engine's serve loop, and the route's publisher thread all append
    to the same trace at different lifecycle stages.

    ``finish()`` is idempotent and pushes the trace into its store
    (ring buffer) exactly once; spans may still be appended afterwards —
    the in-order publisher records its ``publish`` span a beat after the
    engine completes the request, and the ring holds the live object, so
    the span shows up in ``/traces/recent`` regardless."""

    def __init__(self, request_id: Optional[str] = None, store=None,
                 max_spans: int = 512):
        self.trace_id = next(_TRACE_IDS)
        self.request_id = request_id if request_id is not None \
            else f"req-{self.trace_id}"
        self.max_spans = int(max_spans)
        self._store = store
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self.dropped_spans = 0
        self.created_at = interval_now()
        #: the single wall-clock anchor (display only): created_at on
        #: the wall clock — interval math never touches it
        self.wall_anchor = time.time()
        self.finished_at: Optional[float] = None
        self.status: Optional[str] = None
        self.attrs: Dict = {}

    # ---------------------------------------------------------- recording
    def add_span(self, name: str, t0: Optional[float] = None,
                 t1: Optional[float] = None, **attrs) -> None:
        if t0 is None or t1 is None:
            now = interval_now()
            t0 = now if t0 is None else t0
            t1 = now if t1 is None else t1
        span = Span(name, t0, t1, attrs or None)
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped_spans += 1
                return
            self._spans.append(span)

    def event(self, name: str, **attrs) -> None:
        """Zero-duration span (a point on the timeline)."""
        self.add_span(name, **attrs)

    def span(self, name: str, **attrs) -> "_SpanCtx":
        """``with trace.span("prefill"):`` records on exit."""
        return _SpanCtx(self, name, attrs)

    def annotate(self, **attrs) -> None:
        with self._lock:
            self.attrs.update(attrs)

    # --------------------------------------------------------- lifecycle
    @property
    def finished(self) -> bool:
        with self._lock:
            return self.finished_at is not None

    @property
    def duration(self) -> Optional[float]:
        with self._lock:
            if self.finished_at is None:
                return None
            return self.finished_at - self.created_at

    def finish(self, status: str = "ok", **attrs) -> None:
        """Close the trace and hand it to the ring — exactly once; later
        calls (a request failed twice through racing paths) are no-ops
        so a request can never occupy two ring slots."""
        with self._lock:
            if self.finished_at is not None:
                return
            self.finished_at = interval_now()
            self.status = status
            if attrs:
                self.attrs.update(attrs)
            store = self._store
        if store is not None:
            store.add(self)

    # ------------------------------------------------------------- views
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def span_names(self) -> List[str]:
        return [s.name for s in self.spans()]

    def to_dict(self) -> dict:
        """JSON timeline: spans sorted by start time (append order may
        interleave across threads), times rebased to the trace origin."""
        with self._lock:
            spans = sorted(self._spans, key=lambda s: (s.t0, s.t1))
            base = self.created_at
            out = {
                "trace_id": self.trace_id,
                "request_id": self.request_id,
                "status": self.status,
                "duration_ms": None if self.finished_at is None else
                round((self.finished_at - base) * 1e3, 3),
                "dropped_spans": self.dropped_spans,
                "wall_time": round(self.wall_anchor, 6),
                "attrs": dict(self.attrs),
            }
        out["spans"] = [{**s.to_dict(),
                         "t0": round(s.t0 - base, 6),
                         "t1": round(s.t1 - base, 6)} for s in spans]
        return out


class _SpanCtx:
    __slots__ = ("_trace", "_name", "_attrs", "_t0")

    def __init__(self, trace: Trace, name: str, attrs: dict):
        self._trace = trace
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_SpanCtx":
        self._t0 = interval_now()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self._attrs = dict(self._attrs, error=exc_type.__name__)
        self._trace.add_span(self._name, self._t0, interval_now(),
                             **self._attrs)


class TraceRing:
    """Fixed-capacity ring of completed traces (newest last). The
    ``/traces/recent`` endpoint serves from here; memory is bounded by
    capacity × max_spans regardless of uptime."""

    def __init__(self, capacity: int = 1024):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._added = 0
        self._lost_until: Optional[float] = None

    def add(self, trace: Trace) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                lost = self._ring[0].finished_at
                if lost is not None and (self._lost_until is None
                                         or lost > self._lost_until):
                    self._lost_until = lost
            self._ring.append(trace)
            self._added += 1

    def rolled_past(self, t: float) -> bool:
        """Whether a trace that finished at or after interval-clock time
        ``t`` has already rotated out: a reader of everything since ``t``
        would then see only part of it."""
        with self._lock:
            return self._lost_until is not None and self._lost_until >= t

    def recent(self, n: Optional[int] = None) -> List[Trace]:
        with self._lock:
            items = list(self._ring)
        return items if n is None else items[-int(n):]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def total_added(self) -> int:
        with self._lock:
            return self._added


_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[TraceRing] = None


def default_trace_ring() -> TraceRing:
    """Process-default completed-trace ring (capacity 1024: minutes of
    serving). Injectable per component for test isolation, like the
    metrics registry."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = TraceRing()
        return _DEFAULT
