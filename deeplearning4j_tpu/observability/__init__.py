"""observability — unified metrics registry, per-request tracing, and a
live serving telemetry endpoint.

The reference DL4J ships observability as a first-class subsystem
(deeplearning4j-ui-parent: StatsListener → StatsStorage → browser UI);
this package is its SERVING-side counterpart for the jax_graft stack —
where ``ui/`` watches training, ``observability/`` watches the decode
hot path and everything around it:

- :mod:`.metrics` — thread-safe :class:`MetricsRegistry` of labeled
  Counters, Gauges, and fixed-bucket Histograms with a nested-dict
  ``snapshot()`` and Prometheus-style text exposition. The engine /
  supervisor / route / broker counters all live here now; their
  ``stats()`` dicts and counter attributes are thin views.
- :mod:`.tracing` — per-request :class:`Trace`/:class:`Span` timelines
  threaded through consume → admission → prefill → decode blocks →
  publish, carried ACROSS EngineSupervisor takeovers (one trace per
  request, a ``takeover`` span marking each restart), with a fixed
  :class:`TraceRing` of completed traces (``rolled_past(t)`` says when
  it no longer reaches back to ``t``). And :class:`Seam`, the ONE stamp
  source of the engine loop and of ``fit_batch``: each seam of the fixed
  list :data:`SEAMS` (``dl4j.engine.admit``, ``.prefill_readback``,
  ``.prefill_chunk``, ``.dispatch_block``, ``.block_readback``,
  ``.retire``, ``.journal``, ``.publish``, ``.idle_wait``,
  ``.spec_draft``, ``.spec_rewind``; ``dl4j.train.step``, ``.stage``,
  ``.readback``) takes ``interval_now()`` once at entry and once at
  exit, and feeds two sinks — in process the profiler's phase account
  and the requests' spans (each naming the engine ``block`` that
  produced it), and under a ``jax.profiler`` session a
  ``TraceAnnotation`` of the same name on ``/host:CPU`` of the xplane,
  on the device events' clock. A request's own clocks are public:
  ``GenerationRequest.clocks()`` (created / admitted / first_token /
  done) and ``.emissions()`` (when how many tokens became visible).
- :mod:`.slo` — :class:`SLOTracker`: per-request deadline headroom,
  queue-wait, and TTFT accounting with rolling short/long-window
  attainment and burn rate, per-route and per-replica, riding on the
  request clocks the engine stamps (which survive takeovers and
  migrations — the clock never resets).
- :mod:`.devstats` — device-side cost accounting sampled off the hot
  path: device memory / live-array census and exact per-engine
  KV-cache bytes from the live cache leaves.
- :mod:`.flightrec` — :class:`FlightRecorder`: a bounded structured
  event ring (admission, block retire, shed, takeover, migration,
  reconnect, fault) with post-mortem JSON artifacts bundling events +
  traces + registry snapshot + transfer/compile-audit state, written by
  the supervisor and fleet router on crash/wedge/replica death.
- :mod:`.profiler` — :class:`PhaseProfiler`: hot-loop phase/bubble
  accounting (device/host/journal/publish decomposition per decode
  block — phases sum to block wall time — plus pipeline-bubble and
  lane-bubble measures), with a bounded :class:`PhaseTimeline` ring
  (8192 records) that survives supervisor engine rebuilds and is summed
  over any window by ``PhaseProfiler.between(t0, t1)`` (``truncated``
  when the ring no longer reaches back to ``t0``).
- :mod:`.telemetry` — :class:`TelemetryServer`, a background HTTP
  endpoint (``/metrics``, ``/snapshot``, ``/slo``, ``/profile``,
  ``/traces/recent``) reusing the training UI's HTTP plumbing.

Every duration above derives from ONE interval clock
(:func:`.tracing.interval_now`, ``time.perf_counter``): wall-clock time
appears only as per-trace display anchors, so an NTP step can never
corrupt a span, headroom, or phase histogram.

Instrumentation is host-side only (wall clocks, counter bumps): it
compiles nothing, adds no device syncs beyond the existing
``device_fetch`` seam, and graftlint GL008/GL015 statically reject any
metric/trace/SLO/flight-recorder record call that drifts into
jit-traced code.
"""

from .devstats import DeviceStats, device_memory_snapshot, kv_cache_stats
from .flightrec import FlightRecorder, default_flight_recorder
from .integrity import (GoldenCanary, IntegrityConfig, NumericalFault,
                        PageVerifier)
from .metrics import (Counter, DEFAULT_LATENCY_BUCKETS, Gauge, Histogram,
                      MetricsRegistry, default_registry, percentiles)
from .profiler import (EngineChannel, PhaseProfiler, PhaseTimeline,
                       default_profiler)
from .slo import SLORecord, SLOTracker, default_slo_tracker
from .telemetry import TelemetryServer
from .tracing import (SEAMS, Seam, Span, Trace, TraceRing,
                      default_trace_ring, interval_now)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS", "default_registry", "percentiles",
    "SEAMS", "Seam", "Span", "Trace", "TraceRing", "default_trace_ring",
    "interval_now",
    "EngineChannel", "PhaseProfiler", "PhaseTimeline", "default_profiler",
    "SLORecord", "SLOTracker", "default_slo_tracker",
    "DeviceStats", "device_memory_snapshot", "kv_cache_stats",
    "FlightRecorder", "default_flight_recorder",
    "GoldenCanary", "IntegrityConfig", "NumericalFault", "PageVerifier",
    "TelemetryServer",
]
