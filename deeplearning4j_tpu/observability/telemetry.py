"""Live serving-telemetry endpoint.

:class:`TelemetryServer` is a background HTTP server (the training UI's
``ui/server.py`` plumbing — ``JsonHTTPHandler`` + ``BackgroundHTTPServer``
— reused wholesale) exposing the observability layer of a running
serving process:

- ``GET /metrics``        — Prometheus text exposition of the registry;
- ``GET /snapshot``       — one JSON document: the nested registry
  snapshot, per-tag device→host readback DELTAS since server start (the
  TransferAudit view over ``ops.transfer.device_fetch``), the
  CompileAudit report (per-function XLA compiles + delta since start,
  when ``audit_compiles=True``), the device-cost stats (device memory,
  per-engine KV-cache bytes — next to the compile audit), the
  flight-recorder summary, the SLO summary, and every registered source (engine/supervisor ``stats()`` dicts, broker
  counters, ...);
- ``GET /slo``            — the SLO tracker's full document: rolling
  short/long-window attainment + burn rate, deadline-headroom /
  TTFT / queue-wait quantiles, per-route and per-replica splits;
- ``GET /profile``        — the hot-loop phase profiler: per-engine
  decode-block phase decomposition (device/host/journal/publish +
  pipeline bubble, lane bubble), ``?timeline=N`` for the last N
  PhaseTimeline entries (each with its ``block`` id and what the
  device had done last, ``after``) and ``?since=S`` for the sums over
  the last S seconds with ``truncated`` (``PhaseProfiler.between``);
- ``GET /traces/recent``  — the completed-trace ring as JSON timelines
  (``?n=`` limits the count, ``?status=`` filters — ``failed`` matches
  every ``failed:*`` status, any exact status works; ``?since=S`` adds
  ``rolled_past``: whether a trace finished in the last S seconds has
  already rotated out); each ``prefill`` / ``decode_block`` /
  ``verify_block`` span names the engine ``block`` that produced it;
- ``GET /healthz``        — liveness probe.

Reading is free for the serving hot path: every endpoint renders from
already-maintained state (registry children, the trace ring, the
monotonic transfer counters); nothing queries the device and nothing
compiles. Sources are callables evaluated per request and guarded — a
dying engine must degrade the snapshot, not the endpoint.

    srv = TelemetryServer(port=0).add_source(
        "generation", engine.stats).start()
    print(srv.url)           # scripts/telemetry_dump.py consumes this
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional
from urllib.parse import parse_qs, urlparse

from ..ui.server import BackgroundHTTPServer, JsonHTTPHandler
from .devstats import DeviceStats
from .flightrec import FlightRecorder, default_flight_recorder
from .metrics import MetricsRegistry, default_registry
from .profiler import PhaseProfiler, default_profiler
from .slo import SLOTracker, default_slo_tracker
from .tracing import TraceRing, default_trace_ring, interval_now


def _since(query: Dict) -> Optional[float]:
    """``?since=S`` as seconds, or None where absent or not a number."""
    try:
        return float(query["since"][0])
    except (KeyError, IndexError, ValueError):
        return None


class _TelemetryHandler(JsonHTTPHandler):
    """Per-TelemetryServer handler subclass (``server_obj`` is bound by
    ``TelemetryServer.start`` via ``type()``, so several telemetry
    servers in one process never share state the way a class attribute
    would)."""

    server_obj: "TelemetryServer" = None

    def do_GET(self):
        srv = type(self).server_obj
        url = urlparse(self.path)
        if srv is None:
            self._json({"error": "server detached"}, code=503)
        elif url.path == "/metrics":
            self._text(srv.registry.render_prometheus(),
                       "text/plain; version=0.0.4")
        elif url.path == "/snapshot":
            self._json(srv.snapshot())
        elif url.path == "/slo":
            self._json(srv.slo_tracker.snapshot())
        elif url.path == "/profile":
            q = parse_qs(url.query)
            try:
                tl = int(q.get("timeline", ["0"])[0]) or None
            except ValueError:
                tl = None
            self._json(srv.profiler.snapshot(timeline_n=tl,
                                             since_s=_since(q)))
        elif url.path == "/traces/recent":
            q = parse_qs(url.query)
            try:
                n = int(q.get("n", ["0"])[0]) or None
            except ValueError:
                n = None
            status = (q.get("status", [None])[0] or None)
            if status is None:
                traces = srv.trace_store.recent(n)
            else:
                # filter BEFORE the count cut, so ?n=5&status=failed is
                # "the last 5 failures", not "failures among the last 5";
                # bare "failed" covers every failed:<ExcType> status
                traces = [t for t in srv.trace_store.recent(None)
                          if t.status == status or
                          (t.status or "").startswith(status + ":")]
                if n is not None:
                    traces = traces[-n:]
            doc = {"count": len(traces),
                   "total_completed": srv.trace_store.total_added,
                   "traces": [t.to_dict() for t in traces]}
            since = _since(q)
            if since is not None:
                doc["rolled_past"] = srv.trace_store.rolled_past(
                    interval_now() - since)
            self._json(doc)
        elif url.path == "/healthz":
            self._json({"ok": True, "uptime_s": round(srv.uptime, 3)})
        else:
            self._json({"error": "not found", "endpoints": [
                "/metrics", "/snapshot", "/slo", "/profile",
                "/traces/recent", "/healthz"]}, code=404)


class TelemetryServer:
    """Background telemetry endpoint over a registry + trace ring.

    ``audit_compiles=True`` additionally arms a CompileAudit for the
    server's lifetime (one logging call per XLA compile — free in steady
    state, where the whole point is that there are none) so
    ``/snapshot`` can report per-function compile counts and the delta
    since serving started."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 trace_store: Optional[TraceRing] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 audit_compiles: bool = False,
                 slo_tracker: Optional[SLOTracker] = None,
                 devstats: Optional[DeviceStats] = None,
                 flight_recorder: Optional[FlightRecorder] = None,
                 profiler: Optional[PhaseProfiler] = None):
        # loopback by default: the endpoint is unauthenticated and
        # /snapshot+/traces expose serving internals — exposing it
        # beyond the host is an explicit host="0.0.0.0" decision
        self.registry = registry if registry is not None \
            else default_registry()
        self.trace_store = trace_store if trace_store is not None \
            else default_trace_ring()
        self.slo_tracker = slo_tracker if slo_tracker is not None \
            else default_slo_tracker()
        self.devstats = devstats if devstats is not None \
            else DeviceStats(registry=self.registry)
        self.flight_recorder = flight_recorder \
            if flight_recorder is not None else default_flight_recorder()
        self.profiler = profiler if profiler is not None \
            else default_profiler()
        self._http = BackgroundHTTPServer(None, host=host, port=port)
        self._sources: Dict[str, Callable[[], dict]] = {}
        self._audit = None
        self._audit_snap = None
        self._audit_compiles = bool(audit_compiles)
        self._transfer_start: Dict[str, int] = {}
        self._started_at: Optional[float] = None

    # ------------------------------------------------------------ wiring
    def add_source(self, name: str, fn: Callable[[], dict]
                   ) -> "TelemetryServer":
        """Register a snapshot source (an engine/supervisor ``stats``,
        a broker's counters, an injector's ``counters`` — any zero-arg
        callable returning JSON-serializable data)."""
        self._sources[str(name)] = fn
        return self

    def add_engine(self, name: str, engine) -> "TelemetryServer":
        """One-call engine wiring: ``stats()`` as a snapshot source plus
        device-stats attachment (KV-cache bytes gauge in
        ``/snapshot``)."""
        self.add_source(name, engine.stats)
        self.devstats.attach_engine(name, engine)
        return self

    def start(self) -> "TelemetryServer":
        if self._started_at is not None:
            return self
        from ..ops.transfer import fetch_counts
        self._transfer_start = fetch_counts()
        if self._audit_compiles:
            from ..analysis.compile_audit import CompileAudit
            self._audit = CompileAudit().__enter__()
            self._audit_snap = self._audit.snapshot()
        handler = type("_BoundTelemetryHandler", (_TelemetryHandler,),
                       {"server_obj": self})
        self._http.handler_cls = handler
        self._http.start()
        self._started_at = time.monotonic()
        return self

    def stop(self) -> None:
        self._http.stop()
        if self._audit is not None:
            audit, self._audit = self._audit, None
            audit.budget = {}            # lifetime audit: report, don't gate
            audit.total_budget = None
            audit.__exit__(None, None, None)
        self._started_at = None

    @property
    def port(self) -> int:
        return self._http.port

    @property
    def url(self) -> str:
        return self._http.url

    @property
    def uptime(self) -> float:
        if self._started_at is None:
            return 0.0
        return time.monotonic() - self._started_at

    # ------------------------------------------------------------- views
    def transfer_deltas(self) -> Dict[str, int]:
        """Per-tag ``device_fetch`` readbacks since ``start()`` (the
        TransferAudit snapshot-and-diff discipline, held open for the
        server's lifetime)."""
        from ..ops.transfer import fetch_counts
        now = fetch_counts()
        return {t: c - self._transfer_start.get(t, 0)
                for t, c in sorted(now.items())
                if c - self._transfer_start.get(t, 0) > 0}

    def snapshot(self) -> dict:
        out = {
            "uptime_s": round(self.uptime, 3),
            "metrics": self.registry.snapshot(),
            "transfers": self.transfer_deltas(),
            "traces": {"completed": self.trace_store.total_added,
                       "ring": len(self.trace_store)},
        }
        if self._audit is not None:
            rep = self._audit.report()
            rep["new_since_start"] = self._audit.delta(self._audit_snap)
            out["compile_audit"] = rep
        # device-cost stats live NEXT TO the compile audit: both answer
        # "what did the device side actually cost", one at compile
        # granularity, one at memory/flops granularity
        try:
            out["devstats"] = self.devstats.snapshot()
        except Exception as e:   # noqa: BLE001 — degrade, don't 500
            out["devstats"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        try:
            out["slo"] = self.slo_tracker.snapshot()
        except Exception as e:   # noqa: BLE001
            out["slo"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        try:
            out["flightrec"] = self.flight_recorder.stats()
        except Exception as e:   # noqa: BLE001
            out["flightrec"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        # lightweight profiler summary (the timeline lives at /profile):
        # the fleet scrape's bubble-% column reads the headline straight
        # from /snapshot
        try:
            out["profiler"] = self.profiler.summary()
        except Exception as e:   # noqa: BLE001
            out["profiler"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        sources = {}
        for name, fn in self._sources.items():
            try:
                sources[name] = fn()
            except Exception as e:   # noqa: BLE001 — degrade, don't 500
                sources[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
        if sources:
            out["sources"] = sources
        return out
