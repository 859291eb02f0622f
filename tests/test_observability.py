"""observability/ subsystem (ISSUE 5): metrics registry exactness under
thread storms, histogram correctness against numpy, per-request trace
continuity through the serving path (including a scripted crash →
supervised takeover — ONE trace per request, a `takeover` span marking
the seam), telemetry endpoint smoke tests over real HTTP, and the
overhead A/B: telemetry-on decode throughput within 5% of telemetry-off."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       transformer_lm_conf)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.observability import (DeviceStats, FlightRecorder,
                                              Histogram, MetricsRegistry,
                                              PhaseProfiler, SLOTracker,
                                              TelemetryServer, Trace,
                                              TraceRing,
                                              device_memory_snapshot,
                                              kv_cache_stats, percentiles)
from deeplearning4j_tpu.parallel.failures import EngineSupervisor
from deeplearning4j_tpu.parallel.faults import FaultInjector
from deeplearning4j_tpu.streaming.pubsub import (MessageBroker,
                                                 NDArrayPublisher,
                                                 NDArraySubscriber)
from deeplearning4j_tpu.streaming.serving import GenerationServingRoute

VOCAB = 12


@pytest.fixture(scope="module")
def shared_decoder():
    """One tiny LM + decoder for the module: every engine shares the
    jitted programs, so per-test compile cost is paid once."""
    net = ComputationGraph(transformer_lm_conf(
        VOCAB, d_model=32, num_heads=2, num_layers=2, max_length=32,
        learning_rate=1e-2, seed=5)).init()
    dec = TransformerDecoder(net)
    eng = SlotGenerationEngine(net, num_slots=2, decoder=dec)
    eng.submit([1, 2], 3)
    eng.run_until_drained()                  # warm prefill/decode programs
    return net, dec


def _engine(dec_tuple, **kw):
    net, dec = dec_tuple
    kw.setdefault("num_slots", 2)
    return SlotGenerationEngine(net, decoder=dec, **kw)


def _wait(pred, timeout=30.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class TestMetricsRegistry:
    def test_concurrency_storm_exact_totals(self):
        """16 threads hammering shared children: every increment lands
        (the GL006 lock-discipline contract, machine-checked here)."""
        reg = MetricsRegistry()
        c = reg.counter("storm_total", "s", ("worker",))
        shared = reg.counter("storm_shared_total", "s")
        g = reg.gauge("storm_gauge", "g")
        n_threads, n_incs = 16, 2000

        def worker(i):
            mine = c.labels(worker=f"w{i}")
            for _ in range(n_incs):
                mine.inc()
                shared.inc(2)
                g.inc()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(n_threads):
            assert c.labels(worker=f"w{i}").value == n_incs
        assert shared.value == 2 * n_threads * n_incs
        assert g.value == n_threads * n_incs

    def test_histogram_storm_exact_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("storm_seconds", "s", buckets=(0.1, 1.0))

        def worker():
            for k in range(500):
                h.observe(0.05 if k % 2 else 5.0)
        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        d = h._default().to_dict()
        assert d["count"] == 16 * 500
        assert d["buckets"]["0.1"] == 16 * 250      # the 0.05 half
        assert d["buckets"]["+Inf"] == 16 * 500

    def test_redeclaration_is_idempotent_but_kind_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "first", ("l",))
        b = reg.counter("x_total", "second", ("l",))
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total")                    # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("x_total", label_names=("other",))   # schema

    def test_remove_prunes_retired_children(self):
        """Instance churn against one registry is bounded by pruning:
        a removed child leaves exposition; re-labeling recreates it."""
        reg = MetricsRegistry()
        c = reg.counter("churn_total", "c", ("engine",))
        c.labels("e1").inc(3)
        c.labels("e2").inc(5)
        assert c.remove("e1") is True
        assert c.remove("e1") is False
        assert list(c.children()) == ["engine=e2"]
        assert 'engine="e1"' not in reg.render_prometheus()
        assert c.labels("e1").value == 0          # fresh child

    def test_counters_only_go_up(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("up_total").inc(-1)

    def test_gauge_callback_and_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "help c", ("eng",)).labels("e1").inc(3)
        depth = [7]
        reg.gauge("depth", "queue").set_function(lambda: depth[0])
        snap = reg.snapshot()
        assert snap["c_total"]["type"] == "counter"
        assert snap["c_total"]["values"]["eng=e1"] == 3
        assert snap["depth"]["values"][""] == 7
        depth[0] = 9
        assert reg.snapshot()["depth"]["values"][""] == 9

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("req_total", "served requests", ("route",)) \
            .labels(route='a"b\n').inc(5)
        reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0)) \
            .observe(0.5)
        text = reg.render_prometheus()
        assert "# HELP req_total served requests" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{route="a\\"b\\n"} 5' in text
        assert 'lat_seconds_bucket{le="0.1"} 0' in text
        assert 'lat_seconds_bucket{le="1.0"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text


class TestHistogramPercentiles:
    def test_exact_percentiles_match_numpy(self):
        rng = np.random.default_rng(3)
        vals = rng.exponential(0.02, 4000)
        h = Histogram("lat", sample_limit=None)
        h.observe_many(vals)
        for q in (1, 25, 50, 90, 99, 99.9):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(vals, q)), rel=0, abs=1e-12)
        p = percentiles(vals, (50, 99))
        assert p["p50"] == pytest.approx(float(np.percentile(vals, 50)))
        assert p["p99"] == pytest.approx(float(np.percentile(vals, 99)))

    def test_bucket_estimate_within_bucket_resolution(self):
        """Fixed-bucket children (the serving path's bounded-memory mode)
        estimate percentiles by interpolation: the error is bounded by
        the covering bucket's width."""
        rng = np.random.default_rng(5)
        vals = rng.uniform(0.0, 1.0, 5000)
        edges = [round(0.05 * i, 2) for i in range(1, 21)]    # 0.05..1.0
        h = Histogram("lat", buckets=edges, sample_limit=0)
        h.observe_many(vals)
        for q in (10, 50, 90, 99):
            exact = float(np.percentile(vals, q))
            assert abs(h.percentile(q) - exact) <= 0.05 + 1e-9

    def test_bucket_counts_are_cumulative_and_complete(self):
        h = Histogram("lat", buckets=(1.0, 2.0, 3.0), sample_limit=0)
        h.observe_many([0.5, 1.5, 2.5, 2.7, 99.0])
        d = h._default().to_dict()
        assert d["buckets"] == {"1.0": 1, "2.0": 2, "3.0": 4, "+Inf": 5}
        assert d["count"] == 5
        assert d["sum"] == pytest.approx(0.5 + 1.5 + 2.5 + 2.7 + 99.0)

    def test_empty_histogram_percentile_is_none(self):
        assert Histogram("lat").percentile(50) is None


class TestTracing:
    def test_span_timeline_sorted_and_rebased(self):
        ring = TraceRing(8)
        tr = Trace(request_id="r1", store=ring)
        tr.add_span("late", tr.created_at + 2.0, tr.created_at + 3.0)
        tr.add_span("early", tr.created_at + 0.5, tr.created_at + 1.0,
                    k=4)
        tr.finish("ok")
        d = tr.to_dict()
        assert [s["name"] for s in d["spans"]] == ["early", "late"]
        assert d["spans"][0]["t0"] == pytest.approx(0.5, abs=1e-3)
        assert d["spans"][0]["attrs"] == {"k": 4}
        assert d["status"] == "ok"

    def test_finish_is_idempotent_one_ring_slot(self):
        ring = TraceRing(8)
        tr = Trace(store=ring)
        tr.finish("ok")
        tr.finish("failed:Boom")               # racing second finish: no-op
        assert len(ring) == 1
        assert ring.recent()[0].status == "ok"
        # post-finish spans still land on the ringed object (the route's
        # publish span arrives a beat after engine-side completion)
        tr.add_span("publish")
        assert "publish" in ring.recent()[0].span_names()

    def test_max_spans_bounds_memory(self):
        tr = Trace(max_spans=4)
        for i in range(10):
            tr.add_span("decode_block", 0.0, 1.0)
        assert len(tr.spans()) == 4
        assert tr.dropped_spans == 6

    def test_ring_capacity(self):
        ring = TraceRing(3)
        for i in range(5):
            Trace(request_id=f"r{i}", store=ring).finish()
        assert len(ring) == 3
        assert ring.total_added == 5
        assert [t.request_id for t in ring.recent()] == ["r2", "r3", "r4"]

    def test_span_context_manager_records_errors(self):
        tr = Trace()
        with pytest.raises(RuntimeError):
            with tr.span("prefill", batch=3):
                raise RuntimeError("boom")
        s = tr.spans()[0]
        assert s.attrs == {"batch": 3, "error": "RuntimeError"}


class TestEngineTelemetry:
    def test_stats_is_a_view_over_the_registry(self, shared_decoder,
                                               rng_np):
        reg, ring = MetricsRegistry(), TraceRing(64)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 4)
                for _ in range(5)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        stats = eng.stats()
        label = f"engine={eng.engine_id}"
        for key in ("emitted_tokens", "completed", "decode_steps",
                    "prefills", "prefill_batches", "host_readbacks"):
            fam = reg.get(f"generation_{key}_total")
            assert fam is not None
            assert stats[key] == fam.labels(eng.engine_id).value
            assert getattr(eng, key) == stats[key]     # attribute view
        assert stats["completed"] == 5
        snap = reg.snapshot()
        assert snap["generation_completed_total"]["values"][label] == 5
        # block-latency histogram recorded one observation per block
        hist = snap["generation_decode_block_seconds"]["values"][label]
        assert hist["count"] == stats["decode_blocks"]

    def test_every_request_yields_exactly_one_finished_trace(
            self, shared_decoder, rng_np):
        reg, ring = MetricsRegistry(), TraceRing(64)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      block_size=4)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, int(n)), 6)
                for n in rng_np.integers(2, 6, 8)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        assert len(ring) == len(reqs)
        assert len({r.trace.trace_id for r in reqs}) == len(reqs)
        for r in reqs:
            assert r.trace.finished and r.trace.status == "ok"
            names = r.trace.span_names()
            assert names[0] == "submit"
            assert "queued" in names and "prefill" in names
            assert "decode_block" in names

    def test_trace_continuity_across_crash_takeover(self, shared_decoder,
                                                    rng_np):
        """The acceptance bar: a scripted FaultInjector crash triggers a
        supervised takeover; recovered requests CONTINUE their traces
        (one trace per request, a `takeover` span at the seam) and every
        completed request still shows full span coverage."""
        reg, ring = MetricsRegistry(), TraceRing(64)
        inj = FaultInjector(registry=reg)
        inj.raise_once("engine.step", RuntimeError("chaos"), at=3)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      fault_injector=inj)
        sup = EngineSupervisor(eng, timeout=10.0, interval=0.1,
                               max_restarts=2).start()
        try:
            reqs = [sup.submit(rng_np.integers(0, VOCAB, 3), 6)
                    for _ in range(5)]
            outs = [r.result(60) for r in reqs]
            assert all(o is not None for o in outs)
            assert sup.restarts == 1
            assert len({r.trace.trace_id for r in reqs}) == len(reqs)
            assert len(ring) == len(reqs)              # one slot each
            takeovers = 0
            for r in reqs:
                names = r.trace.span_names()
                assert r.trace.finished and r.trace.status == "ok"
                assert "prefill" in names
                takeovers += names.count("takeover")
            # the crash harvested at least one in-flight request
            assert takeovers >= 1
            assert takeovers == sum(n == "takeover" for r in reqs
                                    for n in r.trace.span_names())
            snap = reg.snapshot()
            assert snap["supervisor_restarts_total"]["values"][
                "supervisor=slot-engine"] == 1
            assert snap["fault_injections_total"]["values"][
                "point=engine.step"] == 1
        finally:
            sup.stop()

    def test_route_trace_covers_consume_to_publish(self, shared_decoder,
                                                   rng_np):
        """Through the serving route, a completed request's trace spans
        consume → submit → queued → prefill → decode → publish."""
        net, dec = shared_decoder
        reg, ring = MetricsRegistry(), TraceRing(64)
        broker = MessageBroker()
        out = NDArraySubscriber(broker, "dl4j-gen-output")
        eng = _engine(shared_decoder, registry=reg, trace_store=ring)
        route = GenerationServingRoute(net, broker, engine=eng,
                                       max_new_tokens=4,
                                       registry=reg).start()
        try:
            pub = NDArrayPublisher(broker, "dl4j-gen-input")
            for _ in range(2):
                pub.publish(np.asarray(rng_np.integers(0, VOCAB, 3),
                                       np.int32))
            got = [out.poll(timeout=30) for _ in range(2)]
            assert all(g is not None for g in got)
            assert _wait(lambda: len(ring) == 2)
            # the publish span lands right after serving; wait for it
            assert _wait(lambda: all(
                "publish" in t.span_names() for t in ring.recent()))
            for t in ring.recent():
                names = [s["name"] for s in t.to_dict()["spans"]]
                assert names[0] == "consume"
                assert names[-1] == "publish"
                for needed in ("submit", "queued", "prefill",
                               "decode_block"):
                    assert needed in names
            assert route.served == 2
        finally:
            route.stop()

    def test_route_owned_engine_uses_injected_sinks(self, shared_decoder,
                                                    rng_np):
        """registry=/trace_store= thread through to a ROUTE-owned
        engine: metrics and traces both land in the injected sinks, not
        the process defaults."""
        net, dec = shared_decoder
        reg, ring = MetricsRegistry(), TraceRing(16)
        broker = MessageBroker()
        out = NDArraySubscriber(broker, "dl4j-gen-output")
        route = GenerationServingRoute(net, broker, max_new_tokens=3,
                                       num_slots=2, registry=reg,
                                       trace_store=ring).start()
        try:
            pub = NDArrayPublisher(broker, "dl4j-gen-input")
            pub.publish(np.asarray(rng_np.integers(0, VOCAB, 3), np.int32))
            assert out.poll(timeout=60) is not None
            assert _wait(lambda: len(ring) == 1)
            assert "consume" in ring.recent()[0].span_names()
            eid = route.engine.engine_id
            assert reg.get("generation_completed_total") \
                .labels(eid).value == 1
        finally:
            route.stop()

    def test_tracing_off_records_nothing(self, shared_decoder, rng_np):
        reg, ring = MetricsRegistry(), TraceRing(64)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      tracing=False)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 4)
                for _ in range(3)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        assert len(ring) == 0
        assert all(r.trace is None for r in reqs)
        hist = reg.get("generation_decode_block_seconds")
        assert hist.labels(eng.engine_id).count == 0
        # the counters stay: they ARE the stats machinery
        assert eng.stats()["completed"] == 3


class TestTelemetryEndpoints:
    def test_endpoints_serve_live_state(self, shared_decoder, rng_np):
        reg, ring = MetricsRegistry(), TraceRing(64)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 4)
                for _ in range(3)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        srv = TelemetryServer(registry=reg, trace_store=ring,
                              host="127.0.0.1", port=0)
        srv.add_source("generation", eng.stats).start()
        try:
            base = srv.url
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "generation_emitted_tokens_total" in text
            assert f'engine="{eng.engine_id}"' in text
            snap = json.loads(
                urllib.request.urlopen(base + "/snapshot").read())
            assert snap["sources"]["generation"]["completed"] == 3
            assert snap["metrics"]["generation_completed_total"][
                "values"][f"engine={eng.engine_id}"] == 3
            assert snap["traces"]["completed"] == 3
            doc = json.loads(urllib.request.urlopen(
                base + "/traces/recent?n=2").read())
            assert doc["count"] == 2
            assert all(t["status"] == "ok" for t in doc["traces"])
            health = json.loads(
                urllib.request.urlopen(base + "/healthz").read())
            assert health["ok"] is True
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/nope")
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_snapshot_source_failure_degrades(self):
        srv = TelemetryServer(registry=MetricsRegistry(),
                              trace_store=TraceRing(4),
                              host="127.0.0.1", port=0)
        srv.add_source("broken", lambda: 1 / 0).start()
        try:
            snap = json.loads(urllib.request.urlopen(
                srv.url + "/snapshot").read())
            assert "ZeroDivisionError" in snap["sources"]["broken"]["error"]
        finally:
            srv.stop()


class _Count:
    """Counting wrappers around the engine loop's stamp source and sinks:
    ``interval_now`` (wherever a module imported it), ``Seam``, ``Span``,
    ``Trace``, ``TraceAnnotation`` and the profiler channel's ``record_*``
    / ``mark_idle``. The engine runs synchronously (``run_until_drained``)
    on the test's thread and only that thread is counted (earlier tests'
    serve loops may still idle in theirs), so every count is exact."""

    def __init__(self, monkeypatch, session_active: bool = False):
        import sys

        from deeplearning4j_tpu.models import generation
        from deeplearning4j_tpu.observability import profiler, tracing
        me = threading.get_ident()

        class Counts(dict):
            def bump(self, key):
                if threading.get_ident() == me:
                    self[key] += 1
        self.n = n = Counts(stamps=0, seams=0, spans=0, traces=0,
                            annotations=0, records=0)
        real_now = tracing.interval_now

        def now():
            n.bump("stamps")
            return real_now()
        for mod in list(sys.modules.values()):
            if getattr(mod, "__name__", "").startswith(
                    "deeplearning4j_tpu") and \
                    getattr(mod, "interval_now", None) is real_now:
                monkeypatch.setattr(mod, "interval_now", now)

        class Seam(tracing.Seam):
            __slots__ = ()

            def __init__(self, *a, **kw):
                n.bump("seams")
                super().__init__(*a, **kw)

        class Span(tracing.Span):
            __slots__ = ()

            def __init__(self, *a, **kw):
                n.bump("spans")
                super().__init__(*a, **kw)

        class Trace(tracing.Trace):
            def __init__(self, *a, **kw):
                n.bump("traces")
                super().__init__(*a, **kw)

        class Annotation:
            """Stands in for ``jax.profiler.TraceAnnotation`` with a
            session ``session_active``: counts what the mirror builds."""

            def __init__(self, name, **stats):
                n.bump("annotations")

            @staticmethod
            def is_enabled():
                return session_active

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(generation, "Seam", Seam)
        monkeypatch.setattr(tracing, "Span", Span)
        monkeypatch.setattr(generation, "Trace", Trace)
        monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
        for meth in ("record_block", "record_admission", "record_chunk",
                     "record_spec", "mark_idle"):
            real = getattr(profiler.EngineChannel, meth)

            def counted(self_, *a, _real=real, **kw):
                n.bump("records")
                return _real(self_, *a, **kw)
            monkeypatch.setattr(profiler.EngineChannel, meth, counted)


class TestTelemetryOverhead:
    """What the instrumentation costs, as exact counts (the rate it costs
    on the chip is measured there: PERF.md): stamps and span objects per
    retired block and per admission, none per token."""

    GENS = (5, 9, 7, 13)

    def _drain(self, shared_decoder, rng_np, gens, **kw):
        reg = MetricsRegistry()
        eng = _engine(shared_decoder, num_slots=4, block_size=4,
                      registry=reg, trace_store=TraceRing(16),
                      profiler=PhaseProfiler(registry=reg), **kw)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), g) for g in gens]
        eng.run_until_drained()
        assert [len(r.generated) for r in reqs] == list(gens)
        return eng, reqs

    def test_stamps_and_spans_per_block_and_admission_none_per_token(
            self, shared_decoder, rng_np, monkeypatch):
        count = _Count(monkeypatch)
        eng, reqs = self._drain(shared_decoder, rng_np, self.GENS)
        n = count.n
        st = eng.stats()
        dispatched, admissions = st["decode_blocks"], st["prefill_batches"]
        retired = eng._prof.summary()["blocks"]
        assert retired >= 3 and admissions >= 1
        # a block is five seams (dispatch_block, block_readback, retire,
        # journal, publish), four of them at its retire; an admission is
        # five (admit, prefill_readback, retire, journal, publish)
        assert n["seams"] == dispatched + 4 * retired + 5 * admissions
        assert n["records"] == retired + admissions
        # a request: the submit event, queued, prefill, and one span a
        # block it decoded in — ceil((tokens - 1) / K), not one a token
        per_req = [3 + -(-(g - 1) // 4) for g in self.GENS]
        assert [len(r.trace.spans()) for r in reqs] == per_req
        assert n["spans"] == sum(per_req)
        assert n["traces"] == len(reqs)
        # two stamps a seam, one a decode cycle (the slot sweep's) and a
        # constant number a request, whatever the number of tokens:
        # doubling every answer adds blocks and not one stamp beside them
        other = n["stamps"] - 2 * n["seams"] - dispatched
        assert 0 < other <= 8 * len(reqs)
        for key in n:
            n[key] = 0
        eng2, _ = self._drain(shared_decoder, rng_np,
                              [2 * g for g in self.GENS])
        assert eng2.stats()["decode_blocks"] > dispatched
        assert n["stamps"] - 2 * n["seams"] \
            - eng2.stats()["decode_blocks"] == other
        assert n["annotations"] == 0      # no profiler session: no mirror

    def test_telemetry_off_calls_no_sink(self, shared_decoder, rng_np,
                                         monkeypatch):
        """``tracing=False, profiling=False``: the seams still take their
        stamps (the EWMAs and the request clocks need them) and feed no
        sink — no trace, span, profiler record or annotation, even with a
        profiler session active."""
        count = _Count(monkeypatch, session_active=True)
        eng, reqs = self._drain(shared_decoder, rng_np, self.GENS,
                                tracing=False, profiling=False)
        n = count.n
        assert n["seams"] > 0 and n["stamps"] >= 2 * n["seams"]
        assert n["traces"] == n["spans"] == n["records"] == 0
        assert n["annotations"] == 0
        assert all(r.trace is None for r in reqs)
        for r, g in zip(reqs, self.GENS):
            assert sum(k for _, k in r.emissions()) == g
            assert None not in r.clocks().values()

    def test_profiler_session_mirrors_every_seam(self, shared_decoder,
                                                 rng_np, monkeypatch):
        """With a session active every seam builds exactly one
        annotation, carrying its stats."""
        count = _Count(monkeypatch, session_active=True)
        self._drain(shared_decoder, rng_np, self.GENS)
        assert count.n["annotations"] == count.n["seams"] > 0


class TestSLOTracker:
    """SLO math (ISSUE 9): window exactness under thread storms,
    attainment/burn against a numpy oracle, and deadline-headroom
    continuity across a supervisor takeover."""

    def test_attainment_and_burn_match_numpy_oracle(self):
        rng = np.random.default_rng(3)
        trk = SLOTracker(registry=MetricsRegistry(), name="oracle",
                         target=0.95, capacity=2048)
        times = np.sort(rng.uniform(0.0, 100.0, 600))
        status = rng.choice(["ok", "deadline", "cancelled", "shed"],
                            600, p=[0.7, 0.15, 0.05, 0.1])
        headroom = rng.uniform(-2.0, 5.0, 600)
        for t, st, h in zip(times, status, headroom):
            # ok records carry non-negative headroom (the engine raises
            # DeadlineExceeded otherwise, which lands as status=deadline)
            trk.record(st, headroom=abs(h) if st == "ok" else -abs(h),
                       latency=0.1, now=float(t))
        now = 100.0
        for window in (10.0, 37.5, 80.0, None):
            counted = status != "cancelled"
            if window is not None:
                counted &= times >= now - window
            met = counted & (status == "ok")
            want = 1.0 if not counted.sum() else \
                met.sum() / counted.sum()
            got = trk.attainment(window, now=now)
            assert got == pytest.approx(want, abs=1e-12)
            assert trk.burn_rate(window, now=now) == pytest.approx(
                (1.0 - want) / (1.0 - 0.95), abs=1e-9)

    def test_sixteen_thread_recording_storm_window_exact(self):
        """16 threads × 250 records with deterministic injected clocks:
        every record lands exactly once, and the short/long windows
        count exactly the records whose stamps fall inside them."""
        trk = SLOTracker(registry=MetricsRegistry(), name="storm",
                         short_window=60.0, long_window=600.0,
                         capacity=8192)
        n_threads, per = 16, 250
        barrier = threading.Barrier(n_threads)

        def worker(tid):
            barrier.wait()
            for k in range(per):
                j = tid * per + k                 # global 0..3999
                trk.record("ok" if j % 5 else "deadline",
                           headroom=1.0 if j % 5 else -0.5,
                           latency=0.01, now=j * 0.025)  # t in [0, 100)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = trk.snapshot(now=100.0)
        assert snap["requests"] == n_threads * per
        assert snap["missed"] == n_threads * per // 5
        # short window [40, 100): j*0.025 >= 40  ->  j >= 1600
        short = snap["windows"]["short"]
        assert short["n"] == 2400
        assert short["met"] == 2400 - sum(
            1 for j in range(1600, 4000) if j % 5 == 0)
        long_w = snap["windows"]["long"]
        assert long_w["n"] == 4000
        assert trk._m_requests.labels("storm", "ok").value == \
            sum(1 for j in range(4000) if j % 5)

    def test_cancelled_excluded_sheds_count_as_miss(self):
        trk = SLOTracker(registry=MetricsRegistry(), name="mix",
                         target=0.5)
        trk.record("ok", headroom=1.0, now=1.0)
        trk.record("cancelled", now=2.0)
        trk.record("shed", now=3.0)
        trk.record("failed", now=4.0)
        snap = trk.snapshot(now=5.0)
        assert snap["requests"] == 3          # cancelled not counted
        assert snap["missed"] == 2
        assert trk.attainment(None, now=5.0) == pytest.approx(1 / 3)
        assert snap["by_status"] == {"ok": 1, "cancelled": 1,
                                     "shed": 1, "failed": 1}

    def test_registry_gauges_follow_tracker(self):
        reg = MetricsRegistry()
        trk = SLOTracker(registry=reg, name="g", target=0.9)
        trk.record("ok", headroom=1.0)
        trk.record("deadline", headroom=-1.0)
        vals = reg.snapshot()["slo_attainment_ratio"]["values"]
        assert vals["tracker=g,window=short"] == pytest.approx(0.5)
        burn = reg.snapshot()["slo_burn_rate"]["values"]
        assert burn["tracker=g,window=long"] == pytest.approx(5.0)
        hist = reg.get("slo_deadline_headroom_seconds")
        assert hist.labels("g").count == 2

    def test_engine_records_one_slo_account_per_request(
            self, shared_decoder, rng_np):
        reg = MetricsRegistry()
        trk = SLOTracker(registry=reg, name="eng")
        eng = _engine(shared_decoder, registry=reg, slo=trk,
                      slo_label="rA")
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 4,
                           deadline=60.0, route="unit")
                for _ in range(4)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        snap = trk.snapshot()
        assert snap["requests"] == 4 and snap["missed"] == 0
        assert set(snap["replicas"]) == {"rA"}
        assert set(snap["routes"]) == {"unit"}
        for rec in trk.recent(10):
            assert rec["status"] == "ok"
            assert rec["queue_wait_s"] is not None
            assert 0.0 <= rec["ttft_s"] <= rec["latency_s"]
            # headroom + latency == deadline (both anchored at submit)
            assert rec["headroom_s"] == pytest.approx(
                60.0 - rec["latency_s"], abs=0.05)
            assert rec["tokens"] == 4

    def test_slo_sync_fail_seam_suppresses_spillable_fast_fails(
            self, shared_decoder, rng_np):
        """The fleet dispatch seam: with ``_slo_sync_fail=False`` an
        engine-level synchronous fast-fail (queue-full shed, dead
        engine) records NOTHING — the router spills onward and the
        serving replica (or the router's own shed) accounts the request
        exactly once. Default submits keep accounting sync fails."""
        reg = MetricsRegistry()
        trk = SLOTracker(registry=reg, name="seam")
        eng = _engine(shared_decoder, registry=reg, slo=trk,
                      slo_label="rS", max_pending=1)
        prompt = rng_np.integers(0, VOCAB, 3)
        held = eng.submit(prompt, 4)             # fills the 1-deep queue
        shed_armed = eng.submit(prompt, 4)       # default: accounted
        shed_unarmed = eng.submit(prompt, 4, _slo_sync_fail=False)
        assert shed_armed.done() and shed_unarmed.done()
        snap = trk.snapshot()
        assert snap["by_status"] == {"shed": 1}
        assert shed_unarmed._slo_done is False   # the fleet gate's cue
        eng.run_until_drained()
        assert held.done() and trk.snapshot()["by_status"] == {
            "ok": 1, "shed": 1}
        eng.shutdown()
        dead_unarmed = eng.submit(prompt, 4, _slo_sync_fail=False)
        assert dead_unarmed.done()
        assert trk.snapshot()["by_status"] == {"ok": 1, "shed": 1}

    def test_deadline_headroom_survives_takeover(self, shared_decoder,
                                                 rng_np):
        """The takeover span must not reset the clock: a recovered
        request's headroom/latency are measured from the ORIGINAL
        submission, and it is SLO-accounted exactly once."""
        reg, ring = MetricsRegistry(), TraceRing(64)
        trk = SLOTracker(registry=reg, name="tk")
        inj = FaultInjector(registry=reg,
                            flight_recorder=FlightRecorder(registry=reg))
        inj.raise_once("engine.step", RuntimeError("chaos"), at=3)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      fault_injector=inj, slo=trk, slo_label="rT")
        sup = EngineSupervisor(eng, timeout=10.0, interval=0.1,
                               max_restarts=2,
                               flight_recorder=eng._flightrec).start()
        try:
            t0 = time.monotonic()
            reqs = [sup.submit(rng_np.integers(0, VOCAB, 3), 6,
                               deadline=120.0) for _ in range(5)]
            created = [r._created_t for r in reqs]
            for r in reqs:
                assert r.result(60) is not None
            wall = time.monotonic() - t0
            assert sup.restarts == 1
            # creation stamps never reset, label re-pointed post-takeover
            assert [r._created_t for r in reqs] == created
            assert all(r._slo_labels["replica"] == "rT" for r in reqs)
            snap = trk.snapshot()
            assert snap["requests"] == 5          # exactly once each
            assert snap["missed"] == 0
            for rec in trk.recent(10):
                assert rec["headroom_s"] == pytest.approx(
                    120.0 - rec["latency_s"], abs=0.05)
                assert rec["latency_s"] <= wall + 0.05
            # the crash really harvested in-flight work: at least one
            # request carries a takeover span — and ITS latency is
            # still deadline-consistent (checked above for all)
            assert any("takeover" in r.trace.span_names()
                       for r in reqs)
        finally:
            sup.stop()


class TestFlightRecorder:
    def test_ring_bounded_sequenced_and_counted(self):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=8, registry=reg)
        for i in range(20):
            rec.record("admission", batch=i)
        assert len(rec) == 8
        assert rec.total_events == 20
        evs = rec.events()
        assert [e["seq"] for e in evs] == list(range(13, 21))
        assert reg.get("flightrec_events_total") \
            .labels("admission").value == 20
        st = rec.stats()
        assert st["ring"] == 8 and st["by_kind"] == {"admission": 8}

    def test_events_filter_by_kind_and_count(self):
        rec = FlightRecorder(capacity=32)
        for i in range(4):
            rec.record("shed", depth=i)
            rec.record("takeover", n=i)
        assert len(rec.events(kind="shed")) == 4
        assert [e["n"] for e in rec.events(2, kind="takeover")] == [2, 3]

    def test_postmortem_artifact_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("pm_total", "x").inc(3)
        rec = FlightRecorder(capacity=16, registry=reg)
        rec.record("fault", point="engine.step")
        rec.record("crash", engine="e1")
        ring = TraceRing(4)
        tr = Trace(store=ring)
        tr.event("submit")
        tr.finish("failed:RuntimeError")
        path = rec.write_postmortem(
            str(tmp_path), "unit", reason="unit crash",
            cause=RuntimeError("boom"), traces=[tr, None],
            registry=reg, extra={"k": "v"})
        assert path is not None
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["reason"] == "unit crash"
        assert doc["cause"] == "RuntimeError: boom"
        assert [e["kind"] for e in doc["events"]] == ["fault", "crash"]
        assert doc["request_ids"] == [tr.request_id]
        assert doc["traces"][0]["status"] == "failed:RuntimeError"
        assert doc["metrics"]["pm_total"]["values"][""] == 3
        assert doc["extra"] == {"k": "v"}
        assert rec.dumps == [path]
        assert rec.events()[-1]["kind"] == "postmortem"

    def test_postmortem_artifacts_never_clobber_across_recorders(
            self, tmp_path):
        """seq is per-recorder: a second soak round (fresh recorder,
        same directory, same tag) must land NEXT TO round 1's artifact,
        not os.replace it away (regression: identical filenames)."""
        paths = []
        for _ in range(3):
            rec = FlightRecorder(capacity=8, registry=MetricsRegistry())
            rec.record("crash", engine="e1")
            paths.append(rec.write_postmortem(
                str(tmp_path), "slot-engine", reason="round crash"))
        assert all(p is not None for p in paths)
        assert len(set(paths)) == 3
        for p in paths:
            with open(p, encoding="utf-8") as f:
                assert json.load(f)["reason"] == "round crash"

    def test_postmortem_write_failure_degrades(self, tmp_path):
        rec = FlightRecorder(capacity=8, registry=MetricsRegistry())
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("file, not dir")
        path = rec.write_postmortem(str(blocker), "x", reason="r")
        assert path is None and rec.dumps == []
        assert rec.events()[-1] == {
            "seq": 1, "t": rec.events()[-1]["t"], "kind": "postmortem",
            "tag": "x", "error": "write failed"}

    def test_engine_lifecycle_events_gated_on_tracing(
            self, shared_decoder, rng_np):
        reg = MetricsRegistry()
        rec = FlightRecorder(registry=reg)
        eng = _engine(shared_decoder, registry=reg, flight_recorder=rec)
        for _ in range(3):
            eng.submit(rng_np.integers(0, VOCAB, 3), 4)
        eng.run_until_drained()
        kinds = {e["kind"] for e in rec.events()}
        assert {"admission", "block_retire"} <= kinds
        # telemetry-off arm: lifecycle events skipped (the ≤5% A/B)
        rec2 = FlightRecorder(registry=MetricsRegistry())
        eng2 = _engine(shared_decoder, registry=MetricsRegistry(),
                       tracing=False, flight_recorder=rec2)
        eng2.submit(rng_np.integers(0, VOCAB, 3), 4)
        eng2.run_until_drained()
        assert rec2.events() == []

    def test_supervisor_writes_postmortem_on_crash(self, shared_decoder,
                                                   rng_np, tmp_path):
        reg, ring = MetricsRegistry(), TraceRing(64)
        rec = FlightRecorder(registry=reg)
        inj = FaultInjector(registry=reg, flight_recorder=rec)
        inj.raise_once("engine.step", RuntimeError("chaos"), at=3)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      fault_injector=inj, flight_recorder=rec)
        sup = EngineSupervisor(eng, timeout=10.0, interval=0.1,
                               max_restarts=2,
                               postmortem_dir=str(tmp_path)).start()
        try:
            reqs = [sup.submit(rng_np.integers(0, VOCAB, 3), 6)
                    for _ in range(5)]
            for r in reqs:
                assert r.result(60) is not None
            assert sup.restarts == 1
            paths = rec.dumps
            assert len(paths) == 1
            with open(paths[0], encoding="utf-8") as f:
                doc = json.load(f)
            kinds = [e["kind"] for e in doc["events"]]
            assert "fault" in kinds and "takeover" in kinds
            # embedded traces ARE the harvested requests' timelines
            known = {r.trace.request_id for r in reqs}
            assert set(doc["request_ids"]) \
                == set(doc["extra"]["recovered_request_ids"])
            assert set(doc["request_ids"]) <= known
            assert doc["request_ids"]          # the crash harvested work
        finally:
            sup.stop()


class TestDeviceStats:
    def test_kv_cache_bytes_exact_from_live_leaves(self, shared_decoder):
        """The accounting reads the ACTUAL cache leaves: layers × k/v ×
        slots × heads × T_max × Dh × itemsize, no formula drift."""
        eng = _engine(shared_decoder, registry=MetricsRegistry())
        st = kv_cache_stats(eng)
        # shared decoder: 2 attention layers, 2 heads, T_max 32, Dh 16
        want = 2 * 2 * (2 * 2 * 32 * 16) * 4
        assert st["bytes"] == want
        assert st["addressable_bytes"] == want     # unsharded: all local
        assert st["shards"] == 1 and st["layers"] == 2
        assert st["slot_shape"] == [2, 2, 32, 16]
        assert st["dtype"] == "float32"
        assert st["bytes_per_slot"] == want // 2

    def test_device_memory_snapshot_degrades_on_cpu(self):
        snap = device_memory_snapshot()
        assert snap["devices"], "at least one jax device"
        for d in snap["devices"]:
            assert {"id", "platform", "kind", "memory_stats"} <= set(d)
        census = snap["live_arrays"]
        assert census["count"] is None or census["count"] >= 0
        assert census["bytes"] is None or census["bytes"] >= 0

    def test_devstats_snapshot_and_registry_gauge(self, shared_decoder,
                                                  rng_np):
        reg = MetricsRegistry()
        eng = _engine(shared_decoder, registry=reg)
        eng.submit(rng_np.integers(0, VOCAB, 3), 3)
        eng.run_until_drained()
        ds = DeviceStats(registry=reg).attach_engine("gen", eng)
        snap = ds.snapshot()
        want = kv_cache_stats(eng)["bytes"]
        assert snap["kv_cache"]["gen"]["bytes"] == want
        assert snap["devices"]
        vals = reg.snapshot()["devstats_kv_cache_bytes"]["values"]
        assert vals["engine=gen"] == want
        assert reg.snapshot()["devstats_live_arrays"]["values"][""] > 0


class TestSLOAndDevstatsEndpoints:
    def test_slo_endpoint_and_snapshot_sections(self, shared_decoder,
                                                rng_np):
        reg, ring = MetricsRegistry(), TraceRing(64)
        trk = SLOTracker(registry=reg, name="srv")
        rec = FlightRecorder(registry=reg)
        eng = _engine(shared_decoder, registry=reg, trace_store=ring,
                      slo=trk, slo_label="r0", flight_recorder=rec)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 4,
                           deadline=60.0, route="lm")
                for _ in range(3)]
        eng.run_until_drained()
        assert all(r.done() for r in reqs)
        srv = TelemetryServer(registry=reg, trace_store=ring,
                              slo_tracker=trk, flight_recorder=rec)
        srv.add_engine("gen", eng).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/slo").read())
            assert doc["tracker"] == "srv"
            assert doc["requests"] == 3 and doc["missed"] == 0
            assert set(doc["windows"]) == {"short", "long"}
            assert doc["windows"]["long"]["attainment"] == 1.0
            assert set(doc["replicas"]) == {"r0"}
            assert set(doc["routes"]) == {"lm"}
            assert doc["overall"]["headroom_s"]["min"] > 0
            snap = json.loads(urllib.request.urlopen(
                srv.url + "/snapshot").read())
            # the acceptance bar: exact KV bytes live in /snapshot
            kv = snap["devstats"]["kv_cache"]["gen"]
            assert kv["bytes"] == kv_cache_stats(eng)["bytes"]
            assert snap["slo"]["requests"] == 3
            assert snap["flightrec"]["events_total"] == \
                rec.total_events
            # engine source rides the same add_engine() call
            assert snap["sources"]["gen"]["completed"] == 3
            # SLO gauges render on /metrics too
            text = urllib.request.urlopen(
                srv.url + "/metrics").read().decode()
            assert 'slo_attainment_ratio{tracker="srv",window="long"} 1' \
                in text
        finally:
            srv.stop()

    def test_traces_recent_query_params_over_http(self):
        """?n= and ?status= (ISSUE 9 satellite): filter BEFORE the count
        cut — ?n=2&status=failed is 'the last 2 failures'."""
        ring = TraceRing(32)
        statuses = ["ok", "failed:RuntimeError", "ok",
                    "failed:ValueError", "failed:RuntimeError", "ok"]
        ids = []
        for st in statuses:
            tr = Trace(store=ring)
            tr.event("submit")
            tr.finish(st)
            ids.append(tr.request_id)
        srv = TelemetryServer(registry=MetricsRegistry(),
                              trace_store=ring).start()
        try:
            def get(query):
                return json.loads(urllib.request.urlopen(
                    srv.url + "/traces/recent" + query).read())
            assert get("")["count"] == 6
            assert get("?n=2")["count"] == 2
            doc = get("?status=failed")
            assert doc["count"] == 3
            assert [t["request_id"] for t in doc["traces"]] == \
                [ids[1], ids[3], ids[4]]
            assert all(t["status"].startswith("failed:")
                       for t in doc["traces"])
            doc = get("?n=2&status=failed")      # the last 2 FAILURES
            assert [t["request_id"] for t in doc["traces"]] == \
                [ids[3], ids[4]]
            doc = get("?status=failed:ValueError")
            assert [t["request_id"] for t in doc["traces"]] == [ids[3]]
            assert get("?status=ok")["count"] == 3
            assert get("?status=nope")["count"] == 0
            assert get("?n=bogus")["count"] == 6     # bad n: ignored
        finally:
            srv.stop()


def _load_telemetry_dump():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "telemetry_dump", os.path.join(os.path.dirname(__file__),
                                       "..", "scripts",
                                       "telemetry_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFleetScrape:
    """``telemetry_dump --scrape`` (ISSUE 9): merge N replicas' live
    ``/snapshot`` documents into one fleet summary, over real HTTP."""

    @staticmethod
    def _three_replicas():
        servers, urls, trackers = [], [], []
        for i in range(3):
            reg = MetricsRegistry()
            trk = SLOTracker(registry=reg, name=f"r{i}", target=0.9)
            # r0: 10/10 met; r1: 8/10; r2: 9/10 -> fleet 27/30
            misses = {0: 0, 1: 2, 2: 1}[i]
            for j in range(10):
                ok = j >= misses
                trk.record("ok" if ok else "deadline",
                           ttft=0.01, queue_wait=0.001, latency=0.05,
                           headroom=1.0 if ok else -0.5,
                           replica=f"r{i}")
            reg.counter("served_total", "s").inc(10 + i)
            srv = TelemetryServer(registry=reg, trace_store=TraceRing(4),
                                  slo_tracker=trk).start()
            servers.append(srv)
            urls.append(srv.url)
            trackers.append(trk)
        return servers, urls, trackers

    def test_scrape_merges_three_live_replicas(self):
        td = _load_telemetry_dump()
        servers, urls, _ = self._three_replicas()
        try:
            doc = td.scrape_fleet(urls + ["http://127.0.0.1:9"],
                                  timeout=5.0)
            assert doc["scraped"] == 4 and doc["up"] == 3
            down = doc["replicas"]["http://127.0.0.1:9"]
            assert down["up"] is False and "error" in down
            # pooled attainment is met/n summed across replicas — the
            # numpy-oracle identity, not an average of ratios
            agg = doc["slo"]
            assert agg["requests"] == 30 and agg["missed"] == 3
            assert agg["attainment_long"] == pytest.approx(27 / 30)
            assert agg["burn_rate_long"] == pytest.approx(
                (3 / 30) / (1 - 0.9))
            for i, url in enumerate(urls):
                row = doc["replicas"][url]
                assert row["up"] is True
                assert row["attainment_long"] == pytest.approx(
                    (10 - {0: 0, 1: 2, 2: 1}[i]) / 10)
                assert row["headroom_min_s"] is not None
            # counters summed fleet-wide
            assert doc["counters"]["served_total"] == 10 + 11 + 12
            assert doc["counters"]["slo_requests_total"] == 30
        finally:
            for s in servers:
                s.stop()

    def test_scrape_cli_json_and_exit_codes(self, capsys):
        td = _load_telemetry_dump()
        servers, urls, _ = self._three_replicas()
        try:
            rc = td.main(["--scrape", ",".join(urls), "--json"])
            assert rc == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["up"] == 3
            rc = td.main(["--scrape", ",".join(urls)])
            out = capsys.readouterr().out
            assert rc == 0
            assert "fleet scrape: 3/3 replicas up" in out
            assert "fleet SLO (target 0.9)" in out
        finally:
            for s in servers:
                s.stop()
        # every replica down: exit 2 (automation must not read an
        # empty merge as healthy)
        assert td.main(["--scrape", "http://127.0.0.1:9", "--json"]) == 2
        capsys.readouterr()

    def test_watch_prints_counter_rates_and_gauge_moves(self):
        import io
        td = _load_telemetry_dump()
        samples = [
            {"rates": {"a_total": 10}, "gauges": {"depth": 3.0}},
            {"rates": {"a_total": 30}, "gauges": {"depth": 5.0}},
            {"rates": {"a_total": 30}, "gauges": {"depth": 5.0}},
        ]
        it = iter(samples)
        out = io.StringIO()
        clock_vals = iter([0.0, 2.0, 4.0])
        rc = td.watch(lambda: next(it), period=0.0, count=2, out=out,
                      clock=lambda: next(clock_vals),
                      sleep=lambda s: None)
        assert rc == 0
        text = out.getvalue()
        assert "a_total" in text and "+20" in text and "10.00/s" in text
        assert "depth" in text and "3 -> 5" in text
        # the steady sample prints no spurious delta lines
        assert text.count("a_total") == 1

    def test_watch_cli_against_live_server(self, shared_decoder, rng_np,
                                           capsys):
        td = _load_telemetry_dump()
        reg = MetricsRegistry()
        eng = _engine(shared_decoder, registry=reg)
        srv = TelemetryServer(registry=reg,
                              trace_store=TraceRing(8)).start()
        try:
            eng.submit(rng_np.integers(0, VOCAB, 3), 3)
            eng.run_until_drained()
            rc = td.main([srv.url, "--watch", "0.05", "--count", "1"])
            assert rc == 0
            assert "watch sample" in capsys.readouterr().out
        finally:
            srv.stop()


class TestPhaseProfiler:
    """Hot-loop phase profiler (ISSUE 13): telescoping phase exactness
    under injected clocks, pipeline/lane bubble semantics, the /profile
    endpoint over real HTTP, the on/off overhead A/B, and channel
    continuity across a supervisor takeover."""

    def test_phases_sum_to_wall_time_exactly_under_injected_clocks(self):
        prof = PhaseProfiler(registry=MetricsRegistry())
        ch = prof.channel("eX", num_slots=4)
        # block 1: dispatch 10.00 -> fetched 10.25 -> host 10.31 ->
        # journal 10.34 -> publish 10.35
        ch.record_block(impl="decode_block4_impl", k=4, lanes=3,
                        queued=2, t_dispatch=10.0, t_fetched=10.25,
                        t_host=10.31, t_journal=10.34, t_publish=10.35)
        s = ch.summary()
        assert sum(s["phase_seconds"].values()) == pytest.approx(
            0.35, abs=1e-9)
        assert s["phase_seconds"]["device"] == pytest.approx(0.25)
        assert s["phase_seconds"]["host"] == pytest.approx(0.06)
        assert s["phase_seconds"]["journal"] == pytest.approx(0.03)
        assert s["phase_seconds"]["publish"] == pytest.approx(0.01)
        assert s["bubble_seconds"] == 0.0        # first block: no anchor
        # block 2 dispatched 0.65s after block 1's data was ready:
        # that gap IS the pipeline bubble
        ch.record_block(impl="decode_block4_impl", k=4, lanes=3,
                        queued=0, t_dispatch=10.9, t_fetched=11.0,
                        t_host=11.0, t_journal=11.0, t_publish=11.0)
        s = ch.summary()
        assert s["bubble_seconds"] == pytest.approx(0.65)
        # overlapped dispatch (double buffer: dispatch BEFORE the
        # previous retire) contributes zero bubble
        ch.record_block(impl="decode_block4_impl", k=4, lanes=3,
                        queued=0, t_dispatch=10.95, t_fetched=11.4,
                        t_host=11.45, t_journal=11.45, t_publish=11.5)
        assert ch.summary()["bubble_seconds"] == pytest.approx(0.65)
        # every timeline entry is non-negative and internally consistent
        for e in prof.timeline.recent(None):
            assert e["bubble_ms"] >= 0
            assert all(v >= 0 for v in e["phases_ms"].values())
        assert prof.timeline.total_added == 3

    def test_lane_bubble_counts_idle_lanes_only_while_queued(self):
        prof = PhaseProfiler(registry=MetricsRegistry())
        ch = prof.channel("eY", num_slots=4)
        # 2 of 4 lanes busy for 1s WITH work queued: half the slot-time
        # is chargeable lane bubble
        ch.record_block(impl="i", k=1, lanes=2, queued=3, t_dispatch=0.0,
                        t_fetched=1.0, t_host=1.0, t_journal=1.0,
                        t_publish=1.0)
        assert ch.summary()["lane_bubble_pct"] == pytest.approx(50.0)
        # idle lanes with an EMPTY queue are not waste
        ch.record_block(impl="i", k=1, lanes=2, queued=0, t_dispatch=1.0,
                        t_fetched=2.0, t_host=2.0, t_journal=2.0,
                        t_publish=2.0)
        assert ch.summary()["lane_bubble_pct"] == pytest.approx(25.0)

    def test_warmup_dispatch_excluded_from_steady_durations(self):
        prof = PhaseProfiler(registry=MetricsRegistry())
        ch = prof.channel("eW", num_slots=2)
        # first block (compile-laden, 5s) must not pollute the steady
        # mean; the two post-warmup blocks define it
        for t0, t1 in ((0.0, 5.0), (5.0, 5.1), (6.0, 6.1)):
            ch.record_block(impl="decode_block2_impl", k=2, lanes=2,
                            queued=0, t_dispatch=t0, t_fetched=t1,
                            t_host=t1, t_journal=t1, t_publish=t1)
        m = ch.summary()["impl_measured"]["decode_block2_impl"]
        assert m["n"] == 2
        assert m["mean_s"] == pytest.approx(0.1, rel=1e-6)

    def test_live_engine_accounting_consistency(self, shared_decoder,
                                                rng_np):
        reg = MetricsRegistry()
        prof = PhaseProfiler(registry=reg)
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, profiler=prof)
        for _ in range(6):
            eng.submit(rng_np.integers(0, VOCAB, 3), 6)
        eng.run_until_drained()
        ch = prof.channels()[eng.slo_label]
        s = ch.summary()
        # every RETIRED block is recorded; a dispatched-but-dropped
        # in-flight block (wave drained mid-pipeline: its tokens are
        # pure overshoot, fetched never) is not — so recorded <= dispatched
        assert 0 < s["blocks"] <= eng.decode_blocks
        assert s["admissions"] == eng.prefill_batches
        assert all(v >= 0 for v in s["phase_seconds"].values())
        assert s["bubble_seconds"] >= 0
        for e in prof.timeline.recent(None):
            assert e["bubble_ms"] >= 0
            assert all(v >= 0 for v in e["phases_ms"].values())
        # the registry histograms carry the same observation counts
        fam = reg.get("profiler_phase_seconds")
        dev = fam.labels(eng.slo_label, "device")
        assert dev.count == s["blocks"] + s["admissions"] + s["chunks"]

    def test_k1_legacy_loop_bubbles_more_than_pipelined_k4(
            self, shared_decoder, rng_np):
        """The double-buffer overlap measure: the K=1 dispatch->sync->
        bookkeep loop leaves the device idle every step, the K=4
        pipelined loop overlaps — its bubble fraction must be lower."""
        prompts = [rng_np.integers(0, VOCAB, 3) for _ in range(4)]

        def bubble_pct(block: int) -> float:
            reg = MetricsRegistry()
            prof = PhaseProfiler(registry=reg)
            eng = _engine(shared_decoder, num_slots=2, block_size=block,
                          registry=reg, profiler=prof)
            for p in prompts:
                eng.submit(p, 16)
            eng.run_until_drained()
            return prof.channels()[eng.slo_label].summary()["bubble_pct"]

        b1, b4 = bubble_pct(1), bubble_pct(4)
        assert b1 > b4, f"K=1 bubble {b1}% should exceed K=4 {b4}%"

    def test_static_waves_show_higher_lane_bubble_than_refill(
            self, shared_decoder, rng_np):
        """Bubble-%% sanity (the continuous-batching claim, measured):
        refill=False strands finished lanes until the wave drains while
        work is queued — strictly higher lane bubble than continuous
        batching on the same mixed-length stream."""
        prompts = [rng_np.integers(0, VOCAB, 3) for _ in range(8)]
        gens = [4, 16, 4, 16, 4, 16, 4, 16]   # uneven: stragglers strand
        #                                       short lanes in a wave

        def lane_bubble(refill: bool) -> float:
            reg = MetricsRegistry()
            prof = PhaseProfiler(registry=reg)
            eng = _engine(shared_decoder, num_slots=2, block_size=4,
                          refill=refill, registry=reg, profiler=prof)
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            eng.run_until_drained()
            return prof.channels()[
                eng.slo_label].summary()["lane_bubble_pct"]

        off, on = lane_bubble(False), lane_bubble(True)
        assert off > on, \
            f"static waves lane-bubble {off}% should exceed " \
            f"continuous batching {on}%"

    def test_profile_endpoint_over_http(self, shared_decoder, rng_np):
        reg = MetricsRegistry()
        prof = PhaseProfiler(registry=reg)
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, profiler=prof)
        for _ in range(4):
            eng.submit(rng_np.integers(0, VOCAB, 3), 8)
        eng.run_until_drained()
        srv = TelemetryServer(registry=reg, trace_store=TraceRing(8),
                              profiler=prof).start()
        try:
            with urllib.request.urlopen(f"{srv.url}/profile",
                                        timeout=10) as r:
                doc = json.loads(r.read())
            ch = doc["engines"][eng.slo_label]
            assert ch["blocks"] > 0
            assert set(ch["phase_seconds"]) == {"device", "host",
                                               "journal", "publish"}
            assert "roofline" not in doc
            # ?timeline=N returns the ring tail
            with urllib.request.urlopen(f"{srv.url}/profile?timeline=5",
                                        timeout=10) as r:
                doc = json.loads(r.read())
            recent = doc["timeline"]["recent"]
            assert 0 < len(recent) <= 5
            assert all(isinstance(e["block"], int) and "after" in e
                       for e in recent)
            # ?since=S sums the last S seconds of the ring
            with urllib.request.urlopen(f"{srv.url}/profile?since=600",
                                        timeout=10) as r:
                win = json.loads(r.read())["window"]
            assert win["truncated"] is False
            assert win["kinds"]["block"]["n"] == ch["blocks"]
            assert win["kinds"]["admission"]["n"] == ch["admissions"]
            # /snapshot embeds the lightweight summary for the scrape
            with urllib.request.urlopen(f"{srv.url}/snapshot",
                                        timeout=10) as r:
                snap = json.loads(r.read())
            assert snap["profiler"]["headline"]["blocks"] > 0
            assert "bubble_pct" in snap["profiler"]["headline"]
        finally:
            srv.stop()

    def test_channel_and_timeline_survive_takeover(self, shared_decoder,
                                                   rng_np):
        """The supervisor passes the profiler + stable channel key
        through the engine rebuild: ONE channel keeps accumulating and
        the timeline ring records on both sides of the restart."""
        reg = MetricsRegistry()
        prof = PhaseProfiler(registry=reg)
        inj = FaultInjector()
        inj.raise_once("engine.step", RuntimeError("boom"), at=3)
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, profiler=prof, fault_injector=inj)
        label = eng.slo_label
        sup = EngineSupervisor(eng, timeout=2.0, interval=0.05,
                               max_restarts=2).start()
        try:
            reqs = [sup.submit(rng_np.integers(0, VOCAB, 3), 8)
                    for _ in range(4)]
            assert _wait(lambda: all(r.done() for r in reqs))
            assert sup.stats()["restarts"] >= 1
            chans = prof.channels()
            assert list(chans) == [label]       # ONE channel, rebuilt
            #                                     engine re-entered it
            assert chans[label].summary()["blocks"] > 0
            assert prof.timeline.total_added > 0
            for e in prof.timeline.recent(None):
                assert all(v >= 0 for v in e["phases_ms"].values())
        finally:
            sup.stop()


class TestClockDiscipline:
    """Satellite (ISSUE 13): every observability duration derives from
    the single interval clock — a backwards wall-clock step (NTP) can
    never produce a negative span, SLO quantity, or phase."""

    def test_interval_now_is_monotonic_nondecreasing(self):
        from deeplearning4j_tpu.observability import interval_now
        vals = [interval_now() for _ in range(100)]
        assert all(b >= a for a, b in zip(vals, vals[1:]))

    def test_backwards_wall_clock_cannot_corrupt_spans(self, monkeypatch):
        """Regression: step time.time() BACKWARDS 1h mid-trace — span
        durations, trace duration, and SLO quantities all stay
        non-negative (interval math never reads the wall clock; the
        trace keeps exactly one wall anchor for display)."""
        import deeplearning4j_tpu.observability.tracing as tracing_mod
        ring = TraceRing(4)
        tr = Trace(store=ring)
        wall = {"t": 1_700_000_000.0}
        monkeypatch.setattr(tracing_mod.time, "time",
                            lambda: wall["t"])
        with tr.span("prefill"):
            wall["t"] -= 3600.0                  # NTP step, 1h backwards
            time.sleep(0.002)
        tr.add_span("decode_block")
        wall["t"] -= 3600.0
        tr.finish("ok")
        assert tr.duration is not None and tr.duration >= 0
        doc = tr.to_dict()
        for s in doc["spans"]:
            assert s["duration_ms"] >= 0
        assert doc["duration_ms"] >= 0
        # SLO account through the same storm: stamps are interval
        # anchors, so every derived quantity is non-negative
        trk = SLOTracker(registry=MetricsRegistry(), name="ntp")
        req = type("R", (), {})()
        from deeplearning4j_tpu.observability import interval_now
        now = interval_now()
        req._created_t = now - 0.5
        req._admitted_t = now - 0.4
        req._first_token_t = now - 0.3
        req._deadline_t = now + 10.0
        req.generated = [1, 2, 3]
        req._slo_labels = {}
        wall["t"] -= 3600.0
        rec = trk.observe_request(req, "ok")
        assert rec.queue_wait >= 0 and rec.ttft >= 0
        assert rec.latency >= 0 and rec.per_token >= 0
        assert rec.headroom > 0

    def test_trace_keeps_one_wall_anchor_for_display(self):
        tr = Trace()
        tr.finish()
        doc = tr.to_dict()
        assert doc["wall_time"] == pytest.approx(tr.wall_anchor)

    def test_engine_request_clocks_ride_the_interval_clock(
            self, shared_decoder, rng_np):
        """The serving path end-to-end: request clocks are interval
        anchors (generation.py stamps interval_now), so every derived
        SLO quantity is non-negative by construction."""
        reg = MetricsRegistry()
        trk = SLOTracker(registry=reg, name="clockless")
        eng = _engine(shared_decoder, registry=reg, slo=trk)
        r = eng.submit(rng_np.integers(0, VOCAB, 3), 4, deadline=30.0)
        eng.run_until_drained()
        assert r.state == r.DONE
        rec = trk.recent(1)[0]
        assert rec["queue_wait_s"] >= 0 and rec["ttft_s"] >= 0
        assert rec["latency_s"] >= 0
        assert rec["headroom_s"] is not None and rec["headroom_s"] > 0


def _load_perf_regress():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "perf_regress", os.path.join(os.path.dirname(__file__),
                                     "..", "scripts",
                                     "perf_regress.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestPerfRegress:
    """Perf-regression sentinel (ISSUE 13): normalization across
    protocol generations, noise-aware direction-correct bands, and the
    CLI gate (real run exits 0, synthetically slowed run exits 1)."""

    GEN_DOC = {
        "metric": "lm_generate_decode_tokens_per_sec", "value": 4000.0,
        "unit": "tokens/sec",
        "side_metrics": {
            "prefill_tokens_per_sec": {"value": 90000.0},
            "decode_token_latency_ms": {"p50": 2.0, "p99": 4.0},
            "block_sweep": {"4": {"decode_tokens_per_sec": 4000.0}},
            "continuous_batching": {
                "refill_on_tokens_per_sec": 900.0,
                "refill_off_tokens_per_sec": 700.0},
        },
    }

    def test_normalize_spans_protocol_generations(self):
        pr = _load_perf_regress()
        # a BENCH_MODE=generate run and a default run's lm_generate
        # side metric land on the SAME canonical keys
        a = pr.normalize_record(self.GEN_DOC)
        default_doc = {
            "metric": "resnet50_train_images_per_sec_per_chip",
            "value": 2600.0,
            "side_metrics": {"lm_generate": dict(
                self.GEN_DOC["side_metrics"], value=4000.0)},
        }
        b = pr.normalize_record({"parsed": default_doc})
        key = "lm_generate.decode_tokens_per_sec"
        assert a[key] == b[key] == 4000.0
        assert a["lm_generate.p99_ms"] == 4.0
        assert b["resnet50_train_images_per_sec_per_chip"] == 2600.0
        assert b["lm_generate.block_sweep.k4.decode_tokens_per_sec"] \
            == 4000.0

    def test_noise_aware_band_and_direction(self):
        pr = _load_perf_regress()
        # stable throughput history: the 10% floor applies
        r = pr.check_metric("x_per_sec", [100.0, 101.0, 99.0], 95.0)
        assert r["status"] == "ok"
        r = pr.check_metric("x_per_sec", [100.0, 101.0, 99.0], 85.0)
        assert r["status"] == "regression"
        # noisy history earns a wider band: 25% spread -> ~37.5% band
        r = pr.check_metric("x_per_sec", [100.0, 125.0, 100.0], 75.0)
        assert r["status"] == "ok"
        # latency regresses UP
        r = pr.check_metric("lm_generate.p99_ms", [10.0, 11.0], 15.0)
        assert r["status"] == "regression"
        r = pr.check_metric("lm_generate.p99_ms", [10.0, 11.0], 8.0)
        assert r["status"] == "improved"
        # thin history never gates
        r = pr.check_metric("x_per_sec", [100.0], 10.0)
        assert r["status"] == "no-history"

    def test_cli_real_exits_0_degraded_exits_1(self, tmp_path, capsys):
        pr = _load_perf_regress()
        for i in range(3):
            (tmp_path / f"BENCH_r0{i}.json").write_text(json.dumps(
                {"parsed": dict(self.GEN_DOC,
                                value=4000.0 + 20 * i)}))
        cur = tmp_path / "current.json"
        cur.write_text(json.dumps(self.GEN_DOC))
        hist = str(tmp_path / "BENCH_r*.json")
        assert pr.main(["--history", hist, "--current", str(cur)]) == 0
        capsys.readouterr()
        rc = pr.main(["--history", hist, "--current", str(cur),
                      "--degrade", "0.5"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out
        assert "lm_generate.decode_tokens_per_sec" in out
        # headline-only gating still trips on the headline metrics
        assert pr.main(["--history", hist, "--current", str(cur),
                        "--degrade", "0.5", "--headline-only"]) == 1
        capsys.readouterr()

    def test_history_record_preferred_over_renormalization(
            self, tmp_path):
        pr = _load_perf_regress()
        doc = {"parsed": {"metric": "m_per_sec", "value": 1.0,
                          "history_record": {"canonical_per_sec": 42.0}}}
        (tmp_path / "BENCH_r07.json").write_text(json.dumps(doc))
        hist = pr.load_history(str(tmp_path / "BENCH_r*.json"))
        assert hist == [("BENCH_r07", {"canonical_per_sec": 42.0}, None)]

    def test_shape_fingerprint_fences_generate_series(self, tmp_path):
        """A smoke-shape run must not gate against full-shape history:
        lm_generate.* series draw only from same-fingerprint rounds."""
        pr = _load_perf_regress()
        big = dict(self.GEN_DOC, value=9000.0)
        big["side_metrics"] = dict(
            self.GEN_DOC["side_metrics"],
            config={"batch": 32, "prompt_t": 512, "decode_steps": 64,
                    "vocab": 32000})
        small = dict(self.GEN_DOC)
        small["side_metrics"] = dict(
            self.GEN_DOC["side_metrics"],
            config={"batch": 8, "prompt_t": 32, "decode_steps": 16,
                    "vocab": 256})
        for i in range(3):
            (tmp_path / f"BENCH_r0{i}.json").write_text(
                json.dumps({"parsed": big}))
        hist = pr.load_history(str(tmp_path / "BENCH_r*.json"))
        cur = pr.normalize_record(small)        # 4000 tok/s vs 9000
        rep = pr.regression_report(
            hist, cur, fingerprint=pr.record_fingerprint(small))
        row = [r for r in rep["rows"]
               if r["metric"] == "lm_generate.decode_tokens_per_sec"][0]
        assert row["status"] == "no-history"    # fenced, not regressed
        # the same current at the SAME shape DOES gate
        rep = pr.regression_report(
            hist, cur, fingerprint=pr.record_fingerprint(big))
        row = [r for r in rep["rows"]
               if r["metric"] == "lm_generate.decode_tokens_per_sec"][0]
        assert row["status"] == "regression"

    def test_no_duplicate_canonical_keys(self):
        """A generate-mode doc emits ONE key per quantity: the bare
        prefill/nocache side metrics fold into lm_generate.* instead of
        forming parallel gating series."""
        pr = _load_perf_regress()
        doc = dict(self.GEN_DOC)
        doc["side_metrics"] = dict(
            self.GEN_DOC["side_metrics"],
            nocache_recompute_tokens_per_sec={"value": 1682.0})
        rec = pr.normalize_record(doc)
        assert "prefill_tokens_per_sec" not in rec
        assert "nocache_recompute_tokens_per_sec" not in rec
        assert rec["lm_generate.prefill_tokens_per_sec"] == 90000.0
        assert rec["lm_generate.nocache_recompute_tokens_per_sec"] \
            == 1682.0

    def test_bench_emits_history_record(self):
        """bench.py's _attach_trajectory ships the normalized record +
        verdict without touching the measured result."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(os.path.dirname(__file__),
                                      "..", "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        result = dict(self.GEN_DOC)
        out = bench._attach_trajectory(result)
        assert out["history_record"][
            "lm_generate.decode_tokens_per_sec"] == 4000.0
        assert "perf_regress" in out
        assert "ok" in out["perf_regress"] or \
            "error" in out["perf_regress"]


class TestSeams:
    """ISSUE 26: one stamp source for the engine loop, mirrored onto a
    profiler session's clock; block ids link request spans to engine
    blocks."""

    def test_profiler_session_holds_every_engine_seam(
            self, shared_decoder, rng_np, tmp_path):
        import glob

        import jax
        from deeplearning4j_tpu.observability import tracing
        reg = MetricsRegistry()
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, trace_store=TraceRing(8),
                      profiler=PhaseProfiler(registry=reg)).start()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        eng.submit(rng_np.integers(0, VOCAB, 3), 9).result(timeout=60)
        try:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                # kept short (two blocks, then an idle wait or two): a
                # profiler's stop costs with what it traced
                reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), 9)
                        for _ in range(2)]
                for r in reqs:
                    r.result(timeout=30)
                time.sleep(0.08)
            finally:
                jax.profiler.stop_trace()
        finally:
            eng.shutdown()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        events = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                events += [(e.start_ns, e.name, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("dl4j.")]
        events.sort()
        names = {name for _, name, _ in events}
        assert names == {
            tracing.ADMIT, tracing.PREFILL_READBACK, tracing.DISPATCH_BLOCK,
            tracing.BLOCK_READBACK, tracing.RETIRE, tracing.JOURNAL,
            tracing.PUBLISH, tracing.IDLE_WAIT}
        assert names <= set(tracing.SEAMS)
        # a block is dispatched before it is read back, and ids only grow
        dispatched = [st["block"] for _, name, st in events
                      if name == tracing.DISPATCH_BLOCK]
        assert dispatched == sorted(set(dispatched)) and dispatched
        first = {}
        for t, name, st in events:
            first.setdefault((name, st.get("block")), t)
        read_back = [b for (name, b) in first    # dispatched in the session
                     if name == tracing.BLOCK_READBACK and
                     (tracing.DISPATCH_BLOCK, b) in first]
        assert read_back
        for b in read_back:
            assert first[(tracing.DISPATCH_BLOCK, b)] < \
                first[(tracing.BLOCK_READBACK, b)]
        some = next(st for _, name, st in events
                    if name == tracing.DISPATCH_BLOCK)
        assert some["k"] == 4 and 1 <= some["lanes"] <= 2

    def test_request_spans_name_the_engine_block(self, shared_decoder,
                                                 rng_np):
        reg = MetricsRegistry()
        prof = PhaseProfiler(registry=reg)
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, trace_store=TraceRing(8),
                      profiler=prof)
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), g)
                for g in (9, 6, 11)]
        eng.run_until_drained()
        by_kind = {}
        for e in prof.timeline.recent(None):
            by_kind.setdefault(e["kind"], set()).add(e["block"])
        ids = [e["block"] for e in prof.timeline.recent(None)]
        assert len(ids) == len(set(ids)) and None not in ids
        for r in reqs:
            spans = r.trace.spans()
            pre = [s for s in spans if s.name == "prefill"]
            dec = [s for s in spans if s.name == "decode_block"]
            assert len(pre) == 1 and dec
            assert pre[0].attrs["block"] in by_kind["admission"]
            assert {s.attrs["block"] for s in dec} <= by_kind["block"]
            # ... and in order: a request's blocks were dispatched after
            # its admission, each after the one before
            order = [pre[0].attrs["block"]] + [s.attrs["block"]
                                               for s in dec]
            assert order == sorted(set(order))

    def test_fit_batch_seams_under_a_session(self, tmp_path):
        import glob

        import jax
        from deeplearning4j_tpu.observability import tracing
        from deeplearning4j_tpu.ops.dataset import DataSet
        net = ComputationGraph(transformer_lm_conf(
            VOCAB, d_model=16, num_heads=2, num_layers=1, max_length=8,
            learning_rate=1e-2, seed=1)).init()
        x = np.arange(16, dtype=np.int32).reshape(2, 8) % VOCAB
        ds = DataSet(x, x)
        net.fit_batch(ds)                               # compile
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                net.fit_batch(ds)
            float(net.score_value)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        got = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("dl4j."):
                            got.setdefault(e.name, []).append(
                                dict(e.stats))
        assert len(got[tracing.TRAIN_STAGE]) == 3
        steps = got[tracing.TRAIN_STEP]
        assert [s["step_num"] for s in steps] == [1, 2, 3]


class TestRequestClocks:
    def test_clocks_and_emissions(self, shared_decoder, rng_np):
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=MetricsRegistry(), trace_store=TraceRing(8))
        t_before = time.perf_counter()
        reqs = [eng.submit(rng_np.integers(0, VOCAB, 3), g)
                for g in (9, 2, 12)]
        assert reqs[0].clocks()["admitted"] is None
        assert reqs[0].emissions() == []
        eng.run_until_drained()
        t_after = time.perf_counter()
        for r in reqs:
            c = r.clocks()
            assert set(c) == {"created", "admitted", "first_token", "done"}
            assert t_before <= c["created"] <= c["admitted"] \
                < c["first_token"] <= c["done"] <= t_after
            assert (c["created"], c["admitted"], c["first_token"]) == \
                (r._created_t, r._admitted_t, r._first_token_t)
            em = r.emissions()
            assert em[0] == (c["first_token"], 1)
            assert sum(n for _, n in em) == len(r.generated)
            assert all(1 <= n <= 4 for _, n in em)
            times = [t for t, _ in em]
            assert times == sorted(times) and times[-1] <= c["done"]
            # the spans' ends in the trace are the same stamps
            ends = [s.t1 for s in r.trace.spans()
                    if s.name in ("prefill", "decode_block")]
            assert ends == times
            em.append((0.0, 99))          # a copy: the request's is intact
            assert sum(n for _, n in r.emissions()) == len(r.generated)

    def test_clocks_and_emissions_survive_requeue(self, shared_decoder,
                                                  rng_np):
        """Like the SLO clocks: a takeover re-prefills prompt + tokens so
        far on the new engine, and neither resets a clock nor loses or
        repeats an emission."""
        reg = MetricsRegistry()
        inj = FaultInjector()
        inj.raise_once("engine.step", RuntimeError("boom"), at=2)
        eng = _engine(shared_decoder, num_slots=2, block_size=4,
                      registry=reg, fault_injector=inj,
                      trace_store=TraceRing(8))
        sup = EngineSupervisor(eng, timeout=2.0, interval=0.05,
                               max_restarts=2).start()
        try:
            reqs = [sup.submit(rng_np.integers(0, VOCAB, 3), 12)
                    for _ in range(2)]
            assert _wait(lambda: all(r.done() for r in reqs))
            assert sup.stats()["restarts"] >= 1
            for r in reqs:
                c, em = r.clocks(), r.emissions()
                assert c["created"] <= c["admitted"] < c["first_token"] \
                    <= c["done"]
                assert em[0][0] == c["first_token"]
                assert sum(n for _, n in em) == len(r.generated) == 12
                assert [t for t, _ in em] == sorted(t for t, _ in em)
        finally:
            sup.stop()


class TestWindowReads:
    """``between(t0, t1)`` and ``rolled_past(t)``: what a reader of one
    window gets, and how it learns the ring no longer holds it all."""

    def _fill(self, prof, n, t0=100.0, step=0.1):
        ch = prof.channel("e", num_slots=2)
        for i in range(n):
            t = t0 + i * step
            if i % 4 == 0:
                ch.record_admission(
                    impl="prefill", count=1, block=i, t_dispatch=t,
                    t_fetched=t + 0.010, t_host=t + 0.012,
                    t_journal=t + 0.013, t_publish=t + 0.015)
            else:
                ch.record_block(
                    impl="block4", k=4, lanes=2, queued=0, block=i,
                    t_dispatch=t, t_fetched=t + 0.090, t_host=t + 0.092,
                    t_journal=t + 0.0925, t_publish=t + 0.094)
        return ch

    def test_between_sums_equal_the_records(self):
        prof = PhaseProfiler(registry=MetricsRegistry())
        self._fill(prof, 40)
        lo, hi = 100.95, 103.05        # records 10..30
        win = prof.between(lo, hi)
        inside = [e for e in prof.timeline.recent(None)
                  if lo <= e["t"] < hi]
        assert len(inside) == 21 and win["truncated"] is False
        for kind in ("block", "admission"):
            recs = [e for e in inside if e["kind"] == kind]
            got = win["kinds"][kind]
            assert got["n"] == len(recs)
            assert got["bubble_seconds"] == pytest.approx(
                sum(e["bubble_ms"] for e in recs) / 1e3)
            for ph in ("device", "host", "journal", "publish"):
                assert got["phase_seconds"][ph] == pytest.approx(
                    sum(e["phases_ms"][ph] for e in recs) / 1e3)
        assert sum(a["n"] for a in win["bubble_after"].values()) == 21
        assert prof.between(lo, hi, engine="nobody")["kinds"] == {}
        # hand-computed: a block follows the one before by 100 ms and
        # that one was read back after 90: 10 ms of bubble each; after
        # an admission (read back after 10) 90 ms
        blocks = win["kinds"]["block"]
        assert blocks["phase_seconds"]["device"] == pytest.approx(
            blocks["n"] * 0.090)
        assert win["bubble_after"]["admission"]["bubble_seconds"] == \
            pytest.approx(win["bubble_after"]["admission"]["n"] * 0.090)

    def test_truncated_flips_when_the_ring_rolls(self):
        prof = PhaseProfiler(registry=MetricsRegistry(),
                             timeline_capacity=16)
        self._fill(prof, 16)
        assert prof.between(100.0, 200.0)["truncated"] is False
        self._fill(prof, 4, t0=101.6)               # four roll out
        assert prof.between(100.0, 200.0)["truncated"] is True
        assert prof.between(100.35, 200.0)["truncated"] is False
        assert prof.between(100.3, 200.0)["truncated"] is True

    def test_bubble_is_zero_behind_work_in_flight_and_after_idle(self):
        """An admission's prefill dispatched while a block is in flight
        queues behind it (no bubble), the idle stretch after its readback
        lands on the next block marked ``after: admission``, and a wait
        for work re-anchors the account."""
        prof = PhaseProfiler(registry=MetricsRegistry())
        ch = prof.channel("e", num_slots=2)
        kw = dict(t_host=0.0, t_journal=0.0, t_publish=0.0)
        blk = dict(impl="b", k=4, lanes=1, queued=0)
        ch.record_block(**blk, block=1, t_dispatch=1.000, t_fetched=1.095,
                        **kw)
        # block 2 dispatched at 1.090 (before 1's readback); the prefill
        # at 1.100 behind it, read back at 1.200; then block 2's readback
        ch.record_admission(impl="p", count=1, block=3, overlapped=True,
                            t_dispatch=1.100, t_fetched=1.200, **kw)
        ch.record_block(**blk, block=2, overlapped=True, t_dispatch=1.090,
                        t_fetched=1.201, **kw)
        # the dispatch call returns at 1.207: its 2 ms ride alongside
        ch.record_block(**blk, block=4, t_dispatch=1.205, t_dispatched=1.207,
                        t_fetched=1.300, **kw)
        ch.mark_idle(9.000)
        ch.record_admission(impl="p", count=1, block=5, t_dispatch=9.002,
                            t_fetched=9.010, **kw)
        tl = {e["block"]: e for e in prof.timeline.recent(None)}
        assert tl[3]["bubble_ms"] == 0.0 and tl[2]["bubble_ms"] == 0.0
        assert tl[4]["after"] == "admission"
        assert tl[4]["bubble_ms"] == pytest.approx(5.0)
        assert tl[4]["dispatch_ms"] == pytest.approx(2.0)
        win = prof.between(1.0, 2.0)["bubble_after"]["admission"]
        assert win["n"] == 1
        assert win["bubble_seconds"] + win["dispatch_seconds"] == \
            pytest.approx(0.007)
        assert tl[5]["after"] == "idle"
        assert tl[5]["bubble_ms"] == pytest.approx(2.0)

    def test_trace_ring_says_when_it_rolled_past(self):
        ring = TraceRing(4)
        traces = [Trace(store=ring) for _ in range(6)]
        for t in traces[:4]:
            t.finish()
        assert not ring.rolled_past(traces[0].finished_at)
        for t in traces[4:]:
            t.finish()
        assert ring.rolled_past(traces[0].finished_at)
        assert ring.rolled_past(traces[1].finished_at)
        assert not ring.rolled_past(traces[2].finished_at)

    def test_traces_endpoint_reports_rolled_past(self):
        ring = TraceRing(2)
        for _ in range(3):
            Trace(store=ring).finish()
        srv = TelemetryServer(registry=MetricsRegistry(),
                              trace_store=ring).start()
        try:
            with urllib.request.urlopen(
                    f"{srv.url}/traces/recent?since=600", timeout=10) as r:
                doc = json.loads(r.read())
            assert doc["rolled_past"] is True and doc["count"] == 2
            with urllib.request.urlopen(f"{srv.url}/traces/recent",
                                        timeout=10) as r:
                assert "rolled_past" not in json.loads(r.read())
        finally:
            srv.stop()


def _scoped(text: str, scope: str) -> bool:
    """Whether a lowering's name stacks hold ``scope`` as a whole path
    element: ``.../attn0/...`` (``"attn0/...`` inside a called function,
    whose locations are relative to the call site), or wrapped by a
    transform as in ``jvp(attn0)/...`` and ``transpose(jvp(attn0))/...``."""
    return re.search(rf'[/("]{re.escape(scope)}[/)]', text) is not None


class TestDeviceNames:
    """Named scopes and kernel names reach the lowered programs (HLO
    metadata only: what the device trace carries as ``tf_op`` and, for a
    Mosaic call, as the instruction's own name)."""

    def test_train_step_holds_vertex_loss_and_updater_scopes(self):
        import jax
        import jax.numpy as jnp
        net = ComputationGraph(transformer_lm_conf(
            VOCAB, d_model=16, num_heads=2, num_layers=2, max_length=8,
            learning_rate=1e-2, seed=1)).init()
        x = jnp.zeros((2, 8), jnp.int32)
        text = net._get_train_step(False).lower(
            net.params, net.updater_state, net.state, {"tokens": x},
            {"out": x}, {}, {}, 0, {}).as_text(debug_info=True)
        for scope in ("embed", "attn0", "ffn1", "lnf", "loss",
                      "updater/attn1", "updater/out"):
            assert _scoped(text, scope), scope
        # backward operations keep the vertex that caused them
        assert "transpose(jvp(ffn0))/" in text

    def test_decode_block_holds_layer_cache_update_and_sample_scopes(
            self, shared_decoder):
        import jax.numpy as jnp
        net, dec = shared_decoder
        dec._fn(("block", 4))
        jitted, specs, _ = dec._cost_seam["decode_block4_impl"]
        assert specs is not None
        text = jitted.lower(*specs).as_text(debug_info=True)
        for scope in ("embed", "attn0", "attn1/cache_update", "ffn1",
                      "lnf", "out", "sample"):
            assert _scoped(text, scope), scope

    @pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dq",
                                      "flash_bwd_dkv"])
    def test_flash_kernel_names_reach_the_lowering(self, name):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels import pallas_attention as pa
        q = jnp.zeros((2, 256, 64), jnp.float32)
        tile = pa.make_tile(1, 64, 1, True, 128, 128, True)

        def loss(q, k, v):
            return pa._flash(q, k, v, None, tile).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text(debug_info=True)
        # the calls are jitted (one lowering for a program's layers), so
        # the name stands at the head of the shared function's own stack
        assert re.search(rf"(?<!\w){name}\)*/pallas_call", text)

    @pytest.mark.parametrize("name", ["shortseq_fwd", "shortseq_bwd"])
    def test_shortseq_kernel_names_reach_the_lowering(self, name):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels import pallas_shortseq as ps
        q = jnp.zeros((4, 128, 64), jnp.float32)

        def loss(q, k, v):
            return ps._short(q, k, v, True, 2, True, 1).sum()
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text(debug_info=True)
        assert re.search(rf"\({name}\)+/pallas_call", text)
