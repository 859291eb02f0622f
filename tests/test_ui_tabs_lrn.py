"""UI model/system/activations tabs (reference play TrainModule views +
ConvolutionalIterationListener rendering) and the fused LRN helper
(reference CudnnLocalResponseNormalizationHelper equivalence pattern)."""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                   MultiLayerNetwork)
from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer, DenseLayer,
                                               LocalResponseNormalization,
                                               OutputLayer)
from deeplearning4j_tpu.ops.dataset import DataSet
from deeplearning4j_tpu.ui import (InMemoryStatsStorage, StatsListener,
                                   UIServer)
from deeplearning4j_tpu.ui.legacy_listeners import \
    ConvolutionalIterationListener


def _get(base, path):
    return json.loads(urllib.request.urlopen(base + path, timeout=10).read())


class TestUITabs:
    @pytest.fixture
    def served(self, rng_np):
        storage = InMemoryStatsStorage()
        conf = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.05)
                .updater("adam").weight_init("xavier").activation("relu")
                .list()
                .layer(ConvolutionLayer(n_out=4, kernel_size=[3, 3],
                                        convolution_mode="same"))
                .layer(DenseLayer(n_out=8))
                .layer(OutputLayer(n_out=2, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.convolutional(8, 8, 1)).build())
        net = MultiLayerNetwork(conf).init()
        X = rng_np.normal(size=(8, 8, 8, 1)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng_np.integers(0, 2, 8)]
        net.set_listeners(
            StatsListener(storage, session_id="tabs",
                          histograms_frequency=2),
            ConvolutionalIterationListener(storage, X[:1], frequency=2))
        net.fit([DataSet(X, y)] * 6)
        ui = UIServer(port=0)
        ui.attach(storage)
        yield f"http://127.0.0.1:{ui.port}"

    def test_model_tab(self, served):
        m = _get(served, "/train/model?session=tabs")
        assert [l["type"] for l in m["layers"]] == \
            ["ConvolutionLayer", "DenseLayer", "OutputLayer"]
        assert m["param_mean_magnitudes"]       # magnitudes table filled
        html = urllib.request.urlopen(served + "/train/model.html",
                                      timeout=10).read().decode()
        assert "Model" in html

    def test_system_tab(self, served):
        s = _get(served, "/train/system?session=tabs")
        assert len(s["iterations"]) >= 1
        assert all(v > 0 for v in s["max_rss_mb"])
        assert len(s["rate_iterations"]) == len(s["iterations_per_sec"])
        html = urllib.request.urlopen(served + "/train/system.html",
                                      timeout=10).read().decode()
        assert "System" in html

    def test_activations_tab_and_png(self, served):
        a = _get(served, "/train/activations")
        assert a["layers"], a
        entry = a["layers"][0]
        assert entry["grid_shape"][0] > 0
        assert "grid_b64" not in entry     # pixels ship via the PNG, not JSON
        png = urllib.request.urlopen(
            served + f"/train/activations.png?layer={entry['layer']}",
            timeout=10).read()
        assert png[:8] == b"\x89PNG\r\n\x1a\n"
        assert len(png) > 100
        html = urllib.request.urlopen(served + "/train/activations.html",
                                      timeout=10).read().decode()
        assert "activations" in html.lower()

    def test_histograms_tab(self, served):
        """The histogram tab renders the served /train/histograms data
        (VERDICT r3 item #9: the data endpoint existed since r2 but no
        page consumed it)."""
        import urllib.request
        html = urllib.request.urlopen(served + "/train/histograms.html",
                                      timeout=10).read().decode()
        assert "Parameter histograms" in html
        assert "/train/histograms?session=" in html
        assert "param_histograms" in html        # the JS consumes the data
        d = _get(served, "/train/histograms?session=tabs")
        assert d.get("param_histograms"), d.keys()
        first = next(iter(d["param_histograms"].values()))
        assert first["counts"] and len(first["bins"]) == \
            len(first["counts"]) + 1

    def test_activations_no_cross_session_fallback(self, served):
        """An explicitly requested session with no conv records must return
        an empty record, not another run's activations (ADVICE r3)."""
        a = _get(served, "/train/activations?session=no-such-session")
        assert a == {}
        # no session param: latest conv record across sessions still serves
        assert _get(served, "/train/activations")["layers"]


class TestLrnHelper:
    def test_helper_matches_pure_path_forward_and_grad(self, rng_np):
        """CuDNN-vs-builtin equivalence pattern (SURVEY.md §4) for LRN."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper, get_helper)
        layer = LocalResponseNormalization(k=2.0, n=5, alpha=1e-4, beta=0.75)
        x = jnp.asarray(rng_np.normal(size=(2, 4, 4, 8)), jnp.float32)

        enable_helper("lrn")
        assert get_helper("lrn") is not None    # default provider loads
        y_fast, _ = layer.forward({}, {}, x)
        g_fast = jax.grad(
            lambda a: jnp.sum(layer.forward({}, {}, a)[0] ** 2))(x)

        disable_helper("lrn")
        try:
            y_ref, _ = layer.forward({}, {}, x)
            g_ref = jax.grad(
                lambda a: jnp.sum(layer.forward({}, {}, a)[0] ** 2))(x)
        finally:
            enable_helper("lrn")
        np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(g_fast), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-7)

    def test_lrn_in_network_trains(self, rng_np):
        conf = (NeuralNetConfiguration.Builder().seed(5).learning_rate(0.05)
                .updater("adam").weight_init("xavier").activation("relu")
                .list()
                .layer(ConvolutionLayer(n_out=6, kernel_size=[3, 3],
                                        convolution_mode="same"))
                .layer(LocalResponseNormalization())
                .layer(OutputLayer(n_out=3, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.convolutional(8, 8, 2)).build())
        net = MultiLayerNetwork(conf).init()
        X = rng_np.normal(size=(16, 8, 8, 2)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng_np.integers(0, 3, 16)]
        ds = DataSet(X, y)
        s0 = net.score(ds)
        for _ in range(20):
            net.fit(ds)
        assert net.score(ds) < s0


class TestStorageRecordTypes:
    def test_all_backends_return_non_update_records(self, tmp_path):
        """File/Sqlite storages must surface histogram/flow/convolutional
        records from get_updates (the type=='update' filter hid them from
        every tab on those backends)."""
        from deeplearning4j_tpu.ui.storage import (FileStatsStorage,
                                                   InMemoryStatsStorage,
                                                   SqliteStatsStorage)
        backends = [InMemoryStatsStorage(),
                    FileStatsStorage(tmp_path / "s.jsonl"),
                    SqliteStatsStorage(tmp_path / "s.db")]
        for st in backends:
            st.put_static_info({"session": "s", "type": "init",
                                "iteration": 0})
            st.put_update({"session": "s", "type": "update", "iteration": 1,
                           "score": 1.0})
            st.put_update({"session": "s", "type": "convolutional",
                           "iteration": 2, "layers": []})
            st.put_update({"session": "s", "type": "histogram",
                           "iteration": 3})
            st.put_update({"session": "s", "type": "flow", "iteration": 4,
                           "param_counts": []})
            ups = st.get_updates("s")
            types = sorted(u["type"] for u in ups)
            assert types == ["convolutional", "flow", "histogram",
                             "update"], (type(st).__name__, types)
            assert st.get_static_info("s")["type"] == "init"


class TestLrnDtypeEquivalence:
    def test_helper_matches_pure_path_bf16(self, rng_np):
        """Both paths compute in f32 internally, so helper on/off is
        identical in bf16 too (the docstring contract holds beyond f32)."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper, get_helper)
        layer = LocalResponseNormalization(k=2.0, n=5, alpha=1e-4, beta=0.75)
        x = jnp.asarray(rng_np.normal(size=(2, 4, 4, 8)), jnp.bfloat16)
        enable_helper("lrn")
        assert get_helper("lrn") is not None
        y_fast, _ = layer.forward({}, {}, x)
        disable_helper("lrn")
        try:
            y_ref, _ = layer.forward({}, {}, x)
        finally:
            enable_helper("lrn")
        assert y_fast.dtype == jnp.bfloat16 and y_ref.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(y_fast, np.float32), np.asarray(y_ref, np.float32))


class TestRemoteRecordHardening:
    """Remote-pushed records are untrusted (ADVICE r2): the activations tab
    must escape interpolated fields and activations.png must 400 on
    malformed structure instead of raising in the handler."""

    @pytest.fixture
    def server(self):
        from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
        storage = InMemoryStatsStorage()
        ui = UIServer(port=0)
        ui.attach(storage)
        yield f"http://127.0.0.1:{ui.port}", storage
        ui.stop()

    @staticmethod
    def _post(base, record):
        req = urllib.request.Request(
            base + "/remote/receive", json.dumps(record).encode(),
            {"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=10)

    def test_activations_page_escapes_fields(self, server):
        base, _ = server
        html = urllib.request.urlopen(base + "/train/activations.html",
                                      timeout=10).read().decode()
        # every interpolation in the grids markup goes through esc() or
        # encodeURIComponent — no raw ${l.xxx} left
        assert "esc(l.layer)" in html and "esc(l.shape)" in html
        assert "encodeURIComponent(l.layer)" in html
        import re
        raw = re.findall(r"\$\{(?!esc\(|encodeURIComponent\(|Number\()[^}]*\}",
                         html.split("grids').innerHTML")[1].split("join")[0])
        assert raw == [], raw

    def test_png_rejects_malformed_grid(self, server):
        import base64
        base, _ = server
        # grid_b64 length does not match grid_shape product
        self._post(base, {"type": "convolutional", "session": "s",
                          "iteration": 1, "layers": [{
                              "layer": 0, "shape": [1, 4, 4, 2],
                              "mean": 0.0, "std": 1.0,
                              "grid_shape": [4, 4],
                              "grid_b64": base64.b64encode(
                                  b"\x00" * 7).decode()}]})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/train/activations.png?layer=0",
                                   timeout=10)
        assert e.value.code == 400

    def test_png_rejects_missing_fields(self, server):
        base, _ = server
        self._post(base, {"type": "convolutional", "session": "s",
                          "iteration": 1,
                          "layers": [{"mean": 0.0, "std": 1.0}]})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/train/activations.png",
                                   timeout=10)
        assert e.value.code in (400, 404)


class TestFlowTabAndSessions:
    """Flow tab (reference FlowIterationListener view) + per-view session
    selector (reference TrainModule session handling): two attached
    sessions must BOTH stay reachable, and the flow endpoint serves layer
    boxes with param counts and per-layer forward timings."""

    @pytest.fixture
    def two_sessions(self, rng_np):
        from deeplearning4j_tpu.ui.legacy_listeners import \
            FlowIterationListener
        storage = InMemoryStatsStorage()

        def train(session, seed):
            conf = (NeuralNetConfiguration.Builder().seed(seed)
                    .learning_rate(0.05).updater("sgd").weight_init("xavier")
                    .activation("tanh").list()
                    .layer(DenseLayer(n_out=6))
                    .layer(OutputLayer(n_out=2, loss="mcxent",
                                       activation="softmax"))
                    .set_input_type(InputType.feed_forward(4)).build())
            net = MultiLayerNetwork(conf).init()
            X = rng_np.normal(size=(8, 4)).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[rng_np.integers(0, 2, 8)]
            net.set_listeners(FlowIterationListener(storage,
                                                    session_id=session))
            net.fit([DataSet(X, y)] * 3)

        train("run-one", 1)
        train("run-two", 2)
        ui = UIServer(port=0)
        ui.attach(storage)
        yield f"http://127.0.0.1:{ui.port}"
        ui.stop()

    def test_flow_tab_serves_layer_timing_boxes(self, two_sessions):
        d = _get(two_sessions, "/train/flow?session=run-one")
        assert [l["name"] for l in d["layers"]] == \
            ["DenseLayer", "OutputLayer"]
        assert all(l["params"] > 0 for l in d["layers"])
        # per-layer forward timings measured on the probe batch
        assert all(isinstance(l["time_ms"], float) and l["time_ms"] >= 0
                   for l in d["layers"])
        assert len(d["iterations"]) == len(d["scores"]) >= 1
        html = urllib.request.urlopen(two_sessions + "/train/flow.html",
                                      timeout=10).read().decode()
        assert "Flow" in html and "sesssel" in html

    def test_timing_frequency_zero_disables_probe(self, rng_np):
        """timing_frequency=0 must skip the eager per-layer timing probe
        entirely (each probe is a blocking dispatch per layer; ADVICE
        r3)."""
        from deeplearning4j_tpu.ui.legacy_listeners import \
            FlowIterationListener
        storage = InMemoryStatsStorage()
        lst = FlowIterationListener(storage, session_id="notimer",
                                    timing_frequency=0)
        calls = []
        lst._time_layers = lambda model: calls.append(1)
        conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.05)
                .updater("sgd").weight_init("xavier").activation("tanh")
                .list()
                .layer(DenseLayer(n_out=6))
                .layer(OutputLayer(n_out=2, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(4)).build())
        net = MultiLayerNetwork(conf).init()
        X = rng_np.normal(size=(8, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng_np.integers(0, 2, 8)]
        net.set_listeners(lst)
        net.fit([DataSet(X, y)] * 3)
        assert not calls
        recs = [u for u in storage.get_updates("notimer")
                if u.get("type") == "flow"]
        assert recs and all(r["layer_timings_ms"] is None for r in recs)

    def test_both_sessions_reachable(self, two_sessions):
        sessions = _get(two_sessions, "/train/sessions")
        assert "run-one" in sessions and "run-two" in sessions
        d1 = _get(two_sessions, "/train/flow?session=run-one")
        d2 = _get(two_sessions, "/train/flow?session=run-two")
        assert d1["layers"] and d2["layers"]
        # every tab page embeds the session selector + nav
        for page in ("/train", "/train/model.html", "/train/system.html",
                     "/train/activations.html", "/train/flow.html"):
            html = urllib.request.urlopen(two_sessions + page,
                                          timeout=10).read().decode()
            assert "sesssel" in html, page
            assert "/train/sessions.js" in html, page


class TestFlowListenerComputationGraph:
    def test_flow_listener_on_graph(self, rng_np):
        """FlowIterationListener works on ComputationGraph too: vertex
        names, per-vertex param counts, and per-vertex timings."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.ui.legacy_listeners import \
            FlowIterationListener
        storage = InMemoryStatsStorage()
        g = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
             .updater("sgd").weight_init("xavier").activation("tanh")
             .graph_builder()
             .add_inputs("in")
             .add_layer("d", DenseLayer(n_out=6), "in")
             .add_layer("out", OutputLayer(n_out=2, loss="mcxent",
                                           activation="softmax"), "d")
             .set_outputs("out")
             .set_input_types(InputType.feed_forward(4)).build())
        net = ComputationGraph(g).init()
        net.set_listeners(FlowIterationListener(storage, session_id="gflow"))
        X = rng_np.normal(size=(8, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng_np.integers(0, 2, 8)]
        for _ in range(2):
            net.fit_batch(DataSet(X, y))
        static = storage.get_static_info("gflow")
        assert static["layers"] == ["d", "out"]
        ups = [u for u in storage.get_updates("gflow")
               if u.get("type") == "flow"]
        assert ups and ups[-1]["param_counts"] == [4 * 6 + 6, 6 * 2 + 2]
        assert len(ups[-1]["layer_timings_ms"]) == 2
