"""Checkpoint-format regression tests (reference
regressiontest/RegressionTest050/060/071.java: load zips produced by earlier
releases, assert configs+params+predictions; SURVEY.md §4) and
helper-vs-builtin equivalence tests (reference CuDNNGradientChecks.java /
TestConvolution.java pattern applied to the Pallas LSTM helper)."""

from pathlib import Path

import numpy as np
import pytest

RES = Path(__file__).parent / "resources"


class TestCheckpointRegression:
    """The committed fixture zips freeze the on-disk format; if a future
    serializer change can't load them, backward compatibility broke."""

    def test_mln_dense_roundtrip(self):
        from deeplearning4j_tpu.utils.serializer import ModelSerializer
        net = ModelSerializer.restore_multi_layer_network(
            RES / "regression_mln_v1.zip")
        x = np.load(RES / "regression_mln_v1_input.npy")
        expected = np.load(RES / "regression_mln_v1_output.npy")
        np.testing.assert_allclose(np.asarray(net.output(x)), expected,
                                   rtol=1e-5, atol=1e-6)
        # conf fields survived serde
        assert net.conf.layers[0].n_out == 8
        assert net.conf.layers[1].loss == "mcxent"
        # updater state restored: continuing training must not error
        from deeplearning4j_tpu.ops.dataset import DataSet
        rng = np.random.default_rng(0)
        net.fit([DataSet(rng.normal(size=(8, 4)).astype(np.float32),
                         np.eye(3)[rng.integers(0, 3, 8)]
                         .astype(np.float32))])

    def test_lstm_roundtrip(self):
        from deeplearning4j_tpu.utils.serializer import ModelSerializer
        net = ModelSerializer.restore_multi_layer_network(
            RES / "regression_lstm_v1.zip")
        x = np.load(RES / "regression_lstm_v1_input.npy")
        expected = np.load(RES / "regression_lstm_v1_output.npy")
        np.testing.assert_allclose(np.asarray(net.output(x)), expected,
                                   rtol=1e-5, atol=1e-6)

    def test_model_guesser_on_fixture(self):
        from deeplearning4j_tpu.utils.serializer import ModelGuesser
        net = ModelGuesser.load_model_guess_type(
            RES / "regression_mln_v1.zip")
        assert net.num_params() > 0


class TestStatelessFit:
    """Each minibatch starts from zero rnn state (reference fit semantics):
    no hidden-state bleed between independent batches, and batch-size
    changes mid-fit must not break (the carried h/c would shape-clash)."""

    def _net(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork)
        from deeplearning4j_tpu.nn.conf.layers import (GravesLSTM,
                                                       RnnOutputLayer)
        conf = (NeuralNetConfiguration.Builder().seed(2).learning_rate(0.05)
                .updater("sgd").weight_init("xavier").list()
                .layer(GravesLSTM(n_out=5, activation="tanh"))
                .layer(RnnOutputLayer(n_out=2, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(3)).build())
        return MultiLayerNetwork(conf).init()

    def test_varying_batch_sizes(self, rng_np):
        from deeplearning4j_tpu.ops.dataset import DataSet
        net = self._net()
        for n in (8, 5, 8, 3):
            x = rng_np.normal(size=(n, 4, 3)).astype(np.float32)
            y = np.zeros((n, 4, 2), np.float32)
            y[..., 0] = 1
            net.fit([DataSet(x, y)])
        assert np.isfinite(float(net.score_value))

    def test_output_independent_of_training_state(self, rng_np):
        from deeplearning4j_tpu.ops.dataset import DataSet
        x = rng_np.normal(size=(4, 4, 3)).astype(np.float32)
        y = np.zeros((4, 4, 2), np.float32)
        y[..., 0] = 1
        net = self._net()
        net.fit([DataSet(x, y)], num_epochs=2)
        out1 = np.asarray(net.output(x))
        # more fitting on a DIFFERENT batch must not change output(x)
        # through leaked rnn state — only through the param update itself;
        # here we just re-run output twice and require determinism
        out2 = np.asarray(net.output(x))
        np.testing.assert_array_equal(out1, out2)
        # state kept for rnn layers carries no h/c after fit
        for s in net.state:
            assert "h" not in s and "c" not in s


class TestLstmHelperEquivalence:
    """Pallas fused LSTM vs the pure-scan reference path: forward and
    gradients must agree exactly (the CuDNN-vs-builtin test template)."""

    def _net(self, peephole: bool):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork)
        from deeplearning4j_tpu.nn.conf.layers import (GravesLSTM, LSTM,
                                                       RnnOutputLayer)
        layer = GravesLSTM(n_out=8, activation="tanh") if peephole \
            else LSTM(n_out=8, activation="tanh")
        conf = (NeuralNetConfiguration.Builder().seed(4).learning_rate(0.05)
                .updater("sgd").weight_init("xavier").list()
                .layer(layer)
                .layer(RnnOutputLayer(n_out=2, loss="mcxent",
                                      activation="softmax"))
                .set_input_type(InputType.recurrent(3)).build())
        return MultiLayerNetwork(conf).init()

    @pytest.mark.parametrize("peephole", [False, True])
    def test_forward_and_training_equivalence(self, peephole, rng_np):
        from deeplearning4j_tpu.kernels import register_lstm_helper
        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper)
        from deeplearning4j_tpu.ops.dataset import DataSet
        register_lstm_helper(platforms=("cpu", "tpu"))
        enable_helper("lstm")
        x = rng_np.normal(size=(4, 6, 3)).astype(np.float32)
        y = np.zeros((4, 6, 2), np.float32)
        y[:2, :, 0] = 1
        y[2:, :, 1] = 1
        try:
            helper_net = self._net(peephole)
            out_helper = np.asarray(helper_net.output(x))
            helper_net.fit([DataSet(x, y)], num_epochs=2)
            params_helper = helper_net.params_flat()

            disable_helper("lstm")
            builtin_net = self._net(peephole)
            out_builtin = np.asarray(builtin_net.output(x))
            builtin_net.fit([DataSet(x, y)], num_epochs=2)
            params_builtin = builtin_net.params_flat()
        finally:
            disable_helper("lstm")
        np.testing.assert_allclose(out_helper, out_builtin,
                                   rtol=1e-5, atol=1e-6)
        # training through the custom-VJP kernel matches the builtin path
        np.testing.assert_allclose(params_helper, params_builtin,
                                   rtol=1e-4, atol=1e-6)

    def test_masked_falls_back(self, rng_np):
        """Masked sequences exercise the scan fallback INSIDE the helper
        (lstm_helper's mask branch) and must match the builtin path. Fresh
        nets per path — a shared net would replay its jit cache, comparing
        the helper against itself."""
        from deeplearning4j_tpu.kernels import register_lstm_helper
        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper)
        from deeplearning4j_tpu.ops.dataset import DataSet
        x = rng_np.normal(size=(3, 5, 3)).astype(np.float32)
        y = np.zeros((3, 5, 2), np.float32)
        y[..., 0] = 1
        fmask = np.array([[1, 1, 1, 0, 0],
                          [1, 1, 1, 1, 1],
                          [1, 1, 0, 0, 0]], np.float32)
        ds = DataSet(x, y, fmask, fmask.copy())
        register_lstm_helper(platforms=("cpu", "tpu"))
        enable_helper("lstm")
        try:
            score_h = self._net(peephole=True).score(ds)
            helper_net = self._net(peephole=True)
            helper_net.fit([ds])
            params_h = helper_net.params_flat()
            disable_helper("lstm")
            score_b = self._net(peephole=True).score(ds)
            builtin_net = self._net(peephole=True)
            builtin_net.fit([ds])
            params_b = builtin_net.params_flat()
        finally:
            disable_helper("lstm")
        assert abs(score_h - score_b) < 1e-6
        np.testing.assert_allclose(params_h, params_b, rtol=1e-5, atol=1e-7)


class TestBnHelperEquivalence:
    """Fused custom-VJP batch norm vs the built-in jnp path: forward,
    running stats, and end-to-end training must agree (the
    CudnnBatchNormalizationHelper-vs-builtin test template, SURVEY.md §4)."""

    def _net(self):
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration, InputType,
                                           MultiLayerNetwork)
        from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer,
                                                       BatchNormalization,
                                                       OutputLayer)
        conf = (NeuralNetConfiguration.Builder().seed(7).learning_rate(0.05)
                .updater("sgd").weight_init("xavier").list()
                .layer(ConvolutionLayer(n_out=6, kernel_size=[3, 3],
                                        stride=[1, 1], activation="identity"))
                .layer(BatchNormalization(activation="relu"))
                .layer(OutputLayer(n_out=3, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.convolutional(8, 8, 2)).build())
        return MultiLayerNetwork(conf).init()

    def test_fused_matches_builtin(self, rng_np):
        from deeplearning4j_tpu.kernels.batchnorm import register_default
        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper)
        from deeplearning4j_tpu.ops.dataset import DataSet
        register_default(platforms=("cpu", "tpu"))
        enable_helper("batchnorm_train")
        x = rng_np.normal(size=(8, 8, 8, 2)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng_np.integers(0, 3, 8)]
        try:
            fused = self._net()
            fused.fit([DataSet(x, y)], num_epochs=3)
            out_fused = np.asarray(fused.output(x))
            params_fused = fused.params_flat()

            disable_helper("batchnorm_train")
            builtin = self._net()
            builtin.fit([DataSet(x, y)], num_epochs=3)
            out_builtin = np.asarray(builtin.output(x))
            params_builtin = builtin.params_flat()

            np.testing.assert_allclose(params_fused, params_builtin,
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(out_fused, out_builtin,
                                       rtol=2e-4, atol=2e-5)
        finally:
            enable_helper("batchnorm_train")
            register_default()       # restore TPU-only platforms (no cpu)

    def test_kernel_function_direct(self, rng_np):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.batchnorm import bn_train_fused
        x = jnp.asarray(rng_np.normal(size=(16, 5)) * 2 + 1, jnp.float32)
        gamma = jnp.asarray(rng_np.uniform(0.5, 2, 5), jnp.float32)
        beta = jnp.asarray(rng_np.normal(size=5), jnp.float32)
        eps = 1e-5

        def ref(x, gamma, beta):
            mean = jnp.mean(x, axis=0)[None, :]
            var = jnp.var(x, axis=0)[None, :]
            return (x - mean) / jnp.sqrt(var + eps) * gamma[None, :] + \
                beta[None, :]

        hint = jnp.zeros(5, jnp.float32)
        y, mean, var = bn_train_fused(x, gamma, beta, hint, eps)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, gamma, beta)),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(mean),
                                   np.asarray(jnp.mean(x, axis=0)), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(var),
                                   np.asarray(jnp.var(x, axis=0)), rtol=1e-4)

        # gradients vs autodiff through the reference formula
        w = jnp.asarray(rng_np.normal(size=(16, 5)), jnp.float32)
        g_fused = jax.grad(
            lambda x, g, b: jnp.sum(bn_train_fused(x, g, b, hint, eps)[0] * w),
            argnums=(0, 1, 2))(x, gamma, beta)
        g_ref = jax.grad(
            lambda x, g, b: jnp.sum(ref(x, g, b) * w),
            argnums=(0, 1, 2))(x, gamma, beta)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_large_mean_channels(self, rng_np):
        # E[x^2]-E[x]^2 would catastrophically cancel here; the two-pass
        # variance must not (review finding r1)
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.batchnorm import bn_train_fused
        x = jnp.asarray(rng_np.normal(size=(64, 32, 8)) * 0.1 + 1000.0,
                        jnp.float32)
        gamma = jnp.ones(8, jnp.float32)
        beta = jnp.zeros(8, jnp.float32)
        # warmed-up running mean as the conditioning shift (what the layer
        # passes); within O(std) of the true mean
        hint = jnp.full(8, 999.5, jnp.float32)
        y, mean, var = bn_train_fused(x, gamma, beta, hint, 1e-5)
        np.testing.assert_allclose(np.asarray(var),
                                   np.var(np.asarray(x, np.float64),
                                          axis=(0, 1)), rtol=1e-3)
        assert abs(float(np.asarray(y).std()) - 1.0) < 0.05


class TestGraphFusionBnAddRelu:
    """Graph fusion pass (nn/graph/fusion.py): the BN->add->ReLU residual
    tail executed as one fused op must train identically to the plain walk."""

    def _resnet(self):
        from deeplearning4j_tpu.models import resnet_tiny_conf
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        return ComputationGraph(resnet_tiny_conf(num_classes=4, height=8,
                                                 width=8, channels=2)).init()

    def test_plan_found_and_training_equivalent(self, rng_np):
        from deeplearning4j_tpu.kernels.batchnorm import register_default
        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   enable_helper)
        from deeplearning4j_tpu.nn.graph.fusion import build_fusion_plan
        from deeplearning4j_tpu.ops.dataset import DataSet
        register_default(platforms=("cpu", "tpu"))
        enable_helper("batchnorm_add_act_train")
        enable_helper("batchnorm_train")
        x = rng_np.normal(size=(4, 8, 8, 2)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng_np.integers(0, 4, 4)]
        try:
            fused = self._resnet()
            plan, skip = build_fusion_plan(fused.conf)
            assert len(plan) == 2          # one residual tail per tiny block
            assert len(skip) == 4
            fused.fit([DataSet(x, y)], num_epochs=3)
            out_fused = np.asarray(fused.output(x)[0])
            params_fused = fused.params_flat()

            disable_helper("batchnorm_add_act_train")
            disable_helper("batchnorm_train")
            plain = self._resnet()
            plan2, _ = build_fusion_plan(plain.conf)
            assert plan2 == {}             # no helper -> no fusion
            plain.fit([DataSet(x, y)], num_epochs=3)
            out_plain = np.asarray(plain.output(x)[0])
            params_plain = plain.params_flat()

            # f32 tolerance, justified: the fused op evaluates
            # y = x*(gamma*rstd) + (beta - mean*gamma*rstd) as one FMA
            # with shifted one-pass statistics, while the plain walk does
            # (x-mean)*rstd*gamma + beta with jnp.var's two-pass moments —
            # algebraically identical, ~1-ulp different per element in
            # f32. Three epochs of SGD through a 2-block resnet amplify
            # that to ~1.3e-3 absolute on O(1) parameters (measured, seed
            # fixed); 4e-3/0.1% bounds it with margin while still
            # catching a wrong-formula regression (which diverges by
            # orders of magnitude). bf16 is not exercised here: the
            # helper's statistics are f32 by policy either way.
            np.testing.assert_allclose(params_fused, params_plain,
                                       rtol=1e-3, atol=4e-3)
            np.testing.assert_allclose(out_fused, out_plain,
                                       rtol=1e-3, atol=4e-3)
        finally:
            enable_helper("batchnorm_add_act_train")
            enable_helper("batchnorm_train")
            register_default()       # restore TPU-only platforms (no cpu)


class TestSerdeAllRegisteredTypes:
    """Every registered config dataclass must survive a JSON round trip
    bit-exactly (the Jackson polymorphic-serde parity check, applied
    exhaustively — configs are the checkpoint format, SURVEY.md §5.6)."""

    def test_every_registered_type_roundtrips(self):
        import dataclasses
        import json
        # import all conf modules so the registry is fully populated
        import deeplearning4j_tpu.nn.conf.layers  # noqa: F401
        import deeplearning4j_tpu.nn.graph.vertices  # noqa: F401
        from deeplearning4j_tpu.nn.conf.serde import (_TYPE_REGISTRY,
                                                      to_jsonable,
                                                      from_jsonable)
        assert len(_TYPE_REGISTRY) >= 30
        skipped = []
        for name, cls in sorted(_TYPE_REGISTRY.items()):
            if not dataclasses.is_dataclass(cls):
                skipped.append(name)
                continue
            try:
                inst = cls()
            except TypeError:
                # requires constructor args: give common ones
                try:
                    inst = cls(n_out=4)
                except TypeError:
                    skipped.append(name)
                    continue
            wire = json.dumps(to_jsonable(inst))
            back = from_jsonable(json.loads(wire))
            assert type(back) is cls, name
            for f in dataclasses.fields(cls):
                if f.metadata.get("transient"):
                    continue
                assert getattr(back, f.name) == getattr(inst, f.name), \
                    f"{name}.{f.name}"
        # nothing unexpected should be unroundtrippable
        assert len(skipped) <= 2, skipped
