"""Stage-1 substrate tests: activations, losses, updaters, schedules,
weight init, normalizers. Numeric oracles follow the reference's test style
(exact small-case numerics; SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import (
    activations, losses, make_updater, schedule_lr, normalize_gradient,
    init_weights, DataSet, NormalizerStandardize, NormalizerMinMaxScaler,
    ImagePreProcessingScaler, UPDATER_NAMES,
)
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.losses import get_loss, compute_loss


class TestActivations:
    def test_all_registered_run_and_shape(self):
        x = jnp.linspace(-3, 3, 24).reshape(4, 6)
        for name in activations.activation_names():
            y = get_activation(name)(x)
            assert y.shape == x.shape, name
            assert bool(jnp.all(jnp.isfinite(y))), name

    def test_known_values(self):
        x = jnp.array([[-1.0, 0.0, 2.0]])
        np.testing.assert_allclose(get_activation("relu")(x),
                                   [[0.0, 0.0, 2.0]])
        np.testing.assert_allclose(get_activation("hardtanh")(x),
                                   [[-1.0, 0.0, 1.0]])
        np.testing.assert_allclose(get_activation("sigmoid")(jnp.zeros((1, 1))),
                                   [[0.5]])
        np.testing.assert_allclose(get_activation("leakyrelu")(x),
                                   [[-0.01, 0.0, 2.0]], atol=1e-7)

    def test_softmax_rows_sum_to_one(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 7))
        s = get_activation("softmax")(x)
        np.testing.assert_allclose(np.sum(np.asarray(s), axis=-1),
                                   np.ones(5), rtol=1e-5)

    def test_rrelu_train_vs_test(self):
        x = -jnp.ones((100,))
        test_mode = get_activation("rrelu")(x)
        np.testing.assert_allclose(test_mode, -((1/8 + 1/3) / 2) * np.ones(100),
                                   rtol=1e-5)
        train_mode = get_activation("rrelu")(x, rng=jax.random.PRNGKey(1))
        assert float(jnp.std(train_mode)) > 0


class TestLosses:
    def test_mcxent_matches_manual(self):
        logits = jnp.array([[2.0, 1.0, 0.0], [0.0, 0.0, 0.0]])
        labels = jnp.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])
        per = get_loss("mcxent")(labels, logits, "softmax")
        logp = jax.nn.log_softmax(logits, axis=-1)
        expect = -np.asarray([logp[0, 0], logp[1, 1]])
        np.testing.assert_allclose(per, expect, rtol=1e-5)

    def test_xent_fused_matches_unfused(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
        y = (jax.random.uniform(jax.random.PRNGKey(1), (4, 3)) > 0.5).astype(jnp.float32)
        fused = get_loss("xent")(y, x, "sigmoid")
        p = jnp.clip(jax.nn.sigmoid(x), 1e-7, 1 - 1e-7)
        manual = jnp.sum(-(y * jnp.log(p) + (1 - y) * jnp.log(1 - p)), axis=-1)
        np.testing.assert_allclose(fused, manual, rtol=1e-4)

    def test_mse(self):
        y = jnp.array([[1.0, 2.0]])
        out = jnp.array([[0.0, 0.0]])
        per = get_loss("mse")(y, out, "identity")
        np.testing.assert_allclose(per, [(1.0 + 4.0) / 2], rtol=1e-6)

    def test_mask_zeroes_out_examples(self):
        y = jnp.ones((2, 3))
        x = jnp.zeros((2, 3))
        mask = jnp.array([1.0, 0.0])
        per = get_loss("l2")(y, x, "identity", mask[:, None] * jnp.ones((2, 3)))
        assert float(per[1]) == 0.0
        assert float(per[0]) == 3.0

    def test_all_losses_finite_and_grad(self):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (3, 4))
        y = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (3, 4)))
        for name in losses.loss_names():
            def f(p):
                return compute_loss(name, y, p,
                                    "softmax" if "xent" in name or "likelihood" in name
                                    else "identity")
            val = f(x)
            g = jax.grad(f)(x)
            assert np.isfinite(float(val)), name
            assert bool(jnp.all(jnp.isfinite(g))), name


class TestUpdaters:
    def test_sgd(self):
        u = make_updater("sgd")
        g = jnp.array([1.0, -2.0])
        step, _ = u.update(g, u.init(g), 0.1, 0)
        np.testing.assert_allclose(step, [0.1, -0.2], rtol=1e-6)

    def test_adam_first_step_is_lr_sized(self):
        u = make_updater("adam")
        g = jnp.array([0.5, -0.5])
        step, state = u.update(g, u.init(g), 0.001, 0)
        # With bias correction, first step ≈ lr * sign(g)
        np.testing.assert_allclose(np.abs(step), [0.001, 0.001], rtol=1e-3)

    def test_nesterovs_accelerates(self):
        u = make_updater("nesterovs", momentum=0.9)
        g = jnp.array([1.0])
        state = u.init(g)
        s1, state = u.update(g, state, 0.1, 0)
        s2, state = u.update(g, state, 0.1, 1)
        assert float(s2[0]) > float(s1[0])  # momentum accumulates

    def test_all_updaters_converge_quadratic(self):
        # minimize f(w) = 0.5*||w||^2 from w=5; every rule must reduce |w|
        for name in UPDATER_NAMES:
            if name == "none":
                continue
            # AdaDelta's step scale self-tunes from sqrt(eps) upward, so it
            # starts tiny by construction; give it a workable epsilon.
            u = make_updater(name, epsilon=1e-2 if name == "adadelta" else 1e-8)
            w = jnp.array([5.0])
            state = u.init(w)
            lr = 0.5 if name in ("sgd", "nesterovs") else 0.3
            for it in range(200):
                step, state = u.update(w, state, lr, it)
                w = w - step
            assert abs(float(w[0])) < 1.0, f"{name} failed to descend: {w}"

    def test_state_is_pure(self):
        u = make_updater("adam")
        g = jnp.ones((3,))
        s0 = u.init(g)
        _, s1 = u.update(g, s0, 0.01, 0)
        assert float(jnp.sum(s0["m"])) == 0.0  # original untouched


class TestSchedules:
    def test_policies(self):
        assert float(schedule_lr(0.1, None, 100)) == pytest.approx(0.1)
        assert float(schedule_lr(0.1, "exponential", 2, decay_rate=0.5)) == \
            pytest.approx(0.025)
        assert float(schedule_lr(0.1, "step", 10, decay_rate=0.5, steps=5)) == \
            pytest.approx(0.025)
        assert float(schedule_lr(0.1, "poly", 50, power=1.0,
                                 max_iterations=100)) == pytest.approx(0.05)
        assert float(schedule_lr(0.1, "inverse", 4, decay_rate=1.0, power=1.0)) \
            == pytest.approx(0.02)

    def test_schedule_map(self):
        sched = {0: 0.1, 10: 0.01, 20: 0.001}
        assert float(schedule_lr(0.1, "schedule", 5, schedule=sched)) == \
            pytest.approx(0.1)
        assert float(schedule_lr(0.1, "schedule", 15, schedule=sched)) == \
            pytest.approx(0.01)
        assert float(schedule_lr(0.1, "schedule", 25, schedule=sched)) == \
            pytest.approx(0.001)

    def test_jittable(self):
        f = jax.jit(lambda it: schedule_lr(0.1, "step", it, decay_rate=0.5,
                                           steps=5.0))
        assert float(f(jnp.asarray(10.0))) == pytest.approx(0.025)


class TestGradNorm:
    def test_clip_l2(self):
        g = {"W": jnp.array([3.0, 4.0])}
        out = normalize_gradient(g, "ClipL2PerLayer", threshold=1.0)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(out["W"])), 1.0,
                                   rtol=1e-5)

    def test_clip_elementwise(self):
        g = {"W": jnp.array([3.0, -4.0, 0.5])}
        out = normalize_gradient(g, "ClipElementWiseAbsoluteValue", threshold=1.0)
        np.testing.assert_allclose(out["W"], [1.0, -1.0, 0.5])


class TestWeightInit:
    def test_schemes_shapes_and_stats(self):
        key = jax.random.PRNGKey(0)
        for scheme in ["xavier", "xavier_uniform", "relu", "uniform",
                       "sigmoid_uniform", "relu_uniform", "lecun_normal"]:
            w = init_weights(key, (256, 128), 256, 128, scheme)
            assert w.shape == (256, 128)
            assert abs(float(jnp.mean(w))) < 0.05, scheme
        assert float(jnp.sum(jnp.abs(init_weights(key, (4, 4), 4, 4, "zero")))) == 0

    def test_xavier_variance(self):
        w = init_weights(jax.random.PRNGKey(1), (512, 512), 512, 512, "xavier")
        expect_std = np.sqrt(2.0 / 1024)
        assert float(jnp.std(w)) == pytest.approx(expect_std, rel=0.1)

    def test_distribution(self):
        w = init_weights(jax.random.PRNGKey(2), (1000,), 1, 1, "distribution",
                         distribution={"type": "uniform", "lower": 2, "upper": 3})
        assert float(jnp.min(w)) >= 2.0 and float(jnp.max(w)) <= 3.0


class TestNormalizers:
    def test_standardize_roundtrip(self, rng_np):
        f = rng_np.normal(5.0, 3.0, (100, 4)).astype(np.float32)
        ds = DataSet(f, rng_np.normal(size=(100, 2)).astype(np.float32))
        norm = NormalizerStandardize().fit(ds)
        out = norm.transform(ds)
        np.testing.assert_allclose(out.features.mean(axis=0), np.zeros(4),
                                   atol=1e-4)
        np.testing.assert_allclose(out.features.std(axis=0), np.ones(4),
                                   atol=1e-3)
        back = norm.revert_features(out.features)
        np.testing.assert_allclose(back, f, atol=1e-4)

    def test_minmax(self, rng_np):
        f = rng_np.uniform(-10, 10, (50, 3)).astype(np.float32)
        ds = DataSet(f)
        norm = NormalizerMinMaxScaler().fit(ds)
        out = norm.transform(ds)
        assert out.features.min() >= -1e-6 and out.features.max() <= 1 + 1e-6

    def test_image_scaler(self):
        f = np.full((2, 1, 4, 4), 255.0, np.float32)
        out = ImagePreProcessingScaler().transform(DataSet(f))
        np.testing.assert_allclose(out.features, np.ones_like(f))

    def test_serde(self, rng_np):
        f = rng_np.normal(2.0, 1.5, (60, 5)).astype(np.float32)
        ds = DataSet(f)
        norm = NormalizerStandardize().fit(ds)
        blob = norm.to_bytes()
        from deeplearning4j_tpu.ops.dataset import DataNormalizer
        norm2 = DataNormalizer.from_bytes(blob)
        np.testing.assert_allclose(norm2.mean, norm.mean)
        out1 = norm.transform(ds).features
        out2 = norm2.transform(ds).features
        np.testing.assert_allclose(out1, out2)


class TestDataSet:
    def test_batch_and_merge(self, rng_np):
        ds = DataSet(rng_np.normal(size=(10, 3)).astype(np.float32),
                     rng_np.normal(size=(10, 2)).astype(np.float32))
        batches = ds.batch_by(4)
        assert [b.num_examples() for b in batches] == [4, 4, 2]
        merged = DataSet.merge(batches)
        np.testing.assert_allclose(merged.features, ds.features)


class TestCompilationCacheConfig:
    """ops.platform.configure_compilation_cache: where JAX_COMPILATION_
    CACHE_DIR is set the code sets no directory; otherwise ONE fixed path
    inside the checkout — identical across calls and processes, because a
    cache whose path moves never hits. Each case runs in a fresh
    interpreter (the configuration is once-per-process state)."""

    _PROBE = (
        "import json, jax\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (updates.append(k), real(k, v))\n"
        "from deeplearning4j_tpu.ops.platform import "
        "configure_compilation_cache as c\n"
        "a, b = c(), c(min_compile_secs=0.0)\n"
        "print(json.dumps({'a': a, 'b': b, 'updates': updates, 'cfg': "
        "jax.config.jax_compilation_cache_dir, 'floor': "
        "jax.config.jax_persistent_cache_min_compile_time_secs}))\n")

    def _run(self, env_dir):
        import json
        import os
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", self._PROBE], env=env,
                           cwd="/", capture_output=True, text=True,
                           timeout=120, check=True)
        return root, json.loads(r.stdout.strip().splitlines()[-1])

    def test_env_dir_is_left_to_jax(self, tmp_path):
        _, out = self._run(str(tmp_path / "cc"))
        assert out["a"] == out["b"] == out["cfg"] == str(tmp_path / "cc")
        assert "jax_compilation_cache_dir" not in out["updates"]
        assert out["floor"] == 0.0            # the floor may only lower

    def test_default_is_one_fixed_path_in_the_checkout(self):
        import os
        root, first = self._run(None)
        _, second = self._run(None)           # a second process: same path
        want = os.path.join(root, ".jax_cache")
        for out in (first, second):
            assert out["a"] == out["b"] == out["cfg"] == want
            assert out["updates"].count("jax_compilation_cache_dir") == 1
