"""Decoder-only transformer LM (models/transformer.py) — the TPU-era
long-context flagship built from framework layers (SURVEY.md §5.7: the
reference has no transformer; ring attention/SP are the designed-fresh
extensions this model family rides)."""

import numpy as np
import pytest

from deeplearning4j_tpu.models import generate, lm_batch, transformer_lm_conf
from deeplearning4j_tpu.nn import NeuralNetConfiguration, InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet


def _tiny_lm(vocab=12, **kw):
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("learning_rate", 1e-2)
    kw.setdefault("seed", 5)
    return ComputationGraph(transformer_lm_conf(vocab, **kw)).init()


def _cyclic_batch(rng, vocab=12, n=16, t=16):
    starts = rng.integers(0, vocab, (n, 1))
    seq = (starts + np.arange(t + 1)[None, :]) % vocab
    x, y = lm_batch(seq, vocab)
    return DataSet(x, y)


class TestTransformerLM:
    def test_learns_cyclic_language(self, rng_np):
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        s0 = net.score(ds)
        for _ in range(150):
            net.fit_batch(ds)
        s1 = net.score(ds)
        assert s1 < 0.05 * s0, (s0, s1)
        # greedy generation continues the cycle exactly
        out = generate(net, [3], 8, temperature=0)
        np.testing.assert_array_equal(out, (3 + np.arange(9)) % 12)

    def test_causality(self, rng_np):
        """Output at position t must not depend on tokens after t."""
        net = _tiny_lm()
        a = rng_np.integers(0, 12, (1, 10)).astype(np.int32)
        b = a.copy()
        b[0, 6:] = (b[0, 6:] + 5) % 12        # mutate the future
        oa = np.asarray(net.output(a)[0])
        ob = np.asarray(net.output(b)[0])
        np.testing.assert_allclose(oa[0, :6], ob[0, :6],
                                   rtol=1e-5, atol=1e-6)
        assert np.abs(oa[0, 6:] - ob[0, 6:]).max() > 1e-6

    def test_serde_roundtrip(self, tmp_path, rng_np):
        from deeplearning4j_tpu.utils.serializer import ModelSerializer
        net = _tiny_lm()
        net.fit_batch(_cyclic_batch(rng_np))
        path = tmp_path / "lm.zip"
        ModelSerializer.write_model(net, path)
        loaded = ModelSerializer.restore_computation_graph(path)
        x = rng_np.integers(0, 12, (2, 8)).astype(np.int32)
        np.testing.assert_allclose(np.asarray(net.output(x)[0]),
                                   np.asarray(loaded.output(x)[0]),
                                   rtol=1e-6)
        # a configuration saved before PR 31 carries the removed option
        from deeplearning4j_tpu.nn.conf import from_jsonable, to_jsonable
        attn = net.conf.vertices["attn0"].layer
        assert from_jsonable(dict(to_jsonable(attn), fused_qkv=False)) == attn

    def test_max_length_guard(self):
        net = _tiny_lm(max_length=8)
        with pytest.raises(ValueError):
            net.output(np.zeros((1, 9), np.int32))


class TestTransformerLayerGradients:
    """Finite-difference oracle for the new block layers through the MLN
    gradient-check harness (SURVEY.md §4)."""

    def _check(self, layers, input_type, ds):
        from deeplearning4j_tpu.nn import MultiLayerNetwork
        from deeplearning4j_tpu.nn.gradientcheck import check_gradients
        import jax.numpy as jnp
        conf = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
                .updater("sgd").weight_init("xavier").list())
        for l in layers:
            conf = conf.layer(l)
        conf = conf.set_input_type(input_type).build()
        net = MultiLayerNetwork(conf, compute_dtype=jnp.float64).init()
        return check_gradients(net, ds)

    def test_layernorm_gradients(self, rng_np):
        from deeplearning4j_tpu.nn.conf.layers import (LayerNormalization,
                                                       RnnOutputLayer)
        ds = DataSet(rng_np.normal(size=(2, 5, 3)),
                     np.eye(2)[rng_np.integers(0, 2, (2, 5))].astype(
                         np.float64))
        assert self._check(
            [LayerNormalization(),
             RnnOutputLayer(n_out=2, loss="mcxent", activation="softmax")],
            InputType.recurrent(3), ds)

    def test_ffn_gradients(self, rng_np):
        from deeplearning4j_tpu.nn.conf.layers import (RnnOutputLayer,
                                                       TransformerFeedForward)
        ds = DataSet(rng_np.normal(size=(2, 4, 3)),
                     np.eye(2)[rng_np.integers(0, 2, (2, 4))].astype(
                         np.float64))
        assert self._check(
            [TransformerFeedForward(hidden_mult=2),
             RnnOutputLayer(n_out=2, loss="mcxent", activation="softmax")],
            InputType.recurrent(3), ds)


class TestTransformerSequenceParallel:
    """The flagship LM trains sequence-parallel: T sharded over the 8-device
    sp axis, attention over the ICI ring via the helper seam — one SP step
    must equal one single-device step exactly (ring attention is exact)."""

    def test_sp_step_matches_single_device(self, rng_np):
        import jax
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer, disable_ring_attention)
        ds = _cyclic_batch(rng_np, n=4, t=16)     # T=16 divisible by 8
        solo = _tiny_lm()
        solo.fit_batch(ds)
        sp_net = _tiny_lm()
        trainer = GraphSequenceParallelTrainer(
            sp_net, mesh=make_mesh(axis_names=("sp",)))
        try:
            trainer.fit_batch(ds)
        finally:
            disable_ring_attention()
        for name in solo.params:
            for k in solo.params[name]:
                # adam divides tiny grads by sqrt(v)+eps, amplifying
                # reduction-order noise from the ring's streaming softmax
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[name][k]),
                    np.asarray(solo.params[name][k]),
                    rtol=2e-3, atol=1e-4, err_msg=f"{name}/{k}")
        assert abs(float(sp_net.score_value) - float(solo.score_value)) < 1e-4

    def test_sp_training_converges(self, rng_np):
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer, disable_ring_attention)
        net = _tiny_lm()
        trainer = GraphSequenceParallelTrainer(
            net, mesh=make_mesh(axis_names=("sp",)))
        ds = _cyclic_batch(rng_np, n=8, t=16)
        try:
            s0 = net.score(ds)
            for _ in range(60):
                trainer.fit_batch(ds)
        finally:
            disable_ring_attention()
        assert net.score(ds) < 0.3 * s0

    def test_indivisible_sequence_rejected(self, rng_np):
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer, disable_ring_attention)
        net = _tiny_lm()
        trainer = GraphSequenceParallelTrainer(
            net, mesh=make_mesh(axis_names=("sp",)))
        try:
            with pytest.raises(ValueError):
                trainer.fit_batch(_cyclic_batch(rng_np, n=2, t=11))
        finally:
            disable_ring_attention()


class TestSPRegressions:
    def test_ring_helper_reenables_after_disable(self, rng_np):
        """disable_ring_attention leaves the kind disabled; a later trainer
        must re-enable it or it silently trains without the ring."""
        from deeplearning4j_tpu.nn.helpers import get_helper
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer, disable_ring_attention)
        mesh = make_mesh(axis_names=("sp",))
        t1 = GraphSequenceParallelTrainer(_tiny_lm(), mesh)
        disable_ring_attention()
        assert get_helper("attention") is None
        t2 = GraphSequenceParallelTrainer(_tiny_lm(), mesh)
        try:
            assert get_helper("attention") is not None
        finally:
            disable_ring_attention()

    def test_dp_sp_composed_mesh_matches_single_device(self, rng_np):
        """DP×SP on a (data=2, sp=4) 2-D mesh (VERDICT r3 #6): batch
        sharded over `data`, time over `sp` — one composed step equals one
        single-device step (GSPMD all-reduces grads over data; devices
        along data run independent rings)."""
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer)
        ds = _cyclic_batch(rng_np, n=4, t=16)      # batch 4 / dp 2, T 16 / sp 4
        solo = _tiny_lm()
        solo.fit_batch(ds)
        sp_net = _tiny_lm()
        mesh = make_mesh(8, axis_names=("data", "sp"), shape=(2, 4))
        with GraphSequenceParallelTrainer(
                sp_net, mesh=mesh, data_axis="data",
                ring_impl="pallas") as trainer:
            trainer.fit_batch(ds)
            assert trainer.data_axis == "data"
        assert abs(float(sp_net.score_value) -
                   float(solo.score_value)) < 1e-4
        for name in solo.params:
            for k in solo.params[name]:
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[name][k]),
                    np.asarray(solo.params[name][k]),
                    rtol=2e-3, atol=1e-4, err_msg=f"{name}/{k}")

    def test_dp_sp_rejects_indivisible_batch(self, rng_np):
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer)
        mesh = make_mesh(8, axis_names=("data", "sp"), shape=(2, 4))
        with GraphSequenceParallelTrainer(
                _tiny_lm(), mesh=mesh, data_axis="data") as trainer:
            with pytest.raises(ValueError, match="batch"):
                trainer.fit_batch(_cyclic_batch(rng_np, n=3, t=16))

    def test_sp_long_t_step_matches_single_device(self, rng_np):
        """T=2048 (shard length 256 — the Pallas pair-kernel ring path):
        one SP train step of the full LM equals one single-device step.
        This is the r4 composition test — SP and the Pallas kernel
        multiplying, not just coexisting (VERDICT r3 #3)."""
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer)
        t = 2048
        kw = dict(d_model=16, num_heads=2, num_layers=1, max_length=t)
        ds = _cyclic_batch(rng_np, n=1, t=t)
        solo = _tiny_lm(**kw)
        solo.fit_batch(ds)
        sp_net = _tiny_lm(**kw)
        with GraphSequenceParallelTrainer(
                sp_net, mesh=make_mesh(axis_names=("sp",)),
                ring_impl="pallas") as trainer:
            trainer.fit_batch(ds)
        assert abs(float(sp_net.score_value) -
                   float(solo.score_value)) < 1e-3
        for name in solo.params:
            for k in solo.params[name]:
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[name][k]),
                    np.asarray(solo.params[name][k]),
                    rtol=5e-3, atol=2e-4, err_msg=f"{name}/{k}")

    def test_trainer_close_restores_previous_helper(self, rng_np):
        """The SP trainer claims the process-global 'attention' slot; close()
        (or context exit) must put back EXACTLY what was there before —
        other nets in the process must not silently route through a ring
        bound to the trainer's mesh."""
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer)

        def marker_helper(conf, q, k, v, mask):
            return None

        snap = helpers.snapshot_helper("attention")
        try:
            helpers.restore_helper("attention", (None, False, False))
            helpers.register_helper("attention", marker_helper, ("cpu",))
            helpers.enable_helper("attention")   # a prior test may disable
            mesh = make_mesh(axis_names=("sp",))
            with GraphSequenceParallelTrainer(_tiny_lm(), mesh):
                got = helpers.get_helper("attention")
                assert got is not None and got is not marker_helper
            assert helpers.get_helper("attention") is marker_helper
            # nothing registered before: close() must fully clear the slot
            helpers.restore_helper("attention", (None, False, False))
            t2 = GraphSequenceParallelTrainer(_tiny_lm(), mesh)
            assert helpers.get_helper("attention") is not None
            t2.close()
            t2.close()                      # idempotent
            assert helpers._HELPERS.get("attention") is None
        finally:
            helpers.restore_helper("attention", snap)

    def test_non_lifo_close_does_not_resurrect_stale_ring(self, rng_np):
        """t1 closed while t2 holds the slot must not clobber t2; t2's later
        close must not reinstall t1's dead ring either — the restore walks
        through closed trainers' snapshots to the still-live base helper
        (the user's custom registration here). A closed trainer also refuses
        further fit_batch calls."""
        import warnings as warnings_mod
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer)

        def custom_fn(conf, q, k, v, mask):
            return None

        snap = helpers.snapshot_helper("attention")
        try:
            helpers.restore_helper("attention", (None, False, False))
            helpers.register_helper("attention", custom_fn, ("cpu",))
            helpers.enable_helper("attention")
            mesh = make_mesh(axis_names=("sp",))
            with warnings_mod.catch_warnings():
                warnings_mod.simplefilter("ignore")   # slot-replace warnings
                t1 = GraphSequenceParallelTrainer(_tiny_lm(), mesh)
                t2 = GraphSequenceParallelTrainer(_tiny_lm(), mesh)
            with pytest.warns(UserWarning, match="LIFO"):
                t1.close()
            assert helpers._HELPERS["attention"][0] is t2._ring_helper
            t2.close()
            # t1's dead ring was skipped; the user's helper survives
            assert helpers.get_helper("attention") is custom_fn
            with pytest.raises(RuntimeError, match="closed"):
                t2.fit_batch(_cyclic_batch(rng_np, n=2, t=16))
        finally:
            helpers.restore_helper("attention", snap)

    def test_sp_label_mask_matches_single_device(self, rng_np):
        """Per-token label masks shard over T and must weight the loss
        exactly like the single-device step."""
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            GraphSequenceParallelTrainer, disable_ring_attention)
        ds0 = _cyclic_batch(rng_np, n=4, t=16)
        mask = np.ones((4, 16), np.float32)
        mask[:2, 8:] = 0.0                     # half the rows are short
        ds = DataSet(ds0.features, ds0.labels, labels_mask=mask)
        solo = _tiny_lm()
        solo.fit_batch(ds)
        sp_net = _tiny_lm()
        trainer = GraphSequenceParallelTrainer(
            sp_net, mesh=make_mesh(axis_names=("sp",)))
        try:
            trainer.fit_batch(ds)
        finally:
            disable_ring_attention()
        assert abs(float(sp_net.score_value) -
                   float(solo.score_value)) < 1e-4
        np.testing.assert_allclose(
            np.asarray(sp_net.params["out"]["W"]),
            np.asarray(solo.params["out"]["W"]), rtol=2e-3, atol=1e-4)

    def test_generate_uses_fixed_bucket(self, rng_np):
        """Sampling pads to one bucket shape (one compile, padding invisible
        to causal attention): bucketed == unbucketed-growing results."""
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(80):
            net.fit_batch(ds)
        a = generate(net, [3], 6, temperature=0)            # default bucket
        b = generate(net, [3], 6, temperature=0, bucket=16)
        np.testing.assert_array_equal(a, b)


class TestFlashAttention:
    """Blockwise flash-style attention kernel (kernels/flash_attention.py)
    behind the helper seam — numerically identical to the materialized
    path (the CuDNN-vs-builtin equivalence pattern, SURVEY.md §4), with
    O(T·block) memory."""

    def test_layer_equivalence_via_helper(self, rng_np):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.flash_attention import \
            register_flash_attention
        from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
        from deeplearning4j_tpu.nn.helpers import disable_helper
        layer = SelfAttentionLayer(n_in=6, n_out=8, num_heads=2, causal=True,
                                   weight_init="xavier")
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng_np.normal(size=(2, 12, 6)), jnp.float32)
        mask = jnp.asarray(
            np.concatenate([np.ones((2, 9)), np.zeros((2, 3))], 1),
            jnp.float32)
        register_flash_attention(block_size=4, min_seq_len=1)
        try:
            y_flash, _ = layer.forward(params, {}, x, mask=mask)
            g_flash = jax.grad(lambda p: jnp.sum(
                layer.forward(p, {}, x, mask=mask)[0] ** 2))(params)
        finally:
            disable_helper("attention")
        y_ref, _ = layer.forward(params, {}, x, mask=mask)
        g_ref = jax.grad(lambda p: jnp.sum(
            layer.forward(p, {}, x, mask=mask)[0] ** 2))(params)
        np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)
        for k in g_ref:
            np.testing.assert_allclose(np.asarray(g_flash[k]),
                                       np.asarray(g_ref[k]),
                                       rtol=1e-4, atol=1e-6, err_msg=k)

    def test_min_seq_len_fallback(self, rng_np):
        """Below min_seq_len the helper declines and the built-in path runs
        (identical outputs either way — this pins the decline contract)."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.flash_attention import \
            register_flash_attention
        from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
        from deeplearning4j_tpu.nn.helpers import disable_helper
        layer = SelfAttentionLayer(n_in=4, n_out=8, num_heads=2,
                                   weight_init="xavier")
        params = layer.init_params(jax.random.PRNGKey(1))
        x = jnp.asarray(rng_np.normal(size=(1, 5, 4)), jnp.float32)
        register_flash_attention(min_seq_len=1024)
        try:
            y1, _ = layer.forward(params, {}, x)
        finally:
            disable_helper("attention")
        y2, _ = layer.forward(params, {}, x)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_lm_trains_with_flash(self, rng_np):
        from deeplearning4j_tpu.kernels.flash_attention import \
            register_flash_attention
        from deeplearning4j_tpu.nn.helpers import disable_helper
        register_flash_attention(block_size=8, min_seq_len=1)
        try:
            net = _tiny_lm()
            ds = _cyclic_batch(rng_np)
            s0 = net.score(ds)
            for _ in range(100):
                net.fit_batch(ds)
            assert net.score(ds) < 0.1 * s0
        finally:
            disable_helper("attention")


class TestFlashMaskEdgeCases:
    def test_leading_padding_equivalence(self, rng_np):
        """Leading padding: every query row with at least one VISIBLE key
        matches the materialized -1e30 path exactly; fully-masked rows are
        degenerate in both paths (each emits a different arbitrary convex
        combination of v) — the contract there is finite + bounded, and
        downstream losses mask those rows anyway."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.flash_attention import \
            flash_attention
        q = jnp.asarray(rng_np.normal(size=(2, 8, 2, 4)), jnp.float32)
        k = jnp.asarray(rng_np.normal(size=(2, 8, 2, 4)), jnp.float32)
        v = jnp.asarray(rng_np.normal(size=(2, 8, 2, 4)), jnp.float32)
        km = jnp.asarray(np.concatenate(
            [np.zeros((2, 4)), np.ones((2, 4))], 1), jnp.float32)
        got = flash_attention(q, k, v, causal=True, block_size=4,
                              key_mask=km)
        scale = 1.0 / np.sqrt(4.0)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        neg = jnp.asarray(-1e30, jnp.float32)
        cm = jnp.tril(jnp.ones((8, 8), bool))
        logits = jnp.where(cm[None, None], logits, neg)
        logits = jnp.where(km.astype(bool)[:, None, None, :], logits, neg)
        want = jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(logits, -1), v)
        # causal rows 4..7 see visible keys (>=4): exact equivalence
        np.testing.assert_allclose(np.asarray(got)[:, 4:],
                                   np.asarray(want)[:, 4:],
                                   rtol=1e-4, atol=1e-5)
        # rows 0..3 (only masked keys visible): finite, bounded by v range
        head = np.asarray(got)[:, :4]
        assert np.all(np.isfinite(head))
        assert head.max() <= float(jnp.max(v)) + 1e-5
        assert head.min() >= float(jnp.min(v)) - 1e-5

    def test_register_overwrite_warns(self):
        import warnings
        from deeplearning4j_tpu.nn.helpers import (disable_helper,
                                                   register_helper)
        register_helper("attention", lambda *a: None, ("cpu",))
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                register_helper("attention", lambda *a: 1, ("cpu",))
            assert any("already registered" in str(x.message) for x in w)
        finally:
            disable_helper("attention")

    def test_mln_inference_keeps_integer_ids(self, rng_np):
        """output()/rnn paths must not round token ids through bf16
        (training's staging fix extended to inference)."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn import (NeuralNetConfiguration,
                                           InputType, MultiLayerNetwork)
        from deeplearning4j_tpu.nn.conf.layers import (EmbeddingLayer,
                                                       OutputLayer)
        conf = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
                .updater("sgd").weight_init("xavier").list()
                .layer(EmbeddingLayer(n_in=1000, n_out=8))
                .layer(OutputLayer(n_out=2, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(1000)).build())
        net16 = MultiLayerNetwork(conf, compute_dtype=jnp.bfloat16).init()
        ids = np.asarray([[300], [301]], np.int32)   # bf16 would merge these
        o1 = net16.output(ids[:1])
        o2 = net16.output(ids[1:])
        assert np.abs(np.asarray(o1) - np.asarray(o2)).max() > 0


class TestPallasFlashAttention:
    """Pallas flash kernels (kernels/pallas_attention.py) — interpret mode
    on CPU, real MXU kernels on TPU; equivalence vs the materialized
    reference is the contract (SURVEY.md §4 CuDNN-vs-builtin pattern)."""

    def test_fwd_and_grads_match_reference(self, rng_np):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            pallas_flash_attention
        from deeplearning4j_tpu.parallel.sequence import attention_reference
        q = jnp.asarray(rng_np.normal(size=(2, 16, 2, 8)), jnp.float32)
        k = jnp.asarray(rng_np.normal(size=(2, 16, 2, 8)), jnp.float32)
        v = jnp.asarray(rng_np.normal(size=(2, 16, 2, 8)), jnp.float32)
        for causal in (False, True):
            a = pallas_flash_attention(q, k, v, causal=causal,
                                       q_block=8, k_block=8)
            b = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        ga = jax.grad(lambda q, k, v: jnp.sum(pallas_flash_attention(
            q, k, v, causal=True, q_block=8, k_block=8) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(lambda q, k, v: jnp.sum(attention_reference(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for x, y, n in zip(ga, gb, "qkv"):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-5, err_msg=n)

    def test_helper_chain(self, rng_np):
        """Short -> decline (materialized wins); long unmasked -> Pallas;
        long masked -> jnp blockwise (covered in
        TestPallasFlashRegressions)."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            make_pallas_flash_helper

        class Conf:
            causal = True
        helper = make_pallas_flash_helper(min_seq_len=16, q_block=8,
                                          k_block=8)
        q = jnp.zeros((1, 8, 2, 8))
        assert helper(Conf(), q, q, q, None) is None      # too short
        q = jnp.zeros((1, 16, 2, 8))
        assert helper(Conf(), q, q, q, None) is not None

    def test_lm_trains_with_pallas_flash(self, rng_np):
        from deeplearning4j_tpu.kernels.pallas_attention import \
            register_pallas_flash_attention
        from deeplearning4j_tpu.nn.helpers import disable_helper
        register_pallas_flash_attention(min_seq_len=1, q_block=8, k_block=8)
        try:
            net = _tiny_lm()
            ds = _cyclic_batch(rng_np)
            s0 = net.score(ds)
            for _ in range(100):
                net.fit_batch(ds)
            assert net.score(ds) < 0.1 * s0
        finally:
            disable_helper("attention")


class TestPallasFlashRegressions:
    def test_non_divisible_t(self, rng_np):
        """Padding (causal) / jnp fallback (non-causal) keep non-divisible
        sequence lengths exact — no uninitialized tail rows."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            pallas_flash_attention
        from deeplearning4j_tpu.parallel.sequence import attention_reference
        q = jnp.asarray(rng_np.normal(size=(2, 13, 2, 8)), jnp.float32)
        k = jnp.asarray(rng_np.normal(size=(2, 13, 2, 8)), jnp.float32)
        v = jnp.asarray(rng_np.normal(size=(2, 13, 2, 8)), jnp.float32)
        for causal in (True, False):
            a = pallas_flash_attention(q, k, v, causal=causal,
                                       q_block=8, k_block=8)
            b = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_masked_long_stays_on_kernel(self, rng_np):
        """A masked long sequence rides the Pallas kernel (r4 — the r3
        helper dropped it to the jnp blockwise path and lost the kernel
        win on ragged batches); results match the jnp path on every row
        with a visible key."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.flash_attention import \
            flash_attention
        from deeplearning4j_tpu.kernels.pallas_attention import \
            make_pallas_flash_helper

        class Conf:
            causal = True
        helper = make_pallas_flash_helper(min_seq_len=16, q_block=8,
                                          k_block=8)
        q = jnp.asarray(rng_np.normal(size=(1, 16, 2, 8)), jnp.float32)
        km = jnp.asarray(np.concatenate(
            [np.ones((1, 12)), np.zeros((1, 4))], 1), jnp.float32)
        got = helper(Conf(), q, q, q, km)
        assert got is not None
        want = flash_attention(q, q, q, causal=True, block_size=8,
                               key_mask=km)
        # causal + leading 12 real keys: every query row sees key 0 —
        # all rows non-degenerate, paths agree to float tolerance
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        # short sequences still decline to the materialized path
        qs = jnp.zeros((1, 8, 2, 8))
        assert helper(Conf(), qs, qs, qs, None) is None

    def test_masked_kernel_fwd_and_grads_match_materialized(self, rng_np):
        """In-kernel key masks: forward AND gradients match the
        materialized -1e30 replacement path on rows with visible keys,
        for causal and non-causal, divisible and ragged (padded) T."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            pallas_flash_attention

        def materialized(q, k, v, km, causal):
            scale = 1.0 / np.sqrt(q.shape[-1])
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            neg = jnp.asarray(-1e30, jnp.float32)
            logits = jnp.where(km.astype(bool)[:, None, None, :],
                               logits, neg)
            if causal:
                t = q.shape[1]
                cm = jnp.tril(jnp.ones((t, t), bool))
                logits = jnp.where(cm[None, None], logits, neg)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(logits, -1), v)

        for t in (16, 13):                      # 13: exercises padding
            q = jnp.asarray(rng_np.normal(size=(2, t, 2, 8)), jnp.float32)
            k = jnp.asarray(rng_np.normal(size=(2, t, 2, 8)), jnp.float32)
            v = jnp.asarray(rng_np.normal(size=(2, t, 2, 8)), jnp.float32)
            km = np.ones((2, t), np.float32)
            km[0, t - 3:] = 0.0                 # ragged: row 0 is short
            km[1, :2] = 0.0                     # leading padding on row 1
            km = jnp.asarray(km)
            for causal in (False, True):
                # rows with NO visible key (e.g. causal queries 0-1 of the
                # leading-padded batch row) are degenerate in both paths —
                # each emits a different arbitrary convex combination of v;
                # the equivalence contract covers the rest
                vis = np.cumsum(np.asarray(km), 1) if causal else \
                    np.broadcast_to(np.asarray(km).sum(1, keepdims=True),
                                    (2, t))
                rowm = (vis > 0)[:, :, None, None]
                a = pallas_flash_attention(q, k, v, causal=causal,
                                           q_block=8, k_block=8,
                                           key_mask=km)
                b = materialized(q, k, v, km, causal)
                np.testing.assert_allclose(
                    np.asarray(a) * rowm, np.asarray(b) * rowm,
                    rtol=1e-4, atol=1e-5, err_msg=f"t={t} causal={causal}")
                assert np.all(np.isfinite(np.asarray(a)))

            rw = jnp.asarray((np.cumsum(np.asarray(km), 1) > 0)
                             [:, :, None, None].astype(np.float32))

            def loss_pallas(q, k, v):
                return jnp.sum((pallas_flash_attention(
                    q, k, v, causal=True, q_block=8, k_block=8,
                    key_mask=km) * rw) ** 2)

            def loss_mat(q, k, v):
                return jnp.sum((materialized(q, k, v, km, True) * rw) ** 2)

            ga = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
            gb = jax.grad(loss_mat, argnums=(0, 1, 2))(q, k, v)
            for x, y, n in zip(ga, gb, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=1e-3, atol=1e-4,
                    err_msg=f"d{n} t={t}")

    def test_lm_trains_ragged_batches_on_kernel(self, rng_np):
        """End-to-end: a transformer LM with ragged (key-masked) batches
        trains through the registered Pallas helper — the r4 win the
        in-kernel mask exists for — and converges like the jnp path."""
        from deeplearning4j_tpu.kernels.pallas_attention import \
            register_pallas_flash_attention
        from deeplearning4j_tpu.nn.helpers import disable_helper
        register_pallas_flash_attention(min_seq_len=1, q_block=8, k_block=8)
        try:
            net = _tiny_lm()
            ds0 = _cyclic_batch(rng_np, n=8, t=16)
            mask = np.ones((8, 16), np.float32)
            mask[:4, 10:] = 0.0                # half the rows are short
            ds = DataSet(ds0.features, ds0.labels, features_mask=mask,
                         labels_mask=mask)
            s0 = net.score(ds)
            for _ in range(80):
                net.fit_batch(ds)
            assert net.score(ds) < 0.2 * s0
        finally:
            disable_helper("attention")

    def test_masked_fully_masked_row_finite(self, rng_np):
        """A row whose every key is masked degrades to a finite bounded
        convex combination of v (the shared degenerate-row contract), and
        its gradient contribution stays finite."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            pallas_flash_attention
        q = jnp.asarray(rng_np.normal(size=(1, 16, 2, 8)), jnp.float32)
        km = jnp.zeros((1, 16), jnp.float32)    # everything masked
        out = pallas_flash_attention(q, q, q, causal=False,
                                     q_block=8, k_block=8, key_mask=km)
        o = np.asarray(out)
        assert np.all(np.isfinite(o))
        assert o.max() <= float(jnp.max(q)) + 1e-5
        assert o.min() >= float(jnp.min(q)) - 1e-5
        g = jax.grad(lambda x: jnp.sum(pallas_flash_attention(
            x, x, x, causal=False, q_block=8, k_block=8,
            key_mask=km) ** 2))(q)
        assert np.all(np.isfinite(np.asarray(g)))


class TestShortSeqAttention:
    """Whole-block short-T kernel pair (kernels/pallas_shortseq.py, r5 —
    VERDICT r4 item #1): fwd + grads match the materialized reference in
    interpret mode across causal/masked/q_split variants; the helper
    routes tile-aligned short shapes onto it; invalid configs raise
    instead of writing garbage."""

    def _data(self, rng_np, b=2, t=256, h=4, d=8):
        import jax.numpy as jnp
        mk = lambda: jnp.asarray(rng_np.normal(size=(b, t, h, d)),
                                 jnp.float32)
        km = np.ones((b, t), np.float32)
        km[:, t - 7:] = 0.0                  # ragged tail, key 0 visible
        return mk(), mk(), mk(), jnp.asarray(km)

    @staticmethod
    def _ref(q, k, v, causal=False, key_mask=None):
        """Materialized reference with the kernels' −1e30 replacement
        masking (attention_reference has no key-mask arg)."""
        import jax
        import jax.numpy as jnp
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if key_mask is not None:
            s = jnp.where(key_mask[:, None, None, :] > 0, s, -1e30)
        if causal:
            t = q.shape[1]
            i = jnp.arange(t)
            s = jnp.where(i[:, None] >= i[None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def test_equivalence_and_grads(self, rng_np):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_shortseq import \
            short_attention
        q, k, v, km = self._data(rng_np)
        for causal in (True, False):
            for mask in (None, km):
                for qs in (1, 2, -1):
                    f = lambda q, k, v: jnp.sum(short_attention(
                        q, k, v, causal=causal, key_mask=mask, q_split=qs,
                        interpret=True) ** 2)
                    fr = lambda q, k, v: jnp.sum(self._ref(
                        q, k, v, causal=causal, key_mask=mask) ** 2)
                    got = short_attention(q, k, v, causal=causal,
                                          key_mask=mask, q_split=qs,
                                          interpret=True)
                    want = self._ref(q, k, v, causal=causal,
                                     key_mask=mask)
                    np.testing.assert_allclose(np.asarray(got),
                                               np.asarray(want),
                                               rtol=1e-5, atol=1e-5)
                    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
                    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
                    for a, b_ in zip(g, gr):
                        np.testing.assert_allclose(np.asarray(a),
                                                   np.asarray(b_),
                                                   rtol=1e-3, atol=1e-4)

    def test_helper_routes_short_shapes(self, rng_np):
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            make_pallas_flash_helper

        class Conf:
            causal = True
        helper = make_pallas_flash_helper(min_seq_len=1024,
                                          interpret=True)
        q = jnp.asarray(rng_np.normal(size=(1, 256, 2, 8)), jnp.float32)
        out = helper(Conf(), q, q, q, None)
        assert out is not None               # tile-aligned short: kernel
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(self._ref(q, q, q, causal=True)),
            rtol=1e-5, atol=1e-5)
        q300 = jnp.zeros((1, 300, 2, 8), jnp.float32)
        assert helper(Conf(), q300, q300, q300, None) is None  # unaligned
        q128 = jnp.zeros((1, 128, 2, 8), jnp.float32)
        assert helper(Conf(), q128, q128, q128, None) is None  # tiny

    def test_short_route_gated_on_known_good_shapes(self, rng_np):
        """The DEFAULT-on short-T route declines unusual head dims and
        non-float dtypes instead of raising at kernel construction — the
        materialized path stays the safety net (ADVICE r5)."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_attention import \
            make_pallas_flash_helper

        class Conf:
            causal = True
        helper = make_pallas_flash_helper(min_seq_len=1024,
                                          interpret=True)
        # odd head dim (D=12, not a multiple of 8): decline, don't raise
        q12 = jnp.zeros((1, 256, 2, 12), jnp.float32)
        assert helper(Conf(), q12, q12, q12, None) is None
        # non-float q/k/v: decline
        qi = jnp.zeros((1, 256, 2, 8), jnp.int32)
        assert helper(Conf(), qi, qi, qi, None) is None
        # known-good shape still rides the kernel
        qok = jnp.asarray(rng_np.normal(size=(1, 256, 2, 16)), jnp.float32)
        assert helper(Conf(), qok, qok, qok, None) is not None

    def test_invalid_configs_raise(self, rng_np):
        import jax.numpy as jnp
        import pytest
        from deeplearning4j_tpu.kernels.pallas_shortseq import \
            short_attention
        q = jnp.zeros((2, 256, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="divide B\\*H"):
            short_attention(q, q, q, g_heads=3, interpret=True)
        with pytest.raises(ValueError, match="divide T"):
            short_attention(q, q, q, causal=True, q_split=3, interpret=True)
        with pytest.raises(ValueError, match="g_heads"):
            short_attention(q, q, q, key_mask=jnp.ones((2, 256)),
                            g_heads=8, interpret=True)
        with pytest.raises(ValueError, match="MAX_T"):
            big = jnp.zeros((1, 1024, 2, 8), jnp.float32)
            short_attention(big, big, big, interpret=True)

    def test_masked_g_spans_one_batch_row(self, rng_np):
        """The masked block index map ((i*g)//h) must fetch each batch
        row's OWN mask — a cross-batch mixup would silently reuse row 0's
        mask. Distinct per-row masks pin it."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.kernels.pallas_shortseq import \
            short_attention
        rng = np.random.default_rng(3)
        b, t, h, d = 3, 128, 4, 8
        q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
        km = np.ones((b, t), np.float32)
        km[0, 40:] = 0
        km[1, 80:] = 0                        # row 2 unmasked
        got = short_attention(q, q, q, key_mask=jnp.asarray(km),
                              g_heads=2, interpret=True)
        want = self._ref(q, q, q, key_mask=jnp.asarray(km))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_declared_mesh_runs_the_kernels_per_device_block(self, rng_np):
        """Mosaic refuses automatic partitioning on a real multi-chip
        host, so under a declared mesh (nn.helpers.attention_spmd) the
        kernels run inside a shard_map over it — per (batch, head) block,
        the LOCAL block resolving its own heads-per-step — and outputs and
        gradients equal the single-device ones, for the short-T and the
        flash kernel, masked and unmasked. A batch the data axis does not
        divide is repeated, not split."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.kernels.pallas_attention import \
            pallas_flash_attention
        from deeplearning4j_tpu.kernels.pallas_shortseq import \
            short_attention
        from deeplearning4j_tpu.nn.helpers import (attention_spmd,
                                                   attention_spmd_context)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "tp"))
        qkv_sh = NamedSharding(mesh, P("data", None, "tp", None))
        kernels = {
            "short": lambda q, k, v, m: short_attention(
                q, k, v, causal=True, key_mask=m, interpret=True),
            "flash": lambda q, k, v, m: pallas_flash_attention(
                q, k, v, causal=True, q_block=64, k_block=64,
                key_mask=m, interpret=True)}
        for b in (4, 3):                  # 3: the data axis does not divide
            q, k, v, km = self._data(rng_np, b=b, t=128, h=4, d=8)
            for name, kern in kernels.items():
                for mask in (None, km):
                    def loss(q, k, v):
                        out = kern(q, k, v, mask)
                        return jnp.sum(out ** 2), out
                    step = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True)

                    def declared(q, k, v):
                        with attention_spmd(mesh, "data", "tp"):
                            return step(q, k, v)
                    (_, want), gwant = jax.jit(step)(q, k, v)
                    sharded = jax.jit(
                        declared,
                        in_shardings=(qkv_sh,) * 3 if b == 4 else None)
                    assert "shard_map" in str(jax.make_jaxpr(declared)(
                        q, k, v)), name
                    (_, got), ggot = sharded(q, k, v)
                    if b == 4:
                        assert got.addressable_shards[0].data.shape == \
                            (2, 128, 2, 8), (name, got.sharding)
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(want),
                        rtol=1e-5, atol=1e-5)
                    for a, b_ in zip(ggot, gwant):
                        np.testing.assert_allclose(
                            np.asarray(a), np.asarray(b_),
                            rtol=1e-4, atol=1e-5)
        assert attention_spmd_context() is None      # nothing leaks
