"""Mesh-sharded generation (ROADMAP 1 / r12): tensor/FSDP-parallel
decode over named (data, tp) meshes must preserve EVERY r6–r9 invariant
— token-for-token outputs across mesh shapes (greedy AND fixed-seed
sampled, at every fused-block size), zero steady-state compiles, ≤1
host readback per decode block — plus the new surface: clear mesh
validation errors, SpecLayout rank/divisibility checks, mesh threading
through engine/supervisor/facades, and topology telemetry.

Runs on the conftest-forced 8-virtual-CPU-device platform, so the
shapes {1x1, 2x1, 1x2, 4x1, 2x2} exercise real multi-device GSPMD
without hardware (and without a slow marker — this is tier-1)."""

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileAudit, TransferAudit
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       generate as nocache_generate,
                                       lm_batch, transformer_lm_conf)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops.dataset import DataSet
from deeplearning4j_tpu.parallel.mesh import (generation_mesh, make_mesh,
                                              mesh_tag, parse_mesh_shape,
                                              validate_decode_mesh)
from deeplearning4j_tpu.parallel.spec_layout import (SpecLayout,
                                                     decoder_param_specs,
                                                     validate_param_specs)

#: every shape from the acceptance bar that fits the 8 forced devices
MESH_SHAPES = [(1, 1), (2, 1), (1, 2), (4, 1), (2, 2)]
BLOCK_SIZES = [1, 4, 8]


def _tiny_lm(vocab=12, **kw):
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 2)
    kw.setdefault("max_length", 32)
    kw.setdefault("learning_rate", 1e-2)
    kw.setdefault("seed", 5)
    return ComputationGraph(transformer_lm_conf(vocab, **kw)).init()


@pytest.fixture(scope="module")
def trained_net():
    """One trained tiny LM for the whole module: the parity suites
    compare MANY (mesh, K) points against one reference — retraining
    per test would dominate tier-1 time."""
    rng = np.random.default_rng(12345)
    net = _tiny_lm()
    starts = rng.integers(0, 12, (16, 1))
    seq = (starts + np.arange(17)[None, :]) % 12
    x, y = lm_batch(seq, 12)
    ds = DataSet(x, y)
    for _ in range(150):
        net.fit_batch(ds)
    return net


@pytest.fixture(scope="module")
def parity_prompts():
    rng = np.random.default_rng(777)
    return [rng.integers(0, 12, n) for n in (3, 7, 5, 2)]


class TestMeshValidation:
    """Satellite: make_mesh/validate_decode_mesh fail with CLEAR errors
    (device budget, axis arity, divisibility) instead of the opaque
    numpy reshape failure deep inside jax dispatch."""

    def test_shape_exceeding_devices_names_the_fix(self):
        with pytest.raises(ValueError) as e:
            make_mesh(axis_names=("data", "tp"), shape=(8, 2))
        msg = str(e.value)
        assert "needs 16 devices" in msg
        assert "jax.device_count()=8" in msg
        assert "xla_force_host_platform_device_count" in msg

    def test_n_devices_over_budget(self):
        with pytest.raises(ValueError, match="only 8 device"):
            make_mesh(n_devices=16)

    def test_multi_axis_without_shape(self):
        with pytest.raises(ValueError, match="pass shape"):
            make_mesh(axis_names=("data", "tp"))

    def test_shape_axis_arity_mismatch(self):
        with pytest.raises(ValueError, match="one size per named axis"):
            make_mesh(axis_names=("data", "tp"), shape=(4,))

    def test_zero_axis_size(self):
        with pytest.raises(ValueError, match=">= 1"):
            make_mesh(axis_names=("data", "tp"), shape=(0, 2))

    def test_heads_divisibility_message(self):
        mesh = generation_mesh(1, 4)
        with pytest.raises(ValueError) as e:
            validate_decode_mesh(mesh, num_heads=2)
        assert "num_heads 2" in str(e.value) and "'tp'" in str(e.value)

    def test_slots_divisibility_message(self):
        mesh = generation_mesh(4, 1)
        with pytest.raises(ValueError) as e:
            validate_decode_mesh(mesh, num_slots=3)
        assert "num_slots 3" in str(e.value) and "'data'" in str(e.value)

    def test_decoder_rejects_indivisible_heads(self, trained_net):
        with pytest.raises(ValueError, match="num_heads 2"):
            TransformerDecoder(trained_net, mesh=generation_mesh(1, 4))

    def test_engine_rejects_indivisible_slots(self, trained_net):
        with pytest.raises(ValueError, match="num_slots 3"):
            SlotGenerationEngine(trained_net, num_slots=3,
                                 mesh=generation_mesh(2, 1))

    def test_parse_mesh_shape_grammar(self):
        assert parse_mesh_shape("2x1") == (2, 1)
        assert parse_mesh_shape("1x2") == (1, 2)
        assert parse_mesh_shape("4") == (4, 1)
        with pytest.raises(ValueError, match="DATAxTP"):
            parse_mesh_shape("2x2x2")
        with pytest.raises(ValueError, match="integers"):
            parse_mesh_shape("axb")

    def test_mesh_tag(self):
        assert mesh_tag(None) == ""
        assert mesh_tag(generation_mesh(2, 1)) == "2x1"


class TestSpecLayoutValidation:
    """The name-based spec table is rank- and divisibility-checked
    against the decoder's ACTUAL params (the runtime counterpart of
    graftlint's static GL013 rank check)."""

    def test_role_table_is_valid_for_decoder(self, trained_net):
        dec = TransformerDecoder(trained_net)
        specs = decoder_param_specs(dec)
        validate_param_specs(generation_mesh(2, 2), specs,
                             trained_net.params)   # must not raise

    def test_overranked_spec_names_the_leaf(self, trained_net):
        from jax.sharding import PartitionSpec as P
        dec = TransformerDecoder(trained_net)
        specs = decoder_param_specs(dec)
        attn = dec.attn_names[0]
        specs[attn] = dict(specs[attn])
        specs[attn]["bo"] = P("data", "tp")        # rank-1 leaf, rank-2 spec
        with pytest.raises(ValueError) as e:
            validate_param_specs(generation_mesh(2, 2), specs,
                                 trained_net.params)
        msg = str(e.value)
        assert f"{attn}.bo" in msg and "rank" in msg

    def test_unknown_axis_names_the_mesh(self, trained_net):
        dec = TransformerDecoder(trained_net)
        specs = decoder_param_specs(dec, SpecLayout(tp_axis="model"))
        with pytest.raises(ValueError, match="absent from the mesh"):
            validate_param_specs(generation_mesh(2, 2), specs,
                                 trained_net.params)

    def test_indivisible_dim_is_reported(self, trained_net):
        from jax.sharding import PartitionSpec as P
        dec = TransformerDecoder(trained_net)
        specs = decoder_param_specs(dec)
        emb = [n for n in specs if "W" in specs[n] and "P" in specs[n]][0]
        specs[emb] = {"W": P("tp", None)}          # vocab 12 over tp=8?
        mesh = make_mesh(axis_names=("data", "tp"), shape=(1, 8))
        with pytest.raises(ValueError, match="not divisible"):
            validate_param_specs(mesh, specs, trained_net.params)

    def test_spec_for_missing_param(self, trained_net):
        from jax.sharding import PartitionSpec as P
        dec = TransformerDecoder(trained_net)
        specs = decoder_param_specs(dec)
        attn = dec.attn_names[0]
        specs[attn] = dict(specs[attn], Wz=P(None, "tp"))
        with pytest.raises(ValueError, match="does not have"):
            validate_param_specs(generation_mesh(1, 1), specs,
                                 trained_net.params)


class TestMeshParity:
    """THE acceptance gate: token-for-token identical generation across
    mesh shapes at K ∈ {1, 4, 8} — greedy and fixed-seed sampled — with
    zero steady-state compiles and ≤1 readback per decode block on
    every shape."""

    def test_token_parity_audited_across_shapes(self, trained_net,
                                                parity_prompts):
        prompts = parity_prompts
        ref_dec = TransformerDecoder(trained_net)
        ref_greedy = {k: ref_dec.generate(prompts, 10, temperature=0.0,
                                          block_size=k)
                      for k in BLOCK_SIZES}
        ref_sampled = {k: ref_dec.generate(prompts, 10, temperature=1.0,
                                           seed=11, block_size=k)
                       for k in BLOCK_SIZES}
        # the unsharded decoder is itself K-consistent (r9); every mesh
        # shape below must match ITS K=1 stream
        for k in BLOCK_SIZES[1:]:
            for a, b in zip(ref_greedy[1], ref_greedy[k]):
                np.testing.assert_array_equal(a, b)
        for data, tp in MESH_SHAPES:
            mesh = generation_mesh(data, tp)
            with CompileAudit() as audit, TransferAudit() as transfers:
                dec = TransformerDecoder(trained_net, mesh=mesh)
                for k in BLOCK_SIZES:     # warm every (mesh, K) program
                    dec.generate(prompts, 10, temperature=0.0,
                                 block_size=k)
                    dec.generate(prompts, 10, temperature=1.0, seed=11,
                                 block_size=k)
                snap = audit.snapshot()
                for k in BLOCK_SIZES:
                    out = dec.generate(prompts, 10, temperature=0.0,
                                       block_size=k)
                    for a, b in zip(ref_greedy[k], out):
                        np.testing.assert_array_equal(
                            a, b, err_msg=f"greedy mesh={data}x{tp} K={k}")
                    outs = dec.generate(prompts, 10, temperature=1.0,
                                        seed=11, block_size=k)
                    for a, b in zip(ref_sampled[k], outs):
                        np.testing.assert_array_equal(
                            a, b, err_msg=f"sampled mesh={data}x{tp} K={k}")
                # steady state compiled NOTHING new on this shape
                assert audit.delta(snap) == {}, f"mesh={data}x{tp}"
            # ≤1 readback per decode block on this shape: the K>1 runs
            # above dispatched exactly 2 runs × 2 temps × (⌈9/4⌉ + ⌈9/8⌉)
            # = 20 blocks (10 new tokens each; K=1 is the legacy
            # per-step loop and doesn't ride the block tag)
            assert transfers.fetches("generate.decode") > 0
            transfers.check_per_block("generate.decode", 20)

    def test_non_divisible_batch_pads_and_matches(self, trained_net,
                                                  parity_prompts):
        """3 prompts on a data=2 mesh: rows pad to the axis internally,
        outputs are identical to the unsharded run."""
        prompts = parity_prompts[:3]
        ref = TransformerDecoder(trained_net).generate(
            prompts, 8, temperature=0.0, block_size=4)
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 1))
        out = dec.generate(prompts, 8, temperature=0.0, block_size=4)
        assert len(out) == 3
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    def test_non_divisible_batch_per_row_temps(self, trained_net,
                                               parity_prompts):
        """Per-row temperatures on a ragged row count: the pad must
        extend temps alongside prompts (regression: broadcast_to the
        padded batch crashed on a length-3 temp vector)."""
        prompts = parity_prompts[:3]
        temps = [0.0, 0.7, 1.3]
        ref = TransformerDecoder(trained_net).generate(
            prompts, 8, temperature=temps, seed=11, block_size=4)
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 1))
        out = dec.generate(prompts, 8, temperature=temps, seed=11,
                           block_size=4)
        assert len(out) == 3
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    def test_fsdp_layout_parity(self, trained_net, parity_prompts):
        """fsdp_axis=data (parameters sharded over the batch axis, the
        2-axis-mesh FSDP trick) changes layouts, never tokens."""
        ref = TransformerDecoder(trained_net).generate(
            parity_prompts, 10, temperature=0.0, block_size=4)
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 2),
                                 spec_layout=SpecLayout(fsdp_axis="data"))
        out = dec.generate(parity_prompts, 10, temperature=0.0,
                           block_size=4)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    def test_prefill_boundary_logits_parity(self, trained_net,
                                            parity_prompts):
        """Sharded prefill logits at each row's last real position match
        the no-cache recompute program (ragged lengths — padding must
        stay invisible under sharding too)."""
        prompts = parity_prompts
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        tokens = np.zeros((len(prompts), 8), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 2))
        _, logits, _ = dec.prefill(dec.init_cache(len(prompts)), tokens,
                                   lengths)
        _, logits_r = dec.recompute_logits(tokens, lengths)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_r),
                                   rtol=1e-5, atol=1e-5)

    def test_cache_is_born_sharded(self, trained_net):
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 2))
        caches = dec.init_cache(4)
        for name, c in caches.items():
            assert len(c["k"].sharding.device_set) == 4, name
            spec = c["k"].sharding.spec
            assert tuple(spec)[:2] == ("data", "tp")

    @pytest.mark.parametrize("data,tp,g", [
        pytest.param(1, 4, 1, id="1x4-tp-does-not-divide-groups-g1"),
        pytest.param(1, 2, 2, id="1x2-g2"),
        pytest.param(2, 2, 2, id="2x2-g2"),
    ])
    def test_packed_slab_follows_the_tp_axis(self, data, tp, g):
        """4 heads of 64 pack 2 to a row: 2 head groups shard over tp=2
        and not over tp=4, where the slab falls back to one head a row
        (as 12 heads over tp=4 do: 6 groups). Either way the sharded
        engine serves what the no-cache reference generates."""
        net = _tiny_lm(d_model=256, num_heads=4)
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 12, n) for n in (13, 4, 7, 5)]
        refs = [nocache_generate(net, p, 7, temperature=0)
                for p in prompts]
        assert TransformerDecoder(net).kv_heads_per_row == 2
        dec = TransformerDecoder(net, mesh=generation_mesh(data, tp))
        assert dec.kv_heads_per_row == g
        for c in dec.init_cache(4).values():
            assert c["k"].shape == (4, 4 // g, 32, g * 64)
            assert tuple(c["k"].sharding.spec)[:2] == ("data", "tp")
            assert len(c["k"].sharding.device_set) == data * tp
        eng = SlotGenerationEngine(net, num_slots=4, decoder=dec,
                                   block_size=4, prefill_chunk=8)
        reqs = [eng.submit(p, 7) for p in prompts]
        eng.run_until_drained()
        for r, want in zip(reqs, refs):
            np.testing.assert_array_equal(r.result(5), want)
        assert eng.stats()["kv_heads_per_row"] == g
        # the mesh-free batched path too
        for out, want in zip(dec.generate(prompts, 7, temperature=0.0,
                                          block_size=4), refs):
            np.testing.assert_array_equal(out, want)


class TestShardedEngine:
    """Continuous batching, supervision, and the facades on a mesh."""

    def test_mixed_stream_matches_reference_with_audits(self, trained_net):
        rng = np.random.default_rng(31)
        prompts = [rng.integers(0, 12, n) for n in (3, 6, 2, 5, 4)]
        gens = [4, 7, 3, 6, 5]
        mesh = generation_mesh(2, 2)
        with CompileAudit() as audit, TransferAudit() as transfers:
            dec = TransformerDecoder(trained_net, mesh=mesh)
            eng = SlotGenerationEngine(trained_net, num_slots=2,
                                       block_size=4, decoder=dec)
            reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
            eng.run_until_drained()
            for p, g, r in zip(prompts, gens, reqs):
                want = nocache_generate(trained_net, p, g, temperature=0)
                np.testing.assert_array_equal(r.result(5), want)
            # a second engine over the SAME sharded decoder re-lowers
            # nothing: steady serving state is compile-free
            snap = audit.snapshot()
            eng2 = SlotGenerationEngine(trained_net, num_slots=2,
                                        block_size=4, decoder=dec)
            reqs2 = [eng2.submit(p, g) for p, g in zip(prompts, gens)]
            eng2.run_until_drained()
            assert audit.delta(snap) == {}
            blocks = eng.stats()["decode_blocks"] + \
                eng2.stats()["decode_blocks"]
        transfers.check_per_block("engine.decode", blocks)
        transfers.check_per_block(
            "engine.prefill", eng.stats()["prefill_batches"] +
            eng2.stats()["prefill_batches"])
        # attribution through the pjit seam: the one readback gathered
        # from every device of the 2x2 mesh
        assert transfers.shards("engine.decode") == 4
        # per-mesh compile attribution: the sharded decoder's programs
        # audit under suffixed names, so meshes never collide
        assert any(n.endswith("__m2x2") for n in audit.counts)

    def test_supervisor_restart_on_sharded_engine(self, trained_net):
        from deeplearning4j_tpu.parallel.failures import EngineSupervisor
        from deeplearning4j_tpu.parallel.faults import FaultInjector
        rng = np.random.default_rng(32)
        prompts = [rng.integers(0, 12, n) for n in (3, 5, 4)]
        mesh = generation_mesh(2, 1)
        dec = TransformerDecoder(trained_net, mesh=mesh)
        # clean warm run compiles everything the chaos run needs
        warm = SlotGenerationEngine(trained_net, num_slots=2,
                                    block_size=4, decoder=dec)
        for p in prompts:
            warm.submit(p, 6)
        warm.run_until_drained()
        wants = [nocache_generate(trained_net, p, 6, temperature=0)
                 for p in prompts]
        inj = FaultInjector()
        inj.raise_once("engine.step", RuntimeError("injected crash"), at=2)
        eng = SlotGenerationEngine(trained_net, num_slots=2, block_size=4,
                                   decoder=dec, fault_injector=inj)
        sup = EngineSupervisor(eng, timeout=10.0, interval=0.1,
                               max_restarts=2)
        with CompileAudit() as audit:
            sup.start()
            reqs = [sup.submit(p, 6) for p in prompts]
            outs = [r.result(60) for r in reqs]
            for want, o in zip(wants, outs):
                np.testing.assert_array_equal(o, want)
            assert sup.restarts == 1
            # the replacement engine shares the sharded decoder: the
            # whole supervised run — crash, takeover, recovery
            # re-prefill, drain — lowered NOTHING (the clean warm run
            # above compiled every program it needs)
            assert {n for n in audit.counts
                    if not audit._ignored(n)} == set(), dict(audit.counts)
            stats = sup.stats()
            assert stats["mesh_shape"] == "2x1"
        sup.stop()

    def test_mesh_threads_through_facades(self, trained_net):
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        from deeplearning4j_tpu.streaming.pubsub import (MessageBroker,
                                                         NDArrayPublisher,
                                                         NDArraySubscriber)
        from deeplearning4j_tpu.streaming.serving import \
            GenerationServingRoute
        rng = np.random.default_rng(33)
        mesh = generation_mesh(2, 1)
        pi = ParallelInference(trained_net, generation_slots=2,
                               generation_block_size=4,
                               generation_mesh=mesh)
        try:
            p = rng.integers(0, 12, 3)
            want = nocache_generate(trained_net, p, 6, temperature=0)
            np.testing.assert_array_equal(pi.generate(p, 6, timeout=60),
                                          want)
            assert pi._gen_engine.mesh is mesh
        finally:
            pi.shutdown()
        broker = MessageBroker()
        out_sub = NDArraySubscriber(broker, "dl4j-gen-output")
        route = GenerationServingRoute(trained_net, broker,
                                       max_new_tokens=5, num_slots=2,
                                       block_size=4, mesh=mesh).start()
        try:
            assert route.engine.mesh is mesh
            pub = NDArrayPublisher(broker, "dl4j-gen-input")
            p2 = rng.integers(0, 12, 4)
            pub.publish(np.asarray(p2, np.int32))
            out = out_sub.poll(timeout=60)
            want = nocache_generate(trained_net, p2, 5, temperature=0)
            np.testing.assert_array_equal(np.asarray(out, np.int64), want)
        finally:
            route.stop()

    def test_shared_decoder_mesh_conflict_rejected(self, trained_net):
        dec = TransformerDecoder(trained_net, mesh=generation_mesh(2, 1))
        with pytest.raises(ValueError, match="different mesh"):
            SlotGenerationEngine(trained_net, num_slots=2, decoder=dec,
                                 mesh=generation_mesh(1, 2))

    def test_route_prebuilt_engine_mesh_conflict_rejected(self,
                                                          trained_net):
        """mesh= alongside a prebuilt engine must never be silently
        ignored — the caller would believe decode is sharded when the
        engine serves single-device."""
        from deeplearning4j_tpu.streaming.pubsub import MessageBroker
        from deeplearning4j_tpu.streaming.serving import \
            GenerationServingRoute
        eng = SlotGenerationEngine(trained_net, num_slots=2)
        with pytest.raises(ValueError, match="different mesh"):
            GenerationServingRoute(trained_net, MessageBroker(),
                                   engine=eng,
                                   mesh=generation_mesh(2, 1))
        # same mesh OBJECT through the engine is fine
        mesh = generation_mesh(2, 1)
        eng2 = SlotGenerationEngine(trained_net, num_slots=2, mesh=mesh)
        route = GenerationServingRoute(trained_net, MessageBroker(),
                                       engine=eng2, mesh=mesh)
        assert route.engine.mesh is mesh

    def test_topology_telemetry(self, trained_net):
        from deeplearning4j_tpu.observability.metrics import MetricsRegistry
        reg = MetricsRegistry()
        mesh = generation_mesh(4, 2)
        eng = SlotGenerationEngine(trained_net, num_slots=4, mesh=mesh,
                                   registry=reg)
        stats = eng.stats()
        assert stats["mesh_shape"] == "4x2"
        fam = reg.gauge("generation_mesh_axis_size",
                        "serving-mesh axis size (data/tp)",
                        ("engine", "axis"))
        assert fam.labels(eng.engine_id, "data").value == 4
        assert fam.labels(eng.engine_id, "tp").value == 2
        assert "generation_mesh_axis_size" in str(reg.snapshot())
        # unsharded engines report no mesh and no axis gauges
        eng2 = SlotGenerationEngine(trained_net, num_slots=2,
                                    registry=MetricsRegistry())
        assert eng2.stats()["mesh_shape"] is None
