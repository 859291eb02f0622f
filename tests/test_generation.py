"""KV-cache autoregressive decoding + continuous-batching serving path
(models/generation.py) — decode-vs-teacher-forced logits parity is the
correctness contract (the CuDNN-vs-builtin equivalence pattern of
SURVEY.md §4 applied to the decode path), slot refill the serving
behaviour under test."""

import threading

import numpy as np
import pytest

from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       generate as nocache_generate,
                                       lm_batch, transformer_lm_conf)
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


def _softmax(logits):
    e = np.exp(logits - logits.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


class TestDecodeParity:
    """Per-position logits parity between the cached decode path and the
    teacher-forced full forward — prefill boundary, ragged lengths, and
    several decode steps deep."""

    def test_prefill_boundary_and_ragged_lengths(self, rng_np):
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, n) for n in (5, 9, 3)]
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        tokens = np.zeros((3, 16), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        _, logits, caches = dec.prefill(dec.init_cache(3), tokens, lengths)
        logits = np.asarray(logits)
        for i, p in enumerate(prompts):
            # ragged row vs the row alone through the teacher-forced net:
            # padding must be invisible
            want = np.asarray(net.output(p[None].astype(np.int32))[0])[0, -1]
            np.testing.assert_allclose(_softmax(logits[i]), want,
                                       rtol=1e-5, atol=1e-6)

    def test_decode_steps_match_teacher_forced(self, rng_np):
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, n) for n in (4, 7)]
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        tokens = np.zeros((2, 8), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        nxt, _, caches = dec.prefill(dec.init_cache(2), tokens, lengths)
        ids = np.asarray(nxt)
        seqs = [list(p) + [int(ids[i])] for i, p in enumerate(prompts)]
        pos = lengths.copy()
        for step in range(4):
            nxt, logits, caches = dec.decode_step(caches, ids, pos)
            logits = np.asarray(logits)
            for i in range(2):
                want = np.asarray(net.output(
                    np.asarray(seqs[i], np.int32)[None])[0])[0, -1]
                np.testing.assert_allclose(
                    _softmax(logits[i]), want, rtol=1e-5, atol=1e-6,
                    err_msg=f"step={step} row={i}")
            ids = np.asarray(nxt)
            for i in range(2):
                seqs[i].append(int(ids[i]))
            pos = pos + 1

    def test_greedy_generate_matches_nocache_reference(self, rng_np):
        """After training the cyclic language, cached greedy generation
        equals the no-cache models.generate AND continues the cycle."""
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(150):
            net.fit_batch(ds)
        dec = TransformerDecoder(net)
        out = dec.generate([[3]], 8, temperature=0.0)[0]
        np.testing.assert_array_equal(out, (3 + np.arange(9)) % 12)
        for p in ([3], [1, 2, 3], rng_np.integers(0, 12, 6)):
            want = nocache_generate(net, p, 7, temperature=0)
            np.testing.assert_array_equal(
                dec.generate([p], 7, temperature=0.0)[0], want)

    def test_sampling_determinism(self, rng_np):
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, 4), rng_np.integers(0, 12, 6)]
        a = dec.generate(prompts, 10, temperature=1.0, seed=11)
        b = dec.generate(prompts, 10, temperature=1.0, seed=11)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        c = dec.generate(prompts, 10, temperature=1.0, seed=12)
        assert any(not np.array_equal(x, y) for x, y in zip(a, c))

    def test_eos_and_context_stops(self, rng_np):
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(150):
            net.fit_batch(ds)
        dec = TransformerDecoder(net)
        # greedy from [3] emits 4,5,6,...; eos=6 stops after the 6
        out = dec.generate([[3]], 10, temperature=0.0, eos_id=6)[0]
        np.testing.assert_array_equal(out, [3, 4, 5, 6])
        # a small t_max caps the context mid-generation
        dec_small = TransformerDecoder(net, t_max=6)
        out = dec_small.generate([[3, 4]], 100, temperature=0.0)[0]
        assert len(out) == 6

    def test_recompute_baseline_matches_decode(self, rng_np):
        """The no-cache A/B baseline program computes the same logits the
        cached path does (it had better — the bench compares their
        speed, not their answers)."""
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        tokens = rng_np.integers(0, 12, (2, 8)).astype(np.int32)
        lengths = np.asarray([8, 5], np.int32)
        _, logits_c, _ = dec.prefill(dec.init_cache(2), tokens, lengths)
        _, logits_r = dec.recompute_logits(tokens, lengths)
        np.testing.assert_allclose(np.asarray(logits_c),
                                   np.asarray(logits_r),
                                   rtol=1e-5, atol=1e-6)

    def test_rejects_non_decoder_graphs(self):
        from deeplearning4j_tpu.nn import (InputType,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        g = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
             .updater("sgd").weight_init("xavier").graph_builder()
             .add_inputs("in"))
        g.add_layer("d", DenseLayer(n_in=4, n_out=4), "in")
        g.add_layer("out", OutputLayer(n_in=4, n_out=2, loss="mcxent",
                                       activation="softmax"), "d")
        g.set_outputs("out")
        net = ComputationGraph(g.build()).init()
        with pytest.raises(ValueError, match="decoder"):
            TransformerDecoder(net)



#: (d_model, heads) -> g, the heads sharing one 128-lane row of the slab
PACKED_CASES = [
    pytest.param(128, 2, 2, id="dh64-even-heads-g2"),
    pytest.param(128, 4, 4, id="dh32-g4"),
    pytest.param(256, 2, 1, id="dh128-g1"),
    pytest.param(192, 3, 1, id="dh64-odd-heads-g1"),
]


def _padded(prompts, width):
    tokens = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    return tokens, np.asarray([len(p) for p in prompts], np.int32)


class TestPackedSlab:
    """The slab cache is [B, H/g, T_max, g*Dh]: g = 128 // Dh heads side
    by side in one row where that fills the row and the head count
    allows, else 1 (the old [B, H, T_max, Dh]). Whatever g, every slab
    program stays token- and logit-identical to the no-cache
    references."""

    @pytest.mark.parametrize("d_model,heads,g", PACKED_CASES)
    def test_layout_and_parity_against_nocache(self, d_model, heads, g):
        net = _tiny_lm(d_model=d_model, num_heads=heads)
        dec = TransformerDecoder(net)
        hs = d_model // heads
        layer = net.conf.vertices[dec.attn_names[0]].layer
        assert layer.heads_per_row() == g
        assert dec.kv_heads_per_row == g
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 12, n) for n in (5, 9, 3)]
        refs = [nocache_generate(net, p, 10, temperature=0)
                for p in prompts]
        caches = dec.init_cache(3)
        for c in caches.values():
            assert c["k"].shape == c["v"].shape == \
                (3, heads // g, 32, g * hs)
        # prefill: first token and last-position logits
        tokens, lengths = _padded(prompts, 16)
        nxt, logits, caches = dec.prefill(caches, tokens, lengths)
        _, want = dec.recompute_logits(tokens, lengths)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(nxt), [r[len(p)] for r, p in zip(refs, prompts)])
        # one fused decode block of 4
        toks, ids, pos, stop, caches = dec.decode_block(
            caches, np.asarray(nxt), lengths, block_size=4)
        # the tokens; the slab attention's counters follow them
        toks = dec.split_block(np.asarray(toks))[0]
        for i, (r, p) in enumerate(zip(refs, prompts)):
            np.testing.assert_array_equal(
                toks[i], r[len(p) + 1:len(p) + 5])
        # its logits, one step on, against the full forward
        seqs = [r[:len(p) + 5] for r, p in zip(refs, prompts)]
        _, step_logits, _ = dec.decode_step(
            jax_copy(caches), np.asarray(ids), np.asarray(pos))
        _, want = dec.recompute_logits(*_padded(seqs, 16))
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(want), rtol=1e-5, atol=1e-6)
        # a verify block over the true continuation accepts all of it
        # and adds the model's own next token
        draft = np.stack([r[len(s):len(s) + 3]
                          for r, s in zip(refs, seqs)])
        out, _, vpos, _, caches = dec.verify_block(
            caches, np.asarray(ids), np.asarray(pos), draft)
        out = np.asarray(out)
        for i, (r, s) in enumerate(zip(refs, seqs)):
            assert out[i, 4] == 4                       # emitted count
            np.testing.assert_array_equal(out[i, :4],
                                          r[len(s):len(s) + 4])
        np.testing.assert_array_equal(np.asarray(vpos),
                                      np.asarray(pos) + 4)

    @pytest.mark.parametrize("d_model,heads,g", PACKED_CASES)
    def test_engine_chunked_and_speculative_match_nocache(self, d_model,
                                                          heads, g):
        """prefill_slots, prefill_chunk, decode and verify blocks through
        the engine, on prompts long enough to be chunked."""
        net = _tiny_lm(d_model=d_model, num_heads=heads)
        dec = TransformerDecoder(net)
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 12, n) for n in (13, 4, 19, 6)]
        prompts.append((3 + np.arange(13)) % 4)      # draftable
        refs = [nocache_generate(net, p, 7, temperature=0)
                for p in prompts]
        for kw in ({"prefill_chunk": 8},
                   {"speculative": True, "spec_k": 4}):
            eng = SlotGenerationEngine(net, num_slots=2, decoder=dec,
                                       block_size=4, **kw)
            reqs = [eng.submit(p, 7) for p in prompts]
            eng.run_until_drained()
            for r, want in zip(reqs, refs):
                np.testing.assert_array_equal(r.result(5), want,
                                              err_msg=str(kw))
            st = eng.stats()
            assert st["kv_heads_per_row"] == g
            if "prefill_chunk" in kw:
                assert st["prefill_chunks"] > 0
            else:
                assert st["spec_blocks"] > 0

    def test_scrub_and_corrupt_address_one_slot_one_position(self):
        import jax.numpy as jnp
        net = _tiny_lm(d_model=128, num_heads=2)        # g = 2
        dec = TransformerDecoder(net)
        rng = np.random.default_rng(5)
        tokens = rng.integers(0, 12, (3, 8)).astype(np.int32)
        _, _, caches = dec.prefill(dec.init_cache(3), tokens,
                                   np.full(3, 8, np.int32))
        before = {n: {kk: np.asarray(c[kk]) for kk in c}
                  for n, c in caches.items()}
        assert all(np.abs(b[kk][:, :, :8]).min() > 0
                   for b in before.values() for kk in b)
        caches = dec.corrupt_cache(caches, 1, 3, "nan")
        for n, c in caches.items():
            for kk in ("k", "v"):
                got = np.asarray(c[kk])
                assert got.shape == (3, 1, 32, 128)
                bad = np.isnan(got)
                assert bad[1, :, 3, :].all()        # both heads' lanes
                bad[1, :, 3, :] = False
                assert not bad.any()
                keep = np.ones(got.shape, bool)
                keep[1, :, 3, :] = False
                np.testing.assert_array_equal(got[keep],
                                              before[n][kk][keep])
        caches = dec._fn("scrub_slot")(caches, jnp.asarray([1, 1]))
        for n, c in caches.items():
            for kk in ("k", "v"):
                got = np.asarray(c[kk])
                assert (got[1] == 0).all()
                np.testing.assert_array_equal(got[[0, 2]],
                                              before[n][kk][[0, 2]])

    def test_program_peak_is_recorded_with_the_compile(self):
        """The decoder keeps the compiled decode block's own peak (its
        ``memory_analysis``) beside the signature it first compiled for:
        nothing before a dispatch, at least arguments it holds after."""
        net = _tiny_lm(d_model=128, num_heads=2)
        dec = TransformerDecoder(net)
        assert dec.program_peak_bytes("decode_block4_impl") is None
        caches = dec.init_cache(2)
        held = sum(int(c[kk].nbytes) for c in caches.values() for kk in c)
        dec.decode_block(caches, np.zeros(2, np.int32),
                         np.zeros(2, np.int32), block_size=4)
        peak = dec.program_peak_bytes("decode_block4_impl")
        assert peak is not None and peak >= held
        assert dec.program_peak_bytes("decode_block4_impl") == peak

    @pytest.mark.parametrize("d_model,heads,g", PACKED_CASES)
    def test_cache_bytes_do_not_depend_on_packing(self, d_model, heads, g):
        from deeplearning4j_tpu.observability import (DeviceStats,
                                                      MetricsRegistry)
        from deeplearning4j_tpu.observability.devstats import \
            kv_cache_stats
        net = _tiny_lm(d_model=d_model, num_heads=heads)
        reg = MetricsRegistry()
        eng = SlotGenerationEngine(net, num_slots=2, registry=reg)
        st = kv_cache_stats(eng)
        # layers x (k, v) x slots x H x T_max x Dh x float32
        assert st["bytes"] == 2 * 2 * 2 * 32 * d_model * 4
        assert st["heads_per_row"] == g
        assert st["slot_shape"] == [2, heads // g, 32,
                                    g * (d_model // heads)]
        DeviceStats(registry=reg).attach_engine("gen", eng)
        snap = reg.snapshot()
        assert snap["devstats_kv_cache_bytes"]["values"]["engine=gen"] \
            == st["bytes"]
        assert snap["devstats_kv_heads_per_row"]["values"]["engine=gen"] \
            == g


def jax_copy(tree):
    """A copy of a cache tree the callee may donate."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)

class TestBlockDecode:
    """Fused K-step decode blocks + the pipelined double-buffered loop
    (decode hot-loop pipelining): token-for-token parity across block
    sizes is the contract — the block path may only change WHEN tokens
    cross to the host, never WHICH tokens."""

    def _trained(self, rng_np):
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(150):
            net.fit_batch(ds)
        return net

    def test_greedy_parity_across_block_sizes(self, rng_np):
        """Ragged prompts, rows stopping at different depths: K=4 and
        K=8 emit exactly the K=1 token stream (overshoot truncated)."""
        net = self._trained(rng_np)
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, n) for n in (3, 7, 5, 2)]
        ref = dec.generate(prompts, 10, temperature=0.0, block_size=1)
        for k in (4, 8):
            out = dec.generate(prompts, 10, temperature=0.0, block_size=k)
            for a, b in zip(ref, out):
                np.testing.assert_array_equal(a, b, err_msg=f"K={k}")

    def test_eos_mid_block_truncates_overshoot(self, rng_np):
        """A row hitting eos inside a block is frozen on device and its
        overshoot tokens dropped on host: greedy from [3] on the cyclic
        language stops at 6 regardless of block size."""
        net = self._trained(rng_np)
        dec = TransformerDecoder(net)
        for k in (1, 4, 8):
            out = dec.generate([[3]], 10, temperature=0.0, eos_id=6,
                               block_size=k)[0]
            np.testing.assert_array_equal(out, [3, 4, 5, 6],
                                          err_msg=f"K={k}")

    def test_context_stop_mid_block(self, rng_np):
        """t_max landing inside a block: the lane freezes at the context
        edge and the host truncates at exactly t_max tokens."""
        net = self._trained(rng_np)
        dec = TransformerDecoder(net, t_max=6)
        for k in (1, 4):
            out = dec.generate([[3, 4]], 100, temperature=0.0,
                               block_size=k)[0]
            assert len(out) == 6, f"K={k}"

    def test_sampling_determinism_across_block_sizes(self, rng_np):
        """The key schedule folds the ABSOLUTE step index, so a fixed
        seed draws the same tokens for every block size."""
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, 4), rng_np.integers(0, 12, 6)]
        ref = dec.generate(prompts, 10, temperature=1.0, seed=11,
                           block_size=1)
        for k in (4, 8):
            out = dec.generate(prompts, 10, temperature=1.0, seed=11,
                               block_size=k)
            for a, b in zip(ref, out):
                np.testing.assert_array_equal(a, b, err_msg=f"K={k}")
        other = dec.generate(prompts, 10, temperature=1.0, seed=12,
                             block_size=4)
        assert any(not np.array_equal(a, c) for a, c in zip(ref, other))

    def test_one_readback_per_block(self, rng_np):
        """The pipelined loop performs at most ONE host readback per
        dispatched block (+ the prefill token read)."""
        from deeplearning4j_tpu.analysis import TransferAudit
        net = _tiny_lm()
        dec = TransformerDecoder(net)
        prompts = [rng_np.integers(0, 12, 4) for _ in range(3)]
        with TransferAudit() as transfers:
            dec.generate(prompts, 9, temperature=0.0, block_size=4)
        # 9 tokens = 1 prefill token + ceil(8/4) = 2 blocks
        assert transfers.fetches("generate.prefill") == 1
        assert transfers.fetches("generate.decode") <= 2
        transfers.check_per_block("generate.decode", 2)

    def test_engine_block_mixed_stream_matches_reference(self, rng_np):
        """Continuous batching at block_size=4: mid-stream refills land
        at block boundaries, results still match the no-cache reference
        token-for-token, and the loop reads back at most once per
        dispatched block (prefills batched: one readback per batch)."""
        from deeplearning4j_tpu.analysis import TransferAudit
        net = self._trained(rng_np)
        eng = SlotGenerationEngine(net, num_slots=2, block_size=4)
        prompts = [rng_np.integers(0, 12, n) for n in (3, 6, 2, 5, 4)]
        gens = [4, 7, 3, 6, 5]
        with TransferAudit() as transfers:
            reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
            eng.run_until_drained()
        for p, g, r in zip(prompts, gens, reqs):
            want = nocache_generate(net, p, g, temperature=0)
            np.testing.assert_array_equal(r.result(5), want)
        stats = eng.stats()
        assert stats["completed"] == 5 and stats["prefills"] == 5
        assert stats["decode_steps"] == 4 * stats["decode_blocks"]
        transfers.check_per_block("engine.decode", stats["decode_blocks"])
        transfers.check_per_block("engine.prefill",
                                  stats["prefill_batches"])
        assert stats["host_readbacks"] == \
            transfers.fetches("engine.decode") + \
            transfers.fetches("engine.prefill")

    def test_engine_block_deadline_and_cancel_inside_block(self, rng_np):
        """A deadline expiring / cancel arriving while a block is in
        flight frees the slot at the next boundary; the lane's in-flight
        tokens are dropped and other requests keep decoding."""
        from deeplearning4j_tpu.parallel.faults import (Cancelled,
                                                        DeadlineExceeded,
                                                        FaultInjector)
        net = _tiny_lm()
        inj = FaultInjector()
        inj.hang_for("engine.step", seconds=0.4, at=2)
        eng = SlotGenerationEngine(net, num_slots=3, block_size=4,
                                   fault_injector=inj).start()
        try:
            doomed = eng.submit([1, 2], 24, deadline=0.15)
            victim = eng.submit([2, 3], 24)
            ok = eng.submit([3, 4], 6)
            victim.cancel()
            with pytest.raises(DeadlineExceeded):
                doomed.result(30)
            with pytest.raises(Cancelled):
                victim.result(30)
            assert len(ok.result(30)) == 8
        finally:
            eng.shutdown()

    def test_engine_block_via_parallel_inference_and_route(self, rng_np):
        """block_size threads through the serving facades."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        from deeplearning4j_tpu.streaming.pubsub import (MessageBroker,
                                                         NDArrayPublisher,
                                                         NDArraySubscriber)
        from deeplearning4j_tpu.streaming.serving import \
            GenerationServingRoute
        net = self._trained(rng_np)
        pi = ParallelInference(net, generation_slots=2,
                               generation_block_size=4)
        try:
            p = rng_np.integers(0, 12, 3)
            want = nocache_generate(net, p, 6, temperature=0)
            np.testing.assert_array_equal(pi.generate(p, 6, timeout=60),
                                          want)
            assert pi._gen_engine.block_size == 4
        finally:
            pi.shutdown()
        broker = MessageBroker()
        out_sub = NDArraySubscriber(broker, "dl4j-gen-output")
        route = GenerationServingRoute(net, broker, max_new_tokens=5,
                                       num_slots=2, block_size=4).start()
        try:
            assert route.engine.block_size == 4
            pub = NDArrayPublisher(broker, "dl4j-gen-input")
            p2 = rng_np.integers(0, 12, 4)
            pub.publish(np.asarray(p2, np.int32))
            out = out_sub.poll(timeout=60)
            want = nocache_generate(net, p2, 5, temperature=0)
            np.testing.assert_array_equal(np.asarray(out, np.int64), want)
        finally:
            route.stop()

    def test_supervisor_restart_preserves_block_size(self, rng_np):
        """Crash recovery rebuilds the engine with the SAME block size
        (and the same jitted decode_block program via the shared
        decoder) and still resumes token-for-token."""
        from deeplearning4j_tpu.parallel.failures import EngineSupervisor
        from deeplearning4j_tpu.parallel.faults import FaultInjector
        net = self._trained(rng_np)
        inj = FaultInjector()
        inj.raise_once("engine.step", RuntimeError("injected crash"), at=2)
        eng = SlotGenerationEngine(net, num_slots=2, block_size=4,
                                   fault_injector=inj)
        sup = EngineSupervisor(eng, timeout=10.0, interval=0.1,
                               max_restarts=2).start()
        try:
            prompts = [rng_np.integers(0, 12, n) for n in (3, 5, 4)]
            reqs = [sup.submit(p, 6) for p in prompts]
            outs = [r.result(60) for r in reqs]
            for p, o in zip(prompts, outs):
                want = nocache_generate(net, p, 6, temperature=0)
                np.testing.assert_array_equal(o, want)
            assert sup.restarts == 1
            assert sup.engine.block_size == 4
            # a layout label, not a counter: a takeover does not add it up
            assert sup.stats()["kv_heads_per_row"] == 1
        finally:
            sup.stop()


class TestSlotEngine:
    """Slot-based continuous batching: correctness per request, mid-loop
    refill, and the refill-on-beats-off step count."""

    def _trained(self, rng_np):
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(100):
            net.fit_batch(ds)
        return net

    def test_mixed_stream_results_match_reference(self, rng_np):
        net = self._trained(rng_np)
        eng = SlotGenerationEngine(net, num_slots=2)
        prompts = [rng_np.integers(0, 12, n) for n in (3, 6, 2, 5, 4)]
        gens = [4, 7, 3, 6, 5]
        reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        eng.run_until_drained()
        for p, g, r in zip(prompts, gens, reqs):
            want = nocache_generate(net, p, g, temperature=0)
            np.testing.assert_array_equal(r.result(5), want)
        assert eng.completed == 5
        assert eng.prefills == 5              # every request got a slot

    def test_refill_uses_fewer_steps_than_waves(self, rng_np):
        """Mixed lengths: with refill ON a freed slot serves the queue
        mid-loop, so the same request stream needs strictly fewer batched
        decode steps than static waves (the deterministic core of the
        emitted-tok/s A/B)."""
        net = self._trained(rng_np)
        prompts = [rng_np.integers(0, 12, 3) for _ in range(4)]
        gens = [2, 12, 12, 2]

        def run(refill):
            eng = SlotGenerationEngine(net, num_slots=2, refill=refill)
            reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
            eng.run_until_drained()
            outs = [r.result(5) for r in reqs]
            return eng.decode_steps, outs

        steps_on, outs_on = run(True)
        steps_off, outs_off = run(False)
        for a, b in zip(outs_on, outs_off):
            np.testing.assert_array_equal(a, b)   # same answers either way
        assert steps_on < steps_off, (steps_on, steps_off)

    def test_bad_requests_fail_without_killing_engine(self, rng_np):
        net = _tiny_lm()
        eng = SlotGenerationEngine(net, num_slots=2)
        bad_empty = eng.submit([], 4)
        bad_long = eng.submit(np.zeros(40, np.int32), 4)   # > t_max=32
        ok = eng.submit([1, 2], 3)
        eng.run_until_drained()
        with pytest.raises(ValueError):
            bad_empty.result(1)
        with pytest.raises(ValueError):
            bad_long.result(1)
        assert len(ok.result(5)) == 5

    def test_background_serving_thread(self, rng_np):
        net = self._trained(rng_np)
        eng = SlotGenerationEngine(net, num_slots=2).start()
        try:
            reqs = [eng.submit(rng_np.integers(0, 12, 3), 5)
                    for _ in range(3)]
            outs = [r.result(30) for r in reqs]
            for r, o in zip(reqs, outs):
                want = nocache_generate(net, r.prompt, 5, temperature=0)
                np.testing.assert_array_equal(o, want)
        finally:
            eng.shutdown()


class TestParallelInferenceGenerate:
    def test_concurrent_callers_coalesce(self, rng_np):
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(100):
            net.fit_batch(ds)
        pi = ParallelInference(net, generation_slots=2)
        prompts = [rng_np.integers(0, 12, n) for n in (3, 5, 4, 2)]
        results = [None] * len(prompts)

        def call(i):
            results[i] = pi.generate(prompts[i], 6, timeout=60)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        try:
            for i, p in enumerate(prompts):
                want = nocache_generate(net, p, 6, temperature=0)
                np.testing.assert_array_equal(results[i], want)
        finally:
            pi.shutdown()


class TestGenerationServingRoute:
    def test_route_over_memory_broker(self, rng_np):
        from deeplearning4j_tpu.streaming.pubsub import (MessageBroker,
                                                         NDArrayPublisher,
                                                         NDArraySubscriber)
        from deeplearning4j_tpu.streaming.serving import \
            GenerationServingRoute
        net = _tiny_lm()
        ds = _cyclic_batch(rng_np)
        for _ in range(100):
            net.fit_batch(ds)
        broker = MessageBroker()
        out_sub = NDArraySubscriber(broker, "dl4j-gen-output")
        route = GenerationServingRoute(net, broker, max_new_tokens=5,
                                       num_slots=2).start()
        try:
            pub = NDArrayPublisher(broker, "dl4j-gen-input")
            prompts = [rng_np.integers(0, 12, n) for n in (3, 5, 2)]
            for p in prompts:
                pub.publish(np.asarray(p, np.int32))
            outs = [out_sub.poll(timeout=60) for _ in prompts]
            assert all(o is not None for o in outs)
            # submission order preserved
            for p, o in zip(prompts, outs):
                want = nocache_generate(net, p, 5, temperature=0)
                np.testing.assert_array_equal(np.asarray(o, np.int64), want)
            assert route.served == 3 and route.errors == 0
        finally:
            route.stop()
