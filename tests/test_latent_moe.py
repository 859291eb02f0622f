"""The latent-attention / routed-experts decoder LM on the normal path
(``latent_moe_lm_conf`` → ``ComputationGraph`` → ``TransformerDecoder`` →
``SlotGenerationEngine``), at a tiny size on the CPU: the cache is one row a
token, decode (absorbed) agrees with prefill and recompute (decompressed),
the engine is token-identical to ``generate`` with ``{}`` steady compiles
and one readback a block, its four expert counters are exact on hand-made
routing, the paged twins raise, the sentinel's scrub covers the latent
slab, and the routed-expert kernel agrees with the layer's dense path.
(The comparison with the plain reference is under ``tests/benchmark/``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileAudit, TransferAudit
from deeplearning4j_tpu.kernels import expert_ffn
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder, generate,
                                       latent_moe_lm_conf,
                                       transformer_lm_conf)
from deeplearning4j_tpu.models.generation import MOE_COUNTERS
from deeplearning4j_tpu.nn.conf.layers import (LatentAttentionLayer,
                                               RoutedExpertsLayer, Window)
from deeplearning4j_tpu.nn.conf.layers.attention import gated_ffn
from deeplearning4j_tpu.nn.graph import ComputationGraph

VOCAB, T_MAX = 97, 64


def _net(num_layers=3, **over):
    kw = dict(q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
              dense_hidden=64, num_experts=8, top_k=2, expert_hidden=16,
              routed_scaling=2.5, max_length=T_MAX, rope_theta=1e4)
    kw.update(over)
    net = ComputationGraph(latent_moe_lm_conf(VOCAB, 32, 4, num_layers,
                                              **kw)).init()
    for name, p in net.params.items():
        if "b" in p and "Wr" in p:     # a nonzero selection bias
            p["b"] = jax.random.normal(jax.random.PRNGKey(3),
                                       p["b"].shape, p["b"].dtype) * 0.1
    return net


@pytest.fixture(scope="module")
def net():
    return _net()


@pytest.fixture(scope="module")
def dec(net):
    return TransformerDecoder(net, t_max=T_MAX)


PROMPTS = [np.arange(5) % VOCAB, (np.arange(9) * 7) % VOCAB,
           (np.arange(13) * 11 + 3) % VOCAB]


def test_cache_is_one_row_a_token_and_nothing_per_head(net, dec):
    caches = dec.init_cache(3)
    assert set(caches) == {"attn0", "attn1", "attn2"}
    for leafs in caches.values():
        assert set(leafs) == {"kv"}
        assert leafs["kv"].shape == (3, 1, T_MAX, 16 + 4)
    item = jnp.dtype(net.compute_dtype).itemsize
    assert dec.latent_cache_bytes_per_token == 3 * 20 * item
    assert dec.kv_heads_per_row == 1
    assert dec.moe_names == ["ffn1", "ffn2"]
    gpt = TransformerDecoder(ComputationGraph(
        transformer_lm_conf(VOCAB, 32, 4, 1, max_length=T_MAX)).init())
    assert gpt.latent_cache_bytes_per_token == 0 and gpt.moe_names == []


def test_prefill_then_absorbed_decode_equals_recompute(dec):
    """Prefill writes the rows decompressed attention computed; every decode
    step then reads them absorbed. Both are the same float32 arithmetic up
    to summation order: 2e-5 on logits of order 1."""
    rng = np.random.default_rng(0)
    toks = rng.integers(0, VOCAB, (2, 24)).astype(np.int32)
    pad = np.pad(toks, ((0, 0), (0, 8)))
    caches = dec.init_cache(2)
    _, l0, caches = dec.prefill(caches, np.pad(toks[:, :8], ((0, 0), (0, 8))),
                                np.array([8, 8]))
    np.testing.assert_allclose(
        l0, dec.recompute_logits(pad, np.array([8, 8]))[1], atol=2e-5)
    for t in range(8, 24):
        _, lt, caches = dec.decode_step(caches, toks[:, t], np.array([t, t]))
        want = dec.recompute_logits(pad, np.array([t + 1, t + 1]))[1]
        np.testing.assert_allclose(lt, want, atol=2e-5, err_msg=str(t))


def test_chunked_windows_fill_the_slab_like_one_prefill(dec):
    """``chunk_forward`` (absorbed, window by window) leaves the rows one
    prefill leaves, to float32 round-off."""
    rng = np.random.default_rng(1)
    toks = rng.integers(0, VOCAB, (1, 16)).astype(np.int32)
    _, _, whole = dec.prefill(dec.init_cache(1), toks, np.array([16]))
    layer = dec.net.conf.vertices["attn0"].layer
    params = dec._device_params()
    x = params["embed"]["W"][toks]
    from deeplearning4j_tpu.nn.conf.layers.attention import rms_norm
    x = rms_norm(x, params["ln0a"]["gamma"], 1e-6)
    cache = layer.init_cache(1, T_MAX)
    outs = []
    for lo in (0, 8):
        o, cache = layer.chunk_forward(params["attn0"], x[:, lo:lo + 8],
                                       cache, jnp.array([lo], jnp.int32))
        outs.append(o)
    np.testing.assert_allclose(cache["kv"][:, :, :16],
                               whole["attn0"]["kv"][:, :, :16], atol=1e-6)
    full, _ = layer.forward(params["attn0"], {}, x)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), full,
                               atol=2e-5)


@pytest.mark.parametrize("block", [1, 4])
def test_generate_equals_the_no_cache_baseline(net, dec, block):
    got = dec.generate(PROMPTS, 10, temperature=0.0, block_size=block)
    for g, p in zip(got, PROMPTS):
        np.testing.assert_array_equal(
            g, generate(net, p, 10, temperature=0, bucket=T_MAX))


def test_engine_is_token_identical_with_steady_compiles_and_counters(net, dec):
    """Three waves through the slot engine: tokens equal ``generate``'s,
    waves after the first compile nothing, one readback a block, and the
    expert counters obey their definitions."""
    want = dec.generate(PROMPTS, 9, temperature=0.0, block_size=4)
    eng = SlotGenerationEngine(net, decoder=dec, num_slots=2, block_size=4,
                               seed=0)
    with CompileAudit() as audit, TransferAudit() as transfers:
        snap = None
        for wave in range(3):
            reqs = [eng.submit(p, 9) for p in PROMPTS]
            eng.run_until_drained()
            for r, w in zip(reqs, want):
                np.testing.assert_array_equal(r.result(0), w)
            if wave == 0:
                snap = audit.snapshot()
        assert audit.delta(snap) == {}
    stats = eng.stats()
    assert audit.compiles("decode_block4_impl") == 1
    transfers.check_per_block("engine.decode", stats["decode_blocks"])
    # a block whose lanes all finished meanwhile is dropped unread
    assert transfers.fetches("engine.decode") <= stats["decode_blocks"]
    sl, asg, hit, read, zero, held = (stats[k] for k in MOE_COUNTERS)
    assert zero == 0 and held == asg         # no zero-compute expert, no share
    assert sl > 0 and sl % 2 == 0            # two expert layers a step
    assert sl <= 2 * stats["decode_steps"]
    lanes = asg // 2                         # top_k 2: alive lanes, summed
    assert asg % 2 == 0 and sl <= lanes <= 2 * sl      # 1..2 alive lanes
    assert 2 * sl <= hit <= asg              # >= top_k distinct a layer
    # what a step computed: a stopped lane's choices reach no expert
    assert read == hit
    assert eng.latent_cache_bytes_per_token == 3 * 20 * 4


def test_expert_counters_are_exact_on_hand_made_routing():
    """Router weights of zero and a bias that orders the experts make every
    token choose experts 5 and 2: with B alive lanes a step-layer adds B x 2
    assignments and 2 experts hit, and those 2 are what is read: the stopped
    lane's choices are cast out before the experts."""
    net = _net(num_layers=2)
    p = net.params["ffn1"]
    p["Wr"] = jnp.zeros_like(p["Wr"])
    p["b"] = jnp.asarray([0, 0, .3, 0, 0, .5, 0, 0], p["b"].dtype)
    dec = TransformerDecoder(net, t_max=T_MAX)
    lens = np.array([4, 6, 5], np.int32)
    toks = np.zeros((3, 8), np.int32)
    nxt, _, caches = dec.prefill(dec.init_cache(3), toks, lens)
    out, *_, caches = dec.decode_block(
        caches, nxt, lens, block_size=4,
        stopped=np.array([False, True, False]))
    host, moe = dec.split_block(np.asarray(out))
    assert host.shape == (3, 4)
    assert dict(zip(MOE_COUNTERS, moe.tolist())) == {
        "moe_step_layers": 4, "moe_assignments": 4 * 2 * 2,
        "moe_experts_hit": 4 * 2, "moe_experts_read": 4 * 2,
        "moe_zero_assignments": 0, "moe_held_assignments": 4 * 2 * 2}
    # every lane stopped: nothing is counted for a request, and no expert
    # is read
    out, *_ = dec.decode_block(caches, nxt, lens, block_size=4,
                               stopped=np.ones(3, bool))
    assert dec.split_block(np.asarray(out))[1].tolist() == [0] * 6


def test_model_without_experts_reads_back_what_it_did():
    net = ComputationGraph(transformer_lm_conf(VOCAB, 32, 4, 1,
                                               max_length=T_MAX)).init()
    dec = TransformerDecoder(net)
    nxt, _, caches = dec.prefill(dec.init_cache(2), np.zeros((2, 8), np.int32),
                                 np.array([3, 5]))
    out, *_ = dec.decode_block(caches, nxt, np.array([3, 5]), block_size=4)
    # the tokens as ever, then only the slab attention's two counters: the
    # einsum body reads every position it holds, 4 steps × 2 slots × T_MAX
    toks, counts = dec.split_block(np.asarray(out))
    assert out.shape == (2, 6) and toks.shape == (2, 4)
    assert dec.counter_names == ("slab_positions_read",
                                 "slab_positions_held")
    assert counts.tolist() == [4 * 2 * T_MAX] * 2


def test_paged_paths_raise_and_name_the_mechanism(net, dec):
    layer = net.conf.vertices["attn0"].layer
    assert isinstance(layer, LatentAttentionLayer)
    for call in (lambda: layer.init_page_pool(8, 8),
                 lambda: layer.advance(None, None, None,
                                       Window(pages=np.zeros((1, 1)))),
                 lambda: layer.advance(None, None, None, Window(
                     valid=np.ones(1), pages=np.zeros((1, 1)))),
                 lambda: SlotGenerationEngine(net, decoder=dec, num_slots=2,
                                              paged=True, page_size=8)):
        with pytest.raises(NotImplementedError, match="latent attention"):
            call()


def test_sentinel_scrub_and_chaos_walk_the_latent_slab(net):
    dec = TransformerDecoder(net, t_max=T_MAX, sentinel=True)
    caches = jax.tree_util.tree_map(lambda a: a + 1.0, dec.init_cache(3))
    bad = dec.corrupt_cache(caches, 1, 2, "nan")
    assert bool(jnp.isnan(bad["attn2"]["kv"][1, 0, 2]).all())
    clean = dec._fn("scrub_slot")(bad, jnp.asarray([1], jnp.int32))
    for leafs in clean.values():
        assert float(jnp.abs(leafs["kv"][1]).max()) == 0.0
        assert bool((leafs["kv"][0] == 1.0).all())


@pytest.mark.parametrize("n,k,experts,first,held", [
    (8, 2, 8, 0, 0), (40, 3, 16, 0, 0), (40, 2, 16, 4, 4), (300, 2, 4, 0, 0)])
def test_routed_expert_kernel_equals_the_dense_path(n, k, experts, first,
                                                    held):
    """The Pallas kernel (interpreted) against the layer's plain jnp path,
    every expert held or a share of them; float32, so 1e-5."""
    layer = RoutedExpertsLayer(n_in=128, n_out=128, num_experts=experts,
                               top_k=k, expert_hidden=256,
                               routed_scaling=2.5, first_expert=first,
                               experts_held=held)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               layer.init_params(jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 128), jnp.float32)
    chosen, gates = layer.route(p, x)
    if first:       # some choices are held elsewhere, some cast out
        chosen = chosen.at[0].set(experts)
    want = layer._dense(p, x, chosen, gates)
    got = expert_ffn.routed_experts(x, chosen, gates, p["Wg"], p["Wu"],
                                    p["Wd"], first, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_mask_keeps_unmarked_tokens_out_of_the_experts():
    """Marked rows are bit for bit the unmasked forward's; an unmarked row
    is its shared expert and nothing else; the rows computed are the marked
    tokens' (both counts), and every token's without a mask."""
    layer = RoutedExpertsLayer(n_in=16, n_out=16, num_experts=8, top_k=2,
                               expert_hidden=8, shared_experts=1)
    p = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 16))
    mask = jnp.asarray([[1.0], [0.0], [1.0]])
    y, st = layer.forward(p, layer.init_state(), x, mask=mask)
    full, st_all = layer.forward(p, layer.init_state(), x)
    np.testing.assert_array_equal(y[jnp.asarray([0, 2])],
                                  full[jnp.asarray([0, 2])])
    np.testing.assert_allclose(
        y[1], gated_ffn(x[1], p["Sg"], p["Su"], p["Sd"]), atol=1e-6)
    assert np.abs(np.asarray(full[1] - y[1])).max() > 1e-3
    assert int(st["expert_tokens"].sum()) == 2 * 2
    np.testing.assert_array_equal(st["expert_rows"], st["expert_tokens"])
    assert int(st_all["expert_tokens"].sum()) == 3 * 2
    np.testing.assert_array_equal(st_all["expert_rows"],
                                  st_all["expert_tokens"])


def test_long_inputs_walked_in_blocks_give_what_one_pass_gives(net,
                                                               monkeypatch):
    """A batched admission of long prompts walks attention a few rows at a
    time and the FFNs ``TOKEN_BLOCK`` tokens at a time; with the block cut
    to 8 tokens a [4, 16] batch takes those paths and reads the same."""
    from deeplearning4j_tpu.nn.conf.layers import attention, latent_attention
    toks = np.random.default_rng(2).integers(0, VOCAB, (4, 16))
    fwd = lambda: jax.jit(lambda p, x: net._forward(
        p, net._inference_state(), {"tokens": x}, train=False,
        rng=None)[0]["out"])(net.params, jnp.asarray(toks, jnp.int32))
    whole = fwd()
    monkeypatch.setattr(attention, "TOKEN_BLOCK", 8)
    monkeypatch.setattr(latent_attention, "TOKEN_BLOCK", 8)
    np.testing.assert_allclose(fwd(), whole, atol=1e-6)
