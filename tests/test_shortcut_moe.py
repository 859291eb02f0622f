"""The shortcut-connected double block on the normal path
(``shortcut_moe_lm_conf`` → ``ComputationGraph`` → ``TransformerDecoder``
→ ``SlotGenerationEngine``), at a tiny size on the CPU: the graph's vertices,
eight latent slabs for four layers, decode (absorbed) agreeing with prefill
and recompute (decompressed) with the latent scales on, the engine
token-identical to ``generate`` with its six expert counters exact on
hand-made routing; each new field of ``RoutedExpertsLayer`` (softmax scores,
no renormalisation, zero-compute experts) against the equation written out;
``q_scale`` / ``kv_scale`` in all four attention paths; the tile rule of
``kernels/expert_ffn.py`` and the interpreted kernel against the dense path
at widths that exercise it. (The comparison with the plain reference is
under ``tests/benchmark/``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileAudit
from deeplearning4j_tpu.kernels import expert_ffn
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       shortcut_moe_lm_conf)
from deeplearning4j_tpu.models.generation import MOE_COUNTERS
from deeplearning4j_tpu.nn.conf.layers import (LatentAttentionLayer,
                                               RoutedExpertsLayer)
from deeplearning4j_tpu.nn.conf.layers.attention import token_block
from deeplearning4j_tpu.nn.graph import ComputationGraph

VOCAB, T_MAX = 97, 64
KW = dict(q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
          dense_hidden=64, num_experts=8, zero_experts=4, top_k=3,
          expert_hidden=16, routed_scaling=6.0, q_scale=2.0, kv_scale=1.5,
          max_length=T_MAX, rope_theta=1e4)


def _net(num_layers=2, **over):
    net = ComputationGraph(shortcut_moe_lm_conf(
        VOCAB, 32, 4, num_layers, **dict(KW, **over))).init()
    for p in net.params.values():
        if "Wr" in p:                  # a nonzero selection bias
            p["b"] = jax.random.normal(jax.random.PRNGKey(3),
                                       p["b"].shape, p["b"].dtype) * 1e-3
    return net


@pytest.fixture(scope="module")
def net():
    return _net()


@pytest.fixture(scope="module")
def dec(net):
    return TransformerDecoder(net, t_max=T_MAX)


PROMPTS = [np.arange(5) % VOCAB, (np.arange(9) * 7) % VOCAB,
           (np.arange(13) * 11 + 3) % VOCAB]


# ------------------------------------------------------------- the graph
def test_builder_names_the_double_block_and_the_walk_takes_it(net, dec):
    conf = net.conf
    assert conf.vertex_inputs["res0d"] == ["res0c", "ffn0b", "moe0"]
    assert conf.vertex_inputs["moe0"] == conf.vertex_inputs["ffn0a"] \
        == ["ln0b"]
    order = conf.topological_order
    # the expert vertex is consumed one attention and one FFN after it
    assert order.index("moe0") < order.index("attn0b") \
        < order.index("ffn0b") < order.index("res0d")
    assert dec.attn_names == ["attn0a", "attn0b", "attn1a", "attn1b"]
    assert dec.moe_names == ["moe0", "moe1"]
    moe = conf.vertices["moe1"].layer
    assert (moe.score_function, moe.renormalize, moe.shared_experts,
            moe.zero_experts) == ("softmax", False, 0, 4)
    assert net.params["moe1"]["Wr"].shape == (32, 12)      # 8 + 4 outputs
    assert set(net.params["moe1"]) == {"Wr", "b", "Wg", "Wu", "Wd"}
    attn = conf.vertices["attn1b"].layer
    assert (attn.q_scale, attn.kv_scale) == (2.0, 1.5)
    caches = dec.init_cache(3)
    assert {k: v["kv"].shape for k, v in caches.items()} == {
        n: (3, 1, T_MAX, 20) for n in dec.attn_names}
    assert dec.latent_cache_bytes_per_token == 4 * 20 * 4


def test_prefill_then_absorbed_decode_equals_recompute(dec):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, VOCAB, (2, 20)).astype(np.int32)
    pad = np.pad(toks, ((0, 0), (0, 12)))
    _, l0, caches = dec.prefill(dec.init_cache(2), pad[:, :16],
                                np.array([6, 6]))
    np.testing.assert_allclose(
        l0, dec.recompute_logits(pad, np.array([6, 6]))[1], atol=2e-5)
    for t in range(6, 20):
        _, lt, caches = dec.decode_step(caches, toks[:, t], np.array([t, t]))
        want = dec.recompute_logits(pad, np.array([t + 1, t + 1]))[1]
        np.testing.assert_allclose(lt, want, atol=2e-5, err_msg=str(t))


def test_engine_is_token_identical_and_counts_the_three_kinds_of_choice(
        net, dec):
    want = dec.generate(PROMPTS, 9, temperature=0.0, block_size=4)
    eng = SlotGenerationEngine(net, decoder=dec, num_slots=2, block_size=4,
                               seed=0)
    with CompileAudit() as audit:
        snap = None
        for wave in range(2):
            reqs = [eng.submit(p, 9) for p in PROMPTS]
            eng.run_until_drained()
            for r, w in zip(reqs, want):
                np.testing.assert_array_equal(r.result(0), w)
            if wave == 0:
                snap = audit.snapshot()
        assert audit.delta(snap) == {}
    stats = eng.stats()
    sl, asg, hit, read, zero, held = (stats[k] for k in MOE_COUNTERS)
    assert sl > 0 and asg % 3 == 0 and sl <= asg // 3 <= 2 * sl
    assert held == asg - zero                # every routed expert is held
    assert 0 < zero < asg and hit <= held and hit == read


def test_expert_counters_are_exact_on_hand_made_routing():
    """Router weights of zero and a bias that orders the outputs make every
    token choose zero-compute expert 9 and routed experts 5 and 2; the layer
    holds experts 4..7, so 5 is held here and 2 elsewhere."""
    net = _net(first_expert=4, experts_held=4)
    for name in ("moe0", "moe1"):
        p = net.params[name]
        assert p["Wg"].shape == (4, 32, 16)
        p["Wr"] = jnp.zeros_like(p["Wr"])
        p["b"] = jnp.zeros_like(p["b"]).at[jnp.asarray([9, 5, 2])].set(
            jnp.asarray([.5, .4, .3], p["b"].dtype))
    dec = TransformerDecoder(net, t_max=T_MAX)
    lens = np.array([4, 6, 5], np.int32)
    nxt, _, caches = dec.prefill(dec.init_cache(3), np.zeros((3, 8),
                                                             np.int32), lens)
    out, *_, caches = dec.decode_block(
        caches, nxt, lens, block_size=4,
        stopped=np.array([False, True, False]))
    host, moe = dec.split_block(np.asarray(out))
    assert host.shape == (3, 4)
    steps = 4 * 2                              # steps x expert branches
    assert dict(zip(MOE_COUNTERS, moe.tolist())) == {
        "moe_step_layers": steps, "moe_assignments": steps * 2 * 3,
        "moe_experts_hit": steps, "moe_experts_read": steps,
        "moe_zero_assignments": steps * 2, "moe_held_assignments": steps * 2}
    out, *_ = dec.decode_block(caches, nxt, lens, block_size=4,
                               stopped=np.ones(3, bool))
    # every lane stopped: no choice reaches an expert, none is read
    assert dec.split_block(np.asarray(out))[1].tolist() == [0] * 6


# ------------------------------------- the expert layer's new fields, each
def _layer(**over):
    kw = dict(n_in=16, n_out=16, num_experts=6, top_k=3, expert_hidden=8,
              shared_experts=0, routed_scaling=6.0)
    layer = RoutedExpertsLayer(**dict(kw, **over))
    p = layer.init_params(jax.random.PRNGKey(0))
    p["b"] = jax.random.normal(jax.random.PRNGKey(7), p["b"].shape) * 1e-2
    return layer, p


def _by_hand(layer, p, x):
    """The module docstring's equation, token by token, expert by expert."""
    logits = np.asarray(x @ p["Wr"], np.float64)
    if layer.score_function == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    else:
        s = 1.0 / (1.0 + np.exp(-logits))
    out = np.zeros(x.shape, np.float64)
    lo = layer.first_expert
    hi = lo + (layer.experts_held or layer.num_experts)
    for n in range(x.shape[0]):
        chosen = np.argsort(-(s[n] + np.asarray(p["b"], np.float64)),
                            kind="stable")[:layer.top_k]
        g = layer.routed_scaling * s[n, chosen]
        if layer.renormalize:
            g = g / s[n, chosen].sum()
        for i, gi in zip(chosen, g):
            xn = np.asarray(x[n], np.float64)
            if i >= layer.num_experts:
                out[n] += gi * xn                       # zero-compute
            elif lo <= i < hi:
                a = xn @ np.asarray(p["Wg"][i - lo], np.float64)
                h = a / (1 + np.exp(-a)) * (
                    xn @ np.asarray(p["Wu"][i - lo], np.float64))
                out[n] += gi * (h @ np.asarray(p["Wd"][i - lo], np.float64))
    return out


@pytest.mark.parametrize("over", [
    {}, {"score_function": "softmax"}, {"renormalize": False},
    {"score_function": "softmax", "renormalize": False},
    {"zero_experts": 3},
    {"score_function": "softmax", "renormalize": False, "zero_experts": 3},
    {"score_function": "softmax", "renormalize": False, "zero_experts": 3,
     "first_expert": 2, "experts_held": 2}],
    ids=lambda o: ",".join(f"{k}={v}" for k, v in o.items()) or "defaults")
def test_each_routing_field_follows_its_equation(over):
    layer, p = _layer(**over)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 16), jnp.float32)
    y, counts = layer.forward(p, layer.init_state(), x[None])
    np.testing.assert_allclose(y[0], _by_hand(layer, p, x), atol=2e-5)
    assert counts["expert_tokens"].shape == (6,)
    zero = int(counts.get("zero_tokens", 0))
    assert int(counts["expert_tokens"].sum()) + zero == 10 * 3
    assert (zero > 0) == bool(layer.zero_experts)
    assert ("zero_tokens" in layer.init_state()) == bool(layer.zero_experts)


def test_a_mask_counts_zero_choices_of_marked_tokens_only():
    """... and keeps the others out of the routed experts: a marked row is
    bit for bit the unmasked forward's, an unmarked row is its zero-compute
    share ``(sum of its gates there) x`` alone (no shared expert here), and
    the rows computed are the marked tokens'."""
    layer, p = _layer(zero_experts=3, score_function="softmax",
                      renormalize=False)
    p["b"] = p["b"].at[7].add(1.0)      # every token chooses this identity
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, 16))
    mask = jnp.asarray([[1.0], [0.0], [1.0], [0.0]])
    y, st = layer.forward(p, layer.init_state(), x, mask=mask)
    full, st_all = layer.forward(p, layer.init_state(), x)
    on, off = jnp.asarray([0, 2]), jnp.asarray([1, 3])
    np.testing.assert_array_equal(y[on], full[on])
    chosen, gates = layer.route(p, x[:, 0])
    share = jnp.sum(jnp.where(chosen >= layer.num_experts, gates, 0.0), -1)
    assert float(share.min()) > 0
    np.testing.assert_allclose(y[off, 0], share[off, None] * x[off, 0],
                               atol=1e-6)
    assert np.abs(np.asarray(full[off] - y[off])).max() > 1e-3
    assert int(st["expert_tokens"].sum() + st["zero_tokens"]) == 2 * 3
    np.testing.assert_array_equal(st["expert_rows"], st["expert_tokens"])
    assert int(st_all["expert_tokens"].sum() + st_all["zero_tokens"]) == 12
    np.testing.assert_array_equal(st_all["expert_rows"],
                                  st_all["expert_tokens"])


# ------------------------------------------------ the two latent scales
def test_latent_scales_reach_all_four_attention_paths():
    """A layer with ``q_scale`` / ``kv_scale`` equals a layer without them
    whose two latent gains are multiplied by the scalars — in ``forward``,
    ``prefill_forward``, ``decode_forward`` and ``chunk_forward`` — and the
    slab row holds the SCALED ``c_kv`` beside an unscaled ``k_rope``."""
    kw = dict(n_in=32, n_out=32, num_heads=4, q_rank=24, kv_rank=16,
              nope_dim=8, rope_dim=4, v_dim=8, rope_theta=1e4)
    scaled = LatentAttentionLayer(q_scale=2.0, kv_scale=3.5, **kw)
    plain = LatentAttentionLayer(**kw)
    p = scaled.init_params(jax.random.PRNGKey(0))
    q = dict(p, gq=p["gq"] * 2.0, gkv=p["gkv"] * 3.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    np.testing.assert_allclose(scaled.forward(p, {}, x)[0],
                               plain.forward(q, {}, x)[0], atol=1e-5)
    unscaled = plain.forward(p, {}, x)[0]
    assert float(jnp.abs(scaled.forward(p, {}, x)[0] - unscaled).max()) > 1e-3
    out_s, c_s = scaled.prefill_forward(p, x[:, :8],
                                        scaled.init_cache(2, 16))
    out_p, c_p = plain.prefill_forward(q, x[:, :8], plain.init_cache(2, 16))
    np.testing.assert_allclose(out_s, out_p, atol=1e-5)
    np.testing.assert_allclose(c_s["kv"], c_p["kv"], atol=1e-5)
    raw = plain.prefill_forward(p, x[:, :8], plain.init_cache(2, 16))[1]
    np.testing.assert_allclose(c_s["kv"][..., :16], raw["kv"][..., :16] * 3.5,
                               atol=1e-5)
    np.testing.assert_allclose(c_s["kv"][..., 16:], raw["kv"][..., 16:],
                               atol=1e-6)
    pos = np.array([8, 8])
    d_s, c_s2 = scaled.decode_forward(p, x[:, 8:9], c_s, pos)
    d_p, c_p2 = plain.decode_forward(q, x[:, 8:9], c_p, pos)
    np.testing.assert_allclose(d_s, d_p, atol=1e-5)
    np.testing.assert_allclose(
        d_s[:, 0], scaled.forward(p, {}, x[:, :9])[0][:, 8], atol=1e-5)
    k_s, _ = scaled.chunk_forward(p, x[:, 9:12], c_s2, np.array([9, 9]))
    k_p, _ = plain.chunk_forward(q, x[:, 9:12], c_p2, np.array([9, 9]))
    np.testing.assert_allclose(k_s, k_p, atol=1e-5)
    np.testing.assert_allclose(k_s, scaled.forward(p, {}, x)[0][:, 9:12],
                               atol=1e-5)


# ------------------------------------------- the kernel's tiles, by shape
def test_tiles_follow_the_shapes_and_keep_the_accepted_cells_plan():
    """``th`` and the cap on ``tm`` from d, h and the operand size: what
    ``joyai-llm-flash`` runs with (d 2048, h 768) is what it was; at d 6144,
    h 2048 the double-buffered set fits the 16 MiB a kernel gets."""
    ef = expert_ffn
    assert ef.hidden_chunk(2048, 768, 2) == 256          # as before
    assert ef.hidden_chunk(2048, 768, 4) == 128
    assert ef.max_tile_rows(2048, 256, 2) == 256
    assert ef.max_tile_rows(2048, 128, 4) == 128
    assert ef.hidden_chunk(6144, 2048, 2) == 128
    assert ef.max_tile_rows(6144, 128, 2) == 64
    assert ef.hidden_chunk(32, 16, 4) == 16              # a test's width
    for d, h, item in ((2048, 768, 2), (2048, 768, 4), (6144, 2048, 2)):
        th = ef.hidden_chunk(d, h, item)
        tm = ef.max_tile_rows(d, th, item)
        held = 6 * d * th * item + tm * d * (4 * item + 4)
        assert held <= ef.VMEM_BYTES - ef.SPARE_BYTES, (d, h, item)
    # tm from the assignments EXPECTED here: all of them where every expert
    # is held (256 of 256: 16 lanes x 8 -> 16, a 4096-token block -> 256)
    assert ef.tile_rows(16 * 8, 256, 256) == 16
    assert ef.tile_rows(4096 * 8, 256, 256) == 256
    # a 1/48 share (16 of 768 outputs): a decode step's 192 choices leave 4
    # rows here, a 2048-token block 512 rows over 16 experts -> 64
    assert ef.tile_rows(16 * 12 * 16 // 768, 16, 64) == 16
    assert ef.tile_rows(2048 * 12 * 16 // 768, 16, 64) == 64
    assert token_block(8) == 4096 and token_block(12) == 2048
    assert token_block(2) == 4096


@pytest.mark.parametrize("n,d,h,dtype,zero", [
    (40, 768, 256, jnp.bfloat16, 16),     # th 256 of h 256
    (24, 256, 1024, jnp.float32, 0),      # th 512: two chunks, accumulated
    (300, 128, 384, jnp.float32, 8)])     # th 128: three chunks; tm grows
def test_kernel_equals_the_dense_path_at_the_new_tile_rule(n, d, h, dtype,
                                                           zero):
    """The interpreted kernel through ``routed_experts`` (told the router's
    width, so ``tm`` follows the share) against ``_dense``, a share of the
    experts held and zero-compute choices among the rest."""
    layer = RoutedExpertsLayer(
        n_in=d, n_out=d, num_experts=16, top_k=4, expert_hidden=h,
        shared_experts=0, routed_scaling=6.0, score_function="softmax",
        renormalize=False, zero_experts=zero, first_expert=4,
        experts_held=8)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                               layer.init_params(jax.random.PRNGKey(0)))
    assert expert_ffn.hidden_chunk(d, h, jnp.dtype(dtype).itemsize) \
        == {256: 256, 1024: 512, 384: 128}[h]
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.float32) \
        .astype(dtype)
    chosen, gates = layer.route(p, x)
    want = layer._dense(p, x, chosen, gates)
    got = expert_ffn.routed_experts(x, chosen, gates, p["Wg"], p["Wu"],
                                    p["Wd"], 4, 16 + zero, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
