"""Mamba-2 state-space layers beside grouped-query attention, on the normal
serving path, at tiny sizes on the CPU (float32, so that each comparison can
be tight): the chunked scan against the sequential recurrence; prefill then
decode through the fixed-size state and the grouped slab against the no-cache
forward; a batched admission against each row admitted alone; a reused slot
against a fresh engine; the state-update kernel (interpreted) against the
layer's jnp body; grouped-KV slab attention, kernel and einsum body, against
dense attention with the KV heads repeated; the engine's loud refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import slab_attention as sa
from deeplearning4j_tpu.kernels import ssm_update
from deeplearning4j_tpu.kernels.ssm_update import make_ssm_update_helper
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       hybrid_ssm_lm_conf)
from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer, Window
from deeplearning4j_tpu.nn.conf.layers.state_space import (ssd_chunked,
                                                           ssm_step)
from deeplearning4j_tpu.nn.graph import ComputationGraph

V, T_MAX = 97, 64
TYPES = ("mamba", "attention", "mamba")


def _net(seed=3):
    """Heads of 64, two KV heads: the slab packs both into one 128-lane
    row, as at the published size; chunk 8, so prompts cross chunks."""
    return ComputationGraph(hybrid_ssm_lm_conf(
        V, 256, 4, 2, TYPES, ffn_hidden=64, ssm_heads=4, ssm_head_dim=32,
        ssm_state=16, chunk_size=8, attention_scale=0.05,
        embedding_scale=3.0, residual_scale=0.5, logit_divisor=2.0,
        max_length=T_MAX, seed=seed)).init()


@pytest.fixture(scope="module")
def net():
    return _net()


@pytest.fixture(scope="module")
def dec(net):
    return TransformerDecoder(net, t_max=T_MAX)


def _sequential(x, dt, a, b, c, s0):
    """The recurrence one token after another (float64, numpy)."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    s = np.asarray(s0, np.float64).copy()
    ys = []
    for t in range(x.shape[1]):
        s = np.exp(dt[:, t] * a)[:, :, None, None] * s \
            + (dt[:, t, :, None] * x[:, t])[..., None] \
            * b[:, t, None, None, :]
        ys.append(np.einsum("rhpn,rn->rhp", s, c[:, t]))
    return np.stack(ys, axis=1), s


@pytest.mark.parametrize("t", [5, 8, 21, 40])
def test_chunked_scan_equals_the_recurrence_from_a_carried_state(t):
    r, h, p, n = 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (r, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (r, t, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (r, t, n))
    c = jax.random.normal(ks[4], (r, t, n))
    s0 = jax.random.normal(ks[5], (r, h, p, n))
    y, s = ssd_chunked(x, dt, a, b, c, 8, state0=s0)
    y_ref, s_ref = _sequential(x, dt, a, b, c, s0)
    # float32 against float64: a few ulps of the largest output
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4, atol=1e-4)


def test_a_zero_step_carries_the_state_through_unchanged():
    r, t, h, p, n = 1, 12, 2, 3, 4
    x = jnp.ones((r, t, h, p))
    dt = jnp.full((r, t, h), 0.3).at[:, 7:].set(0.0)   # "padding" from 7
    a, bc = -jnp.ones((h,)), jnp.ones((r, t, n))
    _, full = ssd_chunked(x, dt, a, bc, bc, 4)
    _, cut = ssd_chunked(x[:, :7], dt[:, :7], a, bc[:, :7], bc[:, :7], 4)
    np.testing.assert_allclose(np.asarray(full), np.asarray(cut), rtol=1e-6)


def test_builder_lays_out_the_hybrid_and_the_walk_takes_it(net, dec):
    assert dec.state_names == ["ssm0", "ssm2"]
    assert dec.kv_names == ["attn1"]
    assert net.params["out"] == {}                        # tied head
    assert net.params["attn1"]["Wk"].shape == (256, 2 * 64)
    assert "bo" not in net.params["attn1"]
    caches = dec.init_cache(3)
    assert caches["ssm0"]["ssm"].shape == (3, 4, 32, 16)
    assert caches["ssm0"]["conv"].shape == (3, 3, 4 * 32 + 2 * 16)
    assert caches["attn1"]["k"].shape == (3, 1, T_MAX, 128)   # 2 KV heads
    assert dec.kv_heads_per_row == 2


def test_prefill_then_decode_equals_the_no_cache_forward(dec):
    rng = np.random.default_rng(0)
    lens = np.array([3, 11, 17], np.int32)
    toks = np.zeros((3, 32), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, V, n)
    ids, logits, caches = dec.prefill(dec.init_cache(3), toks, lens)
    _, ref = dec.recompute_logits(toks, lens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    pos = lens.copy()
    for _ in range(6):       # through the fixed-size state and the slab
        toks[np.arange(3), pos] = np.asarray(ids)
        ids, logits, caches = dec.decode_step(caches, ids, pos)
        pos = pos + 1
        _, ref = dec.recompute_logits(toks, pos)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def _admit(dec, caches, prompts, slots):
    """One ``prefill_slots`` call admitting ``prompts`` into ``slots``."""
    tp = 32
    toks = np.zeros((len(prompts), tp), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)
    ids, logits, caches = dec._fn("prefill_slots")(
        dec._device_params(), dec.net._inference_state(), caches,
        jnp.asarray(toks), jnp.asarray(lens),
        jnp.asarray(slots, jnp.int32),
        jnp.zeros(len(prompts), jnp.float32), jax.random.PRNGKey(0))
    return np.asarray(logits), caches


def test_one_admission_of_rows_of_different_lengths_equals_each_alone(dec):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, V, n).astype(np.int32) for n in (2, 9, 23)]
    together, c_all = _admit(dec, dec.init_cache(4), prompts, [3, 0, 2])
    # float32 sums blocked differently at 3 rows and at 1 differ in the
    # last places (2e-6); a padding fault moves the state by tenths
    for p, s, row in zip(prompts, [3, 0, 2], together):
        alone, c_one = _admit(dec, dec.init_cache(4), [p], [s])
        np.testing.assert_allclose(row, alone[0], rtol=1e-4, atol=1e-5)
        for name in dec.state_names:      # padding moved no state
            for kk in ("ssm", "conv"):
                np.testing.assert_allclose(
                    np.asarray(c_all[name][kk][s]),
                    np.asarray(c_one[name][kk][s]), rtol=1e-4, atol=1e-5)
    # the slot nobody was admitted to is untouched
    for name in dec.state_names:
        assert not np.asarray(c_all[name]["ssm"][1]).any()


def test_a_reused_slot_equals_a_fresh_engine(net, dec):
    rng = np.random.default_rng(2)
    first, second = (rng.integers(0, V, n).astype(np.int32) for n in (19, 6))
    one = SlotGenerationEngine(net, num_slots=1, t_max=T_MAX, block_size=4,
                               decoder=dec)
    a = one.submit(first, 9)
    one.run_until_drained()
    b = one.submit(second, 12)           # lands in the slot `a` left
    one.run_until_drained()
    fresh = SlotGenerationEngine(net, num_slots=1, t_max=T_MAX,
                                 block_size=4, decoder=dec)
    c = fresh.submit(second, 12)
    fresh.run_until_drained()
    np.testing.assert_array_equal(b.result(0), c.result(0))
    st = one.stats()
    assert st["ssm_state_resets"] == 2 * len(dec.state_names)
    # every lane was alive in a 1-slot engine until its request stopped
    assert 0 < st["ssm_step_layers"] <= st["ssm_lane_layers"]
    assert a.result(0)[:len(first)].tolist() == first.tolist()


def test_engine_counts_alive_and_every_lane_per_state_layer(net, dec):
    rng = np.random.default_rng(4)
    eng = SlotGenerationEngine(net, num_slots=4, t_max=T_MAX, block_size=4,
                               decoder=dec)
    hs = [eng.submit(rng.integers(0, V, 5).astype(np.int32), 5)]
    eng.run_until_drained()
    st = eng.stats()
    layers = len(dec.state_names)
    # one request on 4 lanes, 4 new tokens after the prefill's: the block
    # that decoded them computes 4 lanes a step, one of them alive (the
    # counts are of retired blocks; one dispatched past the end is not)
    assert st["ssm_lane_layers"] == 4 * layers * 4
    assert st["ssm_step_layers"] == layers * 4
    assert len(hs[0].result(0)) == 10


def _update_operands(shape, dtype):
    s, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    state = jax.random.normal(ks[0], (s, h, p, n)).astype(dtype)
    x = jax.random.normal(ks[1], (s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[3], (h,)))
    b, c = (jax.random.normal(k, (s, n)) for k in ks[4:6])
    d = jax.random.normal(ks[6], (h,))
    return state, x, dt, a, b, c, d


@pytest.mark.parametrize("shape", [
    (3, 4, 16, 128),      # four whole heads a 64-row chunk, three slots a step
    (2, 64, 64, 128),     # a Granite-4.0-H slot: two heads a chunk, two slots
    (2, 5, 64, 128),      # five heads, which two-head chunks do not divide
    (3, 48, 64, 128),     # three slots too large to pair: one a step
    (2, 2, 256, 128),     # a chunk inside one head
], ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_state_update_kernel_interpreted_equals_the_jnp_body(dtype, shape):
    state, x, dt, a, b, c, d = _update_operands(shape, dtype)
    got_s, got_y = make_ssm_update_helper(interpret=True)(
        None, state, x, dt, a, b, c, d)
    ref_s, ref_y = ssm_step(state, x, dt, a, b, c, d)
    assert got_s.dtype == dtype and got_y.dtype == jnp.float32
    # float32 arithmetic in both; a bfloat16 state rounds once on the way
    # out, where the two may round a last-place tie apart
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got_s, np.float32),
                               np.asarray(ref_s, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(ref_y),
                               rtol=1e-5, atol=1e-4)


def test_state_update_plan_pairs_granite_slots_and_steps_an_odd_count_alone():
    bf16 = jnp.bfloat16
    assert ssm_update.plan(32, 64, 64, 128, bf16) == (128, 2)
    assert ssm_update.plan(3, 48, 64, 128, bf16) == (128, 1)
    assert ssm_update.plan(2, 5, 64, 128, bf16) == (64, 2)
    assert ssm_update.plan(3, 4, 16, 128, bf16) == (64, 3)


def _pallas_call_of(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _pallas_call_of(sub)
            if found is not None:
                return found
    return None


def test_state_update_kernel_writes_a_slots_state_in_place():
    """The kernel's one call names the state operand as the buffer of its
    new state, and a donated state's buffer is the one the new state
    occupies: no second copy of every slot's state."""
    state, x, dt, a, b, c, d = _update_operands((2, 4, 16, 128),
                                                jnp.bfloat16)
    helper = make_ssm_update_helper(interpret=True)
    update = lambda st, *rest: helper(None, st, *rest)
    eqn = _pallas_call_of(jax.make_jaxpr(update)(
        state, x, dt, a, b, c, d).jaxpr)
    assert eqn.params["input_output_aliases"] == ((0, 0),)
    assert eqn.invars[0].aval.shape == eqn.outvars[0].aval.shape \
        == state.shape
    assert eqn.outvars[0].aval.dtype == jnp.bfloat16
    where = state.unsafe_buffer_pointer()
    new, _ = jax.jit(update, donate_argnums=0)(state, x, dt, a, b, c, d)
    assert state.is_deleted() and new.unsafe_buffer_pointer() == where


def test_state_update_helper_declines_a_state_that_is_not_whole_lanes():
    z = jnp.zeros
    assert make_ssm_update_helper(interpret=True)(
        None, z((1, 2, 4, 16)), z((1, 2, 4)), z((1, 2)), z((2,)),
        z((1, 16)), z((1, 16)), z((2,))) is None


@pytest.fixture()
def slab_kernel():
    snap = helpers.snapshot_helper("slab_attention")
    sa.register_slab_attention(platforms=("tpu", "cpu"), interpret=True)
    yield
    helpers.restore_helper("slab_attention", snap)


def _dense(q, k, v, qpos, scale, rep):
    """Attention written out: KV heads repeated to the query heads."""
    k, v = (np.repeat(np.asarray(u, np.float64), rep, axis=2)
            for u in (k, v))
    q = np.asarray(q, np.float64)
    logits = np.einsum("bqhd,bthd->bhqt", q, k) * scale
    keep = np.arange(k.shape[1])[None, None, :] <= np.asarray(qpos)[:, :, None]
    logits = np.where(keep[:, None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqt,bthd->bqhd", p, v)


@pytest.mark.parametrize("kv_heads,heads,dh", [(4, 16, 64), (2, 8, 128),
                                               (4, 8, 32)])
@pytest.mark.parametrize("kernel", [False, True])
def test_grouped_slab_attention_equals_dense_with_kv_repeated(
        request, kv_heads, heads, dh, kernel):
    if kernel:
        request.getfixturevalue("slab_kernel")
    layer = SelfAttentionLayer(n_in=heads * dh, n_out=heads * dh,
                               num_heads=heads, num_kv_heads=kv_heads,
                               scale=0.07, causal=True)
    t, b = 128, 2
    rng = np.random.default_rng(kv_heads + heads)
    k = jnp.asarray(rng.normal(size=(b, t, kv_heads, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv_heads, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, heads, dh)), jnp.float32)
    cache = layer.init_cache(b, t)
    g = cache["k"].shape[3] // dh
    assert cache["k"].shape == (b, kv_heads // g, t, g * dh)
    cache = {kk: jax.lax.dynamic_update_slice(
        cache[kk], layer._slab_rows(u, cache[kk]), (0, 0, 0, 0))
        for kk, u in (("k", k), ("v", v))}
    qpos = jnp.asarray([[37], [t - 1]], jnp.int32)
    got = layer._slab_attend(q, cache["k"], cache["v"], qpos)
    want = _dense(q, k, v, qpos, 0.07, heads // kv_heads)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_grouped_prefill_attention_equals_dense_with_kv_repeated():
    layer = SelfAttentionLayer(n_in=32, n_out=32, num_heads=4,
                               num_kv_heads=2, scale=0.3, causal=True,
                               bias=False)
    params = layer.init_params(jax.random.PRNGKey(0))
    assert params["Wk"].shape == (32, 16) and "bo" not in params
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    out, _ = layer.forward(params, None, x)
    q, k, v = layer._project_qkv(params, x)
    t = 9
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (2, t))
    ref = _dense(q, k, v, pos, 0.3, 2).reshape(2, t, 32) \
        @ np.asarray(params["Wo"], np.float64)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["nan", "flip"])
def test_chaos_corruption_poisons_a_slots_whole_state_and_one_kv_cell(
        dec, mode):
    rng = np.random.default_rng(5)
    _, caches = _admit(dec, dec.init_cache(2),
                       [rng.integers(0, V, n) for n in (7, 11)], [0, 1])
    before = jax.tree_util.tree_map(np.asarray, caches)
    after = jax.tree_util.tree_map(
        np.asarray, dec.corrupt_cache(caches, 1, 0, mode))
    poisoned = ((lambda new, old: np.isnan(new).all()) if mode == "nan"
                else (lambda new, old: np.array_equal(new, -old)))
    for name in dec.state_names:
        for kk, leaf in after[name].items():
            assert poisoned(leaf[1], before[name][kk][1])
            np.testing.assert_array_equal(leaf[0], before[name][kk][0])
    for name in dec.kv_names:
        for kk, leaf in after[name].items():
            assert poisoned(leaf[1, :, 0, :], before[name][kk][1, :, 0, :])
            np.testing.assert_array_equal(leaf[1, :, 1:, :],
                                          before[name][kk][1, :, 1:, :])
            np.testing.assert_array_equal(leaf[0], before[name][kk][0])


def test_fixed_state_layer_refuses_a_window_from_a_position(net):
    layer = net.conf.vertices["ssm0"].layer
    with pytest.raises(NotImplementedError):
        layer.advance(net.params["ssm0"], jnp.zeros((1, 4, 256)),
                      layer.init_cache(1, T_MAX),
                      Window(start=jnp.zeros(1, jnp.int32),
                             valid=jnp.full(1, 4, jnp.int32)))


@pytest.mark.parametrize("option,named", [
    ({"paged": True, "prefix_cache": False}, "paged KV pool"),
    ({"paged": True, "page_size": 8}, "prefix cache"),
    ({"speculative": True}, "speculative drafter"),
    ({"prefill_chunk": 16}, "chunked prefill"),
])
def test_engine_refuses_what_a_fixed_size_state_cannot_do(net, dec, option,
                                                          named):
    with pytest.raises(ValueError, match=named):
        SlotGenerationEngine(net, num_slots=2, t_max=T_MAX, decoder=dec,
                             **option)
