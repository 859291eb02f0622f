"""A mask keeps unmarked tokens out of the routed experts
(``RoutedExpertsLayer.forward``): on both ways through the experts, for both
score functions and with a share of the experts held, an unmarked token's
choices reach neither a tile of the kernel's layout nor a weight of the dense
path, while marked rows stay bit for bit what they are without a mask. In a
decode block the mask is the alive lanes: requests beside stopped lanes emit
what they emit alone, the engine reads the experts its requests hit, whatever
a stopped lane holds stays in it, and a training step's padding mask moves
no gradient."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import expert_ffn
from deeplearning4j_tpu.models import (SlotGenerationEngine,
                                       TransformerDecoder,
                                       latent_moe_lm_conf)
from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer, experts
from deeplearning4j_tpu.nn.conf.layers.attention import gated_ffn
from deeplearning4j_tpu.nn.graph import ComputationGraph

VOCAB, T_MAX = 97, 64
D = 128
#: lanes 0, 2 and 3 hold requests; 1, 4 and 5 are stopped
MARKED = np.array([True, False, True, True, False, False])


def _wanted(zero_experts):
    """Each token's two choices, first the stronger: experts 6 and 7 (and 3
    where every choice is a routed one) are chosen by unmarked tokens ONLY,
    expert 4 by both kinds; token 3 and token 5 choose a zero-compute
    expert (8) where there is one."""
    spare = 8 if zero_experts else None
    return np.array([[4, 5], [6, 4], [5, 1], [4, spare or 2], [7, 1],
                     [6, spare or 3]])


def _routed_by_hand(layer, wanted):
    """(params, x [6, 1, D]): token n's row is mostly the n-th unit vector
    and the router's n-th row scores its wanted choices, so ``route`` gives
    ``wanted`` whatever the score function."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               layer.init_params(jax.random.PRNGKey(0)))
    wr = np.zeros(p["Wr"].shape, np.float32)
    for n, (a, b) in enumerate(wanted):
        wr[n, a], wr[n, b] = 1.0, 0.8
    p["Wr"] = jnp.asarray(wr)
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (6, D)) \
        + 8.0 * jnp.eye(6, D)
    chosen, _ = layer.route(p, x)
    np.testing.assert_array_equal(chosen, wanted)
    return p, x[:, None]


@pytest.mark.parametrize("first,held", [(0, 0), (4, 4)],
                         ids=["all-held", "share-held"])
@pytest.mark.parametrize("routing", [
    {}, {"score_function": "softmax", "renormalize": False,
         "zero_experts": 3}], ids=["sigmoid-renorm", "softmax-zero"])
@pytest.mark.parametrize("path", ["dense", "kernel"])
def test_unmarked_choices_get_no_tile_and_no_weight(path, routing, first,
                                                    held, monkeypatch):
    layer = RoutedExpertsLayer(n_in=D, n_out=D, num_experts=8, top_k=2,
                               expert_hidden=128, routed_scaling=2.5,
                               first_expert=first, experts_held=held,
                               **routing)
    wanted = _wanted(layer.zero_experts)
    p, x = _routed_by_hand(layer, wanted)
    reached = []                # the choices handed to the experts, a call

    def spy(fn, index):
        @functools.wraps(fn)
        def inner(*args, **kw):
            reached.append(np.asarray(args[index]))
            return fn(*args, **kw)
        return inner
    if path == "kernel":
        kernel = spy(functools.partial(expert_ffn.routed_experts,
                                       interpret=True), 1)
        monkeypatch.setattr(experts, "get_helper", lambda kind: kernel)
    else:
        monkeypatch.setattr(experts, "get_helper", lambda kind: None)
        monkeypatch.setattr(RoutedExpertsLayer, "_dense",
                            spy(RoutedExpertsLayer._dense, 3))
    y, st = layer.forward(p, layer.init_state(), x,
                          mask=jnp.asarray(MARKED)[:, None])
    full, st_all = layer.forward(p, layer.init_state(), x)

    # what reached the experts: the marked tokens' choices, the others cast
    # past the router's width
    np.testing.assert_array_equal(reached[1], wanted)
    np.testing.assert_array_equal(reached[0][MARKED], wanted[MARKED])
    assert (reached[0][~MARKED] == layer._routed_over()).all()

    # the kernel's layout of them: a tile for each expert held here that a
    # MARKED token chose, none for one only unmarked tokens chose
    n_held = held or 8

    def tiles(choices):
        local = choices.reshape(-1) - first
        local = np.where((local >= 0) & (local < n_held), local, n_held)
        _, _, tile_expert, used = expert_ffn.layout(
            jnp.asarray(local, jnp.int32), n_held, expert_ffn.MIN_TM)
        return np.asarray(tile_expert)[:int(used[0])].tolist()
    here = lambda chosen: sorted({int(e) - first for e in chosen.reshape(-1)
                                  if first <= e < first + n_held})
    assert tiles(reached[0]) == here(wanted[MARKED])
    assert tiles(reached[1]) == here(wanted)
    only_unmarked = set(here(wanted)) - set(here(wanted[MARKED]))
    assert {6 - first, 7 - first} <= only_unmarked

    # marked rows: bit for bit the unmasked forward's. Unmarked rows: the
    # shared expert and the zero-compute share, nothing else
    np.testing.assert_array_equal(y[MARKED], full[MARKED])
    chosen, gates = layer.route(p, x[:, 0])
    share = jnp.sum(jnp.where(chosen >= layer.num_experts, gates, 0.0), -1)
    alone = share[:, None] * x[:, 0] \
        + gated_ffn(x[:, 0], p["Sg"], p["Su"], p["Sd"])
    np.testing.assert_allclose(y[~MARKED, 0], alone[~MARKED], rtol=1e-6,
                               atol=1e-6)
    assert np.abs(np.asarray(full - y)[~MARKED]).max() > 1e-3

    # the counts follow the work
    routed = lambda chosen: int((chosen < layer.num_experts).sum())
    np.testing.assert_array_equal(st["expert_rows"], st["expert_tokens"])
    assert int(st["expert_tokens"].sum()) == routed(wanted[MARKED])
    assert int(st["expert_tokens"][6]) == int(st["expert_tokens"][7]) == 0
    np.testing.assert_array_equal(st_all["expert_rows"],
                                  st_all["expert_tokens"])
    assert int(st_all["expert_tokens"].sum()) == routed(wanted)
    if layer.zero_experts:
        assert int(st["zero_tokens"]) == 1 and int(st_all["zero_tokens"]) == 2


# ------------------------------------------------------- in a decode block
def _net():
    net = ComputationGraph(latent_moe_lm_conf(
        VOCAB, 32, 4, 3, q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4,
        v_dim=8, dense_hidden=64, num_experts=8, top_k=2, expert_hidden=16,
        routed_scaling=2.5, max_length=T_MAX, rope_theta=1e4)).init()
    for p in net.params.values():
        if "Wr" in p:                  # a nonzero selection bias
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


def test_requests_beside_stopped_lanes_emit_what_they_emit_alone(net, dec):
    """Four lanes, three requests of 4, 11 and 19 tokens: after the first
    wave every lane holds stale rows, lanes stop at different steps, and one
    lane is never taken again. Each request reads what it reads in a batch
    of its own, and the engine read exactly the experts its requests hit."""
    lengths = (4, 11, 19)
    alone = [dec.generate([p], n, temperature=0.0, block_size=4)[0]
             for p, n in zip(PROMPTS, lengths)]
    eng = SlotGenerationEngine(net, decoder=dec, num_slots=4, block_size=4,
                               seed=0)
    for wave in range(2):
        reqs = [eng.submit(p, n) for p, n in zip(PROMPTS, lengths)]
        eng.run_until_drained()
        for r, w in zip(reqs, alone):
            np.testing.assert_array_equal(r.result(0), w)
    stats = eng.stats()
    assert stats["moe_experts_hit"] > 0
    assert stats["moe_experts_read"] == stats["moe_experts_hit"]
    # lanes were stopped while others decoded: fewer alive lane-steps than
    # the blocks held
    assert stats["moe_assignments"] // 2 < \
        2 * 4 * stats["decode_steps"]


def test_what_a_stopped_lane_holds_stays_in_it(dec):
    """No operation of a decode step works across lanes: with the stopped
    lane's latent slab rows all NaN (so its hidden state is NaN in every
    layer, the expert layers too) the live lanes emit the same tokens."""
    lens = np.array([4, 6, 5], np.int32)
    toks = np.arange(24, dtype=np.int32).reshape(3, 8) % VOCAB
    stopped = np.array([False, True, False])

    def block(poison):
        nxt, _, caches = dec.prefill(dec.init_cache(3), toks, lens)
        if poison:
            caches = jax.tree_util.tree_map(
                lambda a: a.at[1].set(jnp.nan), caches)
        out, *_ = dec.decode_block(caches, nxt, lens, block_size=4,
                                   stopped=stopped)
        return dec.split_block(np.asarray(out))
    (clean, moe), (dirty, moe_dirty) = block(False), block(True)
    np.testing.assert_array_equal(dirty[~stopped], clean[~stopped])
    np.testing.assert_array_equal(moe_dirty, moe)


def test_a_padding_mask_moves_no_gradient_of_a_training_step(monkeypatch):
    """``ComputationGraph._forward`` hands the layer a batch's padding mask:
    a padded token then reaches no expert — and, its output reaching no loss
    and no other token, loss and gradients are what they are with every
    token computed."""
    net = _net()
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, VOCAB, (3, 12)), jnp.int32)
    labels = jax.nn.one_hot(jnp.roll(toks, -1, axis=1), VOCAB)
    mask = jnp.asarray(np.arange(12)[None] < np.array([12, 7, 9])[:, None],
                       jnp.float32)

    def loss_and_grads():
        def loss(params):
            return net._loss(params, net.state, {"tokens": toks},
                             {"out": labels}, None,
                             input_masks={"tokens": mask})[0]
        return jax.value_and_grad(loss)(net.params)
    kept_out = loss_and_grads()
    monkeypatch.setattr(RoutedExpertsLayer, "_reached",
                        lambda self, chosen, marked: chosen)
    computed = loss_and_grads()
    assert float(kept_out[0]) == pytest.approx(float(computed[0]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(kept_out[1]),
                    jax.tree_util.tree_leaves(computed[1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
