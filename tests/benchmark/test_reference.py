"""The plain reference against the program at a tiny size on the CPU: the
full forward pass, prefill and decode through the cache, and training steps;
and the weights it makes for itself are the ones the program was handed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

gpt2 = tiny.family()
program, reference, wgen = gpt2.program, gpt2.reference, gpt2.weights

SEED = 2 ** 31 + 77          # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def served():
    net, sizes = program.build_net(tiny.TINY_CONFIG, SEED, train=False)
    return net, sizes


def _ref_logits(sizes, tokens):
    end, x = reference.hidden_states(sizes, SEED, jnp.asarray(tokens))
    return np.asarray(reference.head(end, x))


def test_weights_per_layer_equal_weights_in_one_call(served):
    _, sizes = served
    end, blocks = wgen.everything(sizes, SEED)
    again = wgen.ends(sizes, SEED)
    assert all(np.array_equal(end[k], again[k]) for k in end)
    for i, b in enumerate(blocks):
        one = wgen.layer(sizes, SEED, i)
        assert all(np.array_equal(b[k], one[k]) for k in b)
    other = wgen.layer(sizes, SEED + 1, 0)
    assert not np.array_equal(other["wq"], blocks[0]["wq"])
    rounded, _ = wgen.everything(sizes, SEED, jnp.bfloat16)
    assert rounded["wte"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(rounded["wte"], np.float32),
                          np.asarray(end["wte"].astype(jnp.bfloat16),
                                     np.float32))


def test_program_tree_round_trips_and_matches_the_program(served):
    net, sizes = served
    end, blocks = wgen.canonical_view(net.params, sizes["layers"])
    tree = wgen.program_tree(end, blocks)
    assert jax.tree_util.tree_structure(tree) == \
        jax.tree_util.tree_structure(net.params)
    with pytest.raises(RuntimeError):
        bad = dict(tiny.TINY_CONFIG, n_inner=100)   # 100 // 64 -> ffn 64
        program.build_net(bad, SEED, train=False)


def test_forward_agrees_with_computation_graph_output(served):
    net, sizes = served
    toks = np.random.default_rng(0).integers(
        0, sizes["vocab"], (3, 40)).astype(np.int32)
    probs = net.output(toks)[0]
    ref = np.asarray(jax.nn.softmax(_ref_logits(sizes, toks), axis=-1))
    np.testing.assert_allclose(probs, ref, rtol=2e-4, atol=1e-7)


def test_prefill_and_decode_through_the_cache_agree(served):
    from deeplearning4j_tpu.models import TransformerDecoder
    net, sizes = served
    dec = TransformerDecoder(net, t_max=sizes["positions"])
    rng = np.random.default_rng(1)
    lens = [23, 9]
    toks = np.zeros((2, 32), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = rng.integers(0, sizes["vocab"], n)
    caches = dec.init_cache(2)
    ids, logits, caches = dec.prefill(caches, toks, lens)
    ref = _ref_logits(sizes, toks)
    for r, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits)[r], ref[r, n - 1],
                                   rtol=1e-4, atol=2e-5)
    # three decode steps through the cache, teacher-forced on the
    # program's own tokens, against one full forward of the reference
    seqs = [list(toks[r, :n]) for r, n in enumerate(lens)]
    pos = np.asarray(lens, np.int32)
    for _ in range(3):
        for r in range(2):
            seqs[r].append(int(np.asarray(ids)[r]))
        ids, logits, caches = dec.decode_step(caches, ids, pos)
        pos = pos + 1
        full = np.zeros((2, 32), np.int32)
        for r in range(2):
            full[r, :len(seqs[r])] = seqs[r]
        ref = _ref_logits(sizes, full)
        for r in range(2):
            np.testing.assert_allclose(np.asarray(logits)[r],
                                       ref[r, len(seqs[r]) - 1],
                                       rtol=1e-4, atol=2e-5)


def test_fit_batch_steps_agree_in_loss_and_updated_weights():
    from deeplearning4j_tpu.ops.dataset import DataSet
    from benchmark.harness import loadgen
    net, sizes = program.build_net(tiny.TINY_CONFIG, SEED, train=True)
    adam = tiny.TINY_CONFIG["run"]["optimizer"]
    batches = loadgen.train_batches(tiny.TINY_TRAFFIC["tiny-train"],
                                    sizes["vocab"], SEED, 2)
    losses = []
    for x, y in batches:
        net.fit_batch(DataSet(jnp.asarray(x), jnp.asarray(y)))
        losses.append(float(net.score_value))
    ref = reference.train_steps(sizes, SEED, batches, adam)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-6)
    want = wgen.program_tree(*ref["params"])
    for vertex, leaves in want.items():
        for name, value in leaves.items():
            np.testing.assert_allclose(
                np.asarray(net.params[vertex][name]), np.asarray(value),
                rtol=1e-4, atol=1e-7, err_msg=f"{vertex}.{name}")
    # the weights moved by about the learning rate per step, not by nothing
    moved = reference.change_norms(
        sizes, SEED, *wgen.canonical_view(net.params, sizes["layers"]))
    assert (moved > 0).all()


def test_lower_precisions_are_coarser_than_the_reference(served):
    _, sizes = served
    toks = np.random.default_rng(2).integers(
        0, sizes["vocab"], (2, 24)).astype(np.int32)
    exact = _ref_logits(sizes, toks)
    errs = {}
    for prec in ("bfloat16", "fp8"):
        end, x = reference.hidden_states(sizes, SEED, jnp.asarray(toks), prec)
        errs[prec] = float(np.abs(np.asarray(
            reference.head(end, x, prec)) - exact).max())
    assert 0 < errs["bfloat16"] < errs["fp8"]
    assert errs["fp8"] > 4 * errs["bfloat16"]
