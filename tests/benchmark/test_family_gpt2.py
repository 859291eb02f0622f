"""The move of GPT-2's code behind the family interface moved nothing: the
``gpt2`` family, found by name as a run finds it, gives at ``tiny.py``'s size
what PR 28's parent gave (``gpt2_parent.json``, recorded before a line
moved): the seed's weights bit for bit, whole and block by block, the
reference's logits, its three training losses and per-leaf norms, and the
operation counts of both GPT-2 configurations."""

import json

import numpy as np
import pytest

import gpt2_readings
import tiny
from benchmark.harness import loadgen


@pytest.fixture(scope="module")
def parent():
    with open(gpt2_readings.FIXTURE, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def now():
    fam = tiny.family()
    return gpt2_readings.readings(fam.weights, fam.reference, fam.flops,
                                  loadgen)


def test_sizes_are_read_from_the_same_keys(parent, now):
    assert now["sizes"] == parent["sizes"]
    for name in ("gpt2-medium", "gpt2-large"):
        assert now["flops"][name]["sizes"] == parent["flops"][name]["sizes"]


@pytest.mark.parametrize("how", ["whole", "whole_bfloat16", "block_by_block",
                                 "other_seed_block_0"])
def test_seeds_weights_are_bit_for_bit_the_parents(parent, now, how):
    assert now["weights"][how] == parent["weights"][how]
    if how == "block_by_block":
        assert now["weights"][how] == now["weights"]["whole"]


def test_reference_logits_are_the_parents(parent, now):
    assert now["logits"]["best_token"] == parent["logits"]["best_token"]
    for key in ("last_position", "best_logit"):
        np.testing.assert_allclose(now["logits"][key], parent["logits"][key],
                                   rtol=1e-6, atol=1e-7, err_msg=key)


def test_reference_training_steps_are_the_parents(parent, now):
    assert now["train"]["leaf_names"] == parent["train"]["leaf_names"]
    np.testing.assert_allclose(now["train"]["losses"],
                               parent["train"]["losses"], rtol=1e-6)
    for key in ("grad_norms", "change_norms"):
        np.testing.assert_allclose(now["train"][key], parent["train"][key],
                                   rtol=1e-5, err_msg=key)
    assert len(now["train"]["grad_norms"]) == 6 + 13 * 2


@pytest.mark.parametrize("config", ["gpt2-medium", "gpt2-large"])
def test_operation_counts_are_the_parents(parent, now, config):
    assert now["flops"][config] == parent["flops"][config]
    # and the protocol's names are these very functions
    fam = tiny.family()
    s = fam.sizes_of(gpt2_readings._config(config))
    assert fam.prompt_flops(s, 192) == parent["flops"][config][
        "prompt_flops_192"]
    assert fam.decode_flops(s, 192, 96) == parent["flops"][config][
        "decode_flops_192_96"]
    assert fam.train_token_flops(s, 1024) == parent["flops"][config][
        "train_token_flops_1024"]
    assert fam.total_params(s) == parent["flops"][config]["total_params"]


def test_protocols_leaf_views_are_the_modules_own(now):
    """``canonical_view`` / ``leaf_norms`` / ``change_norms`` /
    ``flat_names`` as the runner calls them give what the moved modules
    give under their old signatures."""
    fam = tiny.family()
    net, sizes = fam.program.build_net(tiny.TINY_CONFIG, gpt2_readings.SEED,
                               train=False)
    view = fam.canonical_view(net.params, sizes)
    end, blocks = fam.weights.canonical_view(net.params, sizes["layers"])
    assert fam.flat_names(sizes) == now["train"]["leaf_names"]
    np.testing.assert_array_equal(fam.leaf_norms(view),
                                  fam.reference.leaf_norms(end, blocks))
    moved = fam.change_norms(sizes, gpt2_readings.SEED, view)
    assert moved.shape == (len(fam.flat_names(sizes)),)
    assert (moved == 0).all()            # the seed's own weights, unmoved
