"""The ``shortcut_moe`` family against ``families/README.md`` and against
itself: found by name as a run finds it, sizes read from the published keys
(the share written as the published file writes it), the seed's weights the
same whole and piece by piece, the program's tree matched leaf for leaf; the
plain reference (which imports nothing of the program) against the program
on seeded weights at a tiny size — every new layer, the full forward,
prefill then decode through the eight latent slabs — with the
lower-precision control and the faults that bite in the published cut
judged not correct by tiny limits; the share test of ``model-configs`` §4
with the zero-compute term counted once; hand counts of the published
configuration's parameters and operations; and the readers of
``readers/scmoe.py`` on a hand-made trace."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import tiny_shortcut
from benchmark.harness import manifest as mf

SEED = 2 ** 31 + 77
#: float32 against float32, the same arithmetic in another order: 1e-6 on
#: values of order 1 (readings 3e-8 to 5e-7)
TIGHT = 2e-6


@pytest.fixture(scope="module")
def fam():
    return tiny_shortcut.family()


@pytest.fixture(scope="module")
def built(fam):
    net, sizes, shapes = fam.make_net(tiny_shortcut.CONFIG)
    fam.install(net, tiny_shortcut.CONFIG, sizes, shapes, SEED, train=False)
    from deeplearning4j_tpu.models import TransformerDecoder
    return net, sizes, TransformerDecoder(net, t_max=64)


def _published():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "longcat-flash-chat.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the protocol
def test_family_is_found_by_name_and_brings_the_protocol(fam):
    assert fam.__file__ == os.path.join(
        tiny.ROOT, "benchmark", "families", "shortcut_moe", "__init__.py")
    assert _published()["family"] == "shortcut_moe"
    for name in ("train_steps", "canonical_view", "leaf_norms",
                 "change_norms", "flat_names"):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            getattr(fam, name)()


def test_reference_imports_nothing_of_the_program(fam):
    for mod in (fam.reference, fam.weights, fam.flops):
        with open(mod.__file__) as f:
            assert "deeplearning4j_tpu" not in f.read(), mod.__file__


def test_sizes_are_read_from_the_published_keys(fam):
    s = fam.sizes_of(tiny_shortcut.CONFIG)
    assert (s["d"], s["heads"], s["layers"], s["dense_ffn"]) == \
        (32, 4, 2, 64)
    assert (s["experts"], s["zero"], s["top_k"], s["experts_held"],
            s["first_expert"], s["t_max"]) == (8, 4, 3, 8, 0, 128)
    assert s["q_scale"] == pytest.approx((32 / 24) ** 0.5)
    assert s["kv_scale"] == pytest.approx(2 ** 0.5)
    off = fam.sizes_of(dict(tiny_shortcut.CONFIG, mla_scale_q_lora=False,
                            mla_scale_kv_lora=False))
    assert (off["q_scale"], off["kv_scale"]) == (1.0, 1.0)
    share = fam.sizes_of(tiny_shortcut.config(first=2, held=2))
    assert (share["experts"], share["first_expert"],
            share["experts_held"]) == (8, 2, 2)
    pub = fam.sizes_of(_published())
    assert (pub["experts"], pub["zero"], pub["experts_held"],
            pub["first_expert"], pub["vocab"], pub["layers"]) == \
        (512, 256, 16, 0, 16384, 4)
    assert pub["q_scale"] == 2.0
    assert pub["kv_scale"] == pytest.approx(3.4641, abs=1e-4)


def test_weights_whole_and_piece_by_piece_are_the_same_numbers(fam, built):
    net, sizes, _ = built
    end, blocks = fam.weights.everything(sizes, SEED)
    tree = fam.weights.program_tree(end, blocks)
    assert set(tree) == set(net.params)
    for name, leaves in tree.items():
        assert set(leaves) == set(net.params[name]), name
        for leaf, a in leaves.items():
            np.testing.assert_array_equal(a, net.params[name][leaf])
    for i, b in enumerate(blocks):
        assert tuple(b) == fam.weights.PIECES
        for name in fam.weights.PIECES:
            again = fam.weights.piece(sizes, SEED, i, name)
            assert set(again) == set(b[name])
            for k in again:
                np.testing.assert_array_equal(again[k], b[name][k])
    moe = blocks[1]["moe"]
    assert moe["wr"].shape == (32, 12) and moe["b"].shape == (12,)
    # for choosing only, and small against a softmax score (1/12 here)
    assert 0 < float(jnp.abs(moe["b"]).min())
    assert float(jnp.abs(moe["b"]).max()) < 1e-3
    rounded = fam.weights.piece(sizes, SEED, 1, "ffn0", jnp.bfloat16)
    np.testing.assert_array_equal(
        rounded["wg"], blocks[1]["ffn0"]["wg"].astype(jnp.bfloat16))
    other = fam.weights.piece(sizes, SEED + 1, 1, "ffn0")
    assert float(jnp.abs(other["wg"] - blocks[1]["ffn0"]["wg"]).max()) > 0
    assert float(jnp.abs(blocks[1]["attn0"]["wo"]
                         - blocks[1]["attn1"]["wo"]).max()) > 0
    # a share's experts are the whole model's, cut out; its router is whole
    part = fam.weights.piece(
        fam.sizes_of(tiny_shortcut.config(first=2, held=2)), SEED, 1, "moe")
    np.testing.assert_array_equal(part["wd"], moe["wd"][2:4])
    np.testing.assert_array_equal(part["wr"], moe["wr"])
    assert fam.total_params(sizes) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(net.params))


def test_install_refuses_a_tree_of_another_shape(fam):
    net, sizes, shapes = fam.make_net(tiny_shortcut.CONFIG)
    with pytest.raises(RuntimeError, match="differ in structure or shape"):
        fam.install(net, tiny_shortcut.CONFIG, dict(sizes, expert_ffn=8),
                    shapes, SEED, train=False)
    with pytest.raises(NotImplementedError):
        fam.install(net, tiny_shortcut.CONFIG, sizes, shapes, SEED,
                    train=True)


def test_install_frees_the_weights_it_replaces(fam):
    net, sizes, shapes = fam.make_net(tiny_shortcut.CONFIG)
    fam.install(net, tiny_shortcut.CONFIG, sizes, shapes, SEED, train=False)
    kept = net.params                     # as a decoder's cast cache keeps it
    fam.install(net, tiny_shortcut.CONFIG, sizes, shapes, SEED + 1,
                train=False)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(kept))
    assert not any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(net.params))


# ------------------------------------------- reference against the program
def _x(shape, key=5):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_each_new_layer_equals_the_reference(fam, built):
    """Latent attention with its two scales (decompressed), the dense FFN
    and the expert branch — softmax over routed and zero-compute experts, a
    nonzero bias for choosing only, scaled and not renormalised, the
    identity term — each on seeded weights."""
    net, sizes, _ = built
    ref, conf, p = fam.reference, net.conf.vertices, net.params
    x = _x((2, 12, 32))
    w = {name: fam.weights.piece(sizes, SEED, 1, name)
         for name in fam.weights.PIECES}
    np.testing.assert_allclose(
        conf["ln1c"].layer.forward(p["ln1c"], {}, x)[0],
        ref.rms_norm(x, w["attn1"]["ln_g"], sizes["eps"]), atol=TIGHT)
    for vertex, piece in (("attn1a", "attn0"), ("attn1b", "attn1")):
        np.testing.assert_allclose(
            conf[vertex].layer.forward(p[vertex], {}, x)[0],
            ref.attention(w[piece], x, sizes, "highest"), atol=TIGHT)
    unscaled = ref.attention(w["attn0"], x, dict(sizes, kv_scale=1.0),
                             "highest")
    assert float(jnp.abs(unscaled - ref.attention(
        w["attn0"], x, sizes, "highest")).max()) > 100 * TIGHT
    f = w["ffn1"]
    np.testing.assert_allclose(
        conf["ffn1b"].layer.forward(p["ffn1b"], {}, x)[0],
        ref.gated(x, f["wg"], f["wu"], f["wd"], "highest"), atol=TIGHT)
    layer = conf["moe1"].layer
    y, load = layer.forward(p["moe1"], layer.init_state(), x)
    np.testing.assert_allclose(y, ref.experts(w["moe"], x, sizes, "highest"),
                               atol=TIGHT)
    assert int(load["expert_tokens"].sum() + load["zero_tokens"]) == \
        2 * 12 * sizes["top_k"]
    g = np.asarray(ref.gates(w["moe"], x.reshape(-1, 32), sizes))
    assert g.shape == (24, 12)
    assert ((g > 0).sum(axis=1) == sizes["top_k"]).all()
    # scaled scores, NOT renormalised: three of twelve softmax scores sum
    # to less than one, so the gates to less than the scaling
    assert (g.sum(axis=1) < sizes["scaling"]).all()
    assert int(load["zero_tokens"]) == int((g[:, 8:] > 0).sum()) > 0
    # b chooses and does not weigh
    big = dict(w["moe"], b=w["moe"]["b"] * 1e3)
    g1 = np.asarray(ref.gates(big, x.reshape(-1, 32), sizes))
    assert ((g > 0) != (g1 > 0)).any()
    both = (g > 0) & (g1 > 0)
    np.testing.assert_array_equal(g[both], g1[both])


def test_prefill_then_decode_through_the_slabs_equals_the_reference(fam,
                                                                    built):
    """The reference's full forward (no cache, decompressed) against the
    program's recompute, its prefill, and every absorbed decode step, on
    logits."""
    _, sizes, dec = built
    rng = np.random.default_rng(0)
    toks = rng.integers(0, sizes["vocab"], (2, 24)).astype(np.int32)
    want = np.asarray(fam.reference.logits(sizes, SEED, toks))
    pad = np.pad(toks, ((0, 0), (0, 8)))
    for t in (1, 9, 24):
        got = dec.recompute_logits(pad, np.array([t, t]))[1]
        np.testing.assert_allclose(got, want[:, t - 1], atol=TIGHT)
    caches = dec.init_cache(2)
    _, l0, caches = dec.prefill(caches, np.pad(toks[:, :8], ((0, 0), (0, 8))),
                                np.array([8, 8]))
    np.testing.assert_allclose(l0, want[:, 7], atol=TIGHT)
    for t in range(8, 24):
        _, lt, caches = dec.decode_step(caches, toks[:, t], np.array([t, t]))
        np.testing.assert_allclose(lt, want[:, t], atol=TIGHT,
                                   err_msg=str(t))


def _program_gap(fam, config, fault=None, seed=SEED):
    """Widest |program logit - reference logit| over a prefill and eight
    decode steps: the comparison the planted faults are judged by.
    ``fault(net)`` plants one in the built program."""
    from deeplearning4j_tpu.models import TransformerDecoder
    net, sizes, shapes = fam.make_net(config)
    fam.install(net, config, sizes, shapes, seed, train=False)
    if fault is not None:
        fault(net)
    dec = TransformerDecoder(net, t_max=64)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, sizes["vocab"], (2, 16)).astype(np.int32)
    want = np.asarray(fam.reference.logits(sizes, seed, toks))
    _, l0, caches = dec.prefill(dec.init_cache(2), toks[:, :8],
                                np.array([8, 8]))
    worst = float(np.abs(np.asarray(l0) - want[:, 7]).max())
    for t in range(8, 16):
        _, lt, caches = dec.decode_step(caches, toks[:, t], np.array([t, t]))
        worst = max(worst, float(np.abs(np.asarray(lt) - want[:, t]).max()))
    return worst


def _zero_term_left_out(net):
    for v in net.conf.vertices.values():
        if getattr(getattr(v, "layer", None), "zero_experts", 0):
            layer, real = v.layer, type(v.layer).route

            def route(params, x, layer=layer, real=real):
                chosen, gates = real(layer, params, x)
                return chosen, jnp.where(chosen >= layer.num_experts, 0.0,
                                         gates)
            object.__setattr__(layer, "route", route)


def _one_held_expert_left_out(net):
    for name, p in net.params.items():
        if name.startswith("moe"):
            p["Wd"] = p["Wd"].at[0].set(0.0)


def _shortcut_left_out(net):
    for name, inputs in net.conf.vertex_inputs.items():
        if name.startswith("res") and name.endswith("d"):
            assert inputs[-1].startswith("moe")
            del inputs[-1]


def _kv_scale_left_at_one(net):
    for v in net.conf.vertices.values():
        if hasattr(getattr(v, "layer", None), "kv_scale"):
            v.layer.kv_scale = 1.0


@pytest.mark.parametrize("fault", [
    None, "bfloat16", _zero_term_left_out, _one_held_expert_left_out,
    _shortcut_left_out, _kv_scale_left_at_one],
    ids=lambda f: getattr(f, "__name__", str(f)).strip("_"))
def test_program_against_reference_sound_control_and_planted_faults(fam,
                                                                    fault):
    """Sound, the logits agree to float32 round-off (the tiny limit:
    TIGHT); the program in bfloat16 and each fault the published cut can
    have — the zero-compute term left out, one held expert left out for the
    tokens that chose it, the shortcut left out of the block's sum,
    ``kv_scale`` left at 1 — pass it by orders of magnitude. (A token
    altered where it is produced: the calibration test below.)"""
    config = tiny_shortcut.config()
    if fault == "bfloat16":
        config["run"]["compute_dtype"] = "bfloat16"
        fault = None
        gap = _program_gap(fam, config)
        assert gap > 1e-3
        return
    gap = _program_gap(fam, config, fault)
    if fault is None:
        assert gap <= TIGHT
    else:
        assert gap > 1e-4, gap


def test_shares_of_the_experts_add_up_to_the_uncut_branch(fam):
    """``model-configs`` §4: four shares of two experts each route over all
    twelve outputs and return their own experts' part plus the zero-compute
    term, which every chip computes whole; the routed parts plus the
    zero-compute term counted ONCE add up to the uncut reference's branch.
    The reference is given the same shares."""
    from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
    whole = fam.sizes_of(tiny_shortcut.CONFIG)
    w = fam.weights.piece(whole, SEED, 1, "moe")
    x = _x((2, 10, 32), 9)
    want = np.asarray(fam.reference.experts(w, x, whole, "highest"))
    g = fam.reference.gates(w, x.reshape(-1, 32), whole)
    zero = np.asarray(jnp.sum(g[:, 8:], axis=-1, keepdims=True)
                      * x.reshape(-1, 32)).reshape(x.shape)
    assert float(np.abs(zero).max()) > 0.1
    total, total_ref, routed_to, zero_to = 0.0, 0.0, 0, []
    for first in (0, 2, 4, 6):
        s = fam.sizes_of(tiny_shortcut.config(first=first, held=2))
        ws = fam.weights.piece(s, SEED, 1, "moe")
        layer = RoutedExpertsLayer(
            n_in=32, n_out=32, num_experts=8, zero_experts=4,
            top_k=s["top_k"], expert_hidden=16, shared_experts=0,
            score_function="softmax", renormalize=False,
            routed_scaling=s["scaling"], first_expert=first, experts_held=2)
        p = {"Wr": ws["wr"], "b": ws["b"], "Wg": ws["wg"], "Wu": ws["wu"],
             "Wd": ws["wd"]}
        y, load = layer.forward(p, layer.init_state(), x)
        ref_part = np.asarray(fam.reference.experts(ws, x, s, "highest"))
        np.testing.assert_allclose(y, ref_part, atol=TIGHT)
        total, total_ref = total + np.asarray(y), total_ref + ref_part
        routed_to += int(load["expert_tokens"][first:first + 2].sum())
        zero_to.append(int(load["zero_tokens"]))
    np.testing.assert_allclose(total - 3 * zero, want, atol=4 * TIGHT)
    np.testing.assert_allclose(total_ref - 3 * zero, want, atol=4 * TIGHT)
    assert len(set(zero_to)) == 1                  # every chip the same
    assert routed_to + zero_to[0] == 2 * 10 * whole["top_k"]   # none dropped


def test_reference_takes_the_experts_a_few_at_a_time(fam, monkeypatch):
    sizes = fam.sizes_of(tiny_shortcut.CONFIG)
    whole = fam.weights.piece(sizes, SEED, 1, "moe")
    rest = fam.weights.piece(sizes, SEED, 1, "moe", stacks=False)
    assert set(whole) - set(rest) == {"wg", "wu", "wd"}
    part = fam.weights.experts(sizes, SEED, 1, 3, 4)
    for k in ("wg", "wu", "wd"):
        np.testing.assert_array_equal(part[k], whole[k][3:7])
    x = _x((2, 9, 32), 3)
    want = np.asarray(fam.reference.experts(whole, x, sizes, "highest"))
    monkeypatch.setattr(fam.reference, "EXPERTS_AT_ONCE", 3)
    asked = []

    def stacks(first, count):
        asked.append((first, count))
        return fam.weights.experts(sizes, SEED, 1, first, count)
    got = fam.reference.experts(rest, x, sizes, "highest", stacks)
    assert asked == [(0, 3), (3, 3), (6, 2)]
    np.testing.assert_allclose(got, want, atol=TIGHT)
    share = fam.sizes_of(tiny_shortcut.config(first=4, held=4))
    del asked[:]
    fam.reference.experts(rest, x, share, "highest", stacks)
    assert asked == [(4, 3), (7, 1)]


def test_served_gap_is_the_root_mean_square_of_the_served_tokens_gaps(fam):
    sound = np.array([0, 0, 0.3, 0, 0, 0, 0.4, 0, 0, 0])
    said = fam.reference.gap_statistics(sound)
    assert said["rms"] == pytest.approx(0.5 / np.sqrt(10))
    assert said["agree_share"] == pytest.approx(0.8)
    assert said["widest"] == pytest.approx(0.4)
    assert fam.reference.gap_statistics(np.zeros(0))["rms"] == float("inf")


# ---------------------------------------------- the runner, at a tiny size
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_shortcut.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_run_of_a_tiny_cell_is_correct_and_reports_the_counters(root):
    out = tiny.drive(root, tiny_shortcut.CELL, seed=2 ** 31 + 5, seconds=1.0,
                     trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 30
    zero = out["metrics"]["zero_expert_share.chat-decode"]
    assert zero["unit"] == "%" and 20 <= zero["value"] <= 46   # 4 of 12
    held = out["metrics"]["held_experts_hit_share.chat-decode"]
    assert 100 / 8 <= held["value"] <= 100
    assert "serve.mfu.chat" in out["metrics"]
    # no TPU plane in a CPU trace: the device readers leave their metrics
    # out, never report 0
    for name in ("scmoe_token_ms.chat-decode", "scmoe_roofline.chat-decode",
                 "dense_ffn_token_ms.chat-decode",
                 "latent_attn_token_ms.chat-decode", "decode_token_ms"):
        assert name not in out["metrics"]


def test_tiny_cell_in_lower_precision_is_not_correct(root):
    def lower(ctx):
        ctx.config["run"]["compute_dtype"] = "bfloat16"
    out = tiny.drive(root, tiny_shortcut.CELL, seed=11, seconds=1.0,
                     prepare=lower)
    assert out["correct"] is False, out["compared"]


def test_calibration_as_committed_reads_this_cell(root, monkeypatch, capsys):
    """``calibrate.py`` keeps one engine across seeds and runs the reference
    beside it; the fp8 control and the altered token are judged not
    correct, the sound seeds correct."""
    from benchmark import calibrate, run as bench_run
    monkeypatch.setattr(calibrate, "_ROOT", root)
    monkeypatch.setattr(bench_run, "configure_cache", lambda: None)
    monkeypatch.setattr(bench_run, "find_chips", lambda chips: (
        dict(tiny.FAKE_DEVICE), dict(tiny.FAKE_PEAK)))
    assert calibrate.main(["--workload", tiny_shortcut.CELL, "--seeds",
                           "41,42,43", "--seconds", "1", "--control-seeds",
                           "1", "--fault-seeds", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["seed"] for x in lines] == [41, 42, 43]
    assert [x["failed"] for x in lines] == [0, 0, 0]
    assert lines[0]["verdict"] == {"program": True, "control": False}
    assert lines[1]["verdict"] == {"program": True}
    assert lines[2]["verdict"] == {"token_altered": False}


# ------------------------------------------------------------- hand counts
def test_hand_count_of_the_parameters_cut_and_whole(fam):
    config = _published()
    s = fam.sizes_of(config)
    attn = 6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 \
        + 512 * 64 * 256 + 64 * 128 * 6144
    assert attn == 90572800 == fam.flops.attention_params(s)
    dense = 3 * 6144 * 12288
    assert dense == 226492416
    expert = 3 * 6144 * 2048
    assert expert == 37748736
    outside = 2 * attn + 2 * dense + 6144 * 768 + 768 + 4 * 6144
    assert outside == 638874368
    ends = 2 * 16384 * 6144 + 6144
    cut = 4 * (outside + 16 * expert) + ends
    assert fam.total_params(s) == cut == 5172749312
    held = config["run"]["held_on_device_bytes"]
    assert held["parameters"] == cut
    assert held["weights_bfloat16"] == 2 * cut > 10e9
    assert held["latent_slab_16_slots_x_1536"] == 8 * 576 * 2 * 1536 * 16
    pub = config["published"]
    whole = fam.total_params(dict(s, layers=pub["num_layers"],
                                  experts_held=pub["n_routed_experts"],
                                  vocab=pub["vocab_size"]))
    assert whole == 28 * (outside + 512 * expert) + 2 * 131072 * 6144 + 6144
    assert round(whole / 1e9, 1) == 560.7
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert mf.Manifest(tiny.ROOT).config("longcat-flash-chat")[
        "run"]["engine"] == {"num_slots": 16, "t_max": 1536, "block_size": 4}
    # every number of the catalog's row, unchanged but for the three cuts
    catalog = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert pub[key] == value and config[key] < value
        else:
            assert config[key] == value, key


def test_hand_count_of_decode_and_prompt_operations(fam):
    s = fam.sizes_of(_published())
    attn = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 64 * 128 * 6144 \
        + 512 * 64 * 256
    dense = 3 * 6144 * 12288
    # the router, a quarter of an expert (12 x 16 / 768), four zero rows
    moe = 6144 * 768 + 0.25 * 3 * 6144 * 2048 + 4 * 6144
    layer = 2 * attn + 2 * dense + moe
    step = 2 * (4 * layer + 6144 * 16384)
    per_key_absorbed = 2 * 4 * 2 * 64 * (576 + 512)
    ctx = 255 * 128 + 255 * 256 // 2
    assert fam.decode_flops(s, 128, 256) == pytest.approx(
        255 * step + per_key_absorbed * ctx, rel=1e-12)
    assert fam.decode_flops(s, 128, 1) == 0
    per_key = 2 * 4 * 2 * 64 * (192 + 128)            # decompressed
    assert fam.prompt_flops(s, 128) == pytest.approx(
        128 * 2 * 4 * layer + 2 * 6144 * 16384
        + per_key * (128 * 129 // 2), rel=1e-12)
    need = fam.flops.moe_decode_need(
        s, step_layers=4, assignments=4 * 8 * 12, held_assignments=9,
        zero_assignments=130, experts_hit=7)
    expert = 3 * 6144 * 2048
    assert need["flops"] == 2 * expert * 9 + 2 * 6144 * 768 * 32 \
        + 2 * 6144 * 130
    assert need["bytes"] == 2 * (expert * 7 + 6144 * 768 * 4
                                 + 2 * 6144 * 9)


# ------------------------------------------------ the new per-layer readers
def test_readers_find_the_branch_and_the_dense_ffns_inside_decode_blocks(
        fam):
    """A hand-made device trace: two executions of the decode block, each
    with the scan's own event (left out: it spans its body), one call of the
    named kernel, the router's product (by its width), the top-k (by the
    [lanes, top_k] result), the row gather (by the kernel's own row operand),
    a dense FFN's products (by their weights), a read of a latent slab and
    the head (none of them); a kernel call of an admission outside any
    block is not a decoded step's."""
    from benchmark.harness import trace_reduce
    man = mf.Manifest(tiny.ROOT)
    s = fam.sizes_of(_published())
    slab = "bf16[16,1,1536,576]{3,2,1,0}"
    kernel = ("%moe_expert_ffn.3 = bf16[432,6144]{1,0} custom-call("
              "s32[27]{0} %te, s32[1]{0} %used, bf16[432,6144]{1,0} %x, "
              "bf16[16,6144,2048]{2,1,0} %wg, bf16[16,6144,2048]{2,1,0} %wu, "
              "bf16[16,2048,6144]{2,1,0} %wd), "
              'custom_call_target="tpu_custom_call"')
    ops, modules = [], []
    k = 200.0          # a step-layer's bytes take 0.2 ms: times to match
    for b0 in (1000.0 * k, 31000.0 * k):
        modules.append((b0, 28000.0 * k, "jit_decode_block4_impl(123)"))
        ops += [(b0, 27900.0 * k, f"%while.5 = (s32[], {slab}) while("
                 f"(s32[], {slab}) %tuple.3), condition=%c, body=%b"),
                (b0 + 100 * k, 2000.0 * k, kernel),
                (b0 + 2200 * k, 300.0 * k, "%fusion.7 = f32[16,768]{1,0} "
                 "fusion(bf16[16,6144]{1,0} %h, bf16[6144,768]{1,0} %wr)"),
                (b0 + 2600 * k, 40.0 * k, "%fusion.8 = (f32[16,12]{1,0}, "
                 "s32[16,12]{1,0}) fusion(f32[16,768]{1,0} %scores)"),
                (b0 + 2700 * k, 60.0 * k, "%gather.2 = bf16[432,6144]{1,0} "
                 "gather(bf16[16,6144]{1,0} %h, s32[432]{0} %src)"),
                (b0 + 2800 * k, 20.0 * k, "%sort.1 = (s32[192]{0}, "
                 "s32[192]{0}) sort(s32[192]{0} %local, s32[192]{0} %iota)"),
                (b0 + 3000 * k, 900.0 * k, "%fusion.12 = bf16[16,12288]{1,0}"
                 " fusion(bf16[16,6144]{1,0} %n, bf16[6144,12288]{1,0} %wg, "
                 "bf16[6144,12288]{1,0} %wu)"),
                (b0 + 4000 * k, 500.0 * k, "%fusion.13 = bf16[16,6144]{1,0} "
                 "fusion(bf16[16,12288]{1,0} %hid, bf16[12288,6144]{1,0} "
                 "%wd)"),
                (b0 + 4600 * k, 250.0 * k, f"%fusion.9 = f32[16,64,1,1536]"
                 f"{{3,2,1,0}} fusion(bf16[16,1,64,576]{{3,2,1,0}} %q, "
                 f"{slab} %kv)"),
                (b0 + 5000 * k, 700.0 * k, "%fusion.11 = bf16[16,16384]{1,0} "
                 "fusion(bf16[16,6144]{1,0} %h, bf16[6144,16384]{1,0} %w)")]
    modules.append((70000.0 * k, 9000.0 * k, "jit_prefill_slots_impl(456)"))
    ops.append((70100.0 * k, 5000.0 * k, kernel))
    trace = trace_reduce.Trace((0.0, 90000.0 * k), {"/device:TPU:0": ops},
                               {"/device:TPU:0": modules}, [])
    stats = {"moe_step_layers": 8, "moe_assignments": 8 * 9 * 12,
             "moe_held_assignments": 19, "moe_zero_assignments": 290,
             "moe_experts_hit": 17, "moe_experts_read": 28}
    ctx = types.SimpleNamespace(
        trace=trace, sizes=s, family=fam, peak=mf.peaks("TPU v5 lite"),
        engine_options={"t_max": 1536, "num_slots": 16}, engine_stats=stats)
    branch = (2000.0 + 300.0 + 40.0 + 60.0 + 20.0) * k
    assert man.reader("scmoe_token_ms.chat-decode")(ctx) == pytest.approx(
        2 * branch / 1e6 / 8)
    assert man.reader("dense_ffn_token_ms.chat-decode")(ctx) == \
        pytest.approx(2 * 1400.0 * k / 1e6 / 8)
    assert man.reader("latent_attn_token_ms.chat-decode")(ctx) == \
        pytest.approx(2 * 250.0 * k / 1e6 / 8)
    assert man.reader("prefill_ms.chat-decode")(ctx) == pytest.approx(
        9000.0 * k / 1e6)
    assert man.reader("zero_expert_share.chat-decode")(ctx) == \
        pytest.approx(100.0 * 290 / 864)
    assert man.reader("held_experts_hit_share.chat-decode")(ctx) == \
        pytest.approx(100.0 * 17 / (16 * 8))
    need = fam.flops.moe_decode_need(s, 8, 864, 19, 290, 17)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9) / 8
    share = man.reader("scmoe_roofline.chat-decode")(ctx)
    assert share == pytest.approx(100.0 * least / (branch / 1e9))
    assert 0 < share < 100
    # a program without the counters (the parent), or a run without a
    # trace: nothing, and no raise
    ctx.engine_stats = {k: v for k, v in stats.items()
                        if k not in ("moe_held_assignments",
                                     "moe_zero_assignments")}
    for name in ("scmoe_roofline.chat-decode", "zero_expert_share.chat-decode",
                 "held_experts_hit_share.chat-decode"):
        assert man.reader(name)(ctx) is None
    ctx.trace = None
    for name in ("scmoe_token_ms.chat-decode", "scmoe_roofline.chat-decode",
                 "dense_ffn_token_ms.chat-decode"):
        assert man.reader(name)(ctx) is None
    # another family's sizes (no zero-compute experts, no dense width)
    ctx.trace, ctx.sizes = trace, {"d": 2048, "experts": 256}
    assert man.reader("scmoe_token_ms.chat-decode")(ctx) is None
    assert man.reader("dense_ffn_token_ms.chat-decode")(ctx) is None


def test_the_cell_is_in_the_manifest_with_its_metrics_and_traffic():
    man = mf.Manifest(tiny.ROOT)
    cell = man.cell("longcat-flash-chat.chat-decode")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("longcat-flash-chat", "chat-decode", 1)
    assert [m["name"] for m in man.end_to_end(cell["name"])] == \
        ["tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in man.per_layer(cell["name"])] == [
        "decode_token_ms", "serve.mfu.chat", "idle_share.chat",
        "scmoe_token_ms.chat-decode", "scmoe_roofline.chat-decode",
        "dense_ffn_token_ms.chat-decode", "zero_expert_share.chat-decode",
        "held_experts_hit_share.chat-decode",
        "latent_attn_token_ms.chat-decode", "prefill_ms.chat-decode"]
    for m in man.per_layer(cell["name"]):
        assert callable(man.reader(m["name"]))
    mix = man.traffic("chat-decode")
    assert mix["kind"] == "open_loop"
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.9, "min": 16, "max": 512}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert (mix["temperature"], mix["check_requests"], mix["trace_seconds"],
            mix["trace_lead_seconds"]) == (0.0, 6, 0.6, 5.0)
    eng = man.config("longcat-flash-chat")["run"]["engine"]
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        <= eng["t_max"]
