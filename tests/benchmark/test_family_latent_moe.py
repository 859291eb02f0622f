"""The ``latent_moe`` family against ``families/README.md`` and against
itself: found by name as a run finds it, sizes read from the published keys,
the seed's weights the same whole and layer by layer, the program's tree
matched leaf for leaf; the plain reference (which imports nothing of the
program) against the program on seeded weights at a tiny size — every new
layer, the full forward, prefill then decode through the latent slab — with
the lower-precision control and three planted faults judged not correct by
tiny limits; the share test of ``model-configs`` §4; and hand counts of the
published configuration's parameters and operations."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import tiny_latent
from benchmark.harness import manifest as mf

SEED = 2 ** 31 + 77
#: float32 against float32, the same arithmetic in another order: 1e-6 on
#: values of order 1 (readings 6e-8 to 4e-7)
TIGHT = 2e-6


@pytest.fixture(scope="module")
def fam():
    return tiny_latent.family()


@pytest.fixture(scope="module")
def built(fam):
    net, sizes, shapes = fam.make_net(tiny_latent.CONFIG)
    fam.install(net, tiny_latent.CONFIG, sizes, shapes, SEED, train=False)
    from deeplearning4j_tpu.models import TransformerDecoder
    return net, sizes, TransformerDecoder(net, t_max=64)


# ------------------------------------------------------------ the protocol
def test_family_is_found_by_name_and_brings_the_protocol(fam):
    assert fam.__file__ == os.path.join(
        tiny.ROOT, "benchmark", "families", "latent_moe", "__init__.py")
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        assert json.load(f)["family"] == "latent_moe"
    for name in ("train_steps", "canonical_view", "leaf_norms",
                 "change_norms", "flat_names"):
        with pytest.raises(NotImplementedError, match="served, not trained"):
            getattr(fam, name)()


def test_reference_imports_nothing_of_the_program(fam):
    for mod in (fam.reference, fam.weights, fam.flops):
        with open(mod.__file__) as f:
            assert "deeplearning4j_tpu" not in f.read(), mod.__file__


def test_sizes_are_read_from_the_published_keys(fam):
    s = fam.sizes_of(tiny_latent.CONFIG)
    assert (s["d"], s["heads"], s["layers"], s["dense_layers"]) == \
        (32, 4, 3, 1)
    assert (s["experts"], s["top_k"], s["experts_held"], s["t_max"]) == \
        (8, 3, 8, 128)
    share = fam.sizes_of(tiny_latent.config(first=2, held=2))
    assert (share["first_expert"], share["experts_held"]) == (2, 2)


def test_weights_whole_and_layer_by_layer_are_the_same_numbers(fam, built):
    net, sizes, _ = built
    end, blocks = fam.weights.everything(sizes, SEED)
    tree = fam.weights.program_tree(end, blocks)
    for name, leaves in tree.items():
        for leaf, a in leaves.items():
            np.testing.assert_array_equal(a, net.params[name][leaf])
    for i, b in enumerate(blocks):
        again = fam.weights.layer(sizes, SEED, i)
        assert set(again) == set(b)
        for k in b:
            np.testing.assert_array_equal(again[k], b[k])
    assert "wr" not in blocks[0] and "wr" in blocks[1]
    rounded = fam.weights.layer(sizes, SEED, 1, jnp.bfloat16)
    np.testing.assert_array_equal(rounded["wg"],
                                  blocks[1]["wg"].astype(jnp.bfloat16))
    other = fam.weights.layer(sizes, SEED + 1, 1)
    assert float(jnp.abs(other["wg"] - blocks[1]["wg"]).max()) > 0
    assert float(jnp.abs(blocks[1]["b"]).min()) > 0     # for choosing only
    # a share's experts are the whole model's, cut out
    part = fam.weights.layer(
        fam.sizes_of(tiny_latent.config(first=2, held=2)), SEED, 1)
    np.testing.assert_array_equal(part["wd"], blocks[1]["wd"][2:4])
    assert fam.total_params(sizes) == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(net.params))


def test_install_refuses_a_tree_of_another_shape(fam):
    net, sizes, shapes = fam.make_net(tiny_latent.CONFIG)
    with pytest.raises(RuntimeError, match="differ in structure or shape"):
        fam.install(net, tiny_latent.CONFIG, dict(sizes, expert_ffn=8),
                    shapes, SEED, train=False)
    with pytest.raises(NotImplementedError):
        fam.install(net, tiny_latent.CONFIG, sizes, shapes, SEED, train=True)


# ------------------------------------------- reference against the program
def _x(shape, key=5):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_each_new_layer_equals_the_reference(fam, built):
    """RMSNorm, the rotary pairing, latent attention (decompressed), the
    gated FFN and the expert layer with its nonzero bias, its scaling and
    its shared expert, each on seeded weights."""
    from deeplearning4j_tpu.nn.conf.layers.latent_attention import rope
    net, sizes, _ = built
    ref, conf, p = fam.reference, net.conf.vertices, net.params
    x = _x((2, 12, 32))
    w1 = fam.weights.layer(sizes, SEED, 1)
    np.testing.assert_allclose(
        conf["ln1a"].layer.forward(p["ln1a"], {}, x)[0],
        ref.rms_norm(x, w1["ln1_g"], sizes["eps"]), atol=TIGHT)
    pos = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32)[None], (2, 12))
    r = _x((2, 12, 4, 4), 6)
    np.testing.assert_allclose(rope(r, pos, sizes["theta"]),
                               ref.rope(r, sizes["theta"]), atol=TIGHT)
    np.testing.assert_allclose(
        conf["attn1"].layer.forward(p["attn1"], {}, x)[0],
        ref.attention(w1, x, sizes, "highest"), atol=TIGHT)
    w0 = fam.weights.layer(sizes, SEED, 0)
    np.testing.assert_allclose(
        conf["ffn0"].layer.forward(p["ffn0"], {}, x)[0],
        ref.gated(x, w0["wg"], w0["wu"], w0["wd"], "highest"), atol=TIGHT)
    layer = conf["ffn1"].layer
    y, load = layer.forward(p["ffn1"], layer.init_state(), x)
    np.testing.assert_allclose(y, ref.experts(w1, x, sizes, "highest"),
                               atol=TIGHT)
    assert int(load["expert_tokens"].sum()) == 2 * 12 * sizes["top_k"]
    g = np.asarray(ref.gates(w1, x.reshape(-1, 32), sizes))
    assert ((g > 0).sum(axis=1) == sizes["top_k"]).all()
    np.testing.assert_allclose(g.sum(axis=1), sizes["scaling"], rtol=1e-6)
    # b chooses and does not weigh: without it other experts are chosen
    g0 = np.asarray(ref.gates(dict(w1, b=jnp.zeros_like(w1["b"])),
                              x.reshape(-1, 32), sizes))
    assert ((g > 0) != (g0 > 0)).any()


def test_prefill_then_decode_through_the_slab_equals_the_reference(fam,
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


def _program_gap(fam, config, seed=SEED):
    """Widest |program logit - reference logit| over a prefill and eight
    decode steps: the comparison the planted faults are judged by."""
    from deeplearning4j_tpu.models import TransformerDecoder
    net, sizes, shapes = fam.make_net(config)
    fam.install(net, config, sizes, shapes, seed, train=False)
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


def _bf16(config):
    config["run"]["compute_dtype"] = "bfloat16"


def _least_weighted_expert_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers.experts import RoutedExpertsLayer
    real = RoutedExpertsLayer.route

    def route(self, params, x):
        chosen, gates = real(self, params, x)
        least = jnp.argmin(gates, axis=-1)
        keep = jnp.arange(gates.shape[1])[None, :] != least[:, None]
        return chosen, gates * keep
    monkeypatch.setattr(RoutedExpertsLayer, "route", route)


def _shared_expert_left_out(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers import experts
    monkeypatch.setattr(experts, "gated_ffn",
                        lambda x, *w: jnp.zeros_like(x))


def _bias_used_in_the_weights(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers.experts import RoutedExpertsLayer

    def route(self, params, x):
        s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, params["Wr"])) \
            + params["b"][None]
        picked, chosen = jax.lax.top_k(s, self.top_k)
        return chosen.astype(jnp.int32), self.routed_scaling * picked \
            / jnp.sum(picked, axis=-1, keepdims=True)
    monkeypatch.setattr(RoutedExpertsLayer, "route", route)


@pytest.mark.parametrize("fault,least", [
    (None, 0.0), ("bfloat16", 1e-3),
    (_least_weighted_expert_left_out, 1e-3),
    (_shared_expert_left_out, 1e-3), (_bias_used_in_the_weights, 1e-4)])
def test_program_against_reference_sound_control_and_planted_faults(
        fam, monkeypatch, fault, least):
    """Sound, the logits agree to float32 round-off (the tiny limit:
    TIGHT); the program in bfloat16 and each planted fault — the
    least-weighted of a token's experts left out, the shared expert left
    out, the selection bias used in the weights — pass it by orders of
    magnitude."""
    config = tiny_latent.config()
    if fault == "bfloat16":
        _bf16(config)
    elif fault is not None:
        fault(monkeypatch)
    gap = _program_gap(fam, config)
    if fault is None:
        assert gap <= TIGHT
    else:
        assert gap > max(least, 50 * TIGHT), gap


def test_shares_of_the_experts_add_up_to_the_uncut_layer(fam):
    """``model-configs`` §4: four shares of two experts each route over all
    eight and return their own experts' part plus the shared expert's; the
    parts add up to the uncut reference's layer, the shared expert counted
    once. The reference is given the same shares."""
    from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
    whole = fam.sizes_of(tiny_latent.CONFIG)
    w = fam.weights.layer(whole, SEED, 1)
    x = _x((2, 10, 32), 9)
    want = np.asarray(fam.reference.experts(w, x, whole, "highest"))
    shared = np.asarray(fam.reference.gated(x, w["sg"], w["su"], w["sd"],
                                            "highest"))
    total, total_ref, routed_to = 0.0, 0.0, 0
    for first in (0, 2, 4, 6):
        s = fam.sizes_of(tiny_latent.config(first=first, held=2))
        ws = fam.weights.layer(s, SEED, 1)
        layer = RoutedExpertsLayer(
            n_in=32, n_out=32, num_experts=8, top_k=s["top_k"],
            expert_hidden=16, routed_scaling=s["scaling"],
            first_expert=first, experts_held=2)
        p = {"Wr": ws["wr"], "b": ws["b"], "Wg": ws["wg"], "Wu": ws["wu"],
             "Wd": ws["wd"], "Sg": ws["sg"], "Su": ws["su"], "Sd": ws["sd"]}
        y, load = layer.forward(p, layer.init_state(), x)
        ref_part = np.asarray(fam.reference.experts(ws, x, s, "highest"))
        np.testing.assert_allclose(y, ref_part, atol=TIGHT)
        total, total_ref = total + np.asarray(y), total_ref + ref_part
        routed_to += int(load["expert_tokens"][first:first + 2].sum())
    np.testing.assert_allclose(total - 3 * shared, want, atol=4 * TIGHT)
    np.testing.assert_allclose(total_ref - 3 * shared, want, atol=4 * TIGHT)
    assert routed_to == 2 * 10 * whole["top_k"]      # nobody dropped


# ---------------------------------------------- the runner, at a tiny size
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_latent.make_root(tmp_path_factory.mktemp("bench"))


def test_sound_run_of_a_tiny_cell_is_correct_and_reports_the_counters(root):
    out = tiny.drive(root, tiny_latent.CELL, seed=2 ** 31 + 5, seconds=1.0,
                     trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 30
    share = out["metrics"]["experts_hit_share.chat-2k"]
    assert share["unit"] == "%" and 100 * 3 / 8 <= share["value"] <= 100
    # no TPU plane in a CPU trace: the device readers leave their metrics
    # out, never report 0
    for name in ("moe_token_ms.chat-2k", "moe_roofline.chat-2k",
                 "latent_attn_token_ms.chat-2k", "decode_token_ms"):
        assert name not in out["metrics"]


def test_tiny_cell_in_lower_precision_is_not_correct(root):
    def lower(ctx):
        ctx.config["run"]["compute_dtype"] = "bfloat16"
    out = tiny.drive(root, tiny_latent.CELL, seed=11, seconds=1.0,
                     prepare=lower)
    assert out["correct"] is False, out["compared"]


def test_control_reads_the_gap_of_the_lower_precisions_first_choice(root):
    def ctrl(ctx):
        ctx.control_precision = "fp8"
    out = tiny.drive(root, tiny_latent.CELL, seed=11, seconds=1.0,
                     prepare=ctrl)
    assert out["correct"] is True
    # a root mean square over 240 tokens, of which fp8 moves a few
    assert out["control"]["served_gap"] > \
        10 * out["compared"]["served_gap"]["limit"]


def test_served_gap_is_the_root_mean_square_of_the_served_tokens_gaps(fam):
    """By hand: 7 tokens that are the reference's choice, two a little
    below it and one altered. The widest gap says 4.0 of all three kinds of
    run; the root mean square tells them apart."""
    sound = np.array([0, 0, 0.3, 0, 0, 0, 0.4, 0, 0, 0])
    said = fam.reference.gap_statistics(sound)
    assert said["rms"] == pytest.approx(0.5 / np.sqrt(10))
    assert said["mean"] == pytest.approx(0.07)
    assert said["agree_share"] == pytest.approx(0.8)
    assert said["widest"] == pytest.approx(0.4)
    altered = sound.copy()
    altered[-1] = 4.0
    assert fam.reference.gap_statistics(altered)["rms"] == pytest.approx(
        np.sqrt(16.25 / 10))
    assert fam.reference.gap_statistics(np.zeros(0))["rms"] == float("inf")


def test_reference_takes_an_expert_layer_a_few_experts_at_a_time(
        fam, monkeypatch):
    """At the published size the reference makes an expert layer's own
    weights 32 experts at a time; here 3 at a time (8 experts: 3 + 3 + 2),
    the same numbers and the same layer as from the whole stacks."""
    sizes = fam.sizes_of(tiny_latent.CONFIG)
    whole = fam.weights.layer(sizes, SEED, 2)
    rest = fam.weights.layer(sizes, SEED, 2, stacks=False)
    assert set(whole) - set(rest) == {"wg", "wu", "wd"}
    part = fam.weights.experts(sizes, SEED, 2, 3, 4)
    for k in ("wg", "wu", "wd"):
        np.testing.assert_array_equal(part[k], whole[k][3:7])
    x = _x((2, 9, 32), 3)
    want = np.asarray(fam.reference.experts(whole, x, sizes, "highest"))
    monkeypatch.setattr(fam.reference, "EXPERTS_AT_ONCE", 3)
    asked = []

    def stacks(first, count):
        asked.append((first, count))
        return fam.weights.experts(sizes, SEED, 2, first, count)
    got = fam.reference.experts(rest, x, sizes, "highest", stacks)
    assert asked == [(0, 3), (3, 3), (6, 2)]
    np.testing.assert_allclose(got, want, atol=TIGHT)
    # a share asks for its own experts, numbered over the whole model
    share = fam.sizes_of(tiny_latent.config(first=4, held=4))
    del asked[:]
    got = fam.reference.experts(rest, x, share, "highest", stacks)
    assert asked == [(4, 3), (7, 1)]
    np.testing.assert_allclose(
        got, fam.reference.experts(
            fam.weights.layer(share, SEED, 2), x, share, "highest"),
        atol=TIGHT)


def test_calibration_as_committed_reads_this_cell(root, monkeypatch, capsys):
    """``calibrate.py`` keeps one engine across seeds and runs the reference
    beside it: ``install`` lets the last seed's weights go although the
    decoder still refers to them, and the next window is sound on the new
    ones; the control and the altered token are judged not correct."""
    from benchmark import calibrate, run as bench_run
    monkeypatch.setattr(calibrate, "_ROOT", root)
    monkeypatch.setattr(bench_run, "configure_cache", lambda: None)
    monkeypatch.setattr(bench_run, "find_chips", lambda chips: (
        dict(tiny.FAKE_DEVICE), dict(tiny.FAKE_PEAK)))
    assert calibrate.main(["--workload", tiny_latent.CELL, "--seeds",
                           "41,42,43", "--seconds", "1", "--control-seeds",
                           "1", "--fault-seeds", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["seed"] for x in lines] == [41, 42, 43]
    assert [x["failed"] for x in lines] == [0, 0, 0]
    assert lines[0]["verdict"] == {"program": True, "control": False}
    assert lines[1]["verdict"] == {"program": True}
    assert lines[2]["verdict"] == {"token_altered": False}
    assert lines[2]["numbers"]["served_gap"] > 0.1


def test_install_frees_the_weights_it_replaces(fam):
    net, sizes, shapes = fam.make_net(tiny_latent.CONFIG)
    fam.install(net, tiny_latent.CONFIG, sizes, shapes, SEED, train=False)
    kept = net.params                     # as a decoder's cast cache keeps it
    fam.install(net, tiny_latent.CONFIG, sizes, shapes, SEED + 1,
                train=False)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(kept))
    assert not any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(net.params))


# ------------------------------------------------------------- hand counts
def _published():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


def test_hand_count_of_the_parameters_cut_and_whole(fam):
    config = _published()
    s = fam.sizes_of(config)
    attn = 3145728 + 1536 + 9437184 + 1179648 + 512 + 4194304 + 8388608
    assert attn == 26347520
    expert_layer = 256 * 4718592 + 4718592 + 2048 * 256 + 256
    assert expert_layer == 1213202688
    ends = 2 * 129280 * 2048 + 2048
    cut = 5 * (attn + 4096) + 44040192 + 4 * expert_layer + ends
    assert fam.total_params(s) == cut == 5558141952
    assert config["run"]["held_on_device_bytes"]["parameters"] == cut
    assert config["run"]["held_on_device_bytes"]["weights_bfloat16"] == \
        2 * cut
    whole = fam.total_params(dict(s, layers=config["published"][
        "num_hidden_layers"]))
    assert whole == 40 * (attn + 4096) + 44040192 + 39 * expert_layer + ends
    assert round(whole / 1e9, 1) == 48.9
    assert config["reduced"] == ["num_hidden_layers",
                                 "num_nextn_predict_layers"]
    assert mf.Manifest(tiny.ROOT).config("joyai-llm-flash")[
        "run"]["engine"] == {"num_slots": 16, "t_max": 2560, "block_size": 4}


def test_hand_count_of_decode_and_prompt_operations(fam):
    s = fam.sizes_of(_published())
    attn = 3145728 + 9437184 + 1179648 + 4194304 + 8388608
    moe = 2048 * 256 + (8 + 1) * 4718592         # router, 8 routed + shared
    step = 2 * (5 * attn + 44040192 + 4 * moe + 2048 * 129280)
    assert step == 1224998912
    per_key_absorbed = 2 * 5 * 32 * (576 + 512)
    ctx = 160 * 512 + 160 * 161 // 2
    assert fam.decode_flops(s, 512, 161) == 160 * step \
        + per_key_absorbed * ctx
    assert fam.decode_flops(s, 512, 1) == 0
    per_key = 2 * 5 * 32 * (192 + 128)                # decompressed
    assert fam.prompt_flops(s, 512) == \
        512 * (step - 2 * 2048 * 129280) + 2 * 2048 * 129280 \
        + per_key * (512 * 513 // 2)
    need = fam.flops.moe_decode_need(s, step_layers=4, assignments=4 * 64,
                                     experts_hit=4 * 50)
    assert need["flops"] == 2 * 4718592 * (256 + 32) + 2 * 2048 * 256 * 32
    assert need["bytes"] == 2 * (4718592 * (200 + 4) + 2048 * 256 * 4)


# ------------------------------------------------ the new per-layer readers
def test_readers_find_expert_and_slab_operations_inside_decode_blocks(fam):
    """A hand-made device trace: two executions of the decode block, each
    with the scan's own event (left out: it spans its body), one call of the
    named kernel, the router's product (by its weight's shape), a read of the
    latent slab and an operation of neither; a kernel call of an admission
    outside any block is not a decoded step's."""
    import types

    from benchmark.harness import trace_reduce
    man = mf.Manifest(tiny.ROOT)
    s = fam.sizes_of(_published())
    slab = "bf16[16,1,2560,576]{3,2,1,0}"
    kernel = ("%moe_expert_ffn.3 = bf16[3968,2048]{1,0} custom-call("
              "s32[248]{0} %te, s32[1]{0} %used, bf16[3968,2048]{1,0} %x, "
              "bf16[256,2048,768]{2,1,0} %wg, bf16[256,2048,768]{2,1,0} %wu, "
              "bf16[256,768,2048]{2,1,0} %wd), "
              'custom_call_target="tpu_custom_call"')
    ops, modules = [], []
    for b0 in (1000.0, 21000.0):
        modules.append((b0, 18000.0, "jit_decode_block4_impl(123)"))
        ops += [(b0, 17900.0, f"%while.5 = (s32[], {slab}) while((s32[], "
                              f"{slab}) %tuple.3), condition=%c, body=%b"),
                (b0 + 100, 2000.0, kernel),
                (b0 + 2200, 300.0, "%fusion.7 = f32[16,256]{1,0} fusion("
                 "bf16[16,2048]{1,0} %h, bf16[2048,256]{1,0} %wr)"),
                (b0 + 2600, 500.0, f"%fusion.9 = f32[16,32,1,2560]"
                 f"{{3,2,1,0}} fusion(bf16[16,1,32,576]{{3,2,1,0}} %q, "
                 f"{slab} %kv)"),
                (b0 + 3200, 700.0, "%fusion.11 = bf16[16,129280]{1,0} "
                 "fusion(bf16[16,2048]{1,0} %h, bf16[2048,129280]{1,0} %w)")]
    modules.append((40000.0, 9000.0, "jit_prefill_slots_impl(456)"))
    ops.append((40100.0, 5000.0, kernel))
    trace = trace_reduce.Trace((0.0, 50000.0), {"/device:TPU:0": ops},
                               {"/device:TPU:0": modules}, [])
    ctx = types.SimpleNamespace(
        trace=trace, sizes=s, family=fam, engine_options={"t_max": 2560},
        peak=mf.peaks("TPU v5 lite"),
        engine_stats={"moe_step_layers": 8, "moe_assignments": 8 * 48,
                      "moe_experts_hit": 8 * 40,
                      "moe_experts_read": 8 * 100})
    assert man.reader("moe_token_ms.chat-2k")(ctx) == pytest.approx(
        2 * (2000.0 + 300.0) / 1e6 / 8)
    assert man.reader("latent_attn_token_ms.chat-2k")(ctx) == pytest.approx(
        2 * 500.0 / 1e6 / 8)
    assert man.reader("experts_hit_share.chat-2k")(ctx) == pytest.approx(
        100.0 * 40 / 256)
    assert man.reader("experts_read_share.chat-2k")(ctx) == pytest.approx(
        100.0 * 100 / 256)
    # an admission's device time, by the accepted reader under a name that
    # moves the metric this cell reports
    assert man.reader("prefill_ms.chat-2k")(ctx) == pytest.approx(9000.0 / 1e6)
    need = fam.flops.moe_decode_need(s, 8, 8 * 48, 8 * 40)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9) / 8
    assert man.reader("moe_roofline.chat-2k")(ctx) == pytest.approx(
        100.0 * least / (2300.0 / 1e9))
    # a program without the counters, or a run without a trace: nothing
    ctx.engine_stats = {}
    assert man.reader("moe_roofline.chat-2k")(ctx) is None
    assert man.reader("experts_hit_share.chat-2k")(ctx) is None
    assert man.reader("experts_read_share.chat-2k")(ctx) is None
    ctx.trace = None
    assert man.reader("moe_token_ms.chat-2k")(ctx) is None
    assert man.reader("latent_attn_token_ms.chat-2k")(ctx) is None
