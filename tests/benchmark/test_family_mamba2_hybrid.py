"""The ``mamba2_hybrid`` family against ``families/README.md`` and against
itself: found by name as a run finds it, sizes read from the published keys,
the program's tree matched leaf for leaf; the plain reference (the
SEQUENTIAL recurrence, importing nothing of the program) against the program
on seeded weights at a tiny size — prefill through the chunked scan, then
decode through the fixed-size state and the grouped slab — with the
lower-precision control and four planted faults judged not correct by tiny
limits; hand counts of the published configuration's parameters, operations
and the state update's bytes; and the readers of ``readers/ssm.py`` on a
hand-made trace."""

import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import tiny_mamba
from benchmark.harness import manifest as mf

CELL = "granite-4.0-h-micro.chat-short"


@pytest.fixture(scope="module")
def fam():
    return tiny_mamba.family()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_mamba.make_root(tmp_path_factory.mktemp("mamba"))


def _published():
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the protocol
def test_family_is_found_by_name_and_brings_the_protocol(fam):
    for name in ("sizes_of", "make_net", "install", "prompt_flops",
                 "decode_flops", "train_token_flops", "total_params",
                 "served_token_gaps"):
        assert callable(getattr(fam, name))
    with pytest.raises(NotImplementedError, match="served"):
        fam.train_steps()


def test_reference_imports_nothing_of_the_program(fam):
    with open(fam.reference.__file__) as f:
        assert "deeplearning4j_tpu" not in f.read()


def test_program_tree_matches_and_counts_what_the_family_counts(fam):
    net, sizes, (params, _, _) = fam.make_net(tiny_mamba.CONFIG)
    assert sizes["layer_types"] == tuple(tiny_mamba.CONFIG["layer_types"])
    leaves = sum(int(a.size) for a in
                 __import__("jax").tree_util.tree_leaves(params))
    assert leaves == fam.total_params(sizes)
    assert params["out"] == {}          # the head is the embedding's table
    fam.install(net, tiny_mamba.CONFIG, sizes, (params, {}, {}), 5,
                train=False)
    with pytest.raises(RuntimeError, match="differ"):
        bad = dict(params, ffn0={})
        fam.install(net, tiny_mamba.CONFIG, sizes, (bad, {}, {}), 5,
                    train=False)


def test_sound_run_of_a_tiny_cell_is_correct_and_reports_the_counters(root):
    out = tiny.drive(root, tiny_mamba.CELL, seed=2 ** 31 + 9, seconds=1.0,
                     trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["compared"]["served_gap"]["value"] <= 2e-8
    assert out["failed"] == 0
    share = out["metrics"]["ssm_alive_lane_share.chat-short"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
    assert "serve.mfu.chat" in out["metrics"]
    # no TPU plane in a CPU trace: the device readers leave their metrics
    # out, never report 0
    for name in ("ssm_token_ms.chat-short", "ssm_roofline.chat-short",
                 "decode_token_ms"):
        assert name not in out["metrics"]


def test_prefill_then_decode_through_the_state_equals_the_reference(fam):
    """The reference's full forward (the sequential recurrence, no cache)
    against the program's recompute (the chunked scan), its prefill and
    every decode step through the fixed-size state and the grouped slab,
    on logits, in float32. The tied head's logits are of order 3e-3 here
    (module docstring of ``tiny_mamba``): 1e-7 is some 1e-4 of them, what
    float32 leaves between a chunked and a sequential sum over 24
    tokens and 4 layers."""
    from deeplearning4j_tpu.models import TransformerDecoder
    net, sizes, shapes = fam.make_net(tiny_mamba.CONFIG)
    fam.install(net, tiny_mamba.CONFIG, sizes, shapes, 77, train=False)
    dec = TransformerDecoder(net, t_max=64)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, sizes["vocab"], (2, 24)).astype(np.int32)
    want = np.asarray(fam.reference.logits(sizes, 77, toks))
    assert np.abs(want).max() > 1e-3           # not a vacuous comparison
    tight = 1e-7
    pad = np.pad(toks, ((0, 0), (0, 8)))
    for t in (1, 9, 17, 24):                  # across chunks of 16
        got = dec.recompute_logits(pad, np.array([t, t]))[1]
        np.testing.assert_allclose(got, want[:, t - 1], atol=tight)
    caches = dec.init_cache(2)
    _, l0, caches = dec.prefill(
        caches, np.pad(toks[:, :5], ((0, 0), (0, 11))), np.array([5, 5]))
    np.testing.assert_allclose(l0, want[:, 4], atol=tight)
    for t in range(5, 24):
        _, lt, caches = dec.decode_step(caches, toks[:, t], np.array([t, t]))
        np.testing.assert_allclose(lt, want[:, t], atol=tight,
                                   err_msg=str(t))


def _plant(monkeypatch, fault):
    """The four faults ISSUE 36 names, planted in the program only."""
    from deeplearning4j_tpu.nn.conf.layers import (Mamba2Layer,
                                                   SelfAttentionLayer)
    if fault == "d_term":                 # the D term left out
        old = Mamba2Layer.advance
        monkeypatch.setattr(Mamba2Layer, "advance", lambda self, p, *a: old(
            self, dict(p, D=jnp.zeros_like(p["D"])), *a))
    elif fault == "conv_state":           # decode forgets the conv inputs
        old = Mamba2Layer.decode_forward
        monkeypatch.setattr(
            Mamba2Layer, "decode_forward", lambda self, p, x, c: old(
                self, p, x, dict(c, conv=jnp.zeros_like(c["conv"]))))
    elif fault == "dt_mask":              # a prompt's padding moves the state
        old = Mamba2Layer._mix

        def unmasked(self, p, x, lengths=None):
            out, state, _ = old(self, p, x, None)
            return out, state, old(self, p, x, lengths)[2]
        monkeypatch.setattr(Mamba2Layer, "_mix", unmasked)
    elif fault == "kv_group":             # query group i reads KV head i+1
        old = SelfAttentionLayer._project_qkv

        def shifted(self, p, x):
            q, k, v = old(self, p, x)
            if self._kv_heads() != self.num_heads:
                k, v = jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2)
            return q, k, v
        monkeypatch.setattr(SelfAttentionLayer, "_project_qkv", shifted)


@pytest.mark.parametrize("fault", ["bfloat16", "d_term", "conv_state",
                                   "dt_mask", "kv_group"])
def test_lower_precision_and_each_planted_fault_are_not_correct(
        root, monkeypatch, fault):
    def lower(ctx):
        ctx.config["run"]["compute_dtype"] = "bfloat16"
    if fault != "bfloat16":
        _plant(monkeypatch, fault)
    out = tiny.drive(root, tiny_mamba.CELL, seed=23, seconds=1.0,
                     prepare=lower if fault == "bfloat16" else None)
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
    assert calibrate.main(["--workload", tiny_mamba.CELL, "--seeds",
                           "41,42,43", "--seconds", "2", "--control-seeds",
                           "1", "--fault-seeds", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["seed"] for x in lines] == [41, 42, 43]
    assert lines[0]["verdict"] == {"program": True, "control": False}
    assert lines[1]["verdict"] == {"program": True}
    assert lines[2]["verdict"] == {"token_altered": False}


# ------------------------------------------------------------- hand counts
def test_hand_count_of_the_published_configuration(fam):
    s = fam.sizes_of(_published())
    assert s["layer_types"].count("mamba") == 36
    assert [i for i, t in enumerate(s["layer_types"]) if t == "attention"] \
        == [5, 15, 25, 35]
    # ISSUE 36's table: a Mamba mixer, an attention, the tied ends
    mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert mixer == 25_847_232 == fam.flops.mamba_params(s)
    mlp, norms = 2048 * 16384 + 8192 * 2048, 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    total = 36 * (mixer + mlp + norms) + 4 * (attention + mlp + norms) \
        + 100352 * 2048 + 2048
    assert total == 3_191_396_096 == fam.total_params(s)
    assert _published()["run"]["held_on_device_bytes"]["parameters"] == total


def test_hand_count_of_decode_and_prompt_operations(fam):
    s = fam.sizes_of(_published())
    mamba_w = 2048 * 8512 + 4 * 4352 + 4096 * 2048
    scan = 5 * 64 * 64 * 128
    attn_w = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp, head = 3 * 2048 * 8192, 2048 * 100352
    token = 2 * (36 * mamba_w + 4 * attn_w + 40 * mlp + head) + 36 * scan
    per_key = 2 * 4 * 2 * 2048            # four attentions, q.k and p.v
    # the first new token is the prefill's: 3 steps after 10 prompt tokens
    # attend to 11, 12, 13 keys
    assert fam.decode_flops(s, 10, 4) == pytest.approx(
        3 * token + per_key * (11 + 12 + 13))
    assert fam.prompt_flops(s, 3) == pytest.approx(
        3 * (token - 2 * head) + 2 * head + per_key * (1 + 2 + 3))


def test_hand_count_of_one_state_update_call(fam):
    s = fam.sizes_of(_published())
    need = fam.flops.ssm_decode_need(s, 32)
    state = 32 * 64 * 64 * 128
    assert need["bytes"] == 2 * 2 * state + 4 * (
        32 * (2 * 64 * 64 + 64 + 2 * 128) + 2 * 64)
    assert need["flops"] == 32 * 64 * (5 * 64 * 128 + 2 * 64)
    # 64 MB of state in and out: 82 us at 819 GB/s, bound by memory
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


def test_readers_find_the_mixers_inside_decode_blocks(fam):
    """A hand-made device trace: two executions of the decode block, each
    with the scan's own event (left out: it spans its body), one call of the
    named state-update kernel, the in-projection (by its 8512-wide weight),
    the convolution (4352 channels), the out-projection (4096 inputs), an
    MLP product and a slab read (neither); a kernel call of an admission
    outside any block is not a decoded step's."""
    from benchmark.harness import trace_reduce
    man = mf.Manifest(tiny.ROOT)
    s = fam.sizes_of(_published())
    state = "bf16[32,64,64,128]{3,2,1,0}"
    kernel = (f"%ssm_decode_update.3 = ({state}, f32[32,64,64]{{2,1,0}}) "
              f"custom-call({state} %s, f32[32,64,64]{{2,1,0}} %x, "
              "f32[32,1,64]{2,1,0} %dt, f32[32,2,128]{2,1,0} %bc, "
              'f32[2,64]{1,0} %ad), custom_call_target="tpu_custom_call"')
    ops, modules = [], []
    k = 50.0           # a call's 64 MB take 82 us: times to match
    for b0 in (1000.0 * k, 31000.0 * k):
        modules.append((b0, 28000.0 * k, "jit_decode_block4_impl(123)"))
        ops += [(b0, 27900.0 * k, f"%while.5 = (s32[], {state}) while("
                 f"(s32[], {state}) %tuple.3), condition=%c, body=%b"),
                (b0 + 100 * k, 2000.0 * k, kernel),
                (b0 + 2200 * k, 300.0 * k, "%fusion.7 = bf16[32,8512]{1,0} "
                 "fusion(bf16[32,2048]{1,0} %h, bf16[2048,8512]{1,0} %w)"),
                (b0 + 2600 * k, 40.0 * k, "%fusion.8 = f32[32,4352]{1,0} "
                 "fusion(bf16[32,3,4352]{2,1,0} %c, f32[4,4352]{1,0} %w)"),
                (b0 + 2700 * k, 60.0 * k, "%fusion.2 = bf16[32,2048]{1,0} "
                 "fusion(bf16[32,4096]{1,0} %g, bf16[4096,2048]{1,0} %wo)"),
                (b0 + 3000 * k, 900.0 * k, "%fusion.12 = bf16[32,8192]{1,0}"
                 " fusion(bf16[32,2048]{1,0} %n, bf16[2048,8192]{1,0} %wg)"),
                (b0 + 4600 * k, 250.0 * k, "%slab_decode_attn.1 = "
                 "bf16[32,4,8,128]{3,2,1,0} custom-call("
                 "bf16[32,4,8,128]{3,2,1,0} %q, bf16[32,4,2048,128]{3,2,1,0}"
                 " %k)")]
    modules.append((70000.0 * k, 9000.0 * k, "jit_prefill_slots_impl(456)"))
    ops.append((70100.0 * k, 5000.0 * k, kernel))
    trace = trace_reduce.Trace((0.0, 90000.0 * k), {"/device:TPU:0": ops},
                               {"/device:TPU:0": modules}, [])
    stats = {"ssm_step_layers": 36 * 11, "ssm_lane_layers": 36 * 32}
    ctx = types.SimpleNamespace(
        trace=trace, sizes=s, family=fam, peak=mf.peaks("TPU v5 lite"),
        engine_options={"t_max": 2048, "num_slots": 32}, engine_stats=stats)
    mixers = (2000.0 + 300.0 + 40.0 + 60.0) * k
    assert man.reader("ssm_token_ms.chat-short")(ctx) == pytest.approx(
        2 * mixers / 1e6 / 8)
    need = fam.flops.ssm_decode_need(s, 32)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    share = man.reader("ssm_roofline.chat-short")(ctx)
    assert share == pytest.approx(100.0 * least / (2000.0 * k / 1e9))
    assert 0 < share < 100
    assert man.reader("ssm_alive_lane_share.chat-short")(ctx) == \
        pytest.approx(100.0 * 11 / 32)
    # a program without the kernel or the counters (the parent), or a run
    # without a trace: nothing, and no raise
    ctx.engine_stats = {}
    assert man.reader("ssm_alive_lane_share.chat-short")(ctx) is None
    ctx.trace = trace_reduce.Trace(
        trace.window, {"/device:TPU:0": [o for o in ops if o[2] != kernel]},
        trace.modules, [])
    for name in ("ssm_token_ms.chat-short", "ssm_roofline.chat-short"):
        assert man.reader(name)(ctx) is None
    ctx.trace = None
    for name in ("ssm_token_ms.chat-short", "ssm_roofline.chat-short"):
        assert man.reader(name)(ctx) is None
    # another family's sizes
    ctx.trace, ctx.sizes = trace, {"d": 2048, "experts": 256}
    assert man.reader("ssm_token_ms.chat-short")(ctx) is None


def test_the_cell_is_in_the_manifest_with_its_metrics_and_traffic():
    man = mf.Manifest(tiny.ROOT)
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-micro", "chat-short", 1)
    assert [m["name"] for m in man.end_to_end(CELL)] == \
        ["tpot_p95_ms", "setup_s"]
    assert [m["name"] for m in man.per_layer(CELL)] == [
        "decode_token_ms", "serve.mfu.chat", "idle_share.chat",
        "slab_attn_block_ms.chat",
        "ssm_token_ms.chat-short", "ssm_roofline.chat-short",
        "ssm_alive_lane_share.chat-short", "prefill_ms.chat-short"]
    for m in man.per_layer(CELL):
        assert callable(man.reader(m["name"]))
    config = man.config("granite-4.0-h-micro")
    assert config["reduced"] == [] and config["family"] == "mamba2_hybrid"
    mix = man.traffic("chat-short")
    assert mix["kind"] == "open_loop"
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["new_tokens"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 16, "max": 512}
    assert (mix["temperature"], mix["check_requests"],
            mix["trace_seconds"]) == (0.0, 6, 0.6)
    eng = config["run"]["engine"]
    assert eng == {"num_slots": 32, "t_max": 2048, "block_size": 4}
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] \
        <= eng["t_max"]
