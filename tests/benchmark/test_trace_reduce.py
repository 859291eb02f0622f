"""The reduction from trace to numbers against traces whose answers are
known: one written out by hand (every number worked out on paper) and one
recorded on a TPU v5e, committed under benchmark/fixtures/. Also the counts
of the ``gpt2`` family's flops.py against hand counts for both
configurations, and the load generator's schedule."""

import json
import os

import numpy as np
import pytest

import tiny
from benchmark.harness import flops as kernel_flops, loadgen, stats
from benchmark.harness import trace_reduce as tr

gpt2 = tiny.family()
flops, wgen = gpt2.flops, gpt2.weights

MOSAIC = 'custom_call_target="tpu_custom_call"'


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}\n")


#: two training steps and one decode block, times in ns. Ops of a step:
#: a fusion, then a loop whose event spans a fusion and a Mosaic call.
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    %(m_train)s%(m_train2)s%(m_decode)s }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    %(ops)s }
  event_metadata { key: 1 value { id: 1 name: "jit_train_step(123)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_decode_block4_impl(77)" } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %%p.1), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%%while.2 = (s32[], bf16[8,8]{1,0}) while((s32[], bf16[8,8]{1,0}) %%t), body=%%b" } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.3 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %%p.2), kind=kLoop" } }
  event_metadata { key: 6 value { id: 6 name: "%%custom-call.4 = bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} %%q, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)} %%k, bf16[128,1024,64]{2,1,0} %%v), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    %(spans)s }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fit_batch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.readback" } }
  event_metadata { key: 4 value { id: 4 name: "some_runtime_thing" } }
}
"""


def _step_ops(t0):
    return (_event(3, t0, 2000) + _event(4, t0 + 2000, 3000)
            + _event(5, t0 + 2500, 1000) + _event(6, t0 + 3500, 1000))


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    text = HAND % {
        "m_train": _event(1, 1000, 5000), "m_train2": _event(1, 8000, 5000),
        "m_decode": _event(2, 20000, 4000),
        "ops": _step_ops(1000) + _step_ops(8000) + _event(3, 20000, 4000),
        "spans": _event(1, 0, 30000) + _event(2, 6100, 1800)
        + _event(3, 13000, 6500) + _event(4, 100, 29000)}
    return tr.parse(ProfileData.from_text_proto(text))


def test_hand_trace_busy_and_idle(hand):
    assert hand.window == (0.0, 30000.0)
    assert hand.window_s == pytest.approx(30e-6)
    # busy: 1000-6000, 8000-13000, 20000-24000 = 14 us of 30 us
    assert tr.busy_seconds(hand) == pytest.approx(14e-6)
    assert tr.idle_share(hand) == pytest.approx(100 * (1 - 14 / 30))


def test_hand_trace_program_times(hand):
    times = tr.program_times(hand)
    assert times == {"train_step": [5e-6, 5e-6], "decode_block4_impl": [4e-6]}
    assert tr.program_starts(hand, "train_step") == [1e-6, 8e-6]
    assert tr.program_name("jit_prefill_slots_impl(1398873697491813856)") \
        == "prefill_slots_impl"


def test_hand_trace_self_time_of_ops(hand):
    # per step: fusion.1 2 us; the loop spans 3 us of which its body's
    # fusion.3 and the Mosaic call cover 1 us each: 1 us of its own
    top = dict(tr.top_ops(hand, 10))
    assert top["fusion"] == pytest.approx(2 * (2e-6 + 1e-6) + 4e-6)
    assert top["while"] == pytest.approx(2e-6)
    assert top["custom-call[tpu_custom_call]"] == pytest.approx(2e-6)
    assert list(top)[0] == "fusion"
    calls = tr.ops_matching(hand, MOSAIC)
    assert len(calls) == 2 and calls[0][1] == 1000.0
    assert tr.operand_shapes(calls[0][2]) == [(128, 1024, 64)] * 3
    assert tr.result_shapes(calls[0][2]) == [(128, 1024, 64)]
    real = ('%jvp__.25 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, '
            'f32[128,1024,8]{2,1,0:T(8,128)}) custom-call(bf16[128,1024,64]'
            '{2,1,0:T(8,128)(2,1)S(1)} %bitcast.2764, bf16[128,1024,64]'
            '{2,1,0:T(8,128)(2,1)} %bitcast.2767, bf16[128,1024,64]{2,1,0:'
            'T(8,128)(2,1)} %bitcast.2770), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={bf16[128,1024,'
            '64]{2,1,0}, bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}}')
    assert tr.operand_shapes(real) == [(128, 1024, 64)] * 3
    assert tr.result_shapes(real) == [(128, 1024, 64), (128, 1024, 8)]
    assert tr.op_label(real) == "jvp__[tpu_custom_call]"


def test_hand_trace_gaps_are_named_by_the_benchmarks_spans(hand):
    gaps = tr.idle_gaps(hand, 10)
    assert gaps == [["bench.readback", pytest.approx(7e-6)],
                    ["no_span", pytest.approx(6e-6)],
                    ["bench.fit_batch", pytest.approx(2e-6)],
                    ["no_span", pytest.approx(1e-6)]]


def test_readers_on_the_hand_trace(hand):
    import argparse
    from benchmark.harness import manifest as mf
    man = mf.Manifest(tiny.ROOT)
    sizes = wgen.sizes_of(man.config("gpt2-medium"))
    ctx = argparse.Namespace(
        trace=hand, peak=mf.peaks("TPU v5 lite"), sizes=sizes, records=None,
        family=gpt2, train={"tokens_per_step": 8192, "seq_len": 1024})
    assert man.reader("idle_share.train")(ctx) == \
        pytest.approx(100 * (1 - 14 / 30))
    assert man.reader("train_step_ms")(ctx) == pytest.approx(5e-3)
    assert man.reader("decode_token_ms")(ctx) == pytest.approx(4e-3 / 4)
    hand_ctx = argparse.Namespace(trace=hand)
    from benchmark.readers import programs           # nothing to read: None
    assert programs.device_ms_per_call(hand_ctx, "prefill_slots_impl") is None
    assert man.reader("train.mfu")(ctx) is None            # two steps only
    # one Mosaic call: 128 x 1024 x 1024 x 64, causal, two products:
    # 2 * 2 * 128 * 1024^2 * 64 / 2 = 17.18 GFLOP -> 87.2 us at 197 TFLOP/s;
    # four tensors of 16 MiB -> 81.9 us at 819 GB/s: compute-bound.
    need = 2 * 2 * 128 * 1024 ** 2 * 64 / 2 / 197e12
    assert need == pytest.approx(87.2e-6, rel=1e-3)
    assert man.reader("attn_roofline.train")(ctx) == \
        pytest.approx(100 * need / 1e-6)


def test_window_defaults_to_the_extent_of_the_device_ops():
    from jax.profiler import ProfileData
    text = """planes { id: 1 name: "/device:TPU:0"
      lines { id: 2 name: "XLA Ops" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
        events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "%copy.1 = f32[2]{0} copy(f32[2]{0} %p)" } } }"""
    t = tr.parse(ProfileData.from_text_proto(text))
    assert t.window == (5000.0, 10000.0)
    assert tr.busy_seconds(t) == pytest.approx(2e-6)
    with pytest.raises(ValueError):
        tr.parse(ProfileData.from_text_proto('planes { id: 1 name: "x" }'))


# ------------------------------------------------------------------ flops
def _sizes(name):
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return wgen.sizes_of(json.load(f)), json.load(open(f.name))


def test_flops_hand_counts_gpt2_medium():
    s, config = _sizes("gpt2-medium")
    # per block 4 d^2 + 2 d 4d = 12 d^2 = 12,582,912; x 24 = 301,989,888;
    # head 1024 x 50257 = 51,463,168
    assert flops.matmul_params(s) == 301989888 + 51463168 == 353453056
    # every leaf: blocks 24 x (12 d^2 + 4d + 6d) with d = 1024; embeddings
    # 50257 d + 1024 d; final LN 2 d; head 50257 d + 50257
    per_block = 12 * 1024 ** 2 + 4096 + 6 * 1024
    assert flops.total_params(s) == 24 * per_block + 50257 * 1024 \
        + 1024 * 1024 + 2048 + 51463168 + 50257 == 406262865
    assert config["run"]["held_on_device_bytes"]["parameters"] == 406262865
    # 6 x 353,453,056 + 3 x 4 x 24 x 1024 x 512.5 = 2.2719 GFLOP a token
    assert flops.train_token_flops(s, 1024) == \
        6 * 353453056 + 12 * 24 * 1024 * 512.5 == 2271860736.0


def test_flops_hand_counts_gpt2_large():
    s, config = _sizes("gpt2-large")
    assert flops.matmul_params(s) == 36 * 12 * 1280 ** 2 + 1280 * 50257 \
        == 772117760
    assert flops.total_params(s) == 838271057
    held = config["run"]["held_on_device_bytes"]
    assert held["parameters"] == 838271057
    assert held["weights_bfloat16"] == 2 * 838271057
    eng = config["run"]["engine"]
    assert held["slab_cache_16_slots_x_1024"] == \
        36 * 2 * 1280 * 2 * eng["t_max"] * eng["num_slots"]
    # a prompt of 3 tokens attends to 1 + 2 + 3 = 6 keys
    assert flops.prompt_flops(s, 3) == 2 * 772117760 * 3 + 4 * 36 * 1280 * 6
    # 4 new tokens after a prompt of 10: the first comes out of the prefill;
    # three decode steps attend to 11 + 12 + 13 = 36 keys
    assert flops.decode_flops(s, 10, 4) == \
        2 * 772117760 * 3 + 4 * 36 * 1280 * 36
    assert flops.decode_flops(s, 10, 1) == 0


def test_attention_kernel_need_and_roofline():
    need = kernel_flops.attention_kernel(128, 1024, 1024, 64, True, products=2,
                                  tensors=4)
    assert need == {"flops": 2 * 2 * 128 * 1024 * 1024 * 64 / 2,
                    "bytes": 4 * 128 * 1024 * 64 * 2}
    dkv = kernel_flops.attention_kernel(128, 1024, 1024, 64, True, products=2,
                                 tensors=6)
    assert dkv["flops"] == need["flops"] and dkv["bytes"] == 1.5 * need["bytes"]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = kernel_flops.roofline_seconds(need["flops"], need["bytes"], peak)
    assert r["bound"] == "compute" and \
        r["seconds"] == pytest.approx(need["flops"] / 197e12)
    assert kernel_flops.roofline_seconds(1.0, 1e9, peak)["bound"] == "memory"


# ---------------------------------------------------------------- loadgen
def _mix(name):
    with open(os.path.join(tiny.ROOT, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def test_schedule_is_a_function_of_the_seed_alone():
    mix = _mix("chat-open")
    a = loadgen.open_loop_schedule(mix, 50257, 2 ** 31 + 9, 30.0)
    b = loadgen.open_loop_schedule(mix, 50257, 2 ** 31 + 9, 30.0)
    c = loadgen.open_loop_schedule(mix, 50257, 5, 30.0)
    assert len(a) == len(b) == len(c) == round(mix["rate_per_s"] * 30)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.new_tokens == y.new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    # another seed: the same lengths and the same gaps between arrivals, in
    # another order and another pairing, with other tokens
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    for field in (lambda r: len(r.prompt), lambda r: r.new_tokens):
        assert sorted(map(field, a)) == sorted(map(field, c))
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due_s
                                                        for r in rs]), 9))
    assert gaps(a) == gaps(c)
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c)
                   if len(x.prompt) == len(y.prompt))
    dist = mix["prompt_tokens"]
    assert all(dist["min"] <= len(r.prompt) <= dist["max"] for r in a)
    assert all(0 < r.due_s < 30.0 for r in a)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 50257
               for r in a)
    med = float(np.median([len(r.prompt) for r in a]))
    assert abs(med - dist["median"]) <= 2


def test_lateness_and_buckets_and_percentiles():
    assert loadgen.lateness_ms([1.002, 2.0, 2.9], [1.0, 2.0, 3.0]) == \
        [pytest.approx(2.0), 0.0, 0.0]
    assert loadgen.count_buckets(16) == [1, 2, 4, 8, 16]
    assert loadgen.count_buckets(24) == [1, 2, 4, 8, 16, 24]
    assert loadgen.length_buckets(_mix("chat-open")["prompt_tokens"], 1024) \
        == [64, 128, 256, 512, 1024]
    assert loadgen.length_buckets({"min": 513, "max": 960}, 1024) == [1024]
    xs = list(np.random.default_rng(0).normal(size=101))
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 95) is None
    assert stats.percentile([1.0, float("inf")], 95) == float("inf")
    assert stats.percentile([1.0] * 99 + [float("inf")], 95) == 1.0


def test_train_batches_follow_the_seed():
    x, y = loadgen.train_batches(_mix("train-t1024"), 50257, 3, 2)[0]
    assert x.shape == y.shape == (8, 1024)
    assert np.array_equal(x[:, 1:], y[:, :-1])
    assert len({row.tobytes() for row in x}) == 8     # rows all differ


@pytest.mark.parametrize("seed", [1, 77, 2 ** 31 + 12])
def test_traced_stretch_of_the_replay_holds_an_arrival(seed):
    serve = tiny.runner("open_loop")
    mix = _mix("chat-open")
    sched = loadgen.open_loop_schedule(mix, 50257, seed, 40.0)
    length = mix["trace_seconds"]
    start, end = serve.replay_stretch(mix, sched, length)
    assert end - start == pytest.approx(length)
    assert start >= mix["trace_lead_seconds"]
    # an arrival a tenth of a second into the stretch, so that its admission
    # (one decode block) and prefill (behind one more) end inside it
    assert any(abs(r.due_s - (start + 0.1)) < 1e-9 for r in sched)
    assert end - (start + 0.1) > 0.35
    # a window shorter than the lead-in: the stretch still lies inside it
    short = loadgen.open_loop_schedule(mix, 50257, seed, 3.0)
    s0, s1 = serve.replay_stretch(mix, short, length)
    assert 0.0 <= s0 < short[-1].due_s <= 3.0


# ------------------------------------------- the trace recorded on a v5e
#: benchmark/fixtures/v5e-tiny.xplane.pb.gz (my chip run, PR 25): a
#: 2-layer, d 256, 4-head LM, T 1024. Inside one bench.window span: one
#: admission of two prompts (600 and 700 tokens: one prefill at the 1024
#: bucket through the masked flash kernel), 9 new tokens each in blocks of
#: four, then three fit_batch steps of 2 x 1024 tokens and a readback.
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    import shutil
    src = os.path.join(tiny.ROOT, "benchmark", "fixtures",
                       "v5e-tiny.xplane.pb.gz")
    dst = str(tmp_path_factory.mktemp("trace") / "v5e-tiny.xplane.pb")
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.load(dst)


def _sweep_busy(events, lo, hi):
    """Busy time by an event-point sweep with a depth counter: a second,
    independent way to the union of intervals."""
    points = []
    for start, dur, _ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth, since, busy = 0, 0.0, 0.0
    for t, step in points:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_trace_counts_follow_from_what_was_recorded(recorded):
    times = tr.program_times(recorded)
    assert len(times["prefill_slots_impl"]) == 1      # one admission wave
    assert len(times["train_step"]) == 3
    assert len(times["decode_block4_impl"]) == 3      # 8 tokens + overshoot
    # Mosaic calls: the prefill's masked forward kernel in each of 2 layers;
    # per training step and layer one forward and two backward kernels
    calls = tr.ops_matching(recorded, MOSAIC)
    assert len(calls) == 2 + 3 * 2 * 3
    labels = sorted({tr.op_label(c[2]) for c in calls})
    assert labels == ["jvp__[tpu_custom_call]",
                      "prefill_slots_impl[tpu_custom_call]",
                      "transpose_jvp___[tpu_custom_call]"]
    # 2 rows x 4 heads folded to 8, T 1024, head size 64
    assert all(tr.operand_shapes(c[2])[0] == (8, 1024, 64) for c in calls)
    assert {len([s for s in tr.operand_shapes(c[2])
                 + tr.result_shapes(c[2]) if s == (8, 1024, 64)])
            for c in calls} == {4, 5, 6}     # fwd; dq; dk and dv
    names = sorted({s[2] for s in recorded.spans})
    assert names == ["bench.fit_batch", "bench.readback", "bench.result",
                     "bench.submit"]


def test_recorded_trace_busy_idle_and_gaps(recorded):
    lo, hi = recorded.window
    assert recorded.window_s == pytest.approx(0.020988677, rel=1e-6)
    plane = sorted(recorded.ops)[0]
    busy = _sweep_busy(recorded.ops[plane], lo, hi) / 1e9
    assert tr.busy_seconds(recorded) == pytest.approx(busy, rel=1e-9)
    assert busy == pytest.approx(0.002629898, rel=1e-6)
    assert tr.idle_share(recorded) == pytest.approx(87.46992, rel=1e-6)
    # programs run back to back inside themselves: the sum of the module
    # times is the busy time to within the few ops outside any module
    total = sum(sum(v) for v in tr.program_times(recorded).values())
    assert total == pytest.approx(busy, rel=0.02)
    assert sum(tr.program_times(recorded)["train_step"]) == \
        pytest.approx(0.001847475, rel=1e-6)
    gaps = tr.idle_gaps(recorded, 3)
    assert [g[0] for g in gaps] == ["bench.result"] * 3
    assert gaps[0][1] == pytest.approx(0.004377607, rel=1e-6)
    assert sum(g[1] for g in tr.idle_gaps(recorded, 10 ** 6)) == \
        pytest.approx(recorded.window_s - busy, rel=1e-9)
    # self times add up to the busy time (nothing counted twice)
    self_total = sum(s for s, _ in tr._self_times(
        [e for e in recorded.ops[plane] if e[0] >= lo
         and e[0] + e[1] <= hi]))
    assert self_total == pytest.approx(busy, rel=1e-3)


def test_recorded_trace_attention_roofline_is_a_share(recorded):
    import argparse
    from benchmark.harness import manifest as mf
    man = mf.Manifest(tiny.ROOT)
    ctx = argparse.Namespace(trace=recorded, peak=mf.peaks("TPU v5 lite"),
                             records=None, train=None, sizes=None)
    share = man.reader("attn_roofline.train")(ctx)
    # a tiny shape (8 x 1024 x 64) leaves the kernels far from the roofline
    assert 1.0 < share < 60.0
    assert man.reader("idle_share.chat")(ctx) == \
        pytest.approx(87.46992, rel=1e-6)
    assert man.reader("decode_token_ms")(ctx) == \
        pytest.approx(0.000601701 / 3 / 4 * 1e3, rel=1e-5)
