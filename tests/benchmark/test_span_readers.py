"""The readers PR 26 adds (``readers/spans.py``, ``readers/kernel_names.py``)
on a synthetic context: hand-made records, a hand-filled profiler and trace
ring — each returns the hand-computed value, and None where a ring is
truncated or the program lacks the public call."""

from __future__ import annotations

import argparse
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402

T0, WINDOW = 1000.0, 10.0
CHAT_METRICS = ("first_token_wait_p95_ms", "emit_gap_p95_ms",
                "block_host_ms.chat", "bubble_share.chat",
                "admit_gap_ms.chat")


@pytest.fixture(scope="module")
def man():
    return mf.Manifest(ROOT)


@pytest.fixture
def sinks(monkeypatch):
    """Fresh process-wide profiler and trace ring, as the readers find
    them (``default_profiler()`` / ``default_trace_ring()``)."""
    from deeplearning4j_tpu.observability import (MetricsRegistry,
                                                  PhaseProfiler, TraceRing,
                                                  profiler, tracing)
    prof = PhaseProfiler(registry=MetricsRegistry(), timeline_capacity=64)
    ring = TraceRing(8)
    monkeypatch.setattr(profiler, "_DEFAULT", prof)
    monkeypatch.setattr(tracing, "_DEFAULT", ring)
    return prof, ring


def _ctx(records=(), trace=None):
    ctx = types.SimpleNamespace(
        t0=T0, window_s=WINDOW, trace=trace, args=argparse.Namespace(trace=1),
        records=[types.SimpleNamespace(clocks=c) for c in records])
    return ctx


def _fill(prof, t0=T0):
    """One engine's window by hand: admission A (behind a block in flight),
    the overtaken block, the block after the admission (5 ms of bubble and
    a dispatch call of 2),
    two pipelined blocks, and one block dispatched before the window."""
    ch = prof.channel("e", num_slots=4)

    def block(bid, t, fetched, host, journal, publish, **kw):
        ch.record_block(impl="b4", k=4, lanes=2, queued=0, block=bid,
                        t_dispatch=t, t_fetched=fetched, t_host=host,
                        t_journal=journal, t_publish=publish, **kw)
    block(1, t0 - 0.5, t0 - 0.405, t0 - 0.404, t0 - 0.404, t0 - 0.403)
    block(2, t0 + 1.000, t0 + 1.095, t0 + 1.096, t0 + 1.0965, t0 + 1.097)
    ch.record_admission(impl="p", count=1, block=4, overlapped=True,
                        t_dispatch=t0 + 1.100, t_fetched=t0 + 1.200,
                        t_host=t0 + 1.201, t_journal=t0 + 1.2015,
                        t_publish=t0 + 1.202)
    block(3, t0 + 1.090, t0 + 1.2025, t0 + 1.2035, t0 + 1.204, t0 + 1.2045,
          overlapped=True)
    block(5, t0 + 1.205, t0 + 1.300, t0 + 1.302, t0 + 1.3025, t0 + 1.303,
          t_dispatched=t0 + 1.207)
    block(6, t0 + 1.298, t0 + 1.395, t0 + 1.396, t0 + 1.3965, t0 + 1.397,
          overlapped=True)
    return ch


def _trace(ring, created, ends, tokens=None):
    from deeplearning4j_tpu.observability import Trace
    tr = Trace(store=ring)
    tr.created_at = created
    tr.add_span("queued", created, ends[0] - 0.01)
    tr.add_span("prefill", ends[0] - 0.01, ends[0], block=1)
    for i, t in enumerate(ends[1:]):
        tr.add_span("decode_block", t - 0.09, t, k=4, block=2 + i,
                    tokens=4 if tokens is None else tokens[i])
    tr.finish()
    return tr


def test_every_new_metric_has_its_entry_file_and_cell(man):
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    for name in CHAT_METRICS:
        assert by_name[name]["workloads"] == ["gpt2-large.chat-open"]
        assert by_name[name]["source"] == "program_span"
        assert callable(man.reader(name))
    for name in ("attn_fwd_ms.train", "attn_bwd_ms.train"):
        assert by_name[name]["workloads"] == ["gpt2-medium.train-t1024"]
        assert by_name[name]["source"] == "device_trace"
        assert callable(man.reader(name))


def test_first_token_wait_p95_from_the_request_clocks(man):
    waits = [0.100 + 0.001 * i for i in range(21)]       # 100 .. 120 ms
    records = [{"created": T0 + i, "admitted": T0 + i + 0.05,
                "first_token": T0 + i + 0.05 + w}
               for i, w in enumerate(waits)]
    records.append({"created": T0, "admitted": None, "first_token": None})
    got = man.reader("first_token_wait_p95_ms")(_ctx(records))
    assert got == pytest.approx(119.0)                   # rank 19 of 0..20
    assert man.reader("first_token_wait_p95_ms")(_ctx([])) is None


def test_emit_gap_p95_from_the_trace_ring(man, sinks):
    _, ring = sinks
    _trace(ring, T0 - 5.0, [T0 - 4.9, T0 - 4.0])                 # before
    _trace(ring, T0 + 1.0, [T0 + 1.10, T0 + 1.20, T0 + 1.30, T0 + 1.50])
    _trace(ring, T0 + 2.0, [T0 + 2.10, T0 + 2.35, T0 + 2.45, T0 + 2.55],
           tokens=[4, 0, 4])          # a block that emitted nothing
    _trace(ring, T0 + WINDOW + 1.0, [T0 + 12.0, T0 + 13.0])      # after
    # gaps: 100, 100, 200 and 250, 200 -> sorted 100 100 200 200 250
    got = man.reader("emit_gap_p95_ms")(_ctx())
    assert got == pytest.approx(250.0 - 0.2 * 50.0)      # p95 of five
    # the ring rolls past the window's start: no number of a part
    for i in range(8):
        _trace(ring, T0 + 3.0 + i, [T0 + 3.1 + i, T0 + 3.2 + i])
    assert ring.rolled_past(T0)
    assert man.reader("emit_gap_p95_ms")(_ctx()) is None


def test_timeline_readers_give_the_hand_computed_values(man, sinks):
    prof, _ = sinks
    _fill(prof)
    ctx = _ctx()
    # blocks 2, 3, 5, 6 are in the window; host+journal+publish of each:
    # 2.0, 2.0, 3.0, 2.0 ms
    assert man.reader("block_host_ms.chat")(ctx) == pytest.approx(9.0 / 4)
    # idle as the host saw it: block 2 follows block 1's readback by
    # 1.405 s (no idle mark), the admission and blocks 3, 6 were dispatched
    # behind work in flight (0), block 5 follows the admission's readback
    # by 5 ms and its dispatch call took 2 more
    assert man.reader("bubble_share.chat")(ctx) == pytest.approx(
        100.0 * (1.405 + 0.005 + 0.002) / WINDOW)
    assert man.reader("admit_gap_ms.chat")(ctx) == pytest.approx(7.0)
    # an idle wait before block 2 re-anchors the account
    prof2, _ = sinks
    prof2.timeline._ring.clear()
    ch = prof2.channel("e2", num_slots=4)
    ch.mark_idle(T0 + 0.999)
    ch.record_block(impl="b4", k=4, lanes=1, queued=0, block=9,
                    t_dispatch=T0 + 1.0, t_fetched=T0 + 1.1,
                    t_host=T0 + 1.1, t_journal=T0 + 1.1,
                    t_publish=T0 + 1.1)
    assert man.reader("bubble_share.chat")(ctx) == pytest.approx(
        100.0 * 0.001 / WINDOW)
    assert man.reader("admit_gap_ms.chat")(ctx) is None


@pytest.mark.parametrize("metric", ["block_host_ms.chat",
                                    "bubble_share.chat",
                                    "admit_gap_ms.chat"])
def test_timeline_readers_give_none_on_a_truncated_ring(man, sinks, metric):
    prof, _ = sinks
    _fill(prof)
    assert man.reader(metric)(_ctx()) is not None
    ch = prof.channel("e")
    for i in range(64):                   # the ring rolls past the window
        t = T0 + 2.0 + 0.1 * i
        ch.record_block(impl="b4", k=4, lanes=1, queued=0, block=100 + i,
                        t_dispatch=t, t_fetched=t + 0.09, t_host=t + 0.09,
                        t_journal=t + 0.09, t_publish=t + 0.09)
    assert prof.between(T0, T0 + WINDOW)["truncated"] is True
    assert man.reader(metric)(_ctx()) is None


@pytest.mark.parametrize("metric", CHAT_METRICS[1:])
def test_readers_give_none_on_a_program_without_the_public_calls(
        man, monkeypatch, metric):
    """Laid over the commit before PR 26 (no ``between``, no
    ``rolled_past``) a reader finds nothing and does not raise."""
    from deeplearning4j_tpu.observability import profiler, tracing
    monkeypatch.setattr(profiler, "_DEFAULT", types.SimpleNamespace())
    monkeypatch.setattr(tracing, "_DEFAULT", types.SimpleNamespace())
    assert man.reader(metric)(_ctx()) is None
    ctx = _ctx()
    ctx.t0 = None                          # a cell that opens no such window
    assert man.reader(metric)(ctx) is None


def test_attention_kernels_found_by_name(man):
    def ev(start, dur, text):
        return (float(start), float(dur), text)
    call = ' custom-call(bf16[8,64]{1,0} %x), custom_call_target=' \
        '"tpu_custom_call"'
    ops = [ev(10, 4e6, "%flash_fwd.1 = bf16[8,64]" + call),
           ev(5e6, 6e6, "%flash_bwd_dq.2 = bf16[8,64]" + call),
           ev(12e6, 8e6, "%flash_bwd_dkv.3 = (bf16[8,64])" + call),
           ev(21e6, 1e6, "%get-tuple-element.9 = bf16[8,64] "
                         "get-tuple-element(%flash_bwd_dkv.3), index=0"),
           ev(23e6, 2e6, "%flash_fwd.4 = bf16[8,64]" + call),
           ev(26e6, 3e6, "%fusion.7 = bf16[8,64] fusion(%flash_fwd.4)")]
    modules = [ev(0, 22e6, "jit_train_step(123)"),
               ev(22e6, 8e6, "jit_train_step(123)")]
    trace = trace_reduce.Trace((0.0, 30e6), {"/device:TPU:0": ops},
                               {"/device:TPU:0": modules}, [])
    ctx = _ctx(trace=trace)
    assert man.reader("attn_fwd_ms.train")(ctx) == pytest.approx(
        (4.0 + 2.0) / 2)
    assert man.reader("attn_bwd_ms.train")(ctx) == pytest.approx(
        (6.0 + 8.0) / 2)
    # a program before PR 26 names its kernels jvp__ / transpose_jvp___
    old = [ev(s, d, t.replace("flash_fwd", "jvp__").replace(
        "flash_bwd_dq", "transpose_jvp___").replace(
        "flash_bwd_dkv", "transpose_jvp___")) for s, d, t in ops]
    ctx = _ctx(trace=trace_reduce.Trace(
        (0.0, 30e6), {"/device:TPU:0": old}, {"/device:TPU:0": modules}, []))
    assert man.reader("attn_fwd_ms.train")(ctx) is None
    assert man.reader("attn_bwd_ms.train")(ctx) is None
    assert man.reader("attn_fwd_ms.train")(_ctx()) is None   # untraced
