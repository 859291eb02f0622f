"""`correct` has been shown to fail. Each test skips the harness's look for
a chip and drives the rest of a run on the CPU at a tiny size: once sound
(true), once with the system side computed in a lower precision than the
configuration states, and once for each fault the cells can have, planted
in the program underneath the timed path."""

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _lower_precision(ctx):
    ctx.config["run"]["compute_dtype"] = "bfloat16"


@pytest.mark.parametrize("cell,seed", [("tiny.tiny-open", 2 ** 31 + 5),
                                       ("tiny.tiny-open", 6),
                                       ("tiny.tiny-train", 2 ** 31 + 5)])
def test_sound_run_is_correct(root, cell, seed):
    out = tiny.drive(root, cell, seed=seed, seconds=1.0)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(v["value"] is not None for v in out["compared"].values())
    assert out["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny.tiny-open", "tiny.tiny-train"])
def test_program_in_lower_precision_is_not_correct(root, cell):
    out = tiny.drive(root, cell, seed=11, seconds=1.0,
                     prepare=_lower_precision)
    assert out["correct"] is False, out["compared"]


def test_control_reads_the_gap_of_the_lower_precisions_first_choice(root):
    def ctrl(ctx):
        ctx.control_precision = "fp8"
    out = tiny.drive(root, "tiny.tiny-open", seed=12, seconds=1.0,
                     prepare=ctrl)
    assert out["correct"] is True
    limit = out["compared"]["served_gap"]["limit"]
    assert out["control"]["served_gap"] > 3 * limit


def test_step_that_returns_its_state_unchanged(root, monkeypatch):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    monkeypatch.setenv("DL4J_TPU_DONATE", "0")
    real = ComputationGraph.fit_batch

    def frozen(self, ds):
        params, upd = self.params, self.updater_state
        real(self, ds)
        self.params, self.updater_state = params, upd
    monkeypatch.setattr(ComputationGraph, "fit_batch", frozen)
    out = tiny.drive(root, "tiny.tiny-train", seed=13, seconds=0.3)
    assert out["correct"] is False
    assert out["compared"]["change_norm_gap"]["value"] == \
        pytest.approx(1.0, abs=1e-6)


def test_half_of_the_batch_left_out(root, monkeypatch):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.ops.dataset import DataSet
    real = ComputationGraph.fit_batch

    def half(self, ds):
        n = ds.features.shape[0] // 2
        real(self, DataSet(ds.features[:n], ds.labels[:n]))
    monkeypatch.setattr(ComputationGraph, "fit_batch", half)
    out = tiny.drive(root, "tiny.tiny-train", seed=14, seconds=0.3)
    assert out["correct"] is False
    over = [k for k, v in out["compared"].items()
            if v["limit"] is not None and v["value"] > v["limit"]]
    assert "grad_norm_gap" in over


@pytest.mark.parametrize("cell", ["tiny.tiny-open"])
def test_token_altered_where_it_is_produced(root, cell, monkeypatch):
    from deeplearning4j_tpu.models.generation import GenerationRequest
    real = GenerationRequest._complete

    def altered(self):
        if self.generated:
            self.generated[-1] = (self.generated[-1] + 1) % 211
        real(self)
    monkeypatch.setattr(GenerationRequest, "_complete", altered)
    out = tiny.drive(root, cell, seed=15, seconds=1.0)
    assert out["correct"] is False
    assert out["compared"]["served_gap"]["value"] > \
        10 * out["compared"]["served_gap"]["limit"]


def test_answer_that_loses_its_prompt_is_not_correct(root, monkeypatch):
    from deeplearning4j_tpu.models.generation import GenerationRequest
    real = GenerationRequest._complete

    def clipped(self):
        real(self)
        self._result = self._result[1:]
    monkeypatch.setattr(GenerationRequest, "_complete", clipped)
    out = tiny.drive(root, "tiny.tiny-open", seed=16, seconds=0.5)
    assert out["correct"] is False
    assert out["compared"]["wrong_echo"]["value"] > 0


def test_compile_inside_the_window_is_not_correct(root, monkeypatch):
    serve = tiny.runner("open_loop", root)    # the runner this root's run finds
    real = serve.warm_up

    def partial(engine, traffic, vocab, seed):
        # warm only short prompts: the mix's longer ones then compile
        # inside the window
        short = dict(traffic, prompt_tokens=dict(
            traffic["prompt_tokens"], max=8))
        return real(engine, short, vocab, seed)
    monkeypatch.setattr(serve, "warm_up", partial)
    out = tiny.drive(root, "tiny.tiny-open", seed=17, seconds=1.0)
    assert out["correct"] is False
    assert out["compared"]["window_compiles"]["value"] > 0


def test_traced_run_replays_the_mix_and_a_new_metric_joins(root, capfd):
    out = tiny.drive(root, "tiny.tiny-open", seed=18, seconds=1.0, trace=1)
    err = capfd.readouterr().err
    assert out["correct"] is True
    # the stretch under the profiler is the mix offered again, cancelled
    # before the profiler stops, and no part of the run's records
    assert "[serve] replayed" in err and "[trace] stopped" in err
    assert out["attempted"] == 30 and out["failed"] == 0
    # a metric only this cell reports, brought as a new entry and a new file
    assert out["metrics"]["tiny_queue_wait_ms"]["value"] >= 0
    assert out["metrics"]["gen_late_p95_ms"]["value"] >= 0
    # no device plane on the CPU: the trace's readers find nothing to read
    # and say nothing, never 0
    for name in ("prefill_ms.chat", "decode_token_ms", "idle_share.chat"):
        assert name not in out["metrics"]


def test_collector_pauses_inside_the_window_are_reported():
    import gc
    import types

    from benchmark.harness import manifest as mf
    serve = tiny.runner("open_loop")
    ctx = types.SimpleNamespace(gc_pauses=None)
    reader = mf.Manifest(tiny.ROOT).reader("gc_pause_ms.chat")
    assert reader(ctx) is None            # nothing watched: nothing said
    frozen = gc.get_freeze_count()
    with serve.collector_watch(ctx):
        gc.collect()
    assert [g for g, _ in ctx.gc_pauses].count(2) == 1
    assert reader(ctx) == pytest.approx(
        sum(s for _, s in ctx.gc_pauses) * 1e3) and reader(ctx) > 0
    assert gc.isenabled() and gc.get_freeze_count() == frozen


@pytest.mark.parametrize("cell,fault", [("tiny.tiny-open", "token_altered"),
                                        ("tiny.tiny-train", "half_batch")])
def test_calibration_reads_program_control_and_fault(root, cell, fault,
                                                     monkeypatch, capsys):
    """``calibrate.py`` through the runner's own ``calibrate``: sound seeds
    judged correct, the control and the cell's fault not."""
    import json

    from benchmark import calibrate, run as bench_run
    monkeypatch.setattr(calibrate, "_ROOT", root)
    monkeypatch.setattr(bench_run, "configure_cache", lambda: None)
    monkeypatch.setattr(bench_run, "find_chips", lambda chips: (
        dict(tiny.FAKE_DEVICE), dict(tiny.FAKE_PEAK)))
    assert calibrate.main(["--workload", cell, "--seeds", "31,32",
                           "--seconds", "1", "--control-seeds", "1",
                           "--fault-seeds", "1"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["seed"] for x in lines] == [31, 32]
    first, last = lines[0]["verdict"], lines[1]["verdict"]
    assert first["program"] is True and first["control"] is False
    assert last[fault] is False


def test_rate_sweep_goes_through_the_runner(root, monkeypatch, capsys):
    import json

    from benchmark import calibrate, run as bench_run
    monkeypatch.setattr(calibrate, "_ROOT", root)
    monkeypatch.setattr(bench_run, "configure_cache", lambda: None)
    monkeypatch.setattr(bench_run, "find_chips", lambda chips: (
        dict(tiny.FAKE_DEVICE), dict(tiny.FAKE_PEAK)))
    assert calibrate.main(["--workload", "tiny.tiny-open", "--seeds", "33",
                           "--seconds", "0.5", "--rates", "20,40"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["requests"] for x in lines] == [10, 20]
    assert all(x["failed"] == 0 for x in lines)
    with pytest.raises(SystemExit, match="has no sweep"):      # train brings none
        calibrate.main(["--workload", "tiny.tiny-train", "--seeds", "33",
                        "--rates", "20"])


def test_judge_needs_every_listed_number():
    from benchmark.harness import compare
    limits = {"a": {"limit": 1.0}, "b": {"limit": 0}, "c": {"limit": None}}
    ok, rows = compare.judge({"a": 0.5, "b": 0.0, "c": 9.0}, limits)
    assert ok and rows["c"] == {"value": 9.0, "limit": None}
    assert not compare.judge({"a": 1.5, "b": 0.0}, limits)[0]
    assert not compare.judge({"a": float("nan"), "b": 0.0}, limits)[0]
    assert not compare.judge({"a": 0.5}, limits)[0]       # b never produced
    gap, leaf = compare.worst_leaf_gap([1.0, 2.0, 1e-9], [1.0, 1.0, 1e-12])
    assert (gap, leaf) == (1.0, 1)    # the near-zero leaf is held to the median
    assert list(compare.moved_leaves([1.0, 1.0, 1e-5])) == [True, True, False]
