"""The harness is driven by data: every cell resolves to files that exist,
every metric is wired to metrics its cells report, names keep to the
contract's characters, and a new cell, configuration, model family, mix,
runner and reader are picked up from new files and entries alone. Runs on
the CPU in seconds."""

import json
import os
import re
import subprocess
import sys

import pytest

import extension
import tiny
from benchmark.harness import compare, manifest as mf

ROOT = tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return mf.Manifest(ROOT)


def _cells(man):
    return [w["name"] for w in man.doc["workloads"]]


def test_manifest_has_exactly_the_contract_keys(man):
    assert set(man.doc) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}
    assert man.doc["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= man.doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for c in man.doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man.doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in man.doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in man.doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("cell", _cells(mf.Manifest(ROOT)))
def test_cell_resolves_to_files_that_exist(man, cell):
    w = man.cell(cell)
    config = man.config(w["config"])
    traffic = man.traffic(w["traffic"])
    # the family and the runner are found by name, as files of this tree
    family, runner = man.family(config), man.runner(traffic)
    assert family.__file__ == os.path.join(
        man.bench_dir, "families", config["family"], "__init__.py")
    assert runner.__file__ == os.path.join(
        man.bench_dir, "runners", traffic["kind"] + ".py")
    for name in ("sizes_of", "make_net", "install", "canonical_view",
                 "leaf_norms", "change_norms", "flat_names",
                 "served_token_gaps", "train_steps", "prompt_flops",
                 "decode_flops", "train_token_flops", "total_params"):
        assert callable(getattr(family, name)), name
    assert callable(runner.run) and callable(runner.end_to_end)
    assert family.total_params(family.sizes_of(config)) == \
        config["run"]["held_on_device_bytes"]["parameters"]
    assert config["source"].startswith("https://")
    assert "held_on_device_bytes" in config["run"] and "assumed" in config
    limits = compare.load_limits(man.bench_dir, cell)
    assert any(v.get("limit") is not None for v in limits.values())
    names = {m["name"] for m in man.end_to_end(cell)}
    assert "setup_s" in names and len(names) >= 2
    assert man.per_layer(cell), "a cell reports a per-layer metric"
    for entry in man.doc["configs"]:
        path = os.path.join(ROOT, entry["file"])
        assert os.path.isfile(path) and \
            entry["file"].startswith(man.doc["paths"][0] + "/")
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["reduced"] == entry["reduced"]


def test_every_per_layer_metric_moves_a_metric_its_cells_report(man):
    e2e = {m["name"]: m for m in man.doc["end_to_end"]}
    layers = set()
    for m in man.doc["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in _cells(man)
            assert man.reports(e2e[m["moves"]], cell), (m["name"], cell)
        assert callable(man.reader(m["name"]))
        spec = json.load(open(os.path.join(man.bench_dir, "metrics",
                                           m["name"] + ".json")))
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        layers.add(m["layer"])
    rooflines = [m for m in man.doc["per_layer"]
                 if m["name"].split(".")[0].endswith("_roofline")]
    for m in rooflines:       # a whole-step mfu stands beside each roofline
        assert any("mfu" in o["name"].split(".") and o["moves"] == m["moves"]
                   for o in man.doc["per_layer"]), m["name"]


def test_names_and_units_use_only_the_allowed_characters(man):
    doc = man.doc
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    names += [w["config"] for w in doc["workloads"]]
    names += [w["traffic"] for w in doc["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in doc[k]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(ms) == len(set(ms))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for path in doc["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (base, f)


def _files(top):
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = f.read()
    return out


def test_new_cell_config_mix_and_reader_need_no_edit(tmp_path):
    """New cells, configurations, mixes, a new reader, a second model family
    whose configuration is written in other keys and a runner of a new kind
    of traffic, added to a copy as files and entries: every file the
    benchmark had is byte for byte what it was."""
    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    extension.add_second_family_and_new_runner(root)
    with open(os.path.join(bench, "readers", "dummy.py"), "w") as f:
        f.write("def read(ctx, scale=1.0):\n"
                "    return scale * ctx.train['tokens_per_step']\n")
    with open(os.path.join(bench, "metrics", "dummy_tokens.json"), "w") as f:
        json.dump({"name": "dummy_tokens", "layer": "test", "unit": "tokens",
                   "moves": "train_tokens_per_s", "source": "program_counter",
                   "reader": "dummy:read", "args": {"scale": 2.0}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].append({"name": "dummy_tokens", "unit": "tokens",
                             "better": "higher", "source": "program_counter",
                             "layer": "test", "moves": "train_tokens_per_s",
                             "workloads": ["tiny.tiny-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    had, has = _files(os.path.join(ROOT, "benchmark")), _files(bench)
    assert {"run.py", "calibrate.py", "harness/manifest.py",
            "runners/open_loop.py", "runners/train.py",
            "families/gpt2/__init__.py", "readers/mfu.py"} <= set(had)
    for name, data in had.items():
        assert has[name] == data, name
    assert {"families/other/__init__.py", "runners/score.py",
            "configs/other.json"} <= set(has) - set(had)
    out = tiny.drive(root, "tiny.tiny-train", seconds=0.5, trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"]["dummy_tokens"] == {"value": 2.0 * 4 * 32,
                                              "unit": "tokens"}
    # a share that found nothing to read (no TPU plane in a CPU trace) is
    # left out of the line, never reported as 0
    assert "idle_share.train" not in out["metrics"]
    assert "train.mfu" not in out["metrics"]
    assert list(out)[-1] == "compared"
    out = tiny.drive(root, "tiny.tiny-train", seconds=0.5, trace=0)
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    # the second family, through the open-loop runner as it stands: correct
    # by its own reference, and its marked counts are what serve.mfu reads
    out = tiny.drive(root, "other.tiny-open", seed=21, seconds=1.0, trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] == 30 and out["failed"] == 0
    finished = out["metrics"]["serve.mfu.chat"]["value"] / 100.0 \
        * 1.0 * tiny.FAKE_PEAK["bf16_flops_per_s"] / extension.MARK
    assert finished == pytest.approx(round(finished), abs=1e-6)
    assert 1 <= round(finished) <= 30
    # a runner of a new kind, found by the name in the traffic file
    out = tiny.drive(root, "other.tiny-score", seed=22, seconds=0.3)
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"score_tokens_per_s", "setup_s"}
    assert out["metrics"]["score_tokens_per_s"]["value"] > 0
    out = tiny.drive(root, "other.tiny-score", seed=22, seconds=0.3, trace=1)
    assert out["metrics"]["score_tokens"]["value"] == \
        out["attempted"] * 2 * 16


@pytest.mark.parametrize("cell,looked_for", [
    ("orphan.tiny-open", ["'orphan'", '"family"',
                          os.path.join("benchmark", "families")]),
    ("ghost.tiny-open", ["'ghost'", os.path.join(
        "benchmark", "families", "ghost", "__init__.py")]),
    ("tiny.tiny-closed", ["'closed_loop'", os.path.join(
        "benchmark", "runners", "closed_loop.py")])])
def test_cell_that_names_no_family_or_runner_ends_with_what_was_looked_for(
        tmp_path, cell, looked_for):
    """No default family and no default runner: the run ends before it
    looks for a chip, names what it looked for and prints no result."""
    root = tiny.make_root(tmp_path)
    extension.add_cells_that_name_nothing(root)
    with pytest.raises(LookupError) as err:
        tiny.drive(root, cell)
    for text in looked_for:
        assert text in str(err.value), str(err.value)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" not in p.stderr           # it never got as far
    for text in looked_for:
        assert text in p.stderr, p.stderr[-2000:]


def test_run_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2-medium.train-t1024", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error_not_a_default():
    assert mf.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert mf.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        mf.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        mf.peaks("_source")
