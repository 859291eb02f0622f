"""What the next architecture brings, added to a temp copy of the benchmark
as new files and new entries only: a second model family under another name,
whose configuration is written in other keys (``hidden_size``,
``num_hidden_layers``, …) and whose counts carry a mark, and a runner of a
new kind of traffic. (The test family borrows the ``gpt2`` family's layers
behind its own names, by path; a real one brings its own.)"""

from __future__ import annotations

import json
import os

import tiny

#: every prompt counts as this many operations and every decode as none, so
#: serve.mfu reads (requests finished in the window) x MARK / (window x peak)
MARK = 7.0e9

OTHER_FAMILY = '''"""A second family: other keys in the configuration, marked counts."""
import os

from benchmark.harness import manifest

_gpt2 = manifest.load_by_path(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), os.pardir, "gpt2", "__init__.py"),
    package=True)


def _as_gpt2(config):
    return dict(config, n_embd=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_layer=config["num_hidden_layers"],
                n_positions=config["max_position_embeddings"],
                n_inner=config["intermediate_size"])


def sizes_of(config):
    return _gpt2.sizes_of(_as_gpt2(config))


def make_net(config):
    return _gpt2.make_net(_as_gpt2(config))


def install(net, config, sizes, shapes, seed, train):
    return _gpt2.install(net, _as_gpt2(config), sizes, shapes, seed, train)


canonical_view, leaf_norms = _gpt2.canonical_view, _gpt2.leaf_norms
change_norms, flat_names = _gpt2.change_norms, _gpt2.flat_names
served_token_gaps, train_steps = _gpt2.served_token_gaps, _gpt2.train_steps
total_params = _gpt2.total_params


def prompt_flops(sizes, length):
    return %r


def decode_flops(sizes, prompt, new):
    return 0.0


def train_token_flops(sizes, seq_len):
    return %r
''' % (MARK, MARK)

OTHER_CONFIG = {
    "source": "test only", "family": "other", "hidden_size": 64,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "max_position_embeddings": 128, "intermediate_size": 256,
    "vocab_size": 211, "reduced": [], "run": tiny.TINY_CONFIG["run"]}

#: a runner of a new kind: scores seeded batches through ``net.output`` for
#: the window, with the net, its weights and the ids all from ``ctx.family``
SCORE_RUNNER = '''"""Runner for traffic kind ``score``: forward passes only."""
import time

import numpy as np


def run(ctx):
    net, sizes, shapes = ctx.family.make_net(ctx.config)
    ctx.family.install(net, ctx.config, sizes, shapes, ctx.args.seed,
                       train=False)
    ctx.sizes = sizes
    rng = np.random.default_rng(int(ctx.args.seed))
    rows, seq = int(ctx.traffic["batch_rows"]), int(ctx.traffic["seq_len"])
    ids = rng.integers(0, sizes["vocab"], (rows, seq)).astype(np.int32)
    probs = np.asarray(net.output(ids)[0])                    # warm-up
    setup_s = time.perf_counter() - ctx.t_start
    t0, calls = time.perf_counter(), 0
    while time.perf_counter() - t0 < ctx.args.seconds:
        probs = np.asarray(net.output(ids)[0])
        calls += 1
    ctx.scored = {"tokens": calls * rows * seq,
                  "elapsed_s": time.perf_counter() - t0}
    return {"numbers": {"rows_not_normalised": float(
                (np.abs(probs.sum(-1) - 1.0) > 1e-3).sum())},
            "attempted": calls, "failed": 0, "setup_s": setup_s,
            "memory_peak_bytes": ctx.memory_peak()}


def end_to_end(ctx, name):
    if name == "score_tokens_per_s":
        return ctx.scored["tokens"] / ctx.scored["elapsed_s"]
    return None
'''

SCORE_READER = '''def tokens(ctx):
    return float(ctx.scored["tokens"]) if getattr(ctx, "scored", None) \\
        else None
'''


def _write(path, text):
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)


def add_second_family_and_new_runner(root: str) -> None:
    """New files under ``root``'s benchmark/ and new entries in its
    BENCHMARK.json; no file that is there is touched but BENCHMARK.json."""
    bench = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bench, "families", "other"))
    _write(os.path.join(bench, "families", "other", "__init__.py"),
           OTHER_FAMILY)
    _write(os.path.join(bench, "runners", "score.py"), SCORE_RUNNER)
    _write(os.path.join(bench, "readers", "score.py"), SCORE_READER)
    tiny._dump(OTHER_CONFIG, os.path.join(bench, "configs", "other.json"))
    tiny._dump({"kind": "score", "why": "test", "batch_rows": 2,
                "seq_len": 16},
               os.path.join(bench, "traffic", "tiny-score.json"))
    tiny._dump({"name": "score_tokens", "unit": "tokens", "layer": "test",
                "moves": "score_tokens_per_s", "source": "program_counter",
                "reader": "score:tokens"},
               os.path.join(bench, "metrics", "score_tokens.json"))
    tiny._dump(tiny.SERVE_LIMITS,
               os.path.join(bench, "limits", "other.tiny-open.json"))
    tiny._dump({"numbers": {"rows_not_normalised": {"limit": 0}}},
               os.path.join(bench, "limits", "other.tiny-score.json"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    doc["configs"].append({"name": "other", "source": "test only",
                           "file": "benchmark/configs/other.json",
                           "reduced": [], "why": "test"})
    for traffic in ("tiny-open", "tiny-score"):
        doc["workloads"].append({"name": "other." + traffic,
                                 "config": "other", "traffic": traffic,
                                 "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "tiny.tiny-open" in m.get("workloads", ()):
            m["workloads"].append("other.tiny-open")
    doc["end_to_end"].append({"name": "score_tokens_per_s",
                              "unit": "tokens/s", "better": "higher",
                              "bound": 0.1, "source": "host_clock",
                              "workloads": ["other.tiny-score"]})
    doc["per_layer"].append({"name": "score_tokens", "unit": "tokens",
                             "better": "higher", "source": "program_counter",
                             "layer": "test", "moves": "score_tokens_per_s",
                             "workloads": ["other.tiny-score"]})
    tiny._dump(doc, os.path.join(root, "BENCHMARK.json"))


def add_cells_that_name_nothing(root: str) -> None:
    """A configuration with no ``family`` key, one whose family has no
    files, and a traffic mix of a kind with no runner file, each with a
    cell."""
    bench = os.path.join(root, "benchmark")
    orphan = {k: v for k, v in tiny.TINY_CONFIG.items() if k != "family"}
    tiny._dump(orphan, os.path.join(bench, "configs", "orphan.json"))
    tiny._dump(dict(tiny.TINY_CONFIG, family="ghost"),
               os.path.join(bench, "configs", "ghost.json"))
    tiny._dump(dict(tiny.TINY_TRAFFIC["tiny-open"], kind="closed_loop"),
               os.path.join(bench, "traffic", "tiny-closed.json"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    for name in ("orphan", "ghost"):
        doc["configs"].append({"name": name, "source": "test only",
                               "file": f"benchmark/configs/{name}.json",
                               "reduced": [], "why": "test"})
    for name, config, traffic in (("orphan.tiny-open", "orphan", "tiny-open"),
                                  ("ghost.tiny-open", "ghost", "tiny-open"),
                                  ("tiny.tiny-closed", "tiny",
                                   "tiny-closed")):
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "test"})
        tiny._dump(tiny.SERVE_LIMITS,
                   os.path.join(bench, "limits", name + ".json"))
    tiny._dump(doc, os.path.join(root, "BENCHMARK.json"))
