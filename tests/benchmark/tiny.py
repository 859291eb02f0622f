"""A temp copy of the benchmark with tiny cells added — new files and new
entries only, the way a later PR adds a cell — and a driver that skips the
harness's look for a chip and runs the rest of a run on the CPU."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CONFIG = {
    "source": "test only", "family": "gpt2", "n_embd": 64, "n_head": 4, "n_inner": None,
    "n_layer": 2, "n_positions": 128, "vocab_size": 211, "reduced": [],
    "run": {"compute_dtype": "float32", "weights_dtype": "float32",
            "engine": {"num_slots": 4, "t_max": 128, "block_size": 4},
            "optimizer": {"name": "adam", "learning_rate": 0.0003,
                          "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-08}}}
TINY_TRAFFIC = {
    "tiny-open": {"kind": "open_loop", "why": "test", "rate_per_s": 30.0,
                  "prompt_tokens": {"dist": "lognormal", "median": 20,
                                    "sigma": 0.5, "min": 5, "max": 40},
                  "new_tokens": {"dist": "uniform", "min": 4, "max": 12},
                  "temperature": 0.0, "check_requests": 48,
                  "trace_seconds": 0.2},
    "tiny-train": {"kind": "train", "why": "test", "batch_rows": 4,
                   "seq_len": 32, "staged_ahead": 2, "distinct_batches": 4,
                   "reference_rows": 2}}
SERVE_LIMITS = {"numbers": {"served_gap": {"limit": 2e-4},
                            "wrong_echo": {"limit": 0},
                            "never_finished": {"limit": 0},
                            "window_compiles": {"limit": 0}}}
TRAIN_LIMITS = {"numbers": {"loss_gap.1": {"limit": 1e-4},
                            "loss_gap.2": {"limit": 1e-4},
                            "loss_gap.3": {"limit": 1e-4},
                            # sound 2e-7..3e-7, the reference in bfloat16
                            # in the program's place 5e-4..7e-4 (3 seeds)
                            "grad_norm_gap": {"limit": 1e-4},
                            "change_norm_gap": {"limit": 1e-2},
                            "final_loss_finite": {"limit": 0}}}
#: tiny cell -> the cell of the benchmark whose metrics it joins
CELLS = {"tiny.tiny-open": "gpt2-large.chat-open",
         "tiny.tiny-train": "gpt2-medium.train-t1024"}
#: a per-layer metric that only a tiny cell reports, brought the way a later
#: PR brings one: a new entry and a new file under metrics/
NEW_METRIC = {
    "per_layer": {"name": "tiny_queue_wait_ms", "unit": "ms",
                  "better": "lower", "source": "program_span",
                  "layer": "engine / scheduler (SlotGenerationEngine)",
                  "moves": "ttft_p95_ms", "workloads": ["tiny.tiny-open"]},
    "file": {"name": "tiny_queue_wait_ms", "unit": "ms",
             "layer": "engine / scheduler (SlotGenerationEngine)",
             "moves": "ttft_p95_ms", "source": "program_span",
             "reader": "engine:queue_wait_p95"}}


def _dump(doc, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)


def make_root(tmp: str) -> str:
    """Copy BENCHMARK.json and benchmark/ to ``tmp`` and add the tiny
    configuration, mixes, cells and limits."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "benchmark")
    _dump(TINY_CONFIG, os.path.join(bench, "configs", "tiny.json"))
    for name, doc in TINY_TRAFFIC.items():
        _dump(doc, os.path.join(bench, "traffic", name + ".json"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny", "source": "test only",
                           "file": "benchmark/configs/tiny.json",
                           "reduced": [], "why": "test"})
    for cell, like in CELLS.items():
        doc["workloads"].append({"name": cell, "config": "tiny",
                                 "traffic": cell.split(".", 1)[1],
                                 "chips": 1, "why": "test"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
        _dump(TRAIN_LIMITS if "train" in cell else SERVE_LIMITS,
              os.path.join(bench, "limits", cell + ".json"))
    doc["per_layer"].append(NEW_METRIC["per_layer"])
    _dump(NEW_METRIC["file"],
          os.path.join(bench, "metrics", "tiny_queue_wait_ms.json"))
    _dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def family(config=None, root: str = ROOT):
    """The model family of a configuration (the tiny one's by default), as
    a run finds it: by the name in the configuration, from ``root``."""
    from benchmark.harness import manifest as mf
    return mf.Manifest(root).family(TINY_CONFIG if config is None
                                    else config)


def runner(kind: str, root: str = ROOT):
    """The runner of a kind of traffic, as a run finds it."""
    from benchmark.harness import manifest as mf
    return mf.Manifest(root).runner({"kind": kind})


FAKE_DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
FAKE_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e9}


def drive(root: str, workload: str, seed: int = 3, seconds: float = 1.5,
          trace: int = 0, prepare=None):
    """The rest of a run after the look for a chip, in this process."""
    from benchmark import run as bench_run
    from benchmark.harness import manifest as mf
    manifest = mf.Manifest(root)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return bench_run.run_cell(manifest, manifest.cell(workload), args,
                              dict(FAKE_DEVICE), dict(FAKE_PEAK), prepare)
