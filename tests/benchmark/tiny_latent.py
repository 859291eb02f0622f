"""A tiny configuration of the ``latent_moe`` family and a temp copy of the
benchmark with one open-loop cell of it, added the way ``tiny.py`` adds its
cells: new files and new entries only."""

from __future__ import annotations

import copy
import json
import os

import tiny

CONFIG = {
    "source": "test only", "family": "latent_moe", "vocab_size": 211,
    "hidden_size": 32, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 64, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "n_routed_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "max_position_embeddings": 256, "reduced": [],
    "run": {"compute_dtype": "float32", "weights_dtype": "float32",
            "engine": {"num_slots": 4, "t_max": 128, "block_size": 4}}}
CELL = "tiny-latent.tiny-open"
#: sound runs read 0 to 4e-7 (float32 against float32: the same token, or
#: one whose logit ties to round-off); the program in bfloat16 reads 1e-3
#: and more, and every planted fault more still
LIMITS = {"numbers": {"served_gap": {"limit": 2e-5},
                      "wrong_echo": {"limit": 0},
                      "never_finished": {"limit": 0},
                      "window_compiles": {"limit": 0}}}


def config(**share):
    """The tiny configuration; ``first=, held=`` give it a share of the
    experts (``run.experts``)."""
    c = copy.deepcopy(CONFIG)
    if share:
        c["run"]["experts"] = share
    return c


def family():
    return tiny.family(CONFIG)


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmark")
    tiny._dump(CONFIG, os.path.join(bench, "configs", "tiny-latent.json"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny-latent", "source": "test only",
                           "file": "benchmark/configs/tiny-latent.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": "tiny-latent",
                             "traffic": "tiny-open", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "joyai-llm-flash.chat-2k" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tiny._dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    tiny._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root
