"""A tiny configuration of the ``shortcut_moe`` family and a temp copy of
the benchmark with one open-loop cell of it, added the way ``tiny.py`` adds
its cells: new files and new entries only."""

from __future__ import annotations

import copy
import json
import os

import tiny

CONFIG = {
    "source": "test only", "family": "shortcut_moe", "attention_bias": False,
    "vocab_size": 211, "hidden_size": 32, "ffn_hidden_size": 64,
    "expert_ffn_hidden_size": 16, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "qk_nope_head_dim": 8, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 8, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "attention_method": "MLA",
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "reduced": [],
    "run": {"compute_dtype": "float32", "weights_dtype": "float32",
            "engine": {"num_slots": 4, "t_max": 128, "block_size": 4}}}
CELL = "tiny-shortcut.tiny-open"
#: the cell of the benchmark whose metrics the tiny cell joins
LIKE = "longcat-flash-chat.chat-decode"
#: sound runs read 0 to 1e-6 (float32 against float32: the same token, or
#: one whose logit ties to round-off); the program in bfloat16 reads 1e-3
#: and more, and every planted fault more still
LIMITS = {"numbers": {"served_gap": {"limit": 2e-5},
                      "wrong_echo": {"limit": 0},
                      "never_finished": {"limit": 0},
                      "window_compiles": {"limit": 0}}}


def config(first: int = 0, held: int = 0):
    """The tiny configuration; ``first=, held=`` give it a share of the
    eight routed experts, written the way the published file writes it:
    ``n_routed_experts`` what is held, the whole count under
    ``published``."""
    c = copy.deepcopy(CONFIG)
    if held:
        c["published"] = {"n_routed_experts": c["n_routed_experts"]}
        c["n_routed_experts"] = held
        c["reduced"] = ["n_routed_experts"]
        c["run"]["experts"] = {"first": first}
    return c


def family():
    return tiny.family(CONFIG)


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmark")
    tiny._dump(CONFIG, os.path.join(bench, "configs", "tiny-shortcut.json"))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny-shortcut", "source": "test only",
                           "file": "benchmark/configs/tiny-shortcut.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": "tiny-shortcut",
                             "traffic": "tiny-open", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if LIKE in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tiny._dump(LIMITS, os.path.join(bench, "limits", CELL + ".json"))
    tiny._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root
