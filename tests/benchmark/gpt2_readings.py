"""What the ``gpt2`` family makes from a seed, read at ``tiny.py``'s size on
the CPU: the seed's weights (whole and block by block), the plain
reference's logits for one sequence, its three training losses and per-leaf
norms, and the operation counts of both GPT-2 configurations.

``gpt2_parent.json`` holds these readings as PR 28's parent (f2ada60) gave
them, through ``benchmark.harness.{weights,reference,flops}`` before those
files moved to ``benchmark/families/gpt2/``; ``test_family_gpt2.py`` holds
the family to them. ``readings`` takes the three modules, wherever they
live."""

from __future__ import annotations

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np

import tiny

SEED = 2 ** 31 + 77          # larger than 32 signed bits hold
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "gpt2_parent.json")


def _digest(tree) -> str:
    """sha256 over the leaves of a {name: array} dict, by sorted name, each
    as float32 bytes."""
    h = hashlib.sha256()
    for name in sorted(tree):
        a = np.asarray(tree[name].astype(jnp.float32))
        h.update(name.encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()


def _config(name):
    with open(os.path.join(tiny.ROOT, "benchmark", "configs",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def readings(weights, reference, flops, loadgen):
    sizes = weights.sizes_of(tiny.TINY_CONFIG)
    out = {"sizes": sizes}
    # ---- the seed's weights
    end, blocks = weights.everything(sizes, SEED)
    low_end, low_blocks = weights.everything(sizes, SEED, jnp.bfloat16)
    out["weights"] = {
        "whole": [_digest(end)] + [_digest(b) for b in blocks],
        "whole_bfloat16": [_digest(low_end)] + [_digest(b)
                                                for b in low_blocks],
        "block_by_block": [_digest(weights.ends(sizes, SEED))] + [
            _digest(weights.layer(sizes, SEED, i))
            for i in range(sizes["layers"])],
        "other_seed_block_0": _digest(weights.layer(sizes, SEED + 1, 0))}
    # ---- the reference's logits for one sequence
    toks = np.random.default_rng(5).integers(
        0, sizes["vocab"], (1, 24)).astype(np.int32)
    e, x = reference.hidden_states(sizes, SEED, jnp.asarray(toks))
    logits = np.asarray(reference.head(e, x), np.float64)[0]
    out["logits"] = {"last_position": logits[-1].tolist(),
                     "best_token": logits.argmax(-1).tolist(),
                     "best_logit": logits.max(-1).tolist()}
    # ---- three training steps
    batches = loadgen.train_batches(tiny.TINY_TRAFFIC["tiny-train"],
                                    sizes["vocab"], SEED, 3)
    ref = reference.train_steps(sizes, SEED, batches,
                                tiny.TINY_CONFIG["run"]["optimizer"])
    out["train"] = {"losses": [float(v) for v in ref["losses"]],
                    "grad_norms": ref["grad_norms"].tolist(),
                    "change_norms": ref["change_norms"].tolist(),
                    "leaf_names": reference.flat_names(sizes["layers"])}
    # ---- the counts, both configurations
    out["flops"] = {}
    for name in ("gpt2-medium", "gpt2-large"):
        s = weights.sizes_of(_config(name))
        out["flops"][name] = {
            "sizes": s,
            "matmul_params": flops.matmul_params(s),
            "total_params": flops.total_params(s),
            "forward_token_flops_300": flops.forward_token_flops(s, 300),
            "prompt_flops_192": flops.prompt_flops(s, 192),
            "prompt_flops_768": flops.prompt_flops(s, 768),
            "decode_flops_192_96": flops.decode_flops(s, 192, 96),
            "decode_flops_10_1": flops.decode_flops(s, 10, 1),
            "train_token_flops_1024": flops.train_token_flops(s, 1024)}
    return out
