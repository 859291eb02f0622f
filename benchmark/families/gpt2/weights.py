"""Seeded GPT-2 weights, made on the device.

The benchmark makes every weight itself from ``--seed``: the program is handed
them (``install``), and the plain reference makes the same ones again after
the window (``layer`` / ``ends``), so the reference takes nothing the program
has made. Generation is per layer — the unit both sides consume — and every
leaf is float32 master weights, the type ``ComputationGraph`` holds.

Distribution: GPT-2's own initialisation (normal, std 0.02; residual
projections scaled by 1/sqrt(2 L)), except that biases and LayerNorm
parameters are random too (a bias left at zero could be dropped unseen).
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
#: fold-in tags: one stream per kind of leaf, then per layer
_ENDS_TAG, _LAYER_TAG = 1, 2


def sizes_of(config: Dict) -> Dict[str, int]:
    """The sizes the generator needs, from a configuration file's published
    keys (GPT-2 ``config.json`` names)."""
    d = int(config["n_embd"])
    return {"vocab": int(config["vocab_size"]), "d": d,
            "heads": int(config["n_head"]), "layers": int(config["n_layer"]),
            "positions": int(config["n_positions"]),
            "ffn": int(config.get("n_inner") or 4 * d)}


def root_key(seed: int):
    """A key from any whole number up to a little over 2**31: split into two
    31-bit halves, so no signed 32-bit conversion is ever made."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, d: int, ffn: int, layers: int) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 15)
    proj = STD / math.sqrt(2.0 * layers)
    return {
        "ln1_g": 1.0 + _normal(ks[0], (d,), 0.05),
        "ln1_b": _normal(ks[1], (d,), STD),
        "wq": _normal(ks[2], (d, d), STD),
        "wk": _normal(ks[3], (d, d), STD),
        "wv": _normal(ks[4], (d, d), STD),
        "wo": _normal(ks[5], (d, d), proj),
        "bo": _normal(ks[6], (d,), STD),
        "ln2_g": 1.0 + _normal(ks[7], (d,), 0.05),
        "ln2_b": _normal(ks[8], (d,), STD),
        "w1": _normal(ks[9], (d, ffn), STD),
        "b1": _normal(ks[10], (ffn,), STD),
        "w2": _normal(ks[11], (ffn, d), proj),
        "b2": _normal(ks[12], (d,), STD),
    }


def _ends(key, vocab: int, d: int, positions: int) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 6)
    return {
        "wte": _normal(ks[0], (vocab, d), STD),
        "wpe": _normal(ks[1], (positions, d), STD),
        "lnf_g": 1.0 + _normal(ks[2], (d,), 0.05),
        "lnf_b": _normal(ks[3], (d,), STD),
        "head_w": _normal(ks[4], (d, vocab), STD),
        "head_b": _normal(ks[5], (vocab,), STD),
    }


@functools.partial(jax.jit, static_argnames=("d", "ffn", "layers"))
def _layer_jit(key, index, *, d, ffn, layers):
    return _layer(jax.random.fold_in(jax.random.fold_in(key, _LAYER_TAG),
                                     index), d, ffn, layers)


@functools.partial(jax.jit, static_argnames=("vocab", "d", "positions"))
def _ends_jit(key, *, vocab, d, positions):
    return _ends(jax.random.fold_in(key, _ENDS_TAG), vocab, d, positions)


def layer(sizes: Dict[str, int], seed: int, index: int):
    """One block's weights (canonical names), float32."""
    return _layer_jit(root_key(seed), index, d=sizes["d"], ffn=sizes["ffn"],
                      layers=sizes["layers"])


def ends(sizes: Dict[str, int], seed: int):
    """Embeddings, final LayerNorm and the (untied) output head."""
    return _ends_jit(root_key(seed), vocab=sizes["vocab"], d=sizes["d"],
                     positions=sizes["positions"])


@functools.partial(jax.jit, static_argnames=("vocab", "d", "positions", "ffn",
                                             "layers", "dtype"))
def _all_jit(key, *, vocab, d, positions, ffn, layers, dtype):
    # every block's leaves in one vmapped generation per kind of leaf (a
    # program of 19 generators, not 13 L + 6: it compiles in seconds), then
    # split by block; vmap(f)(keys)[i] is f(keys[i]), bit for bit
    lkey = jax.random.fold_in(key, _LAYER_TAG)
    keys = jax.vmap(lambda i: jax.random.fold_in(lkey, i))(
        jnp.arange(layers))
    stacked = jax.vmap(lambda k: _layer(k, d, ffn, layers))(keys)
    stacked = {n: a.astype(dtype) for n, a in stacked.items()}
    end = _ends(jax.random.fold_in(key, _ENDS_TAG), vocab, d, positions)
    return ({n: a.astype(dtype) for n, a in end.items()},
            [{n: a[i] for n, a in stacked.items()} for i in range(layers)])


def everything(sizes: Dict[str, int], seed: int, dtype=jnp.float32):
    """(ends, [layer 0 .. L-1]) in ONE jitted call — what set-up hands the
    program. Leaf for leaf the same numbers as ``ends`` and ``layer``,
    rounded to ``dtype`` (the rounding the program's own cast of float32
    masters applies) where the weights are served in a narrower type."""
    return _all_jit(root_key(seed), vocab=sizes["vocab"], d=sizes["d"],
                    positions=sizes["positions"], ffn=sizes["ffn"],
                    layers=sizes["layers"], dtype=jnp.dtype(dtype).name)


def program_tree(end: Dict, blocks) -> Dict[str, Dict]:
    """The canonical weights under the names ``transformer_lm_conf`` gives its
    vertices (models/transformer.py): the parameter pytree the program
    holds. Parameterless vertices (the residual adds) hold ``{}``."""
    tree = {"embed": {"W": end["wte"], "P": end["wpe"]},
            "lnf": {"gamma": end["lnf_g"], "beta": end["lnf_b"]},
            "out": {"W": end["head_w"], "b": end["head_b"]}}
    for i, b in enumerate(blocks):
        tree[f"ln{i}a"] = {"gamma": b["ln1_g"], "beta": b["ln1_b"]}
        tree[f"attn{i}"] = {"Wq": b["wq"], "Wk": b["wk"], "Wv": b["wv"],
                            "Wo": b["wo"], "bo": b["bo"]}
        tree[f"res{i}a"] = {}
        tree[f"ln{i}b"] = {"gamma": b["ln2_g"], "beta": b["ln2_b"]}
        tree[f"ffn{i}"] = {"W1": b["w1"], "b1": b["b1"], "W2": b["w2"],
                           "b2": b["b2"]}
        tree[f"res{i}b"] = {}
    return tree


#: canonical leaf name -> (program vertex pattern, program leaf), for reading
#: the program's state back leaf by leaf in the reference's order
BLOCK_LEAVES = {"ln1_g": ("ln{i}a", "gamma"), "ln1_b": ("ln{i}a", "beta"),
                "wq": ("attn{i}", "Wq"), "wk": ("attn{i}", "Wk"),
                "wv": ("attn{i}", "Wv"), "wo": ("attn{i}", "Wo"),
                "bo": ("attn{i}", "bo"),
                "ln2_g": ("ln{i}b", "gamma"), "ln2_b": ("ln{i}b", "beta"),
                "w1": ("ffn{i}", "W1"), "b1": ("ffn{i}", "b1"),
                "w2": ("ffn{i}", "W2"), "b2": ("ffn{i}", "b2")}
END_LEAVES = {"wte": ("embed", "W"), "wpe": ("embed", "P"),
              "lnf_g": ("lnf", "gamma"), "lnf_b": ("lnf", "beta"),
              "head_w": ("out", "W"), "head_b": ("out", "b")}


def canonical_view(tree: Dict[str, Dict], layers: int):
    """(ends, [blocks]) view of a program-named pytree (params, or one Adam
    moment): the inverse of :func:`program_tree`, copying nothing."""
    end = {k: tree[v][leaf] for k, (v, leaf) in END_LEAVES.items()}
    blocks = [{k: tree[v.format(i=i)][leaf]
               for k, (v, leaf) in BLOCK_LEAVES.items()}
              for i in range(layers)]
    return end, blocks
