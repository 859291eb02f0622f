"""Seeded weights of the ``mamba2_hybrid`` family, made on the device.

The benchmark makes every weight itself from ``--seed``: the program is handed
them (``everything``), and the plain reference makes the same ones again, a
piece of a layer at a time (``piece``: a layer's mixer — Mamba-2 or
attention — with the gain of the norm before it, or its MLP likewise;
``ends``), so it takes nothing the program has made. Every leaf is generated
in float32 and, for the program, rounded to the type it is served in.

Distribution (the configuration's ``assumed``): normal, std 0.02, every
projection; the embedding (the head is the embedding, tied) normal with std
0.02 / embedding_multiplier, so that the stream starts at std 0.02 as in
every other family: at std 0.02 the tied head's logit of the CURRENT token
(12·|E_id|² against E_v·h) stands some five standard deviations above the
rest at the published size, and greedy decoding repeats the last token
whatever the layers compute — a comparison that could not see them; RMSNorm gains
(the blocks', the final one and the Mamba gate norm's) 1 + N(0, 0.05); the
Mamba convolution's weights and bias uniform on (-0.5, 0.5), PyTorch's
``Conv1d`` default for a fan-in of 4; ``A = -exp(A_log)`` with ``exp(A_log)``
uniform on (1, 16) and ``dt_bias`` the inverse softplus of a step
log-uniform on (1e-3, 1e-1), both as the published Mamba-2 initialises them,
so that ``exp(dt·A)`` spreads over (0, 1); ``D`` uniform on (0.5, 1.5).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
GAIN_STD = 0.05
CONV_BOUND = 0.5
_ENDS_TAG, _LAYER_TAG = 1, 2
#: a layer's two pieces, in the order they run
PIECES = ("mixer", "mlp")


def sizes_of(config: Dict) -> Dict:
    """The sizes the generator needs, from a configuration file's published
    keys (Granite-4.0-H ``config.json`` names); ``run.engine.t_max`` the
    context a slot holds."""
    run = config.get("run", {})
    d = int(config["hidden_size"])
    heads = int(config["mamba_n_heads"])
    p = int(config["mamba_d_head"])
    n = int(config["mamba_d_state"])
    groups = int(config["mamba_n_groups"])
    if groups != 1 or heads * p != int(config["mamba_expand"]) * d:
        raise ValueError("the family builds one group of B and C, and "
                         "mamba_n_heads x mamba_d_head = mamba_expand x "
                         "hidden_size")
    return {
        "vocab": int(config["vocab_size"]), "d": d,
        "layer_types": tuple(config["layer_types"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "ffn": int(config["shared_intermediate_size"]),
        "ssm_heads": heads, "ssm_head_dim": p, "ssm_state": n,
        "conv": int(config["mamba_d_conv"]),
        "chunk": int(config["mamba_chunk_size"]),
        "attn_scale": float(config["attention_multiplier"]),
        "embed_scale": float(config["embedding_multiplier"]),
        "residual": float(config["residual_multiplier"]),
        "logit_div": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"]),
        "positions": int(config["max_position_embeddings"]),
        "t_max": int(run.get("engine", {}).get(
            "t_max", config["max_position_embeddings"]))}


def inner(s: Dict) -> int:
    return s["ssm_heads"] * s["ssm_head_dim"]


def conv_dim(s: Dict) -> int:
    return inner(s) + 2 * s["ssm_state"]


def root_key(seed: int):
    """A key from any whole number up to a little over 2**31: split into two
    31-bit halves, so no signed 32-bit conversion is ever made."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _uniform(key, shape, lo, hi, dtype=jnp.float32):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def _gain(key, n, dtype):
    return (1.0 + jax.random.normal(key, (n,), jnp.float32)
            * GAIN_STD).astype(dtype)


def _mamba(key, s: Dict, dtype) -> Dict:
    d, h, cd = s["d"], s["ssm_heads"], conv_dim(s)
    ks = jax.random.split(key, 10)
    dt0 = jnp.exp(_uniform(ks[5], (h,), jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ln_g": _gain(ks[0], d, dtype),
        "w_in": _normal(ks[1], (d, inner(s) + cd + h), STD, dtype),
        "conv_w": _uniform(ks[2], (s["conv"], cd), -CONV_BOUND, CONV_BOUND,
                           dtype),
        "conv_b": _uniform(ks[3], (cd,), -CONV_BOUND, CONV_BOUND, dtype),
        "a_log": jnp.log(_uniform(ks[4], (h,), 1.0, 16.0)).astype(dtype),
        # the inverse softplus of dt0
        "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
        "d": _uniform(ks[6], (h,), 0.5, 1.5, dtype),
        "norm_g": _gain(ks[7], inner(s), dtype),
        "w_out": _normal(ks[8], (inner(s), d), STD, dtype)}


def _attention(key, s: Dict, dtype) -> Dict:
    d, dh = s["d"], s["d"] // s["heads"]
    ks = jax.random.split(key, 5)
    return {"ln_g": _gain(ks[0], d, dtype),
            "wq": _normal(ks[1], (d, s["heads"] * dh), STD, dtype),
            "wk": _normal(ks[2], (d, s["kv_heads"] * dh), STD, dtype),
            "wv": _normal(ks[3], (d, s["kv_heads"] * dh), STD, dtype),
            "wo": _normal(ks[4], (s["heads"] * dh, d), STD, dtype)}


def _mlp(key, s: Dict, dtype) -> Dict:
    d, f = s["d"], s["ffn"]
    ks = jax.random.split(key, 4)
    return {"ln_g": _gain(ks[0], d, dtype),
            "wg": _normal(ks[1], (d, f), STD, dtype),
            "wu": _normal(ks[2], (d, f), STD, dtype),
            "wd": _normal(ks[3], (f, d), STD, dtype)}


def _ends(key, s: Dict, dtype) -> Dict:
    ks = jax.random.split(key, 2)
    # in slices of rows: [vocab, d] at once is 0.8 GB in float32
    cut = next(c for c in (64, 16, 4, 1) if s["vocab"] % c == 0)
    rows = (s["vocab"] // cut, s["d"])
    std = STD / s["embed_scale"]
    wte = jax.lax.map(lambda k: _normal(k, rows, std, dtype),
                      jax.random.split(ks[0], cut)).reshape(s["vocab"], s["d"])
    return {"wte": wte, "lnf_g": _gain(ks[1], s["d"], dtype)}


def frozen(sizes: Dict):
    """The sizes as a hashable (a jit's static argument)."""
    return tuple(sorted(sizes.items()))


_MAKERS = {"mamba": _mamba, "attention": _attention, "mlp": _mlp}


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "dtype"))
def _piece_jit(key, index, *, sizes, kind, dtype):
    tag = PIECES.index("mlp" if kind == "mlp" else "mixer")
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, _LAYER_TAG), index), tag)
    return _MAKERS[kind](k, dict(sizes), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("sizes", "dtype"))
def _ends_jit(key, *, sizes, dtype):
    return _ends(jax.random.fold_in(key, _ENDS_TAG), dict(sizes),
                 jnp.dtype(dtype))


def piece(sizes: Dict, seed: int, index: int, name: str, dtype=jnp.float32):
    """One piece of layer ``index`` (canonical names): ``mixer`` its Mamba-2
    mixer or its attention, as ``layer_types`` says, ``mlp`` its gated MLP,
    each with the gain of the norm before it. One jitted call a piece."""
    kind = name if name == "mlp" else sizes["layer_types"][index]
    return _piece_jit(root_key(seed), index, sizes=frozen(sizes), kind=kind,
                      dtype=jnp.dtype(dtype).name)


def ends(sizes: Dict, seed: int, dtype=jnp.float32):
    """The embedding (also the head: tied) and the final RMSNorm's gain."""
    return _ends_jit(root_key(seed), sizes=frozen(sizes),
                     dtype=jnp.dtype(dtype).name)


def everything(sizes: Dict, seed: int, dtype=jnp.float32):
    """(ends, [{piece: weights} for layer 0 .. L-1]) in ``dtype``: leaf for
    leaf the numbers of ``ends`` and ``piece``, rounded inside the call that
    makes them (no float32 copy of the model is ever held)."""
    return (ends(sizes, seed, dtype),
            [{name: piece(sizes, seed, i, name, dtype) for name in PIECES}
             for i in range(sizes["layers"])])


#: canonical leaf -> the program's leaf (``hybrid_ssm_lm_conf``'s vertices)
_MAMBA = {"w_in": "W_in", "conv_w": "conv_w", "conv_b": "conv_b",
          "a_log": "A_log", "dt_bias": "dt_bias", "d": "D",
          "norm_g": "norm_g", "w_out": "W_out"}
_ATTN = {"wq": "Wq", "wk": "Wk", "wv": "Wv", "wo": "Wo"}
_MLP = {"wg": "Wg", "wu": "Wu", "wd": "Wd"}


def program_tree(end: Dict, blocks, layer_types) -> Dict[str, Dict]:
    """The canonical weights under the names ``hybrid_ssm_lm_conf`` gives
    its vertices: the parameter pytree the program holds. Parameterless
    vertices (the residual adds, the tied head) hold ``{}``."""
    tree = {"embed": {"W": end["wte"]}, "lnf": {"gamma": end["lnf_g"]},
            "out": {}}
    for i, (b, kind) in enumerate(zip(blocks, layer_types)):
        mixer, leaves = (f"ssm{i}", _MAMBA) if kind == "mamba" \
            else (f"attn{i}", _ATTN)
        tree[f"ln{i}a"] = {"gamma": b["mixer"]["ln_g"]}
        tree[mixer] = {v: b["mixer"][k] for k, v in leaves.items()}
        tree[f"res{i}a"] = {}
        tree[f"ln{i}b"] = {"gamma": b["mlp"]["ln_g"]}
        tree[f"ffn{i}"] = {v: b["mlp"][k] for k, v in _MLP.items()}
        tree[f"res{i}b"] = {}
    return tree
