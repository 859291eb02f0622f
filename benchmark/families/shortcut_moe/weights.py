"""Seeded weights of the ``shortcut_moe`` family, made on the device.

The benchmark makes every weight itself from ``--seed``: the program is handed
them (``everything``), and the plain reference makes the same ones again, a
piece of a layer at a time (``piece``: an attention, a dense FFN, the router;
``experts``: a few experts' own weights; ``ends``), so it takes nothing the
program has made. Every leaf is generated in float32 and, for the program,
rounded to the type it is served in.

Distribution (the configuration's ``assumed``): normal, std 0.02, every
projection, the router and both embeddings; RMSNorm gains 1 + N(0, 0.05)
(a gain left at one could be dropped unseen); the router's selection bias
``b`` N(0, 1e-4): small against a softmax score over 768 outputs (mean
1.3e-3), nonzero so that "for choosing only" is exercised — at N(0, 0.01)
the bias alone would choose the same twelve experts for every token.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
GAIN_STD = 0.05
BIAS_STD = 1e-4
_ENDS_TAG, _LAYER_TAG = 1, 2
#: a layer's pieces in the order they run, and each one's tag in its key
PIECES = ("attn0", "ffn0", "moe", "attn1", "ffn1")


def sizes_of(config: Dict) -> Dict:
    """The sizes the generator needs, from a configuration file's published
    keys (LongCat-Flash ``config.json`` names). ``n_routed_experts`` is what
    THIS chip holds; the router's width comes from
    ``published.n_routed_experts`` where the file states one beside it (all
    of them live here where it does not), plus ``zero_expert_num``;
    ``run.experts.first`` is the first expert held (0); ``vocab_size`` the
    slice held; ``run.engine.t_max`` the context a slot holds."""
    held = int(config["n_routed_experts"])
    d = int(config["hidden_size"])
    run = config.get("run", {})

    def scale(flag, rank):
        return math.sqrt(d / int(config[rank])) if config.get(flag) else 1.0
    return {
        "vocab": int(config["vocab_size"]), "d": d,
        "heads": int(config["num_attention_heads"]),
        "layers": int(config["num_layers"]),
        "dense_ffn": int(config["ffn_hidden_size"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "q_scale": scale("mla_scale_q_lora", "q_lora_rank"),
        "kv_scale": scale("mla_scale_kv_lora", "kv_lora_rank"),
        "experts": int(config.get("published", {}).get("n_routed_experts",
                                                       held)),
        "zero": int(config.get("zero_expert_num", 0)),
        "top_k": int(config["moe_topk"]),
        "expert_ffn": int(config["expert_ffn_hidden_size"]),
        "first_expert": int(run.get("experts", {}).get("first", 0)),
        "experts_held": held,
        "scaling": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "positions": int(config["max_position_embeddings"]),
        "t_max": int(run.get("engine", {}).get(
            "t_max", config["max_position_embeddings"]))}


def root_key(seed: int):
    """A key from any whole number up to a little over 2**31: split into two
    31-bit halves, so no signed 32-bit conversion is ever made."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _stack(key, count, shape, std, dtype):
    """[count, *shape] normals made one slice after another (each from its
    own key): made at once, the generator's temporaries are three times the
    float32 result."""
    return jax.lax.map(lambda k: _normal(k, shape, std, dtype),
                       jax.random.split(key, count))


def _gain(key, n, dtype):
    return (1.0 + jax.random.normal(key, (n,), jnp.float32)
            * GAIN_STD).astype(dtype)


def _attention(key, s: Dict, dtype) -> Dict:
    d, h = s["d"], s["heads"]
    ks = jax.random.split(key, 8)
    return {
        "ln_g": _gain(ks[0], d, dtype),
        "wqa": _normal(ks[1], (d, s["q_rank"]), STD, dtype),
        "q_g": _gain(ks[2], s["q_rank"], dtype),
        "wqb": _normal(ks[3], (s["q_rank"], h * (s["nope"] + s["rope"])),
                       STD, dtype),
        "wkva": _normal(ks[4], (d, s["kv_rank"] + s["rope"]), STD, dtype),
        "kv_g": _gain(ks[5], s["kv_rank"], dtype),
        "wkvb": _normal(ks[6], (s["kv_rank"], h * (s["nope"] + s["v"])),
                        STD, dtype),
        "wo": _normal(ks[7], (h * s["v"], d), STD, dtype)}


def _dense(key, s: Dict, dtype) -> Dict:
    d, f = s["d"], s["dense_ffn"]
    ks = jax.random.split(key, 4)
    return {"ln_g": _gain(ks[0], d, dtype),
            "wg": _normal(ks[1], (d, f), STD, dtype),
            "wu": _normal(ks[2], (d, f), STD, dtype),
            "wd": _normal(ks[3], (f, d), STD, dtype)}


def _expert_stacks(key, s: Dict, first, count: int, dtype) -> Dict:
    """``wg``, ``wu``, ``wd`` of ``count`` experts from ``first`` on
    (numbered over the whole model), one expert after another, each from a
    key of its own: an expert's weights depend neither on which chip holds
    it nor on how many are made at once."""
    d, f = s["d"], s["expert_ffn"]
    ks = jax.random.split(key, 5)
    out = {}
    for name, k, shape in (("wg", 2, (d, f)), ("wu", 3, (d, f)),
                           ("wd", 4, (f, d))):
        keys = jax.lax.dynamic_slice_in_dim(
            jax.random.split(ks[k], s["experts"]), first, count)
        out[name] = jax.lax.map(
            lambda kk: _normal(kk, shape, STD, dtype), keys)
    return out


def _router(key, s: Dict, dtype) -> Dict:
    """The router over the routed and the zero-compute experts, and its
    selection bias: the same on every chip, whatever experts it holds."""
    ks = jax.random.split(key, 5)
    width = s["experts"] + s["zero"]
    return {"wr": _normal(ks[0], (s["d"], width), STD, dtype),
            "b": _normal(ks[1], (width,), BIAS_STD, dtype)}


def _piece(key, s: Dict, name: str, dtype, stacks: bool) -> Dict:
    if name.startswith("attn"):
        return _attention(key, s, dtype)
    if name.startswith("ffn"):
        return _dense(key, s, dtype)
    p = _router(key, s, dtype)
    if stacks:
        p.update(_expert_stacks(key, s, s["first_expert"],
                                s["experts_held"], dtype))
    return p


def _ends(key, s: Dict, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    # in slices of rows: [vocab, d] at once is 0.4 GB in float32
    cut = next(c for c in (64, 16, 4, 1) if s["vocab"] % c == 0)
    rows = (s["vocab"] // cut, s["d"])
    wte = _stack(ks[0], cut, rows, STD, dtype).reshape(s["vocab"], s["d"])
    head = _stack(ks[2], cut, rows, STD, dtype).reshape(s["vocab"], s["d"])
    return {"wte": wte, "lnf_g": _gain(ks[1], s["d"], dtype),
            "head_w": head.T}


def frozen(sizes: Dict):
    """The sizes as a hashable (a jit's static argument)."""
    return tuple(sorted(sizes.items()))


def _piece_key(key, index, name: str):
    layer = jax.random.fold_in(jax.random.fold_in(key, _LAYER_TAG), index)
    return jax.random.fold_in(layer, PIECES.index(name))


@functools.partial(jax.jit,
                   static_argnames=("sizes", "name", "dtype", "stacks"))
def _piece_jit(key, index, *, sizes, name, dtype, stacks):
    return _piece(_piece_key(key, index, name), dict(sizes), name,
                  jnp.dtype(dtype), stacks)


@functools.partial(jax.jit, static_argnames=("sizes", "count"))
def _experts_jit(key, index, first, *, sizes, count):
    return _expert_stacks(_piece_key(key, index, "moe"), dict(sizes), first,
                          count, jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes", "dtype"))
def _ends_jit(key, *, sizes, dtype):
    return _ends(jax.random.fold_in(key, _ENDS_TAG), dict(sizes),
                 jnp.dtype(dtype))


def piece(sizes: Dict, seed: int, index: int, name: str, dtype=jnp.float32,
          stacks: bool = True) -> Dict:
    """One piece of double block ``index`` (canonical names): ``attn0`` /
    ``attn1`` an attention with the gain of the norm before it, ``ffn0`` /
    ``ffn1`` a dense FFN likewise, ``moe`` the router, its bias and — with
    ``stacks`` — this chip's experts. One jitted call a piece: a dense FFN
    is 0.9 GB in float32, the experts held 2.4 GB, and the reference holds
    one piece at a time (``experts`` makes the stacks a few at a time)."""
    return _piece_jit(root_key(seed), index, sizes=frozen(sizes), name=name,
                      dtype=jnp.dtype(dtype).name, stacks=stacks)


def experts(sizes: Dict, seed: int, index: int, first: int, count: int):
    """``wg``, ``wu``, ``wd`` of the experts ``first .. first + count`` of
    block ``index`` (numbered over the whole model), float32: the numbers
    ``piece(.., "moe")`` puts at those places of its stacks."""
    return _experts_jit(root_key(seed), index, first, sizes=frozen(sizes),
                        count=count)


def ends(sizes: Dict, seed: int, dtype=jnp.float32):
    """The embedding, the final RMSNorm's gain and the (untied) head."""
    return _ends_jit(root_key(seed), sizes=frozen(sizes),
                     dtype=jnp.dtype(dtype).name)


def everything(sizes: Dict, seed: int, dtype=jnp.float32):
    """(ends, [{piece: weights} for block 0 .. L-1]) in ``dtype``: leaf for
    leaf the numbers of ``ends`` and ``piece``, rounded where the weights
    are served in a narrower type (the rounding happens inside the call that
    makes them: no float32 copy of the model is ever held)."""
    return (ends(sizes, seed, dtype),
            [{name: piece(sizes, seed, i, name, dtype) for name in PIECES}
             for i in range(sizes["layers"])])


#: canonical leaf -> the program's leaf (``shortcut_moe_lm_conf``'s vertices)
_ATTN = {"wqa": "Wqa", "q_g": "gq", "wqb": "Wqb", "wkva": "Wkva",
         "kv_g": "gkv", "wkvb": "Wkvb", "wo": "Wo"}
_DENSE = {"wg": "Wg", "wu": "Wu", "wd": "Wd"}
_EXPERT = {"wr": "Wr", "b": "b", "wg": "Wg", "wu": "Wu", "wd": "Wd"}
#: piece -> (the norm before it, its vertex, the add after it)
_VERTICES = {"attn0": ("ln{}a", "attn{}a", "res{}a"),
             "ffn0": ("ln{}b", "ffn{}a", "res{}b"),
             "attn1": ("ln{}c", "attn{}b", "res{}c"),
             "ffn1": ("ln{}d", "ffn{}b", "res{}d")}


def program_tree(end: Dict, blocks) -> Dict[str, Dict]:
    """The canonical weights under the names ``shortcut_moe_lm_conf`` gives
    its vertices: the parameter pytree the program holds. Parameterless
    vertices (the residual adds) hold ``{}``."""
    tree = {"embed": {"W": end["wte"]}, "lnf": {"gamma": end["lnf_g"]},
            "out": {"W": end["head_w"]}}
    for i, b in enumerate(blocks):
        for name, (norm, vertex, add) in _VERTICES.items():
            leaves = _ATTN if name.startswith("attn") else _DENSE
            tree[norm.format(i)] = {"gamma": b[name]["ln_g"]}
            tree[vertex.format(i)] = {v: b[name][k]
                                      for k, v in leaves.items()}
            tree[add.format(i)] = {}
        tree[f"moe{i}"] = {v: b["moe"][k] for k, v in _EXPERT.items()}
    return tree
