"""Seeded weights of the ``latent_moe`` family, made on the device.

The benchmark makes every weight itself from ``--seed``: the program is handed
them (``everything``), and the plain reference makes the same ones again,
layer by layer (``layer`` / ``ends``; an expert layer's own weights a few
experts at a time, ``experts``), so it takes nothing the program has made. Every leaf is generated in float32
and, for the program, rounded to the type it is served in.

Distribution (the configuration's ``assumed``): normal, std 0.02, every
projection, the router and both embeddings; RMSNorm gains 1 + N(0, 0.05)
(a gain left at one could be dropped unseen); the router's selection bias
``b`` N(0, 0.01), nonzero so that "for choosing only" is exercised.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
GAIN_STD = 0.05
BIAS_STD = 0.01
_ENDS_TAG, _LAYER_TAG = 1, 2


def sizes_of(config: Dict) -> Dict[str, int]:
    """The sizes the generator needs, from a configuration file's published
    keys (DeepSeek-V3 ``config.json`` names). ``run.experts`` (``first``,
    ``held``) is this chip's share of the routed experts, all of them where
    it is absent; ``run.engine.t_max`` the context a slot holds."""
    experts = int(config["n_routed_experts"])
    share = config.get("run", {}).get("experts", {})
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "dense_ffn": int(config["intermediate_size"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "experts": experts, "top_k": int(config["num_experts_per_tok"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared": int(config["n_shared_experts"]),
        "first_expert": int(share.get("first", 0)),
        "experts_held": int(share.get("held", experts)),
        "scaling": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "positions": int(config["max_position_embeddings"]),
        "t_max": int(config.get("run", {}).get("engine", {}).get(
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
    float32 result, 5 GB for one expert stack."""
    return jax.lax.map(lambda k: _normal(k, shape, std, dtype),
                       jax.random.split(key, count))


def _gain(key, n, dtype):
    return (1.0 + jax.random.normal(key, (n,), jnp.float32)
            * GAIN_STD).astype(dtype)


def _expert_stacks(ks, s: Dict, first, count: int, dtype):
    """``wg``, ``wu``, ``wd`` of ``count`` experts from ``first`` on
    (numbered over the whole model), one expert after another, each from a
    key of its own: an expert's weights depend neither on which chip holds
    it nor on how many are made at once."""
    d, f = s["d"], s["expert_ffn"]
    out = {}
    for name, k, shape in (("wg", 11, (d, f)), ("wu", 12, (d, f)),
                           ("wd", 13, (f, d))):
        keys = jax.lax.dynamic_slice_in_dim(
            jax.random.split(ks[k], s["experts"]), first, count)
        out[name] = jax.lax.map(
            lambda kk: _normal(kk, shape, STD, dtype), keys)
    return out


def _layer(key, s: Dict, expert: bool, dtype,
           stacks: bool = True) -> Dict[str, jnp.ndarray]:
    d, h = s["d"], s["heads"]
    ks = jax.random.split(key, 20)
    p = {
        "ln1_g": _gain(ks[0], d, dtype),
        "wqa": _normal(ks[1], (d, s["q_rank"]), STD, dtype),
        "q_g": _gain(ks[2], s["q_rank"], dtype),
        "wqb": _normal(ks[3], (s["q_rank"], h * (s["nope"] + s["rope"])),
                       STD, dtype),
        "wkva": _normal(ks[4], (d, s["kv_rank"] + s["rope"]), STD, dtype),
        "kv_g": _gain(ks[5], s["kv_rank"], dtype),
        "wkvb": _normal(ks[6], (s["kv_rank"], h * (s["nope"] + s["v"])),
                        STD, dtype),
        "wo": _normal(ks[7], (h * s["v"], d), STD, dtype),
        "ln2_g": _gain(ks[8], d, dtype)}
    if not expert:
        f = s["dense_ffn"]
        p.update(wg=_normal(ks[9], (d, f), STD, dtype),
                 wu=_normal(ks[10], (d, f), STD, dtype),
                 wd=_normal(ks[11], (f, d), STD, dtype))
        return p
    e, f = s["experts"], s["expert_ffn"]
    p.update(wr=_normal(ks[9], (d, e), STD, dtype),
             b=_normal(ks[10], (e,), BIAS_STD, dtype))
    if stacks:
        p.update(_expert_stacks(ks, s, s["first_expert"], s["experts_held"],
                                dtype))
    if s["shared"]:
        fs = s["shared"] * f
        p.update(sg=_normal(ks[14], (d, fs), STD, dtype),
                 su=_normal(ks[15], (d, fs), STD, dtype),
                 sd=_normal(ks[16], (fs, d), STD, dtype))
    return p


def _ends(key, s: Dict, dtype) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 3)
    # in slices of rows too: [vocab, d] at once is a gigabyte in float32
    cut = next(c for c in (64, 16, 4, 1) if s["vocab"] % c == 0)
    rows = (s["vocab"] // cut, s["d"])
    wte = _stack(ks[0], cut, rows, STD, dtype).reshape(s["vocab"], s["d"])
    head = _stack(ks[2], cut, rows, STD, dtype).reshape(s["vocab"], s["d"])
    return {"wte": wte, "lnf_g": _gain(ks[1], s["d"], dtype),
            "head_w": head.T}


def frozen(sizes: Dict):
    """The sizes as a hashable (a jit's static argument)."""
    return tuple(sorted(sizes.items()))


def _layer_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, _LAYER_TAG), index)


@functools.partial(jax.jit,
                   static_argnames=("sizes", "expert", "dtype", "stacks"))
def _layer_jit(key, index, *, sizes, expert, dtype, stacks=True):
    return _layer(_layer_key(key, index), dict(sizes), expert,
                  jnp.dtype(dtype), stacks)


@functools.partial(jax.jit, static_argnames=("sizes", "count"))
def _experts_jit(key, index, first, *, sizes, count):
    return _expert_stacks(jax.random.split(_layer_key(key, index), 20),
                          dict(sizes), first, count, jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes", "dtype"))
def _ends_jit(key, *, sizes, dtype):
    return _ends(jax.random.fold_in(key, _ENDS_TAG), dict(sizes),
                 jnp.dtype(dtype))


def layer(sizes: Dict, seed: int, index: int, dtype=jnp.float32,
          stacks: bool = True):
    """One block's weights (canonical names); the first ``dense_layers``
    blocks carry a dense FFN, the others the router, this chip's experts and
    the shared expert. One jitted call a layer: an expert layer is 4.8 GB
    in float32, and nothing holds two. Without ``stacks`` the experts' own
    weights are left out (``experts`` makes them a few at a time)."""
    return _layer_jit(root_key(seed), index, sizes=frozen(sizes),
                      expert=index >= sizes["dense_layers"],
                      dtype=jnp.dtype(dtype).name, stacks=stacks)


def experts(sizes: Dict, seed: int, index: int, first: int, count: int):
    """``wg``, ``wu``, ``wd`` of the experts ``first .. first + count`` of
    block ``index`` (numbered over the whole model), float32: the numbers
    ``layer`` puts at those places of its stacks."""
    return _experts_jit(root_key(seed), index, first, sizes=frozen(sizes),
                        count=count)


def ends(sizes: Dict, seed: int, dtype=jnp.float32):
    """The embedding, the final RMSNorm's gain and the (untied) head."""
    return _ends_jit(root_key(seed), sizes=frozen(sizes),
                     dtype=jnp.dtype(dtype).name)


def everything(sizes: Dict, seed: int, dtype=jnp.float32):
    """(ends, [layer 0 .. L-1]) in ``dtype``: leaf for leaf the numbers of
    ``ends`` and ``layer``, rounded where the weights are served in a
    narrower type (the rounding happens inside the call that makes them:
    no float32 copy of the model is ever held)."""
    return (ends(sizes, seed, dtype),
            [layer(sizes, seed, i, dtype) for i in range(sizes["layers"])])


#: canonical leaf -> the program's leaf (``latent_moe_lm_conf``'s vertices)
_ATTN = {"wqa": "Wqa", "q_g": "gq", "wqb": "Wqb", "wkva": "Wkva",
         "kv_g": "gkv", "wkvb": "Wkvb", "wo": "Wo"}
_DENSE = {"wg": "Wg", "wu": "Wu", "wd": "Wd"}
_EXPERT = {"wr": "Wr", "b": "b", "wg": "Wg", "wu": "Wu", "wd": "Wd",
           "sg": "Sg", "su": "Su", "sd": "Sd"}


def program_tree(end: Dict, blocks) -> Dict[str, Dict]:
    """The canonical weights under the names ``latent_moe_lm_conf`` gives
    its vertices: the parameter pytree the program holds. Parameterless
    vertices (the residual adds) hold ``{}``."""
    tree = {"embed": {"W": end["wte"]}, "lnf": {"gamma": end["lnf_g"]},
            "out": {"W": end["head_w"]}}
    for i, b in enumerate(blocks):
        ffn = _EXPERT if "wr" in b else _DENSE
        tree[f"ln{i}a"] = {"gamma": b["ln1_g"]}
        tree[f"attn{i}"] = {v: b[k] for k, v in _ATTN.items()}
        tree[f"res{i}a"] = {}
        tree[f"ln{i}b"] = {"gamma": b["ln2_g"]}
        tree[f"ffn{i}"] = {v: b[k] for k, v in ffn.items() if k in b}
        tree[f"res{i}b"] = {}
    return tree
