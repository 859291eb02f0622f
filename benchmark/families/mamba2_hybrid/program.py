"""The one place (with the runners) that touches the program: building
``hybrid_ssm_lm_conf`` at a configuration's published sizes and handing the
net the benchmark's own weights.

``ComputationGraph.init()`` is run under ``jax.eval_shape``: it gives the
parameter tree's structure and shapes without making an array (the program's
own initialisation of 3.2 B parameters in float32 would take 12.8 GB, and
the benchmark replaces the numbers anyway). The benchmark's weights must
match that structure leaf for leaf, or the run stops.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import weights as wgen

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def make_net(config: Dict):
    """(net, sizes, (parameter, state, updater-state) shapes): the graph at
    the configuration's sizes, initialised abstractly — no array is made."""
    from deeplearning4j_tpu.models import hybrid_ssm_lm_conf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    s = wgen.sizes_of(config)
    conf = hybrid_ssm_lm_conf(
        vocab_size=s["vocab"], d_model=s["d"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], layer_types=s["layer_types"],
        ffn_hidden=s["ffn"], ssm_heads=s["ssm_heads"],
        ssm_head_dim=s["ssm_head_dim"], ssm_state=s["ssm_state"],
        conv_kernel=s["conv"], chunk_size=s["chunk"],
        attention_scale=s["attn_scale"], embedding_scale=s["embed_scale"],
        residual_scale=s["residual"], logit_divisor=s["logit_div"],
        eps=s["eps"], tie_embeddings=bool(config["tie_word_embeddings"]),
        max_length=s["positions"], seed=0)
    net = ComputationGraph(
        conf, compute_dtype=_DTYPES[config["run"]["compute_dtype"]])
    shapes = jax.eval_shape(
        lambda: (net.init(), (net.params, net.state, net.updater_state))[1])
    net.params = net.state = net.updater_state = None   # traced, not arrays
    return net, s, shapes


def install(net, config: Dict, sizes: Dict, shapes, seed: int,
            train: bool) -> None:
    """Hand ``net`` the seed's weights in the type they are served in, an
    empty layer state and no updater state (the decoder never reads it)."""
    if train:
        raise NotImplementedError("the mamba2_hybrid family is served only")
    p_shapes, s_shapes, _ = shapes
    # the old weights go first, whoever still refers to them (a decoder
    # keeps the tree it last cast)
    for old in jax.tree_util.tree_leaves(net.params):
        old.delete()
    net.params = net.updater_state = None
    end, blocks = wgen.everything(sizes, seed,
                                  _DTYPES[config["run"]["weights_dtype"]])
    tree = wgen.program_tree(end, blocks, sizes["layer_types"])
    want = jax.tree_util.tree_map(lambda a: a.shape, p_shapes)
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    if want != got:
        raise RuntimeError("the program's parameter tree and the "
                           "benchmark's weights differ in structure or "
                           f"shape:\nprogram {want}\nbenchmark {got}")
    net.params = tree
    net.state = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), s_shapes)
    net.updater_state = {}
    net.iteration = 0
