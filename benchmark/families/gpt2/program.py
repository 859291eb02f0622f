"""The one place (with the runners) that touches the program: building
``transformer_lm_conf`` at a configuration's published sizes and handing the
net the benchmark's own weights.

``ComputationGraph.init()`` is run under ``jax.eval_shape``: it gives the
parameter tree's structure, shapes and the updaters without making an array
(the program's own initialisation of 838 M parameters, leaf by leaf, would be
set-up time spent on numbers the benchmark then replaces). The benchmark's
weights must match that structure leaf for leaf, or the run stops.
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
    from deeplearning4j_tpu.models import transformer_lm_conf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    sizes = wgen.sizes_of(config)
    run = config["run"]
    conf = transformer_lm_conf(
        vocab_size=sizes["vocab"], d_model=sizes["d"],
        num_heads=sizes["heads"], num_layers=sizes["layers"],
        ff_mult=sizes["ffn"] // sizes["d"], max_length=sizes["positions"],
        learning_rate=float(run.get("optimizer", {}).get(
            "learning_rate", 3e-4)), seed=0)
    net = ComputationGraph(conf, compute_dtype=_DTYPES[run["compute_dtype"]])
    shapes = jax.eval_shape(
        lambda: (net.init(), (net.params, net.state, net.updater_state))[1])
    return net, sizes, shapes


def build_net(config: Dict, seed: int, train: bool):
    """A ``ComputationGraph`` at the configuration's sizes holding the
    seed's weights (see :func:`install`)."""
    net, sizes, shapes = make_net(config)
    install(net, config, sizes, shapes, seed, train)
    return net, sizes


def install(net, config: Dict, sizes: Dict, shapes, seed: int,
            train: bool) -> None:
    """Hand ``net`` the seed's weights and a fresh state: float32 masters
    with zeroed Adam moments and the iteration count at 0 for training; for
    serving the weights in the type they are served in and no updater state
    (the decoder never reads it)."""
    p_shapes, s_shapes, u_shapes = shapes
    if jax.tree_util.tree_leaves(s_shapes):
        raise RuntimeError("the graph holds layer state the benchmark does "
                           "not make; extend program.install")
    net.params = net.updater_state = None      # let the old ones go first
    tree = weights_tree(config, sizes, seed)
    want = jax.tree_util.tree_map(lambda a: a.shape, p_shapes)
    got = jax.tree_util.tree_map(lambda a: a.shape, tree)
    if want != got:
        raise RuntimeError("the program's parameter tree and the "
                           "benchmark's weights differ in structure or "
                           f"shape:\nprogram {want}\nbenchmark {got}")
    net.params = tree
    net.state = jax.tree_util.tree_map(lambda a: a, s_shapes)
    net.updater_state = _zeros(u_shapes) if train else {}
    net.iteration = 0


def _zeros(shapes):
    return jax.jit(lambda: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes))()


def weights_tree(config: Dict, sizes: Dict, seed: int):
    """The seed's weights under the program's names, in the type the
    configuration stores them in."""
    end, blocks = wgen.everything(sizes, seed,
                                  _DTYPES[config["run"]["weights_dtype"]])
    return wgen.program_tree(end, blocks)
