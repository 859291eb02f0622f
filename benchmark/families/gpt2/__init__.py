"""The ``gpt2`` family: learned positions, pre-LN blocks, GELU FFN, full
multi-head attention, an untied head with a bias (``transformer_lm_conf``'s
block; each configuration file lists the departures from the published GPT-2
under ``assumed``). What ``../README.md`` asks of a family, from the four
modules beside this file:

``program.py``    the program's builder at a configuration's sizes, and the
                  hand-over of the seed's weights
``weights.py``    the seed's weights, whole and block by block, and the view
                  of a program-named tree in the reference's order
``reference.py``  the plain reference with its lower-precision control
``flops.py``      model operations per token
"""

from __future__ import annotations

from . import flops, program, reference, weights

# ---- the program
sizes_of = weights.sizes_of
make_net = program.make_net
install = program.install

# ---- the counts
prompt_flops = flops.prompt_flops
decode_flops = flops.decode_flops
train_token_flops = flops.train_token_flops
total_params = flops.total_params

# ---- the plain reference
served_token_gaps = reference.served_token_gaps
train_steps = reference.train_steps


# ---- per-leaf readings, in one order (``flat_names``)
def canonical_view(tree, sizes):
    """A program-named tree (parameters, or one Adam moment) in the
    reference's order, copying nothing."""
    return weights.canonical_view(tree, sizes["layers"])


def leaf_norms(view):
    return reference.leaf_norms(*view)


def change_norms(sizes, seed, view):
    return reference.change_norms(sizes, seed, *view)


def flat_names(sizes):
    return reference.flat_names(sizes["layers"])
