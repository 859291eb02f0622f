"""The ``mamba2_hybrid`` family: pre-RMSNorm blocks whose mixer is, layer by
layer as ``layer_types`` says, a Mamba-2 state-space layer (a fixed-size
state a slot is all it caches) or grouped-query attention with no positional
encoding; a gated MLP in every layer; both branches scaled where they are
added; a scaled token-only embedding and a head tied to it with a logit
divisor: the Granite-4.0-H block, under the keys its ``config.json`` uses
(``hybrid_ssm_lm_conf``'s graph). Served only: the training protocol's
functions say so when called. What ``../README.md`` asks of a family, from
the four modules beside this file:

``program.py``    the program's builder at a configuration's sizes, and the
                  hand-over of the seed's weights
``weights.py``    the seed's weights, whole and piece by piece
``reference.py``  the plain reference (the SEQUENTIAL recurrence) with its
                  lower-precision control
``flops.py``      model operations per token, and what one call of the
                  decode step's state update moves
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


def _served_only(*args, **kwargs):
    raise NotImplementedError(
        "the mamba2_hybrid family is served, not trained: at 16 bytes a "
        "parameter one 10-layer period and the embedding take 15.2 GB of "
        "the chip's 16.9 before any activation, so the family brings no "
        "training reference and no per-leaf views")


train_steps = _served_only
canonical_view = leaf_norms = change_norms = flat_names = _served_only
