"""The ``shortcut_moe`` family: shortcut-connected DOUBLE blocks — two latent
attentions (a low-rank row a token is all the cache holds; the two
normalised latents scaled by ``sqrt(d / rank)``) and two dense gated FFNs a
layer, and ONE expert branch that leaves after the first attention and joins
one attention and one FFN later: softmax scores over the routed AND the
zero-compute experts, a selection bias, top-k, weights scaled and not
renormalised, a zero-compute expert the identity, no shared expert, no drops
— between a token-only embedding and an untied head with no bias: the
LongCat-Flash block, under the keys its ``config.json`` uses
(``shortcut_moe_lm_conf``'s graph). Served only: the training protocol's
functions say so when called. What ``../README.md`` asks of a family, from
the four modules beside this file:

``program.py``    the program's builder at a configuration's sizes, and the
                  hand-over of the seed's weights
``weights.py``    the seed's weights, whole and piece by piece
``reference.py``  the plain reference with its lower-precision control
``flops.py``      model operations per token, and the expert branch's
                  operations and bytes a decode step
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
        "the shortcut_moe family is served, not trained: at 16 bytes a "
        "parameter four double blocks with the floor of 8 experts each do "
        "not fit a chip, so the family brings no training reference and no "
        "per-leaf views")


train_steps = _served_only
canonical_view = leaf_norms = change_norms = flat_names = _served_only
