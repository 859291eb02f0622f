"""The ``latent_moe`` family: pre-RMSNorm blocks of latent attention (a
low-rank row a token is all the cache holds; rotary positions inside
attention) and gated FFNs — the leading ones dense, the rest routed experts
without drops (sigmoid scores, a selection bias, top-k, renormalised and
scaled weights, a shared expert) — between a token-only embedding and an
untied head with no bias: the DeepSeek-V3 block, under the keys its
``config.json`` uses (``latent_moe_lm_conf``'s graph). Served only: the
training protocol's functions say so when called. What ``../README.md`` asks
of a family, from the four modules beside this file:

``program.py``    the program's builder at a configuration's sizes, and the
                  hand-over of the seed's weights
``weights.py``    the seed's weights, whole and layer by layer
``reference.py``  the plain reference with its lower-precision control
``flops.py``      model operations per token, and the expert layers'
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
        "the latent_moe family is served, not trained: fit_batch has no "
        "drop-free expert path at a chip's share of the experts (ROADMAP), "
        "so the family brings no training reference and no per-leaf views")


train_steps = _served_only
canonical_view = leaf_norms = change_norms = flat_names = _served_only
