"""Blockwise (flash-style) attention for long contexts — the helper-seam
kernel that removes the O(T²) logits materialization from
SelfAttentionLayer (nn/conf/layers/attention.py registers kind
="attention" helpers the way the cuDNN seam registers conv helpers).

The math is the streaming softmax already proven in ring attention
(parallel/sequence._block_attend — running max / denominator /
numerator): here the k/v blocks stream through a ``lax.scan`` on ONE
device instead of rotating around the ICI ring, so peak memory is
O(T·block) instead of O(T²), and ``jax.checkpoint`` over the scan body
keeps the backward at the same footprint (blocks recompute instead of
storing per-block probabilities).

Equivalence contract: identical to the materialized path on every query
row with at least one visible (unmasked, causally-allowed) key. Rows
with NO visible key are degenerate in both paths — each emits a
different arbitrary convex combination of v (finite and bounded); such
rows only arise from all-padding inputs and are excluded by loss masks.

At short T the materialized-softmax XLA path is at least as fast — the
helper is therefore enabled explicitly (``register_flash_attention``)
or picked per-call by the layer when T exceeds ``min_seq_len``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def flash_attention(q, k, v, causal: bool = False, block_size: int = 512,
                    key_mask=None):
    """q/k/v [B, T, H, D] → [B, T, H, D] without materializing [B,H,T,T].

    ``key_mask`` [B, T]: 1 for real keys, 0 for padding (masked keys are
    excluded from every block's softmax)."""
    from ..parallel.sequence import _block_attend

    b, t, h, d = q.shape
    bs = min(block_size, t)
    pad = (-t) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        km = key_mask if key_mask is not None else jnp.ones((b, t), q.dtype)
        key_mask = jnp.pad(km, ((0, 0), (0, pad)))
    n_blocks = k.shape[1] // bs
    kb = k.reshape(b, n_blocks, bs, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, bs, h, d).transpose(1, 0, 2, 3, 4)
    mb = None
    if key_mask is not None:
        mb = key_mask.reshape(b, n_blocks, bs).transpose(1, 0, 2)

    neg = jnp.asarray(-jnp.inf, q.dtype)

    @jax.checkpoint
    def body(carry, xs):
        m, l, o, idx = carry
        if mb is None:
            k_cur, v_cur = xs
            keep = None
        else:
            # masked/padded keys: logits replaced by -1e30 inside
            # _block_attend (same degradation as the materialized path on
            # fully-masked rows)
            k_cur, v_cur, keep = xs
        m, l, o = _block_attend(q, k_cur, v_cur, m, l, o,
                                0, idx * bs, causal, k_keep=keep)
        return (m, l, o, idx + 1), None

    m0 = jnp.full((b, h, t), neg, q.dtype)
    l0 = jnp.zeros((b, h, t), q.dtype)
    o0 = jnp.zeros_like(q)
    if mb is None:
        (m, l, o, _), _ = lax.scan(body, (m0, l0, o0, 0), (kb, vb))
    else:
        (m, l, o, _), _ = lax.scan(body, (m0, l0, o0, 0), (kb, vb, mb))
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return o / denom


# sequence length above which the blockwise path replaces the
# materialized-softmax path when the flash helper is registered
DEFAULT_MIN_SEQ_LEN = 1024


def make_flash_helper(block_size: int = 512,
                      min_seq_len: int = DEFAULT_MIN_SEQ_LEN):
    def helper(conf, q, k, v, mask):
        if q.shape[1] < min_seq_len:
            return None                      # fall back to the layer's path
        return flash_attention(q, k, v, causal=conf.causal,
                               block_size=block_size, key_mask=mask)
    return helper


def register_flash_attention(block_size: int = 512,
                             min_seq_len: int = DEFAULT_MIN_SEQ_LEN,
                             platforms=("tpu", "cpu")) -> None:
    from ..nn.helpers import enable_helper, register_helper
    register_helper("attention",
                    make_flash_helper(block_size, min_seq_len), platforms)
    enable_helper("attention")
