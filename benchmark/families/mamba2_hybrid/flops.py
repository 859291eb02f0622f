"""Model operations per token from the configuration's sizes, and what one
call of the decode step's state-update kernel must move. Nothing here is
measured and nothing comes from XLA's cost analysis (which cannot see inside
a Mosaic call): each function counts what the algorithm needs.

A multiply-add counts as two operations; nothing recomputed is counted. A
Mamba-2 mixer's sequence work is counted as its recurrence, per token and
head: the state decayed (P·N multiplies), the input's outer product added in
and the state read out against C (2·P·N multiply-adds) — 5·P·N operations,
whether a prompt's chunked scan or a decode step's update computes it (the
chunked form's extra products within a chunk are an implementation's, not
the model's). Attention is grouped-query: a key costs every query head its
score and its weighted sum.
"""

from __future__ import annotations

from typing import Dict

from .weights import conv_dim, inner


def _mamba_weights(s: Dict) -> int:
    """Multiply-adds a token's mixer projections and convolution take."""
    return s["d"] * (inner(s) + conv_dim(s) + s["ssm_heads"]) \
        + s["conv"] * conv_dim(s) + inner(s) * s["d"]


def _scan(s: Dict) -> int:
    """Operations of one token's state update and read-out, every head."""
    return 5 * s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]


def _attention_weights(s: Dict) -> int:
    dh = s["d"] // s["heads"]
    return 2 * s["d"] * s["heads"] * dh + 2 * s["d"] * s["kv_heads"] * dh


def _attention_per_key(s: Dict) -> int:
    """Multiply-adds per attended key (scores and sum, every query head)."""
    return 2 * s["d"]


def _counts(s: Dict):
    mamba = sum(t == "mamba" for t in s["layer_types"])
    return mamba, len(s["layer_types"]) - mamba


def forward_token_flops(s: Dict, context: float) -> float:
    """Forward operations for ONE token that attends to ``context`` keys
    (itself included), the head included."""
    mamba, attn = _counts(s)
    macs = mamba * _mamba_weights(s) + attn * _attention_weights(s) \
        + len(s["layer_types"]) * 3 * s["d"] * s["ffn"] \
        + s["d"] * s["vocab"]
    return 2.0 * macs + mamba * _scan(s) \
        + 2.0 * attn * _attention_per_key(s) * context


def prompt_flops(s: Dict, length: int) -> float:
    """Forward operations to prefill a prompt of ``length`` tokens causally
    (token t attends to t+1 keys); the head runs on the last position
    only."""
    mamba, attn = _counts(s)
    body = forward_token_flops(s, 0.0) - 2.0 * s["d"] * s["vocab"]
    return body * length + 2.0 * s["d"] * s["vocab"] \
        + 2.0 * attn * _attention_per_key(s) * (length * (length + 1) / 2.0)


def decode_flops(s: Dict, prompt: int, new: int) -> float:
    """Forward operations for the ``new`` tokens decoded after a prompt of
    ``prompt`` tokens; the first comes out of the prefill, so ``new - 1``
    decode steps run, step j (from 1) attending to prompt + j keys."""
    _, attn = _counts(s)
    steps = max(new - 1, 0)
    ctx = steps * prompt + steps * (steps + 1) / 2.0
    return steps * forward_token_flops(s, 0.0) \
        + 2.0 * attn * _attention_per_key(s) * ctx


def train_token_flops(s: Dict, seq_len: int) -> float:
    """Forward + backward operations per trained token (three times the
    forward pass). The family has no training cell; the count is what one
    would need."""
    return 3.0 * forward_token_flops(s, (seq_len + 1) / 2.0)


def mamba_params(s: Dict) -> int:
    h = s["ssm_heads"]
    return _mamba_weights(s) + conv_dim(s) + 3 * h + inner(s)


def total_params(s: Dict) -> int:
    """Every parameter the program holds: per layer its mixer, its MLP and
    two norms' gains; the embedding once (the head is tied to it) and the
    final norm's gain."""
    mamba, attn = _counts(s)
    per_layer = 3 * s["d"] * s["ffn"] + 2 * s["d"]
    return mamba * mamba_params(s) + attn * _attention_weights(s) \
        + len(s["layer_types"]) * per_layer + s["vocab"] * s["d"] + s["d"]


# ---- one call of the decode step's state update -------------------------
def ssm_decode_need(s: Dict, slots: int,
                    itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes one decode step's state update of ONE mixer
    needs over ``slots`` slots (``ssm_decode_update``'s contract, whatever
    implements it): every slot's state [H, P, N] read and written in the
    type it is held in (``itemsize``), the token's x [H, P], dt [H], B and C
    [N] in and y [H, P] out in float32, and A, D [H] once; per slot and
    head the 5·P·N operations of the recurrence and P multiply-adds of the
    D term. The convolution and the gate are not the call's."""
    h, p, n = s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"]
    state = slots * h * p * n
    bytes_ = 2.0 * itemsize * state \
        + 4.0 * (slots * (2 * h * p + h + 2 * n) + 2 * h)
    flops = slots * h * (5.0 * p * n + 2.0 * p)
    return {"flops": flops, "bytes": bytes_}
