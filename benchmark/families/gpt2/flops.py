"""Model operations per token from the configuration's sizes. Nothing here is
measured and nothing comes from XLA's cost analysis (which cannot see inside
a Mosaic call): each function counts what the algorithm needs.

A multiply-add counts as two operations. Recomputed operations are never
counted, so a share of the peak built on these numbers errs low, not high.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(sizes: Dict[str, int]) -> int:
    """Parameters that take part in a matrix product per token: q, k, v, o
    (4 d^2) and the two FFN matrices (2 d ffn) per block, and the output
    head (d V). Embedding rows are looked up, not multiplied."""
    d, ffn = sizes["d"], sizes["ffn"]
    return sizes["layers"] * (4 * d * d + 2 * d * ffn) + d * sizes["vocab"]


def total_params(sizes: Dict[str, int]) -> int:
    """Every parameter the program holds for this configuration (untied
    head with bias, no q/k/v bias, learned positions)."""
    d, ffn = sizes["d"], sizes["ffn"]
    per_block = 4 * d * d + d + 2 * d * ffn + ffn + d + 4 * d
    return (sizes["layers"] * per_block + sizes["vocab"] * d
            + sizes["positions"] * d + 2 * d + d * sizes["vocab"]
            + sizes["vocab"])


def forward_token_flops(sizes: Dict[str, int], context: float) -> float:
    """Forward operations for ONE token that attends to ``context`` keys
    (itself included): every matrix product once, plus q.k and p.v over the
    context in every block."""
    return 2.0 * matmul_params(sizes) \
        + 4.0 * sizes["layers"] * sizes["d"] * context


def prompt_flops(sizes: Dict[str, int], length: int) -> float:
    """Forward operations to prefill a prompt of ``length`` tokens causally:
    token t attends to t+1 keys."""
    return 2.0 * matmul_params(sizes) * length \
        + 4.0 * sizes["layers"] * sizes["d"] * (length * (length + 1) / 2.0)


def decode_flops(sizes: Dict[str, int], prompt: int, new: int) -> float:
    """Forward operations for the ``new`` tokens decoded after a prompt of
    ``prompt`` tokens; the first new token comes out of the prefill, so
    ``new - 1`` decode steps run, step j (from 1) attending to prompt + j
    keys: the prompt, the tokens decoded before, and its own input token."""
    steps = max(new - 1, 0)
    ctx = steps * prompt + steps * (steps + 1) / 2.0
    return 2.0 * matmul_params(sizes) * steps \
        + 4.0 * sizes["layers"] * sizes["d"] * ctx


def train_token_flops(sizes: Dict[str, int], seq_len: int) -> float:
    """Forward + backward operations per trained token at sequence length
    ``seq_len``: three times the forward pass (backward is twice the forward),
    attention averaged over the causal triangle. No recomputation counted."""
    mean_context = (seq_len + 1) / 2.0
    return 3.0 * forward_token_flops(sizes, mean_context)
