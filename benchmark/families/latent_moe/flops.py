"""Model operations per token from the configuration's sizes, and what the
expert layers of one decode step need. Nothing here is measured and nothing
comes from XLA's cost analysis (which cannot see inside a Mosaic call): each
function counts what the algorithm needs.

A multiply-add counts as two operations; nothing recomputed is counted, and
only the ACTIVE experts count: the ``top_k`` a token chose among those held
here, plus the shared expert — never the experts a dense formulation would
also multiply. Prefill counts attention decompressed (per-head k and v made
from the latent row), decode counts it absorbed (the latent row read in
place), as the program runs them.
"""

from __future__ import annotations

from typing import Dict


def _attention_weights(s: Dict) -> int:
    """Multiply-adds a token's projections take in one attention layer,
    decompressed or absorbed alike."""
    h, qk = s["heads"], s["nope"] + s["rope"]
    n = s["d"] * s["q_rank"] + s["q_rank"] * h * qk \
        + s["d"] * (s["kv_rank"] + s["rope"]) + h * s["v"] * s["d"]
    # decompressed: k_nope and v of the token from its latent row; absorbed:
    # q_nope through W_K and the weighted latent sum through W_V — the same
    # matrix either way
    return n + s["kv_rank"] * h * (s["nope"] + s["v"])


def _attention_per_key(s: Dict, absorbed: bool) -> int:
    """Multiply-adds per attended key in one layer (scores and sum)."""
    h = s["heads"]
    if absorbed:
        return h * (s["kv_rank"] + s["rope"]) + h * s["kv_rank"]
    return h * (s["nope"] + s["rope"]) + h * s["v"]


def active_experts(s: Dict) -> float:
    """Experts that compute a token in an expert layer here: its ``top_k``
    times the share of the experts held, plus the shared expert."""
    return s["top_k"] * s["experts_held"] / s["experts"] + s["shared"]


def expert_params(s: Dict) -> int:
    return 3 * s["d"] * s["expert_ffn"]


def _ffn_weights(s: Dict) -> float:
    """Multiply-adds a token's FFNs take over all layers."""
    dense = s["dense_layers"] * 3 * s["d"] * s["dense_ffn"]
    moe = (s["layers"] - s["dense_layers"]) * (
        s["d"] * s["experts"] + active_experts(s) * expert_params(s))
    return dense + moe


def forward_token_flops(s: Dict, context: float, absorbed: bool) -> float:
    """Forward operations for ONE token that attends to ``context`` keys
    (itself included)."""
    per_token = s["layers"] * _attention_weights(s) \
        + _ffn_weights(s) + s["d"] * s["vocab"]
    return 2.0 * per_token \
        + 2.0 * s["layers"] * _attention_per_key(s, absorbed) * context


def prompt_flops(s: Dict, length: int) -> float:
    """Forward operations to prefill a prompt of ``length`` tokens causally
    (token t attends to t+1 keys), attention decompressed; the head runs on
    the last position only."""
    per_token = s["layers"] * _attention_weights(s) + _ffn_weights(s)
    return 2.0 * per_token * length + 2.0 * s["d"] * s["vocab"] \
        + 2.0 * s["layers"] * _attention_per_key(s, False) \
        * (length * (length + 1) / 2.0)


def decode_flops(s: Dict, prompt: int, new: int) -> float:
    """Forward operations for the ``new`` tokens decoded after a prompt of
    ``prompt`` tokens, attention absorbed; the first new token comes out of
    the prefill, so ``new - 1`` decode steps run, step j (from 1) attending
    to prompt + j keys."""
    steps = max(new - 1, 0)
    ctx = steps * prompt + steps * (steps + 1) / 2.0
    return steps * forward_token_flops(s, 0.0, True) \
        + 2.0 * s["layers"] * _attention_per_key(s, True) * ctx


def train_token_flops(s: Dict, seq_len: int) -> float:
    """Forward + backward operations per trained token (three times the
    forward pass, attention decompressed and averaged over the causal
    triangle). The family has no training cell; the count is what one
    would need."""
    return 3.0 * forward_token_flops(s, (seq_len + 1) / 2.0, False)


def total_params(s: Dict) -> int:
    """Every parameter the program holds for this configuration: this
    chip's experts, the whole vocabulary, both ends."""
    h, d = s["heads"], s["d"]
    attn = d * s["q_rank"] + s["q_rank"] \
        + s["q_rank"] * h * (s["nope"] + s["rope"]) \
        + d * (s["kv_rank"] + s["rope"]) + s["kv_rank"] \
        + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * d
    dense = 3 * d * s["dense_ffn"]
    moe = d * s["experts"] + s["experts"] \
        + (s["experts_held"] + s["shared"]) * expert_params(s)
    n_moe = s["layers"] - s["dense_layers"]
    return s["layers"] * (attn + 2 * d) + s["dense_layers"] * dense \
        + n_moe * moe + 2 * s["vocab"] * d + d


# ---- the expert layers of decode steps, from the engine's counters -------
def moe_decode_need(s: Dict, step_layers: int, assignments: int,
                    experts_hit: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the expert layers need for the decode steps a
    window's counters describe (``moe_step_layers`` (step, layer) pairs,
    ``moe_assignments`` token-expert pairs of alive lanes,
    ``moe_experts_hit`` distinct experts chosen, summed over the pairs):
    each assignment and each alive lane's pass through the shared expert is
    one gated FFN; each expert hit, and the shared one, is read once a
    layer, with the router; activations are not counted."""
    alive = assignments / float(s["top_k"])
    ffn = expert_params(s)
    flops = 2.0 * ffn * (assignments + s["shared"] * alive) \
        + 2.0 * s["d"] * s["experts"] * alive
    bytes_ = float(itemsize) * (
        ffn * (experts_hit + s["shared"] * step_layers)
        + s["d"] * s["experts"] * step_layers)
    return {"flops": flops, "bytes": bytes_}
