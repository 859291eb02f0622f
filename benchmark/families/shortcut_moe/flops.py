"""Model operations per token from the configuration's sizes, and what the
expert branch of one decode step needs. Nothing here is measured and nothing
comes from XLA's cost analysis (which cannot see inside a Mosaic call): each
function counts what the algorithm needs.

A multiply-add counts as two operations; nothing recomputed is counted, and
only the ACTIVE experts count: of the ``top_k`` a token chose, the share
held here (``experts_held`` of the router's ``experts + zero`` outputs,
under even routing) — never the experts a dense formulation would also
multiply; a zero-compute choice costs the token's row once. Prefill counts
attention decompressed (per-head k and v made from the latent row), decode
counts it absorbed (the latent row read in place), as the program runs them.
A layer is a DOUBLE block: two attentions, two dense FFNs, one expert branch.
"""

from __future__ import annotations

from typing import Dict


def _attention_weights(s: Dict) -> int:
    """Multiply-adds a token's projections take in ONE attention,
    decompressed or absorbed alike."""
    h, qk = s["heads"], s["nope"] + s["rope"]
    n = s["d"] * s["q_rank"] + s["q_rank"] * h * qk \
        + s["d"] * (s["kv_rank"] + s["rope"]) + h * s["v"] * s["d"]
    # decompressed: k_nope and v of the token from its latent row; absorbed:
    # q_nope through W_K and the weighted latent sum through W_V — the same
    # matrix either way
    return n + s["kv_rank"] * h * (s["nope"] + s["v"])


def _attention_per_key(s: Dict, absorbed: bool) -> int:
    """Multiply-adds per attended key in ONE attention (scores and sum)."""
    h = s["heads"]
    if absorbed:
        return h * (s["kv_rank"] + s["rope"]) + h * s["kv_rank"]
    return h * (s["nope"] + s["rope"]) + h * s["v"]


def routed_over(s: Dict) -> int:
    """The router's width: routed and zero-compute experts."""
    return s["experts"] + s["zero"]


def active_experts(s: Dict) -> float:
    """Experts with weights that compute a token in an expert branch here,
    under even routing: its ``top_k`` times the share of the router's
    outputs that are experts held here."""
    return s["top_k"] * s["experts_held"] / routed_over(s)


def expert_params(s: Dict) -> int:
    return 3 * s["d"] * s["expert_ffn"]


def _layer_weights(s: Dict) -> float:
    """Multiply-adds a token takes in one double block outside attention's
    keys: two attentions' projections, two dense FFNs, the router, the
    experts active here and the zero-compute choices' rows."""
    zero = s["top_k"] * s["zero"] / routed_over(s)
    return 2 * _attention_weights(s) + 2 * 3 * s["d"] * s["dense_ffn"] \
        + s["d"] * routed_over(s) + active_experts(s) * expert_params(s) \
        + zero * s["d"]


def forward_token_flops(s: Dict, context: float, absorbed: bool) -> float:
    """Forward operations for ONE token that attends to ``context`` keys
    (itself included)."""
    per_token = s["layers"] * _layer_weights(s) + s["d"] * s["vocab"]
    return 2.0 * per_token + 2.0 * s["layers"] * 2 \
        * _attention_per_key(s, absorbed) * context


def prompt_flops(s: Dict, length: int) -> float:
    """Forward operations to prefill a prompt of ``length`` tokens causally
    (token t attends to t+1 keys), attention decompressed; the head runs on
    the last position only."""
    return 2.0 * s["layers"] * _layer_weights(s) * length \
        + 2.0 * s["d"] * s["vocab"] \
        + 2.0 * s["layers"] * 2 * _attention_per_key(s, False) \
        * (length * (length + 1) / 2.0)


def decode_flops(s: Dict, prompt: int, new: int) -> float:
    """Forward operations for the ``new`` tokens decoded after a prompt of
    ``prompt`` tokens, attention absorbed; the first new token comes out of
    the prefill, so ``new - 1`` decode steps run, step j (from 1) attending
    to prompt + j keys."""
    steps = max(new - 1, 0)
    ctx = steps * prompt + steps * (steps + 1) / 2.0
    return steps * forward_token_flops(s, 0.0, True) \
        + 2.0 * s["layers"] * 2 * _attention_per_key(s, True) * ctx


def train_token_flops(s: Dict, seq_len: int) -> float:
    """Forward + backward operations per trained token (three times the
    forward pass, attention decompressed and averaged over the causal
    triangle). The family has no training cell; the count is what one
    would need."""
    return 3.0 * forward_token_flops(s, (seq_len + 1) / 2.0, False)


def attention_params(s: Dict) -> int:
    h, d = s["heads"], s["d"]
    return d * s["q_rank"] + s["q_rank"] \
        + s["q_rank"] * h * (s["nope"] + s["rope"]) \
        + d * (s["kv_rank"] + s["rope"]) + s["kv_rank"] \
        + s["kv_rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * d


def total_params(s: Dict) -> int:
    """Every parameter the program holds for this configuration: this
    chip's experts, its slice of the vocabulary, both ends."""
    d = s["d"]
    outside = 2 * attention_params(s) + 2 * 3 * d * s["dense_ffn"] \
        + d * routed_over(s) + routed_over(s) + 4 * d
    return s["layers"] * (outside + s["experts_held"] * expert_params(s)) \
        + 2 * s["vocab"] * d + d


# ---- the expert branch of decode steps, from the engine's counters -------
def moe_decode_need(s: Dict, step_layers: int, assignments: int,
                    held_assignments: int, zero_assignments: int,
                    experts_hit: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and bytes the expert branches need for the decode steps a
    window's counters describe (``moe_step_layers`` (step, layer) pairs;
    of alive lanes ``moe_assignments`` choices, ``moe_held_assignments`` of
    experts held here, ``moe_zero_assignments`` of zero-compute experts,
    ``moe_experts_hit`` distinct held experts chosen, summed over the
    pairs): a held assignment is one gated FFN on one row, read and written
    once; a zero-compute choice one row scaled; each held expert hit is read
    once a layer, with the router; a choice held elsewhere needs nothing
    here."""
    alive = assignments / float(s["top_k"])
    ffn = expert_params(s)
    flops = 2.0 * ffn * held_assignments \
        + 2.0 * s["d"] * routed_over(s) * alive \
        + 2.0 * s["d"] * zero_assignments
    bytes_ = float(itemsize) * (
        ffn * experts_hit + s["d"] * routed_over(s) * step_layers
        + 2 * s["d"] * held_assignments)
    return {"flops": flops, "bytes": bytes_}
