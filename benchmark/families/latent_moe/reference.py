"""The plain reference: the family's forward pass in straightforward float32
``jax.numpy``, every matrix product at ``highest`` precision (on a TPU a
float32 product otherwise runs in bfloat16 passes).

No kernel, no cache, no batching tricks, and nothing of the program:
attention is the decompressed form only (per-head keys and values made from
each token's latent row; the program's absorbed decode path has to agree
with it), the experts are the plain definition (every expert held computes
every token, weighted by a gate that is zero where the router did not choose
it), and weights come from ``weights.py`` (the seed) a layer at a time, an
expert layer's own a few experts at a time — one expert layer is 4.8 GB in
float32.

The equations (``x`` a block's input, every norm RMSNorm with the
configuration's epsilon, no bias anywhere):

    h <- h + Attn(RMSNorm(h));   h <- h + FFN(RMSNorm(h))
    Attn:  c_q = RMSNorm(x Wqa);  [q_nope ; q_rope] = c_q Wqb  (per head)
           [c_kv ; k_r] = x Wkva;  c_kv <- RMSNorm(c_kv)
           [k_nope ; v] = c_kv Wkvb  (per head);  k_rope = RoPE(k_r)
           s = (q_nope.k_nope + RoPE(q_rope).k_rope) / sqrt(nope + rope)
           out = concat_h(softmax(s) v) Wo          causal, softmax in f32
    FFN (dense):   (silu(x Wg) * x Wu) Wd
    FFN (experts): s = sigmoid(x Wr); chosen = top_k(s + b)
                   g_i = scaling * s_i / sum_{chosen} s_j
                   y = sum_{i chosen, held here} g_i E_i(x) + E_shared(x)
    end: RMSNorm, then the untied head (no bias); the embedding is a lookup.

RoPE rotates adjacent pairs ``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/d)``
(the configuration's ``assumed`` says why that is the published model's
``rope_interleave``).

``precision`` selects how matrix products are computed:
``"highest"``  float32, the reference proper;
``"bfloat16"`` operands rounded to bfloat16, float32 accumulation;
``"fp8"``      operands rounded to float8_e4m3 with a per-tensor scale,
               float32 accumulation — the control for a configuration that
               states bfloat16. The router's scores stay float32 in every
               precision, as the configuration states them.
"""

from __future__ import annotations

import functools
import math
import sys
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as wgen

_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0
EXPERTS_AT_ONCE = 32          # 0.6 GB of expert weights at the published size


def _round(x, precision: str):
    if precision == "highest":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision: str):
    """a [..., K] @ b [K, N] in float32 accumulation."""
    return jnp.einsum("...k,kn->...n", _round(a, precision),
                      _round(b, precision), precision=_HI)


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.reshape((1,) * (x.ndim - 1) + (-1,))


def rope(x, theta: float):
    """x [B, T, ..., d] at positions 0..T-1: adjacent pairs rotated."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * jnp.cos(ang) - x1 * jnp.sin(ang),
                      x0 * jnp.sin(ang) + x1 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def attention(p: Dict, x, s: Dict, precision: str):
    """Latent attention, decompressed, causal, on x [B, T, d]."""
    b, t, _ = x.shape
    h, nope, rp, v = s["heads"], s["nope"], s["rope"], s["v"]
    cq = rms_norm(_mm(x, p["wqa"], precision), p["q_g"], s["eps"])
    q = _mm(cq, p["wqb"], precision).reshape(b, t, h, nope + rp)
    kva = _mm(x, p["wkva"], precision)
    ckv = rms_norm(kva[..., :s["kv_rank"]], p["kv_g"], s["eps"])
    kvb = _mm(ckv, p["wkvb"], precision).reshape(b, t, h, nope + v)
    q_rope = rope(q[..., nope:], s["theta"])
    k_rope = rope(kva[..., s["kv_rank"]:], s["theta"])            # [B, T, r]
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q[..., :nope], precision),
                        _round(kvb[..., :nope], precision), precision=_HI) \
        + jnp.einsum("bqhd,bkd->bhqk", _round(q_rope, precision),
                     _round(k_rope, precision), precision=_HI)
    scores = scores / math.sqrt(nope + rp)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision),
                     _round(kvb[..., nope:], precision), precision=_HI)
    return _mm(out.reshape(b, t, h * v), p["wo"], precision)


def gated(x, wg, wu, wd, precision: str):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def gates(p: Dict, x, s: Dict):
    """[N, E] float32: each token's weight on each expert, zero where the
    router did not choose it. Scores are float32 sigmoid of a ``highest``
    product whatever the precision of the rest."""
    sc = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, p["wr"], precision=_HI))
    _, chosen = jax.lax.top_k(sc + p["b"][None, :], s["top_k"])
    mask = jnp.sum(jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32),
                   axis=1)
    picked = sc * mask
    return s["scaling"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed(w: Dict, x, g, y, precision: str):
    """``y`` [N, d] plus the experts whose stacks ``w`` holds (``wg``,
    ``wu``, ``wd`` [n, ...]), one after another on every token of ``x``
    [N, d], each weighted by its column of ``g`` [N, n]."""
    def one(e, y):
        ye = gated(x, w["wg"][e], w["wu"][e], w["wd"][e], precision)
        return y + ye * jax.lax.dynamic_slice_in_dim(g, e, 1, axis=1)
    return jax.lax.fori_loop(0, g.shape[1], one, y)


_routed_jit = jax.jit(routed, static_argnames=("precision",))


def experts(p: Dict, x, s: Dict, precision: str, stacks=None):
    """The expert layer on x [B, T, d]: the experts held here, one after
    another on every token, and the shared expert. ``stacks(first, count)``
    hands over the weights of that many experts (numbered over the whole
    model) — cut out of ``p`` unless given: at the published size they are
    made EXPERTS_AT_ONCE at a time, 0.6 GB and not a layer's 4.8."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    lo, n = s["first_expert"], s["experts_held"]
    if stacks is None:
        stacks = lambda a, c: {k: p[k][a - lo:a - lo + c]
                               for k in ("wg", "wu", "wd")}
    g = gates(p, x, s)[:, lo:lo + n]
    y = _gated_jit(x, p["sg"], p["su"], p["sd"], precision=precision) \
        if s["shared"] else jnp.zeros_like(x)
    for a in range(0, n, EXPERTS_AT_ONCE):
        c = min(EXPERTS_AT_ONCE, n - a)
        y = _routed_jit(stacks(lo + a, c), x, g[:, a:a + c], y,
                        precision=precision).block_until_ready()
    return y.reshape(shape)


def attention_part(p: Dict, x, sizes, precision: str = "highest"):
    """The first half of a pre-RMSNorm block on x [B, T, d] (float32):
    (x + Attn(RMSNorm(x)), its RMSNorm: the FFN's input); ``sizes`` as
    ``weights.frozen`` gives them (hashable)."""
    s = dict(sizes)
    x = x + attention(p, rms_norm(x, p["ln1_g"], s["eps"]), s, precision)
    return x, rms_norm(x, p["ln2_g"], s["eps"])


_attention_jit = jax.jit(attention_part,
                         static_argnames=("sizes", "precision"))
_gated_jit = jax.jit(gated, static_argnames=("precision",))


def head(end: Dict, x, eps: float, precision: str = "highest"):
    """[B, T, d] -> logits [B, T, V]."""
    return _mm(rms_norm(x, end["lnf_g"], eps), end["head_w"], precision)


def embed(sizes: Dict, seed: int, tokens):
    """(final norm and head, the token rows' embeddings [B, T, d])."""
    end = wgen.ends(sizes, seed)
    return end, end.pop("wte")[tokens]


def hidden_states(sizes: Dict, seed: int, x, precision: str = "highest"):
    """Final-block hidden states [B, T, d] of embedded rows ``x``. Weights
    come a block at a time, an expert layer's own a few experts at a time,
    each piece waited for before the next is made: about 3 GB in all at the
    published size, so the reference also fits beside a program that is
    still held (``calibrate.py``). Right padding is invisible to earlier
    positions under the causal mask."""
    for i in range(sizes["layers"]):
        p = wgen.layer(sizes, seed, i, stacks=False)
        x, h = _attention_jit(p, x, sizes=wgen.frozen(sizes),
                              precision=precision)
        if "wr" in p:
            x = x + experts(p, h, sizes, precision,
                            lambda a, c: wgen.experts(sizes, seed, i, a, c))
        else:
            x = x + _gated_jit(h, p["wg"], p["wu"], p["wd"],
                               precision=precision)
        x.block_until_ready()
    return x


def logits(sizes: Dict, seed: int, tokens, precision: str = "highest"):
    """[B, T, V] float32 logits of token rows [B, T]: the whole forward
    pass, for tests at a small size."""
    end, x = embed(sizes, seed, jnp.asarray(tokens, jnp.int32))
    return head(end, hidden_states(sizes, seed, x, precision), sizes["eps"],
                precision)


# ----------------------------------------------------------------- serving
POSITIONS_AT_ONCE = 256       # a row's logits are [T, V]: 1.3 GB at 2560


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _gaps(end, x, chosen, eps, precision):
    """One row, POSITIONS_AT_ONCE positions at a time: how far the logit of
    ``chosen`` [T] lies below the best logit, and the best token, at every
    position of ``x`` [T, d]."""
    t = x.shape[0]
    pad = -t % POSITIONS_AT_ONCE
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, POSITIONS_AT_ONCE,
                                                x.shape[1])
    ch = jnp.pad(chosen, (0, pad)).reshape(-1, POSITIONS_AT_ONCE)

    def one(args):
        xr, c = args
        lg = head(end, xr, eps, precision)                        # [P, V]
        got = jnp.take_along_axis(lg, c[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got, \
            jnp.argmax(lg, axis=-1).astype(jnp.int32)
    gap, best = jax.lax.map(one, (xs, ch))
    return gap.reshape(-1)[:t], best.reshape(-1)[:t]


def gap_statistics(gaps: np.ndarray) -> Dict[str, float]:
    """What is said of the served tokens' gaps (float64). ``rms`` is the one
    compared; the others are printed beside it."""
    g = np.asarray(gaps, np.float64)
    if not g.size:
        return {"rms": float("inf")}
    return {"rms": float(np.sqrt(np.mean(g * g))), "mean": float(g.mean()),
            "agree_share": float(np.mean(g == 0.0)),
            "q95": float(np.quantile(g, 0.95)),
            "q99": float(np.quantile(g, 0.99)), "widest": float(g.max())}


def served_token_gaps(sizes: Dict, seed: int,
                      sequences: Sequence[np.ndarray],
                      prompt_lens: Sequence[int],
                      control: str = "") -> Dict[str, float]:
    """The serving comparison. ``sequences`` are whole served sequences
    (prompt + generated ids); the reference runs once over each and reads,
    for every served token, how far its logit lies below the reference's
    best. Returns ``{"served_gap", "tokens"}`` and, with ``control`` (a
    lower precision), ``"control_gap"``: the same of the token that the
    reference computed in that precision puts first, at the same positions.

    ``served_gap`` here is the ROOT MEAN SQUARE of those gaps over the
    served tokens, not the widest of them (the protocol's wording, right for
    a dense model). With routed experts a bfloat16 hidden state sends a few
    tokens in a hundred to another eighth expert than the float32 reference
    does, each such token's logits move by tenths, and the widest gap over a
    thousand tokens reads the same for bfloat16, for fp8 and with an expert
    left out (PERF.md, PR 29). The root mean square weighs every token: a
    lower precision or a broken expert moves most of them, and one token
    altered where it is produced (a gap of 4-6) still adds 0.1-0.3."""
    tmax = max(sizes["t_max"], max(len(q) for q in sequences))
    mine, ctl = [], []
    for i in np.argsort([-len(q) for q in sequences], kind="stable"):
        toks = np.zeros(tmax, np.int32)               # one shape, one compile
        toks[:len(sequences[i])] = sequences[i]
        served = slice(prompt_lens[i] - 1, len(sequences[i]) - 1)
        nxt = jnp.asarray(np.roll(toks, -1))
        end, x0 = embed(sizes, seed, jnp.asarray(toks)[None])
        x = hidden_states(sizes, seed, x0)[0]
        gap, _ = _gaps(end, x, nxt, sizes["eps"], "highest")
        mine.append(np.asarray(gap)[served])
        if control:
            x_c = hidden_states(sizes, seed, x0, control)[0]
            _, choice = _gaps(end, x_c, nxt, sizes["eps"], control)
            gap_c, _ = _gaps(end, x, choice, sizes["eps"], "highest")
            ctl.append(np.asarray(gap_c)[served])
    said = {"served": gap_statistics(np.concatenate(mine))}
    out = {"served_gap": said["served"]["rms"],
           "tokens": int(sum(len(g) for g in mine))}
    if control:
        said["control"] = gap_statistics(np.concatenate(ctl))
        out["control_gap"] = said["control"]["rms"]
    for who, st in said.items():
        print(f"[reference] {who}, {out['tokens']} tokens: "
              + ", ".join(f"{k} {v:.4f}" for k, v in st.items()),
              file=sys.stderr, flush=True)
    return out
