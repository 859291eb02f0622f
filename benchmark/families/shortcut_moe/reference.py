"""The plain reference: the family's forward pass in straightforward float32
``jax.numpy``, every matrix product at ``highest`` precision (on a TPU a
float32 product otherwise runs in bfloat16 passes).

No kernel, no cache, no batching tricks, and nothing of the program:
attention is the decompressed form only (per-head keys and values made from
each token's latent row; the program's absorbed decode path has to agree
with it), the experts are the plain definition (every expert held computes
every token, weighted by a gate that is zero where the router did not choose
it; the zero-compute experts' gates times the token itself), and weights
come from ``weights.py`` (the seed) a piece of a layer at a time, the
experts' own a few at a time — a dense FFN is 0.9 GB in float32, the
sixteen experts held 2.4 GB.

The equations (``x`` a double block's input, every norm RMSNorm with the
configuration's epsilon, no bias anywhere):

    a0 = x  + Attn_0(RMSNorm(x));    n0 = RMSNorm(a0)
    s  = MoE(n0)                     the shortcut: leaves here ...
    b0 = a0 + FFN_0(n0)
    a1 = b0 + Attn_1(RMSNorm(b0))
    x' = a1 + FFN_1(RMSNorm(a1)) + s         ... and joins here
    Attn:  c_q = RMSNorm(x Wqa) * q_scale;  [q_nope ; q_rope] = c_q Wqb
           [c_kv ; k_r] = x Wkva;  c_kv <- RMSNorm(c_kv) * kv_scale
           [k_nope ; v] = c_kv Wkvb  (per head);  k_rope = RoPE(k_r)
           s = (q_nope.k_nope + RoPE(q_rope).k_rope) / sqrt(nope + rope)
           out = concat_h(softmax(s) v) Wo          causal, softmax in f32
    FFN:   (silu(x Wg) * x Wu) Wd
    MoE:   p = softmax(x Wr) over E + Z outputs;  chosen = top_k(p + b)
           g_i = scaling * p_i                      (not renormalised)
           y = sum_{i chosen, held here} g_i E_i(x)
               + (sum_{i chosen, i >= E} g_i) x     (zero-compute: identity)
    end: RMSNorm, then the untied head (no bias); the embedding is a lookup.

``q_scale`` = sqrt(d / q_rank) and ``kv_scale`` = sqrt(d / kv_rank) where
the configuration switches them on (``mla_scale_q_lora`` /
``mla_scale_kv_lora``); ``k_r`` is not scaled. RoPE rotates adjacent pairs
``(x[2i], x[2i+1])`` by ``pos * theta^(-2i/d)`` (the configuration's
``assumed`` says why).

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
EXPERTS_AT_ONCE = 4           # 0.6 GB of expert weights at the published size


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
    cq = rms_norm(_mm(x, p["wqa"], precision), p["q_g"], s["eps"]) \
        * s["q_scale"]
    q = _mm(cq, p["wqb"], precision).reshape(b, t, h, nope + rp)
    kva = _mm(x, p["wkva"], precision)
    ckv = rms_norm(kva[..., :s["kv_rank"]], p["kv_g"], s["eps"]) \
        * s["kv_scale"]
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
    """[N, E + Z] float32: each token's weight on each routed and each
    zero-compute expert, zero where the router did not choose it. Scores
    are a float32 softmax of a ``highest`` product whatever the precision
    of the rest; they are scaled and NOT renormalised over the chosen."""
    sc = jax.nn.softmax(jnp.einsum("nd,de->ne", x, p["wr"], precision=_HI),
                        axis=-1)
    _, chosen = jax.lax.top_k(sc + p["b"][None, :], s["top_k"])
    mask = jnp.sum(jax.nn.one_hot(chosen, s["experts"] + s["zero"],
                                  dtype=jnp.float32), axis=1)
    return s["scaling"] * sc * mask


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
    """The expert branch on x [B, T, d]: the experts held here, one after
    another on every token, and the zero-compute experts' term (whole: it
    has no home). ``stacks(first, count)`` hands over the weights of that
    many experts (numbered over the whole model) — cut out of ``p`` unless
    given: at the published size they are made EXPERTS_AT_ONCE at a time."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    lo, n = s["first_expert"], s["experts_held"]
    if stacks is None:
        stacks = lambda a, c: {k: p[k][a - lo:a - lo + c]
                               for k in ("wg", "wu", "wd")}
    g = gates(p, x, s)
    y = jnp.sum(g[:, s["experts"]:], axis=-1, keepdims=True) * x
    g = g[:, lo:lo + n]
    for a in range(0, n, EXPERTS_AT_ONCE):
        c = min(EXPERTS_AT_ONCE, n - a)
        y = _routed_jit(stacks(lo + a, c), x, g[:, a:a + c], y,
                        precision=precision).block_until_ready()
    return y.reshape(shape)


def attention_part(p: Dict, x, sizes, precision: str = "highest"):
    """x + Attn(RMSNorm(x)) on x [B, T, d] (float32), ``p`` an attention
    piece with the gain of the norm before it; ``sizes`` as
    ``weights.frozen`` gives them (hashable). One row of the batch after
    another: a row's scores are [heads, T, T], 0.6 GB at 64 x 1536 x 1536."""
    s = dict(sizes)

    def row(xr):
        xr = xr[None]
        return (xr + attention(p, rms_norm(xr, p["ln_g"], s["eps"]), s,
                               precision))[0]
    return jax.lax.map(row, x)


def dense_part(p: Dict, x, eps: float, precision: str = "highest"):
    """(RMSNorm(x), FFN(RMSNorm(x))) of a dense piece on x [B, T, d], one
    row of the batch after another (a row's hidden state is [T, ffn])."""
    def row(xr):
        n = rms_norm(xr, p["ln_g"], eps)
        return n, gated(n, p["wg"], p["wu"], p["wd"], precision)
    return jax.lax.map(row, x)


_attention_jit = jax.jit(attention_part,
                         static_argnames=("sizes", "precision"))
_dense_jit = jax.jit(dense_part, static_argnames=("eps", "precision"))


def double_block(sizes: Dict, x, pieces, precision: str = "highest",
                 stacks=None):
    """One shortcut-connected double block on x [B, T, d]. ``pieces(name)``
    hands over a piece's weights when it is its turn (and no sooner: one is
    held at a time); ``stacks`` as :func:`experts` takes it."""
    fz = wgen.frozen(sizes)
    a0 = _attention_jit(pieces("attn0"), x, sizes=fz, precision=precision)
    n0, f0 = _dense_jit(pieces("ffn0"), a0, eps=sizes["eps"],
                        precision=precision)
    b0 = (a0 + f0).block_until_ready()
    s = experts(pieces("moe"), n0, sizes, precision, stacks)
    a1 = _attention_jit(pieces("attn1"), b0, sizes=fz, precision=precision)
    _, f1 = _dense_jit(pieces("ffn1"), a1, eps=sizes["eps"],
                       precision=precision)
    return (a1 + f1 + s).block_until_ready()


def head(end: Dict, x, eps: float, precision: str = "highest"):
    """[B, T, d] -> logits [B, T, V]."""
    return _mm(rms_norm(x, end["lnf_g"], eps), end["head_w"], precision)


def embed(sizes: Dict, seed: int, tokens):
    """(final norm and head, the token rows' embeddings [B, T, d])."""
    end = wgen.ends(sizes, seed)
    return end, end.pop("wte")[tokens]


def hidden_states(sizes: Dict, seed: int, x, precision: str = "highest"):
    """Final-block hidden states [B, T, d] of embedded rows ``x``. Weights
    come a piece of a layer at a time, the experts' own a few at a time,
    each waited for before the next is made: about 3 GB in all at the
    published size, so the reference also fits beside a program that is
    still held (``calibrate.py``). Right padding is invisible to earlier
    positions under the causal mask."""
    for i in range(sizes["layers"]):
        x = double_block(
            sizes, x, lambda name: wgen.piece(sizes, seed, i, name,
                                              stacks=False),
            precision, lambda a, c: wgen.experts(sizes, seed, i, a, c))
    return x


def logits(sizes: Dict, seed: int, tokens, precision: str = "highest"):
    """[B, T, V] float32 logits of token rows [B, T]: the whole forward
    pass, for tests at a small size."""
    end, x = embed(sizes, seed, jnp.asarray(tokens, jnp.int32))
    return head(end, hidden_states(sizes, seed, x, precision), sizes["eps"],
                precision)


# ----------------------------------------------------------------- serving
POSITIONS_AT_ONCE = 256       # a row's logits are [T, V]: 0.1 GB at 1536


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
    (prompt + generated ids); the reference runs once over all of them,
    padded to one length (right padding is invisible to earlier positions
    under the causal mask), and reads,
    for every served token, how far its logit lies below the reference's
    best. Returns ``{"served_gap", "tokens"}`` and, with ``control`` (a
    lower precision), ``"control_gap"``: the same of the token that the
    reference computed in that precision puts first, at the same positions.

    ``served_gap`` here is the ROOT MEAN SQUARE of those gaps over the
    served tokens, not the widest of them (the protocol's wording, right for
    a dense model), as the ``latent_moe`` family returns it and for its
    reason (PERF.md section 7, PR 29 (1)): with routed experts a bfloat16
    hidden state sends some tokens to another last expert than the float32
    reference does, each such token's logits move, and the widest gap over
    a thousand tokens is an extreme value that reads alike for bfloat16,
    for fp8 and with an expert left out. The root mean square weighs every
    token: a lower precision or a broken branch moves most of them."""
    tmax = max(sizes["t_max"], max(len(q) for q in sequences))
    toks = np.zeros((len(sequences), tmax), np.int32)    # one shape
    for i, q in enumerate(sequences):
        toks[i, :len(q)] = q
    # every sequence through one pass: a piece's weights are made once
    end, x0 = embed(sizes, seed, jnp.asarray(toks))
    x = hidden_states(sizes, seed, x0)
    x_c = hidden_states(sizes, seed, x0, control) if control else None
    mine, ctl = [], []
    for i, q in enumerate(sequences):
        served = slice(prompt_lens[i] - 1, len(q) - 1)
        nxt = jnp.asarray(np.roll(toks[i], -1))
        gap, _ = _gaps(end, x[i], nxt, sizes["eps"], "highest")
        mine.append(np.asarray(gap)[served])
        if control:
            _, choice = _gaps(end, x_c[i], nxt, sizes["eps"], control)
            gap_c, _ = _gaps(end, x[i], choice, sizes["eps"], "highest")
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
