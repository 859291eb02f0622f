"""The plain reference: the family's forward pass in straightforward float32
``jax.numpy``, every matrix product at ``highest`` precision (on a TPU a
float32 product otherwise runs in bfloat16 passes).

No kernel, no cache, no chunking, no batching tricks, and nothing of the
program: a Mamba-2 mixer is its SEQUENTIAL recurrence (a ``lax.scan`` over
the tokens, one state update a token — the program's prefill runs the
chunked scan and its decode the state-update kernel, and both have to agree
with this), attention is grouped-query attention written out, and weights
come from ``weights.py`` (the seed) a piece of a layer at a time.

The equations (``h`` the residual stream, every norm RMSNorm with the
configuration's epsilon, no bias but the convolution's):

    h_0 = 12 * E[ids]                                  (embedding_multiplier)
    each layer:  h <- h + 0.22 * Mixer(RMSNorm(h))     (residual_multiplier)
                 h <- h + 0.22 * (silu(a) * b) W_d,  [a, b] = RMSNorm(h) W_in
    Mamba-2:     [z, xBC, dt] = x W_in  (4096 / 4352 / 64)
                 xBC <- silu(causal depthwise conv_4(xBC) + bias)
                 [x, B, C] = split(xBC, 4096 / 128 / 128)     one group
                 dt <- softplus(dt + dt_bias);  A = -exp(A_log)
                 S_t = exp(dt_t A_h) S_{t-1} + dt_t x_{t,h} (x) B_t    [64, 128]
                 y_{t,h} = S_t C_t + D_h x_{t,h}
                 out = (RMSNorm(y * silu(z)) * w) W_out    norm over all 4096
    attention:   q (32 heads), k, v (8 heads) of 64; query head i reads KV
                 head i // 4; scores * 0.015625 (attention_multiplier),
                 causal, softmax in f32; no positions (nope), no bias
    end:         RMSNorm; logits = (h E^T) / 8           (tied, logits_scaling)

Departures from the published modelling code, each noted: the gate norm's
epsilon is the model's ``rms_norm_eps`` (the catalog gives no other); the
convolution and the recurrence run in float32 here at every precision (the
control rounds the matrix products' operands only); the published code's
``time_step_limit`` clamp of dt is (0, inf), no clamp, as here.

``precision`` selects how matrix products are computed:
``"highest"``  float32, the reference proper;
``"bfloat16"`` operands rounded to bfloat16, float32 accumulation;
``"fp8"``      operands rounded to float8_e4m3 with a per-tensor scale,
               float32 accumulation — the control for a configuration that
               states bfloat16.
"""

from __future__ import annotations

import functools
import sys
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as wgen

_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


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


def mamba(p: Dict, x, s: Dict, precision: str):
    """The Mamba-2 mixer on x [B, T, d] (already normalised), its
    recurrence one token after another."""
    b, t, _ = x.shape
    h, hp, n, k = s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"], s["conv"]
    i, cd = wgen.inner(s), wgen.conv_dim(s)
    zxd = _mm(x, p["w_in"], precision)
    z, xbc, dt = zxd[..., :i], zxd[..., i:i + cd], zxd[..., i + cd:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * p["conv_w"][j][None, None, :]
               for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"][None, None, :])
    xs = xbc[..., :i].reshape(b, t, h, hp)
    bm, cm = xbc[..., i:i + n], xbc[..., i + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"][None, None, :])     # [B, T, H]
    a = -jnp.exp(p["a_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a[None, :])[:, :, None, None] * state \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=_HI) \
            + p["d"][None, :, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((b, h, hp, n), jnp.float32),
                        (xs.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                         bm.transpose(1, 0, 2), cm.transpose(1, 0, 2)))
    y = y.transpose(1, 0, 2, 3).reshape(b, t, i)
    g = rms_norm(y * jax.nn.silu(z), p["norm_g"], s["eps"])
    return _mm(g, p["w_out"], precision)


def attention(p: Dict, x, s: Dict, precision: str):
    """Grouped-query causal attention without positions on x [B, T, d]
    (already normalised), one row of the batch after another (a row's
    scores are [heads, T, T])."""
    h, kvh = s["heads"], s["kv_heads"]
    dh = s["d"] // h

    def row(xr):
        t = xr.shape[0]
        q = _mm(xr, p["wq"], precision).reshape(t, h, dh)
        kk = _mm(xr, p["wk"], precision).reshape(t, kvh, dh)
        v = _mm(xr, p["wv"], precision).reshape(t, kvh, dh)
        kk = jnp.repeat(kk, h // kvh, axis=1)     # query head i: KV i // 4
        v = jnp.repeat(v, h // kvh, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", _round(q, precision),
                        _round(kk, precision), precision=_HI) \
            * s["attn_scale"]
        causal = jnp.tril(jnp.ones((t, t), bool))
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", _round(pr, precision),
                         _round(v, precision), precision=_HI)
        return _mm(out.reshape(t, h * dh), p["wo"], precision)
    return jax.lax.map(row, x)


def mlp(p: Dict, x, precision: str):
    return _mm(jax.nn.silu(_mm(x, p["wg"], precision))
               * _mm(x, p["wu"], precision), p["wd"], precision)


@functools.partial(jax.jit, static_argnames=("sizes", "kind", "precision"))
def _layer(mixer, ffn, x, *, sizes, kind, precision):
    """One layer on x [B, T, d]: the mixer's branch, then the MLP's, each
    scaled where it is added."""
    s = dict(sizes)
    n = rms_norm(x, mixer["ln_g"], s["eps"])
    body = mamba if kind == "mamba" else attention
    x = x + s["residual"] * body(mixer, n, s, precision)
    n = rms_norm(x, ffn["ln_g"], s["eps"])
    return x + s["residual"] * mlp(ffn, n, precision)


def embed(sizes: Dict, seed: int, tokens):
    """(the ends, the token rows' embeddings [B, T, d] times the
    multiplier)."""
    end = wgen.ends(sizes, seed)
    return end, end["wte"][tokens] * sizes["embed_scale"]


def hidden_states(sizes: Dict, seed: int, x, precision: str = "highest"):
    """Final-layer hidden states [B, T, d] of embedded rows ``x``, a layer's
    weights made when it is its turn (one layer held at a time). Right
    padding is invisible to earlier positions: everything here is causal."""
    fz = wgen.frozen(sizes)
    for i, kind in enumerate(sizes["layer_types"]):
        x = _layer(wgen.piece(sizes, seed, i, "mixer"),
                   wgen.piece(sizes, seed, i, "mlp"), x, sizes=fz,
                   kind=kind, precision=precision).block_until_ready()
    return x


def head(end: Dict, x, sizes: Dict, precision: str = "highest"):
    """[..., d] -> logits [..., V]: the final norm, the tied head, the
    divisor."""
    return _mm(rms_norm(x, end["lnf_g"], sizes["eps"]), end["wte"].T,
               precision) / sizes["logit_div"]


def logits(sizes: Dict, seed: int, tokens, precision: str = "highest"):
    """[B, T, V] float32 logits of token rows [B, T]: the whole forward
    pass, for tests at a small size."""
    end, x = embed(sizes, seed, jnp.asarray(tokens, jnp.int32))
    return head(end, hidden_states(sizes, seed, x, precision), sizes,
                precision)


# ----------------------------------------------------------------- serving
POSITIONS_AT_ONCE = 256       # a row's logits are [T, V]: 0.8 GB at 2048


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def _gaps(end, x, chosen, sizes, precision):
    """One row, POSITIONS_AT_ONCE positions at a time: how far the logit of
    ``chosen`` [T] lies below the best logit, and the best token, at every
    position of ``x`` [T, d]."""
    s = dict(sizes)
    t = x.shape[0]
    pad = -t % POSITIONS_AT_ONCE
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, POSITIONS_AT_ONCE,
                                                x.shape[1])
    ch = jnp.pad(chosen, (0, pad)).reshape(-1, POSITIONS_AT_ONCE)

    def one(args):
        xr, c = args
        lg = head(end, xr, s, precision)                           # [P, V]
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
    padded to one length, and reads, for every served token, how far its
    logit lies below the reference's best. Returns ``{"served_gap",
    "tokens"}`` and, with ``control`` (a lower precision), ``"control_gap"``:
    the same of the token that the reference computed in that precision
    puts first, at the same positions.

    ``served_gap`` is the ROOT MEAN SQUARE of those gaps over the served
    tokens, as the ``latent_moe`` and ``shortcut_moe`` families return it: a
    state carried in bfloat16 through 36 recurrent layers and 500 tokens
    moves every later token's logits a little, and the widest of a thousand
    gaps is an extreme value of that; the root mean square weighs every
    token, so a lower precision or a broken path (which moves most of them)
    stands apart from it. The widest gap is printed beside it."""
    tmax = max(sizes["t_max"], max(len(q) for q in sequences))
    toks = np.zeros((len(sequences), tmax), np.int32)     # one shape
    for i, q in enumerate(sequences):
        toks[i, :len(q)] = q
    end, x0 = embed(sizes, seed, jnp.asarray(toks))
    x = hidden_states(sizes, seed, x0)
    x_c = hidden_states(sizes, seed, x0, control) if control else None
    fz = wgen.frozen(sizes)
    mine, ctl = [], []
    for i, q in enumerate(sequences):
        served = slice(prompt_lens[i] - 1, len(q) - 1)
        nxt = jnp.asarray(np.roll(toks[i], -1))
        gap, _ = _gaps(end, x[i], nxt, sizes=fz, precision="highest")
        mine.append(np.asarray(gap)[served])
        if control:
            _, choice = _gaps(end, x_c[i], nxt, sizes=fz, precision=control)
            gap_c, _ = _gaps(end, x[i], choice, sizes=fz,
                             precision="highest")
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
