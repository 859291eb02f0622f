"""The plain reference: GPT-2's forward pass, loss, gradients and Adam in
straightforward float32 ``jax.numpy``, every matrix product at ``highest``
precision (on a TPU a float32 product otherwise runs in bfloat16 passes).

No kernel, no cache, no batching tricks, and nothing of the program: weights
come from ``weights.py`` (the seed), layer by layer, so one block's weights
are on the device at a time for serving, and the whole model plus Adam's
state for training only after the program's own state has been freed.

Departures from the published GPT-2, which are the program's
(``transformer_lm_conf``) and are listed under ``assumed`` in each
configuration file: no bias on the q/k/v projections, an output head that is
not tied to the token embedding and has a bias, GELU in its tanh form
(GPT-2's ``gelu_new``), LayerNorm epsilon 1e-5.

``precision`` selects how matrix products are computed:
``"highest"``  float32, the reference proper;
``"bfloat16"`` operands rounded to bfloat16, float32 accumulation — the
               control for a configuration that states float32;
``"fp8"``      operands rounded to float8_e4m3 with a per-tensor scale,
               float32 accumulation — the control for a configuration that
               states bfloat16 (the step a later PR would be tempted by).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as wgen

LN_EPS = 1e-5
_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _quantize(x, precision: str):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, precision):
    return _quantize(x, precision)


# a lower-precision product rounds the operands of its backward products as
# well (each with a scale of its own, as fp8 training does), so the gradient
# that flows back through a rounded operand is rounded, not cut off
_rounded.defvjp(lambda x, precision: (_quantize(x, precision), None),
                lambda precision, _, g: (_quantize(g, precision),))


def _round(x, precision: str):
    if precision == "highest":
        return x
    if precision not in ("bfloat16", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    return _rounded(x, precision)


def _mm(a, b, precision: str):
    """a [..., K] @ b [K, N] in float32 accumulation."""
    return jnp.einsum("...k,kn->...n", _round(a, precision),
                      _round(b, precision), precision=_HI)


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g[None, None, :] \
        + b[None, None, :]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p: Dict, x, heads: int, precision: str = "highest"):
    """One pre-LN block on x [B, T, d] (float32), causal attention."""
    b, t, d = x.shape
    hd = d // heads
    h = _ln(x, p["ln1_g"], p["ln1_b"])
    q = _mm(h, p["wq"], precision).reshape(b, t, heads, hd)
    k = _mm(h, p["wk"], precision).reshape(b, t, heads, hd)
    v = _mm(h, p["wv"], precision).reshape(b, t, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                   _round(k, precision), precision=_HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(a, precision),
                   _round(v, precision), precision=_HI).reshape(b, t, d)
    x = x + _mm(o, p["wo"], precision) + p["bo"][None, None, :]
    h = _ln(x, p["ln2_g"], p["ln2_b"])
    h = _gelu(_mm(h, p["w1"], precision) + p["b1"][None, None, :])
    return x + _mm(h, p["w2"], precision) + p["b2"][None, None, :]


def embed(end: Dict, tokens):
    """tokens [B, T] int32 -> [B, T, d]."""
    t = tokens.shape[1]
    return end["wte"][tokens] + end["wpe"][None, :t]


def head(end: Dict, x, precision: str = "highest"):
    """[B, T, d] -> logits [B, T, V]."""
    return _mm(_ln(x, end["lnf_g"], end["lnf_b"]), end["head_w"],
               precision) + end["head_b"][None, None, :]


_block_jit = jax.jit(block, static_argnames=("heads", "precision"))
_embed_jit = jax.jit(embed)


# ----------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("precision",))
def _gap_rows(end, x, tokens, first, last, precision):
    """For rows of hidden states x [B, T, d] and the tokens [B, T] they were
    computed from: at every position t in [first-1, last-1) of a row, the
    logits predict token t+1. Returns (gap of the given next token below the
    best logit [B, T-1], the best token [B, T-1], validity mask)."""
    logits = head(end, x, precision)[:, :-1]                # [B, T-1, V]
    nxt = tokens[:, 1:]
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    pos = jnp.arange(1, tokens.shape[1], dtype=jnp.int32)[None, :]
    valid = (pos >= first[:, None]) & (pos < last[:, None])
    return best - got, jnp.argmax(logits, axis=-1).astype(jnp.int32), valid


@functools.partial(jax.jit, static_argnames=("precision",))
def _gap_of(end, x, chosen, precision):
    """Gap, in the float32 logits of x, of an arbitrary choice per position:
    chosen [B, T-1] (the control's own first choice)."""
    logits = head(end, x, precision)[:, :-1]
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return best - got


def hidden_states(sizes: Dict[str, int], seed: int, tokens,
                  precision: str = "highest"):
    """Final-block hidden states [B, T, d] of padded token rows, block by
    block with one block's weights on the device at a time. Right padding is
    invisible to earlier positions under the causal mask."""
    end = wgen.ends(sizes, seed)
    x = _embed_jit(end, tokens)
    for i in range(sizes["layers"]):
        x = _block_jit(wgen.layer(sizes, seed, i), x, heads=sizes["heads"],
                       precision=precision)
    return end, x


def served_token_gaps(sizes: Dict[str, int], seed: int,
                      sequences: Sequence[np.ndarray],
                      prompt_lens: Sequence[int], control: str = "",
                      rows_per_block: int = 4) -> Dict[str, float]:
    """The serving comparison. ``sequences`` are whole served sequences
    (prompt + generated ids); the reference runs once over each and reads,
    for every served token, how far its logit lies below the reference's
    best. Returns ``{"served_gap": widest gap, "tokens": count}`` and, with
    ``control`` (a lower precision), ``"control_gap"``: the widest gap of the
    token that the reference computed in that precision puts first, at the
    same positions."""
    order = np.argsort([-len(s) for s in sequences], kind="stable")
    worst, worst_ctl, count = 0.0, 0.0, 0
    for lo in range(0, len(order), rows_per_block):
        idx = order[lo:lo + rows_per_block]
        tmax = sizes["positions"]     # one padded shape: one compile
        toks = np.zeros((len(idx), tmax), np.int32)
        first = np.zeros(len(idx), np.int32)
        last = np.zeros(len(idx), np.int32)
        for r, i in enumerate(idx):
            toks[r, :len(sequences[i])] = sequences[i]
            first[r], last[r] = prompt_lens[i], len(sequences[i])
        toks_d = jnp.asarray(toks)
        end, x = hidden_states(sizes, seed, toks_d)
        gap, _, valid = _gap_rows(end, x, toks_d, jnp.asarray(first),
                                  jnp.asarray(last), "highest")
        gap, valid = np.asarray(gap), np.asarray(valid)
        worst = max(worst, float(np.max(np.where(valid, gap, 0.0))))
        count += int(valid.sum())
        if control:
            end_c, x_c = hidden_states(sizes, seed, toks_d, control)
            _, choice, _ = _gap_rows(end_c, x_c, toks_d, jnp.asarray(first),
                                     jnp.asarray(last), control)
            gap_c = np.asarray(_gap_of(end, x, choice, "highest"))
            worst_ctl = max(worst_ctl,
                            float(np.max(np.where(valid, gap_c, 0.0))))
    out = {"served_gap": worst, "tokens": count}
    if control:
        out["control_gap"] = worst_ctl
    return out


# ---------------------------------------------------------------- training
def _rows_loss(end, x, labels, precision):
    """Summed next-token cross-entropy of rows x [B, T, d] against integer
    labels [B, T] (float32 log-softmax)."""
    logits = head(end, x, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    got = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - got)


@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def _block_vjp(p, x, g, heads, precision):
    _, pull = jax.vjp(lambda pp, xx: block(pp, xx, heads, precision), p, x)
    return pull(g)                                   # (dparams, dx)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_grad(end, x, labels, scale, precision):
    """Loss of these rows (scaled by 1/total tokens) with its gradients to
    the final LayerNorm, the head and the hidden states."""
    def f(e, xx):
        return _rows_loss(e, xx, labels, precision) * scale
    loss, (de, dx) = jax.value_and_grad(f, argnums=(0, 1))(end, x)
    return loss, de, dx


@jax.jit
def _embed_grad(end, tokens, dx):
    _, pull = jax.vjp(lambda e: embed(e, tokens), end)
    return pull(dx)[0]


_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))


def loss_and_grads(end: Dict, blocks: List[Dict], tokens, labels, heads: int,
                   precision: str = "highest", rows_per_block: int = 2,
                   keep: Sequence[int] = ()):
    """Mean next-token loss over all rows of ``tokens`` [B, T] and its
    gradients (ends, [blocks]), accumulated over blocks of rows; within a
    block of rows the backward pass walks the layers with one ``vjp`` each
    (inputs kept, the block recomputed). ``keep`` restricts the mean to those
    row indices (a planted fault: half the batch left out)."""
    rows = list(keep) if len(keep) else list(range(tokens.shape[0]))
    scale = jnp.float32(1.0 / (len(rows) * tokens.shape[1]))
    total = jnp.float32(0.0)
    g_end, g_blocks = None, None
    for lo in range(0, len(rows), rows_per_block):
        sel = np.asarray(rows[lo:lo + rows_per_block])
        tk, lb = tokens[sel], labels[sel]
        xs = [_embed_jit(end, tk)]
        for p in blocks:
            xs.append(_block_jit(p, xs[-1], heads=heads,
                                 precision=precision))
        loss, de, dx = _head_grad(end, xs[-1], lb, scale, precision)
        total = total + loss
        gb = []
        for p, x in zip(reversed(blocks), reversed(xs[:-1])):
            dp, dx = _block_vjp(p, x, dx, heads, precision)
            gb.append(dp)
        gb.reverse()
        de = _tree_add(de, _embed_grad(end, tk, dx))
        g_end = de if g_end is None else _tree_add(g_end, de)
        g_blocks = gb if g_blocks is None else \
            [_tree_add(a, b) for a, b in zip(g_blocks, gb)]
    return total, g_end, g_blocks


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam_leaf_tree(p, g, m, v, lr, t, b1, b2, eps):
    """Adam as DL4J's updater states it: bias correction folded into the
    step size, epsilon added to sqrt(v) uncorrected. t counts from 1."""
    def one(pp, gg, mm, vv):
        mm = b1 * mm + (1.0 - b1) * gg
        vv = b2 * vv + (1.0 - b2) * gg * gg
        alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        return pp - alpha * mm / (jnp.sqrt(vv) + eps), mm, vv
    out = jax.tree_util.tree_map(one, p, g, m, v)
    unz = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return unz(0), unz(1), unz(2)


_norms = jax.jit(lambda tree: jax.tree_util.tree_map(
    lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree))
_diff_norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
    lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))


def flat_names(layers: int) -> List[str]:
    """Leaf names in the one order every per-leaf reading uses."""
    names = list(wgen.END_LEAVES)
    for i in range(layers):
        names += [f"{k}.{i}" for k in wgen.BLOCK_LEAVES]
    return names


def flatten(end: Dict, blocks: List[Dict]) -> np.ndarray:
    """Per-leaf scalars of an (ends, [blocks]) tree as one vector in
    ``flat_names`` order."""
    vals = [end[k] for k in wgen.END_LEAVES]
    for b in blocks:
        vals += [b[k] for k in wgen.BLOCK_LEAVES]
    return np.asarray([float(v) for v in jax.device_get(vals)], np.float64)


def leaf_norms(end: Dict, blocks: List[Dict]) -> np.ndarray:
    return flatten(_norms(end), [_norms(b) for b in blocks])


def change_norms(sizes: Dict[str, int], seed: int, end: Dict,
                 blocks: List[Dict]) -> np.ndarray:
    """Per-leaf norm of (these parameters - the seed's initial ones), the
    initial ones made again a block at a time."""
    d_end = _diff_norms(end, wgen.ends(sizes, seed))
    d_blocks = [_diff_norms(b, wgen.layer(sizes, seed, i))
                for i, b in enumerate(blocks)]
    return flatten(d_end, d_blocks)


def train_steps(sizes: Dict[str, int], seed: int,
                batches: Sequence[Tuple[np.ndarray, np.ndarray]], adam: Dict,
                precision: str = "highest", rows_per_block: int = 2,
                keep: Sequence[int] = (), frozen: bool = False
                ) -> Dict[str, object]:
    """Follow the first ``len(batches)`` training steps from the seed's
    weights. Returns each step's loss, the per-leaf norm of the first
    gradient and the per-leaf norm of the parameters' change after the last
    step. ``keep`` and ``frozen`` plant the two faults a training cell can
    have (half of the batch left out; a step that returns its state
    unchanged) for the control readings."""
    end, blocks = wgen.everything(sizes, seed)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m_end, v_end = zeros(end), zeros(end)
    m_blocks = [zeros(b) for b in blocks]
    v_blocks = [zeros(b) for b in blocks]
    hp = dict(b1=float(adam["beta1"]), b2=float(adam["beta2"]),
              eps=float(adam["epsilon"]))
    lr = jnp.float32(adam["learning_rate"])
    losses, grad_norms = [], None
    for step, (x, y) in enumerate(batches):
        loss, g_end, g_blocks = loss_and_grads(
            end, blocks, jnp.asarray(x), jnp.asarray(y), sizes["heads"],
            precision, rows_per_block, keep)
        losses.append(float(loss))
        if step == 0:
            grad_norms = leaf_norms(g_end, g_blocks)
        if frozen:
            continue
        t = jnp.float32(step + 1)
        end, m_end, v_end = _adam_leaf_tree(end, g_end, m_end, v_end, lr, t,
                                            **hp)
        for i in range(len(blocks)):
            blocks[i], m_blocks[i], v_blocks[i] = _adam_leaf_tree(
                blocks[i], g_blocks[i], m_blocks[i], v_blocks[i], lr, t,
                **hp)
        del g_end, g_blocks
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms(sizes, seed, end, blocks),
            "params": (end, blocks)}
