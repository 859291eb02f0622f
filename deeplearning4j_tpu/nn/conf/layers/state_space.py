"""Mamba-2 state-space mixer (the SSD layer of Dao & Gu, 2024) — a sequence
layer whose decode cache is a FIXED-SIZE state a slot, not rows indexed by
position.

Per token t and head h (head dim P, state size N, one group of B and C):

    [z, xBC, dt] = x W_in                       (no bias)
    xBC          = silu(causal depthwise conv_K(xBC) + b)
    [x, B, C]    = split(xBC)                   x [H, P]; B, C [N]
    dt           = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t          = exp(dt_t A_h) S_{t-1} + dt_t x_{t,h} ⊗ B_t     [P, N]
    y_{t,h}      = S_t C_t + D_h x_{t,h}
    out          = W_out (RMSNorm(y ⊙ silu(z)) · w)    norm over all H·P

A whole sequence (``forward``, a prompt's prefill) runs the chunked SSD
form: within a chunk the outputs are a masked matrix product, across chunks
the state is carried by one small recurrence — matmuls, not a token-by-token
scan. A decode step updates each slot's state once (``ssm_update`` helper:
``kernels/ssm_update.py`` on the TPU, :func:`ssm_step` elsewhere).

The cache a slot holds (:meth:`Mamba2Layer.init_cache`): ``ssm`` [S, H, P, N]
and ``conv`` [S, K-1, conv_dim] — the last K-1 inputs of the convolution,
stored lane-dense (a [conv_dim, K-1] minor dimension of 3 would pad to 128
lanes on the TPU: 43 times the bytes). A padded prompt leaves the state where
its last real token left it: ``dt`` is 0 past a row's length, so
``exp(0·A) = 1`` and ``0·x⊗B = 0`` carry the state through unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from ..input_type import InputType
from ..serde import register_config
from .attention import TOKEN_BLOCK, Window, in_token_blocks, rms_norm
from .base import BaseRecurrentLayerConf
from ...helpers import get_helper


# graftlint: traced
def _segsum(a):
    """a [..., L] → [..., L, L]: ``sum(a[j+1..i])`` at (i, j), i >= j, and
    -inf above the diagonal (so that ``exp`` gives the causal decay)."""
    c = jnp.cumsum(a, axis=-1)
    seg = c[..., :, None] - c[..., None, :]
    n = a.shape[-1]
    keep = jnp.tril(jnp.ones((n, n), bool))
    return jnp.where(keep, seg, -jnp.inf)


# graftlint: traced
def ssd_chunked(x, dt, a, b, c, chunk: int, state0=None):
    """The chunked SSD scan in float32. x [R, T, H, P], dt [R, T, H] (0 where
    a position must not move the state), a [H] (negative), b/c [R, T, N],
    state0 [R, H, P, N] or None (zeros). T need not be a multiple of
    ``chunk``: the tail is padded with dt = 0. Returns (y [R, T, H, P]
    without the D term, final state [R, H, P, N])."""
    r, t, h, p = x.shape
    n = b.shape[-1]
    l = min(chunk, t)
    pad = -t % l
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    k = (t + pad) // l
    x = x.reshape(r, k, l, h, p)
    b = b.reshape(r, k, l, n)
    c = c.reshape(r, k, l, n)
    da = (dt * a[None, None, :]).reshape(r, k, l, h).transpose(0, 3, 1, 2)
    xdt = x * dt.reshape(r, k, l, h)[..., None]            # [R, K, L, H, P]
    hi = jax.lax.Precision.HIGHEST
    cum = jnp.cumsum(da, axis=-1)                          # [R, H, K, L]
    # 1. within a chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) x_j dt_j
    cb = jnp.einsum("rkln,rksn->rkls", c, b, precision=hi)
    decay = jnp.exp(_segsum(da))                           # [R, H, K, L, L]
    y = jnp.einsum("rkls,rhkls,rkshp->rklhp", cb, decay, xdt, precision=hi)
    # 2. each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)                  # [R, H, K, L]
    states = jnp.einsum("rkln,rhkl,rklhp->rkhpn", b, to_end, xdt,
                        precision=hi)
    # 3. the state carried across chunks, from state0
    if state0 is None:
        state0 = jnp.zeros((r, h, p, n), jnp.float32)
    states = jnp.concatenate([state0[:, None].astype(jnp.float32), states],
                             axis=1)                       # [R, K+1, H, P, N]
    ends = jnp.pad(cum[..., -1], ((0, 0), (0, 0), (1, 0)))  # [R, H, K+1]
    carry = jnp.exp(_segsum(ends))                         # [R, H, K+1, K+1]
    states = jnp.einsum("rhzk,rkhpn->rzhpn", carry, states, precision=hi)
    # 4. the carried state read out inside each chunk
    y = y + jnp.einsum("rkln,rkhpn,rhkl->rklhp", c, states[:, :-1],
                       jnp.exp(cum), precision=hi)
    return y.reshape(r, k * l, h, p)[:, :t], states[:, -1]


# graftlint: traced
def ssm_step(state, x, dt, a, b, c, d):
    """One token's state update for every slot, float32 arithmetic: state
    [S, H, P, N] (any float type; returned in it), x [S, H, P], dt [S, H],
    a/d [H], b/c [S, N]. Returns (new state, y [S, H, P] float32 with the D
    term) — the body ``kernels/ssm_update.py`` must agree with."""
    s = state.astype(jnp.float32)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dtf * a[None, :])[:, :, None, None]
    s = s * decay + (dtf[:, :, None] * xf)[..., None] \
        * b.astype(jnp.float32)[:, None, None, :]
    y = jnp.einsum("shpn,sn->shp", s, c.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) \
        + d[None, :, None] * xf
    return s.astype(state.dtype), y


@register_config
@dataclasses.dataclass
class Mamba2Layer(BaseRecurrentLayerConf):
    """Mamba-2 mixer [N, T, n_in] → [N, T, n_out] (module docstring)."""
    num_heads: int = 8            # H (mamba_n_heads)
    head_dim: int = 16            # P (mamba_d_head)
    state_size: int = 16          # N (mamba_d_state)
    conv_kernel: int = 4          # K (mamba_d_conv)
    chunk_size: int = 256
    eps: float = 1e-5             # the gated norm's
    #: the decode walk keeps this layer's cache (``advance``); its cache is a
    #: fixed-size state a slot, which admission overwrites whole
    causal = True
    fixed_state = True

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.state_size

    def get_output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timesteps)

    def init_params(self, key, dtype=jnp.float32) -> Dict:
        h, cd, k = self.num_heads, self.conv_dim, self.conv_kernel
        width = self.inner + cd + h
        ki, kc, ka, kt, ko = jax.random.split(key, 5)
        dt0 = jnp.exp(jax.random.uniform(kt, (h,), jnp.float32,
                                         jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "W_in": self._winit(ki, (self.n_in, width), self.n_in, width,
                                dtype),
            "conv_w": (jax.random.uniform(kc, (k, cd), jnp.float32, -1, 1)
                       / jnp.sqrt(k)).astype(dtype),
            "conv_b": jnp.zeros((cd,), dtype),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(ka, (h,), jnp.float32, 1,
                                                16)).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "norm_g": jnp.ones((self.inner,), dtype),
            "W_out": self._winit(ko, (self.inner, self.n_out), self.inner,
                                 self.n_out, dtype)}

    def regularizable(self):
        return ("W_in", "W_out")

    # ------------------------------------------------------------ pieces
    # graftlint: traced
    def _in_proj(self, params, x):
        """x [..., n_in] → (z [..., inner], xBC [..., conv_dim], dt [..., H])."""
        with jax.named_scope("in_proj"):
            zxd = x @ params["W_in"]
        i, cd = self.inner, self.conv_dim
        return zxd[..., :i], zxd[..., i:i + cd], zxd[..., i + cd:]

    # graftlint: traced
    def _split(self, xbc):
        """Convolved xBC [..., conv_dim] → (x [..., H, P], B, C [..., N])."""
        i, n = self.inner, self.state_size
        x = xbc[..., :i].reshape(xbc.shape[:-1] + (self.num_heads,
                                                   self.head_dim))
        return x, xbc[..., i:i + n], xbc[..., i + n:]

    # graftlint: traced
    def _dt(self, params, dt):
        """softplus(dt + dt_bias) in float32, and A = -exp(A_log)."""
        bias = params["dt_bias"].astype(jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + bias.reshape((1,) * (dt.ndim - 1) + (-1,)))
        return dt, -jnp.exp(params["A_log"].astype(jnp.float32))

    # graftlint: traced
    def _out(self, params, y, z, dtype):
        """W_out(RMSNorm(y ⊙ silu(z)) · w): the gate before the norm, the
        norm over all H·P."""
        with jax.named_scope("gate_norm"):
            g = y.reshape(z.shape).astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            g = rms_norm(g, params["norm_g"], self.eps).astype(dtype)
        with jax.named_scope("out_proj"):
            return g @ params["W_out"]

    # graftlint: traced
    def _mix(self, params, x, lengths=None):
        """The whole mixer over x [R, T, n_in] from a zero state, positions
        at or past ``lengths`` [R] (None: none) moving nothing, a few rows
        at a time (``TOKEN_BLOCK`` tokens: bounds what one layer holds of a
        batched admission — its [rows, T, 8512] projection, the float32
        convolution and the [rows, H, T/L, L, L] decay tensor). Returns (out
        [R, T, n_out], final state [R, H, P, N] float32, each row's last
        K-1 convolution inputs [R, K-1, conv_dim], zeros before position
        0)."""
        r, t, _ = x.shape
        if lengths is None:
            lengths = jnp.full((r,), t, jnp.int32)
        k = self.conv_kernel

        def rows(x, lengths):
            z, xbc_in, dt = self._in_proj(params, x)
            padded = jnp.pad(xbc_in, ((0, 0), (k - 1, 0), (0, 0)))
            with jax.named_scope("conv"):
                pf = padded.astype(jnp.float32)
                w = params["conv_w"].astype(jnp.float32)
                conv = sum(pf[:, j:j + t] * w[j][None, None, :]
                           for j in range(k)) \
                    + params["conv_b"].astype(jnp.float32)[None, None, :]
                xbc = jax.nn.silu(conv)
            # the inputs at positions len-K+1 .. len-1 (padded: len .. )
            at = lengths[:, None] \
                + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
            tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
            xs, b, c = self._split(xbc)
            dt, a = self._dt(params, dt)
            real = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
            with jax.named_scope("ssd"):
                y, state = ssd_chunked(xs, dt, a, b, c, self.chunk_size)
                y = y + params["D"].astype(jnp.float32)[None, None, :, None] \
                    * xs
            return self._out(params, y, z, x.dtype), state, tail
        return in_token_blocks(rows, x, lengths,
                               block=max(1, TOKEN_BLOCK // t))

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        lengths = None if mask is None else \
            jnp.sum(mask.astype(jnp.int32), axis=1)
        return self._mix(params, x, lengths)[0], state

    # ---- the decode walk's door (models/generation.py) ----
    def init_cache(self, batch: int, t_max: int, dtype=jnp.float32,
                   sharding=None) -> Dict:
        """{"ssm": [B, H, P, N], "conv": [B, K-1, conv_dim]} in ``dtype``,
        whatever ``t_max``: the state does not grow with the context."""
        shapes = {"ssm": (batch, self.num_heads, self.head_dim,
                          self.state_size),
                  "conv": (batch, self.conv_kernel - 1, self.conv_dim)}
        if sharding is not None:
            return {k: jnp.zeros(s, dtype, device=sharding)
                    for k, s in shapes.items()}
        return {k: jnp.zeros(s, dtype) for k, s in shapes.items()}

    # graftlint: traced
    def advance(self, params, x, cache, window: Window):
        """The decode walk's door: no cache is ``forward``; a fresh prompt
        replaces the state (:meth:`prefill_forward`); one token a row
        updates it (:meth:`decode_forward`). Pages and windows from a
        position (chunked prefill, verify) would need the state at a
        position, which a fixed-size state does not keep: the engine refuses
        them for a model with this layer (ROADMAP R-M7)."""
        if cache is None:
            return self.forward(params, None, x, mask=window.mask)[0], None
        if window.pages is None:
            if window.start is None:
                return self.prefill_forward(params, x, cache, window.valid)
            if window.valid is None:
                return self.decode_forward(params, x, cache)
        raise NotImplementedError(
            "a state-space layer cannot resume from a position or a page "
            "(chunked prefill, speculative verify, paged pool)")

    # graftlint: traced
    def prefill_forward(self, params, x, cache: Dict, lengths):
        """Prompts [B, T, n_in] of ``lengths`` [B], from a zero state: the
        chunked scan, its final state (dt 0 past a row's length) and each
        row's last K-1 convolution inputs (zeros before position 0) REPLACE
        the cache's rows. Returns (out [B, T, n_out], new cache)."""
        out, state, tail = self._mix(params, x, lengths)
        return out, {"ssm": state.astype(cache["ssm"].dtype),
                     "conv": tail.astype(cache["conv"].dtype)}

    # graftlint: traced
    def decode_forward(self, params, x, cache: Dict):
        """One token a row, x [B, 1, n_in]: the convolution over the carried
        K-1 inputs and this one, then one state update a slot (the
        ``ssm_update`` helper where registered, :func:`ssm_step` else).
        Returns (out [B, 1, n_out], new cache)."""
        z, xbc_in, dt = self._in_proj(params, x[:, 0])
        with jax.named_scope("conv"):
            win = jnp.concatenate([cache["conv"],
                                   xbc_in[:, None].astype(cache["conv"].dtype)],
                                  axis=1)                  # [B, K, conv_dim]
            conv = jnp.sum(win.astype(jnp.float32)
                           * params["conv_w"].astype(jnp.float32)[None],
                           axis=1) \
                + params["conv_b"].astype(jnp.float32)[None, :]
            xbc = jax.nn.silu(conv)
        xs, b, c = self._split(xbc)
        dt, a = self._dt(params, dt)
        d = params["D"].astype(jnp.float32)
        with jax.named_scope("ssd"):
            helper = get_helper("ssm_update")
            got = helper(self, cache["ssm"], xs, dt, a, b, c, d) \
                if helper is not None else None
            state, y = got if got is not None else \
                ssm_step(cache["ssm"], xs, dt, a, b, c, d)
        out = self._out(params, y[:, None], z[:, None], x.dtype)
        return out, {"ssm": state, "conv": win[:, 1:]}
