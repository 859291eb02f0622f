"""Pallas kernel for a Mamba-2 decode step's state update: every slot's
state ``[H, P, N]`` is read once, decayed and given the token's input, written
back IN PLACE (``input_output_aliases``), and read out against ``C`` with the
``D`` term added — the arithmetic of ``state_space.ssm_step``, in float32,
on the state in the type it is held in.

    S_h <- exp(dt_h A_h) S_h + (dt_h x_h) ⊗ B        S_h [P, N]
    y_h  = S_h C + D_h x_h

Grid ``(slots,)``: one step holds one slot's whole state (H × [P, N] tiles,
1 MB in bfloat16 at Granite-4.0-H's 64 × 64 × 128) and works the heads one
after another, each tile a [P, N] block with N on the 128 lanes. The
per-head scalars and the token's x travel as lane vectors ([1, H], and x
transposed to [P, H]) so that a head's decay is one lane of a vector and its
x one column, broadcast along the tile; the outputs gather back into a
[P, H] block by a lane select. Between grid steps Pallas double-buffers the
next slot's state in while this one's is written out.

The ``pallas_call`` is named ``ssm_decode_update``: the compiler makes that
the instruction's name, which a device trace shows.

:func:`make_ssm_update_helper` makes what the ``ssm_update`` helper kind
registers for the TPU; it declines (returns None) for a state that is not
whole lanes or not of a float type it takes, and the layer's ``jnp`` body
(``ssm_step``) runs."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _interpret_default

KERNEL_NAME = "ssm_decode_update"
LANES = 128


def _kernel(s_ref, xt_ref, dt_ref, bc_ref, ad_ref, so_ref, yt_ref):
    """One slot: s_ref/so_ref [H, P, N]; xt_ref/yt_ref [P, H] (x and y of
    the slot, heads on the lanes); dt_ref [1, H]; bc_ref [2, N] (B, C);
    ad_ref [2, H] (A, D)."""
    heads = s_ref.shape[0]
    xt = xt_ref[...]                                   # [P, H] f32
    dt = dt_ref[...]                                   # [1, H]
    decay = jnp.exp(dt * ad_ref[0:1, :])               # [1, H]
    u = xt * dt                                        # [P, H]
    bvec, cvec = bc_ref[0:1, :], bc_ref[1:2, :]        # [1, N]
    lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
    y = xt * ad_ref[1:2, :]                            # the D term
    for h in range(heads):
        s = s_ref[h].astype(jnp.float32) * decay[:, h:h + 1] \
            + u[:, h:h + 1] * bvec                     # [P, N]
        so_ref[h] = s.astype(so_ref.dtype)
        col = jnp.sum(s * cvec, axis=1, keepdims=True)  # [P, 1]
        y = y + jnp.where(lane == h, col, 0.0)
    yt_ref[...] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(state, xt, dt, bc, ad, *, interpret: bool = False):
    """state [S, H, P, N] (updated in place), xt [S, P, H] f32 (x with heads
    on the last axis), dt [S, 1, H] f32, bc [S, 2, N] f32 (B; C), ad [2, H]
    f32 (A; D) → (new state, yt [S, P, H] f32)."""
    s, h, p, n = state.shape
    slot = lambda i: (i, 0, 0)
    return pl.pallas_call(
        _kernel,
        name=KERNEL_NAME,
        grid=(s,),
        in_specs=[pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((None, p, h), slot),
                  pl.BlockSpec((None, 1, h), slot),
                  pl.BlockSpec((None, 2, n), slot),
                  pl.BlockSpec((2, h), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((None, p, h), slot)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s, p, h), jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(state, xt, dt, bc, ad)


def make_ssm_update_helper(interpret=None):
    """The ``ssm_update`` helper: ``helper(conf, state, x, dt, a, b, c, d)``
    with state [S, H, P, N], x [S, H, P], dt [S, H], a/d [H], b/c [S, N] →
    (new state, y [S, H, P] f32), or None where it declines (module
    docstring)."""
    def helper(conf, state, x, dt, a, b, c, d):
        del conf
        if state.shape[-1] % LANES or jnp.dtype(state.dtype) not in (
                jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
            return None
        f32 = jnp.float32
        new, yt = ssm_decode_update(
            state, jnp.swapaxes(x.astype(f32), 1, 2),
            dt.astype(f32)[:, None, :],
            jnp.stack([b.astype(f32), c.astype(f32)], axis=1),
            jnp.stack([a.astype(f32), d.astype(f32)]),
            interpret=bool(_interpret_default() if interpret is None
                           else interpret))
        return new, jnp.swapaxes(yt, 1, 2)
    return helper


def register_ssm_update(platforms=("tpu", "cpu"), interpret=None,
                        _default: bool = False) -> None:
    from ..nn.helpers import enable_helper, register_helper
    register_helper("ssm_update", make_ssm_update_helper(interpret),
                    platforms, _default=_default)
    enable_helper("ssm_update")


def register_default() -> None:
    """Lazy-discovery entry point (nn/helpers._DEFAULT_PROVIDERS), TPU only:
    on the CPU the kernel would run interpreted, and the layer's jnp body
    is the faster one there."""
    register_ssm_update(platforms=("tpu",), _default=True)
