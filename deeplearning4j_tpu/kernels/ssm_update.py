"""Pallas kernel for a Mamba-2 decode step's state update: every slot's
state ``[H, P, N]`` is read once, decayed and given the token's input, written
back IN PLACE (``input_output_aliases``), and read out against ``C`` with the
``D`` term added — the arithmetic of ``state_space.ssm_step``, in float32,
on the state in the type it is held in.

    S_h <- exp(dt_h A_h) S_h + (dt_h x_h) ⊗ B        S_h [P, N]
    y_h  = S_h C + D_h x_h

A slot's state is worked as ROWS: ``[H, P, N]`` seen as ``[H·P, N]``, N on
the 128 lanes, ``w`` rows at a time (a chunk: 128 rows, two heads, at
Granite-4.0-H's 64 × 64 × 128). A head's scalars — its decay ``exp(dt·A)``,
``dt`` and ``D`` — lie in SMEM, which a vector multiply takes as they are;
the token's x, which varies by row, the caller lays out lane-dense,
``[H·P / w, w]`` a slot (row r of the state is element r). A chunk's row of
``u = dt·x``, broadcast down the sublanes and transposed, is each state
row's ``u`` broadcast along its lanes, so a head's rows of a chunk are

    S <- S · decay_h + u ⊗ B          a multiply, a multiply and an add a vreg

with no lane slice, no per-head broadcast and no select over the state. The
read-out goes to the matrix unit, whose result comes out lane-dense. Since
``S_new·C = decay·(S_old·C) + u·(B·C)``, it contracts the OLD state — held
in bfloat16, so exact as it is read — against C split in three bfloat16
parts (``c_hi + c_mid + c_lo`` is C to float32's precision): one
``[8, N]·[w, N]ᵀ`` product a chunk gives the three partial sums as rows of
``[8, w]``, exact products accumulated in float32, and
``y = decay·(S_old·C) + u·(B·C) + D·x``: the float32 state's read-out,
reassociated, before the state is rounded for storage. A float32 state is
split in three parts the same way.

Grid ``(slots / spb,)``: ``spb`` slots a step, as many as keep a step's state
within ``STEP_BYTES`` (two at Granite's 1 MB a slot), so the fixed cost of a
grid step is paid half as often as one slot a step would. Between steps
Pallas double-buffers the next slots' state in while these are written out.
Every slot is updated, whether a request holds its lane or not.

The ``pallas_call`` is named ``ssm_decode_update``: the compiler makes that
the instruction's name, which a device trace shows.

:func:`make_ssm_update_helper` makes what the ``ssm_update`` helper kind
registers for the TPU. It takes a call by what it can observe (:func:`plan`)
and declines (returns None) for a state that is not whole lanes, rows a
chunk cannot take whole, or a type it does not take; the layer's ``jnp``
body (``ssm_step``) then runs."""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _interpret_default

KERNEL_NAME = "ssm_decode_update"
LANES = 128
#: bytes of state one grid step takes at most (read, and again written)
STEP_BYTES = 2 << 20
#: the float types a state may be held in
TYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def plan(slots: int, heads: int, head_dim: int, state_size: int,
         dtype) -> Optional[Tuple[int, int]]:
    """``(w, spb)`` — rows a chunk and slots a grid step — for a state
    ``[slots, heads, head_dim, state_size]`` of ``dtype``, or None where the
    kernel does not take the shape: the state is not whole lanes, or a chunk
    (the largest row count up to 128 that divides ``heads · head_dim``) is
    not whole sublane tiles or does not hold whole heads or lie inside
    one."""
    dtype = jnp.dtype(dtype)
    if state_size % LANES or dtype not in TYPES:
        return None
    tile = 32 // dtype.itemsize          # sublanes of a tile: 8 f32, 16 bf16
    w = math.gcd(heads * head_dim, LANES)
    whole = head_dim % w == 0 or (w % head_dim == 0
                                  and head_dim % tile == 0)
    if w % tile or not whole:
        return None
    slot = heads * head_dim * state_size * dtype.itemsize
    spb = max(n for n in range(1, slots + 1)
              if slots % n == 0 and (n == 1 or n * slot <= STEP_BYTES))
    return w, spb


def _chunk(i: int, j: int, w: int, head_dim: int):
    """The index of chunk ``j`` (rows j·w … j·w + w − 1) of step slot ``i``
    in a ``[spb, H, P, N]`` block: part of one head, or whole heads."""
    if w <= head_dim:
        return _rows(i, j * w, w, head_dim)
    g = w // head_dim
    return i, pl.ds(j * g, g)


def _rows(i: int, r: int, n: int, head_dim: int):
    """The index of ``n`` rows from row ``r`` of step slot ``i``, all of one
    head."""
    return i, r // head_dim, pl.ds(r % head_dim, n)


def _split3(v):
    """float32 ``v`` as three bfloat16 parts whose float32 sum is ``v`` to
    float32's precision: each part the top 16 bits of what the ones before
    left. Masked bits, not a round trip through bfloat16, which a compiler
    that allows excess precision may drop as a no-op."""
    def top(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits >> 16 << 16, jnp.float32)
    hi = top(v)
    mid = top(v - hi)
    lo = v - hi - mid
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def _kernel(s_ref, hd_ref, x_ref, bc_ref, so_ref, y_ref):
    """``spb`` slots: s_ref/so_ref [spb, H, P, N]; hd_ref [spb, 3, H]
    float32 in SMEM (each head's decay, dt and D); x_ref/y_ref [spb, K, w]
    float32, lane-dense by state row; bc_ref [spb, 2, N] float32 (B; C)."""
    spb, _, head_dim, n = s_ref.shape
    k, w = x_ref.shape[1:]
    rows = min(w, head_dim)                # a chunk's rows of one head
    f32 = jnp.float32
    contract = (((1,), (1,)), ((), ()))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, n), 0)
    for i in range(spb):
        b, c = bc_ref[i, 0:1, :], bc_ref[i, 1:2, :]       # [1, N]
        bdotc = jnp.sum(b * c, axis=1, keepdims=True)      # [1, 1]
        hi, mid, lo = (p.astype(f32) for p in _split3(c))
        c3 = jnp.where(sub == 0, hi, jnp.where(sub == 1, mid, jnp.where(
            sub == 2, lo, 0.0))).astype(jnp.bfloat16)      # [8, N]
        x = x_ref[i]                                       # [K, w]
        for j in range(k):
            # each head's scalars along the chunk's lanes
            heads = [(j * w + t * rows) // head_dim for t in range(w // rows)]
            scalars = []
            for q in range(3):
                v = hd_ref[i, q, heads[0]]
                for t, h in enumerate(heads[1:], 1):
                    v = jnp.where(lane >= t * rows, hd_ref[i, q, h], v)
                scalars.append(v)
            decay, dt, d = scalars
            xr = x[j:j + 1]                                # [1, w]
            u = xr * dt
            # row r's u along its lanes: [1, w] down the sublanes, transposed
            ub = jnp.transpose(jnp.broadcast_to(u, (n, w))) * b   # [w, N]
            old = s_ref[_chunk(i, j, w, head_dim)].reshape(w, n)
            for t, h in enumerate(heads):
                part = slice(t * rows, (t + 1) * rows)
                new = old[part].astype(f32) * hd_ref[i, 0, h] + ub[part]
                so_ref[_rows(i, j * w + t * rows, rows, head_dim)] = \
                    new.astype(so_ref.dtype)
            parts = (old,) if old.dtype == jnp.bfloat16 else _split3(old)
            sc = sum(jax.lax.dot_general(c3, p, contract,
                                         preferred_element_type=f32)
                     for p in parts)                       # [8, w]
            sc = sc[0:1] + sc[1:2] + sc[2:3]               # S_old·C
            y_ref[i, j:j + 1, :] = decay * sc + u * bdotc + d * xr


# jitted: the calls of a program that share shapes (every layer of a model)
# trace the kernel body and lower it to Mosaic once, not once a layer
@functools.partial(jax.jit, static_argnames=("w", "spb", "interpret"))
def ssm_decode_update(state, hd, x, bc, *, w: int, spb: int,
                      interpret: bool = False):
    """state [S, H, P, N] (updated in place); hd [S, 3, H] float32 (each
    head's exp(dt·A), dt and D); x [S, H·P / w, w] float32 (by state row);
    bc [S, 2, N] float32 (B; C) → (new state, y [S, H·P / w, w] float32)."""
    s, h, p, n = state.shape
    k = x.shape[1]
    block = spb * h * p * n * jnp.dtype(state.dtype).itemsize
    at = lambda i: (i, 0, 0)
    rows = pl.BlockSpec((spb, k, w), at)
    slab = pl.BlockSpec((spb, h, p, n), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        _kernel,
        name=KERNEL_NAME,
        grid=(s // spb,),
        in_specs=[slab,
                  pl.BlockSpec((spb, 3, h), at, memory_space=pltpu.SMEM),
                  rows, pl.BlockSpec((spb, 2, n), at)],
        out_specs=[slab, rows],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state in and out, double-buffered; x, y, B and C are
            # kilobytes beside them, a chunk's float32 temporaries 64 KB
            vmem_limit_bytes=4 * block + (8 << 20)),
    )(state, hd, x, bc)


def make_ssm_update_helper(interpret=None):
    """The ``ssm_update`` helper: ``helper(conf, state, x, dt, a, b, c, d)``
    with state [S, H, P, N], x [S, H, P], dt [S, H], a/d [H], b/c [S, N] →
    (new state, y [S, H, P] f32), or None where it declines (module
    docstring)."""
    def helper(conf, state, x, dt, a, b, c, d):
        del conf
        s, h, p, n = state.shape
        got = plan(s, h, p, n, state.dtype)
        if got is None:
            return None
        w, spb = got
        f32 = jnp.float32
        dtf = dt.astype(f32)
        hd = jnp.stack([jnp.exp(dtf * a.astype(f32)[None, :]), dtf,
                        jnp.broadcast_to(d.astype(f32), dtf.shape)], axis=1)
        new, y = ssm_decode_update(
            state, hd, x.astype(f32).reshape(s, h * p // w, w),
            jnp.stack([b.astype(f32), c.astype(f32)], axis=1), w=w, spb=spb,
            interpret=bool(_interpret_default() if interpret is None
                           else interpret))
        return new, y.reshape(s, h, p)
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
