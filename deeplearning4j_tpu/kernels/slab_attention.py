"""Pallas kernel for decode attention over the lane-dense slab cache: a
window's queries (one token a row in a decode step) attend every position of
their slot's K and V, ``[B, H/g, T, g·Dh]`` AS STORED — no transpose, reshape
or copy of a slab-sized array on the way in or out.

The built-in body of ``SelfAttentionLayer._slab_attend`` reads a layer's whole
K and V into fast memory and only then computes on it, so a decode step
waits for the read and works on it one after the other (PERF.md §5, PR 27:
6.0 ms waiting, 4.7 ms working). Here the position axis is the innermost
grid axis: Pallas double-buffers the K and V blocks, so tile *i* is worked
on while tile *i + 1* lands, with an online softmax (running maximum, sum
and accumulator in f32 scratch) carrying a row across tiles.

Grid ``(slots, head-group blocks, position tiles)``; one step takes ``hb``
head groups × ``tb`` positions of K and of V (2.6 MB each at gpt2-large's
``[16, 10, 1024, 128]`` bf16: a slot's whole K — a grid step costs some
0.35 µs, so a block has to be worth several). Both contractions run over
whole 128-lane rows, as ``_slab_attend`` describes them: the logits of a
head group are ``Qblk[g·C, g·Dh] · K_rowᵀ[g·Dh, tb]`` with ``Qblk``
block-diagonal (the zeros contribute exact 0.0), the weighted sum is
``P[g·C, tb] · V_row[tb, g·Dh]`` of which head j keeps its own Dh lanes
(the caller's diagonal). f32 logits and softmax; EVERY position of every
slot is read and masked by ``kpos <= qpos`` — tiles beyond a lane's position
are not skipped, so a step's time does not follow the load (ROADMAP S2 (b)
is the item that would change that). The arithmetic differs from the
built-in body's only by the online softmax's reassociation.

The ``pallas_call`` is named ``slab_decode_attn``: the compiler makes that
the instruction's name, which a device trace shows.

:func:`make_slab_attention_helper` makes what the ``slab_attention`` kind
registers for the TPU. It takes a call by what it can observe — whole-lane
rows, a ``T`` a tile divides, a short window, slab and queries of one float
type — and returns None otherwise: the layer's einsum body is the
always-available fallback. Int8 rows (ROADMAP S10) would be dequantised
here, a K or V block at a time, before the two products."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..nn.helpers import attention_spmd_context, note_attention_plan
from .pallas_attention import _interpret_default

KERNEL_NAME = "slab_decode_attn"
LANES = 128
#: the longest window (queries a row) the one body serves: a decode step is
#: 1, a speculative verify window 4; a prefill chunk is longer and keeps
#: the einsum body
MAX_WINDOW = 8
#: positions of the largest tile; a T none of TILES divides declines
TILES = (1024, 512, 256, 128)
#: bytes of one K (or V) block at most: two operands, double-buffered
BLOCK_BYTES = 4 << 20


def plan(window: int, head_groups: int, t: int, lanes: int,
         dtype) -> Optional[Tuple[int, int]]:
    """``(hb, tb)`` — head groups and positions a grid step takes — for a
    slab ``[·, head_groups, t, lanes]`` of ``dtype`` and ``window`` queries a
    row, or None where the kernel does not serve the shape."""
    if lanes % LANES or not 1 <= window <= MAX_WINDOW:
        return None
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return None
    tb = next((n for n in TILES if t % n == 0), None)
    if tb is None:
        return None
    fit = max(BLOCK_BYTES // (tb * lanes * jnp.dtype(dtype).itemsize), 1)
    hb = max(n for n in range(1, min(fit, head_groups) + 1)
             if head_groups % n == 0)
    return hb, tb


def _kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, g: int, window: int):
    """One grid step: ``hb`` head groups × ``tb`` positions of one slot.
    q_ref/o_ref [hb, g·C, L], k_ref/v_ref [hb, tb, L]; m/l [hb, g·C, 128]
    (a column, broadcast) and acc [hb, g·C, L] carry a row across tiles."""
    b, ti = pl.program_id(0), pl.program_id(2)
    hb, tb, _ = k_ref.shape
    rows = g * window

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # row c·g + j is query c of the window, head j of the group
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    limit = jnp.full((rows, 1), qpos_ref[b * window], jnp.int32)
    for c in range(1, window):
        limit = jnp.where(row >= c * g, qpos_ref[b * window + c], limit)
    kpos = ti * tb + jax.lax.broadcasted_iota(jnp.int32, (rows, tb), 1)
    keep = kpos <= limit                                     # [rows, tb]

    for h in range(hb):
        s = jax.lax.dot_general(
            q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [rows, tb]
        s = jnp.where(keep, s, -1e30)
        m_prev = m_ref[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_ref[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[h],
            preferred_element_type=jnp.float32)
        m_ref[h] = jnp.broadcast_to(m_new, (rows, LANES))
        l_ref[h] = jnp.broadcast_to(l_new, (rows, LANES))

    @pl.when(ti == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(o_ref.dtype)


# jitted: the calls of a program that share shapes (every layer of a model)
# trace the kernel body and lower it to Mosaic once, not once a layer
@functools.partial(jax.jit, static_argnames=("g", "hb", "tb", "scale",
                                             "interpret"))
def slab_decode_attention(q, ck, cv, qpos, *, g: int, hb: int, tb: int,
                          scale: float, interpret: bool = False):
    """q [B, H/g, g·C, L] (row c·g + j: query c, head j, block-diagonal over
    the lanes), ck/cv [B, H/g, T, L], qpos [B, C] int32 → [B, H/g, g·C, L]
    in the slab's type: softmax(q·Kᵀ · scale, over positions <= qpos) · V."""
    b, hg, rows, lanes = q.shape
    t = ck.shape[2]
    window = rows // g
    block = hb * tb * lanes * jnp.dtype(ck.dtype).itemsize
    at = lambda i, j, ti, qpos: (i, j, 0, 0)
    slab = pl.BlockSpec((None, hb, tb, lanes),
                        lambda i, j, ti, qpos: (i, j, ti, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hg // hb, t // tb),
        in_specs=[pl.BlockSpec((None, hb, rows, lanes), at), slab, slab],
        out_specs=pl.BlockSpec((None, hb, rows, lanes), at),
        scratch_shapes=[pltpu.VMEM((hb, rows, LANES), jnp.float32),
                        pltpu.VMEM((hb, rows, LANES), jnp.float32),
                        pltpu.VMEM((hb, rows, lanes), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, g=g, window=window),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, cv.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # K and V blocks double-buffered; the queries, the output,
            # the scratch and a tile's logits are kilobytes beside them
            vmem_limit_bytes=4 * block + (8 << 20)),
    )(qpos.reshape(-1).astype(jnp.int32), q, ck, cv)


def make_slab_attention_helper(interpret=None):
    """The ``slab_attention`` helper: ``helper(conf, qblk, ck, cv, qpos,
    scale)`` with ``qblk`` [B, C, H/g, g, g·Dh] the block-diagonal queries,
    ck/cv [B, H/g, T, g·Dh], ``qpos`` [B, C] → the rows
    [B, C, H/g, g, g·Dh] the einsum body would give, or None where the
    kernel declines (module docstring). Under a declared mesh
    (``nn.helpers.attention_spmd``) the call runs in a ``shard_map`` over
    the data and tp axes the slab is sharded over — slots and head groups
    are independent, no collective — and the plan is made from the local
    shapes. ``interpret``: None derives it from the default backend."""
    def helper(conf, qblk, ck, cv, qpos, scale):
        del conf
        b, c, hg, g, lanes = qblk.shape
        if not qblk.dtype == ck.dtype == cv.dtype:
            return None       # the einsum promotes; the kernel would round
        ctx = attention_spmd_context()
        b_ax = h_ax = None
        if ctx is not None:
            mesh, b_ax, h_ax = ctx
            fits = lambda ax, n: ax if ax in mesh.shape \
                and n % mesh.shape[ax] == 0 else None
            b_ax, h_ax = fits(b_ax, b), fits(h_ax, hg)
        hg_local = hg // (mesh.shape[h_ax] if h_ax else 1)
        tiles = plan(c, hg_local, ck.shape[2], lanes, ck.dtype)
        if tiles is None:
            return None
        hb, tb = tiles
        note_attention_plan("slab_stream", g=g, hb=hb, tb=tb)
        local = functools.partial(
            slab_decode_attention, g=g, hb=hb, tb=tb, scale=float(scale),
            interpret=bool(_interpret_default() if interpret is None
                           else interpret))
        if ctx is not None:
            spec = P(b_ax, h_ax, None, None)
            local = jax.shard_map(
                local, mesh=mesh, in_specs=(spec, spec, spec, P(b_ax, None)),
                out_specs=spec, check_vma=False)
        q4 = qblk.transpose(0, 2, 1, 3, 4).reshape(b, hg, c * g, lanes)
        rows = local(q4, ck, cv, qpos)
        return rows.reshape(b, hg, c, g, lanes).transpose(0, 2, 1, 3, 4)
    return helper


def register_slab_attention(platforms=("tpu", "cpu"), interpret=None,
                            _default: bool = False) -> None:
    from ..nn.helpers import enable_helper, register_helper
    register_helper("slab_attention", make_slab_attention_helper(interpret),
                    platforms, _default=_default)
    enable_helper("slab_attention")


def register_default() -> None:
    """Lazy-discovery entry point (nn/helpers._DEFAULT_PROVIDERS), TPU only:
    on the CPU the kernel would run interpreted, and the layer's einsum
    body is the faster one there."""
    register_slab_attention(platforms=("tpu",), _default=True)
