"""Pallas kernel for decode attention over the lane-dense slab cache: a
window's queries (one token a row in a decode step) attend their slot's K
and V, ``[B, H/g, T, g·Dh]`` AS STORED — no transpose, reshape or copy of a
slab-sized array on the way in or out — and read only the positions their
slot has reached.

The built-in body of ``SelfAttentionLayer._slab_attend`` reads a layer's whole
K and V into fast memory and only then computes on it, so a decode step
waits for the read and works on it one after the other (PERF.md §5, PR 27:
6.0 ms waiting, 4.7 ms working). Here the position axis is the innermost
grid axis: Pallas double-buffers the K and V blocks, so tile *i* is worked
on while tile *i + 1* lands, with an online softmax (running maximum, sum
and accumulator in f32 scratch) carrying a row across tiles.

Grid ``(head-group blocks, steps)``; a step takes ``hb`` head groups ×
``tb`` positions of one slot's K and V (1.25 MiB each at gpt2-large's
``[16, 10, 1024, 128]`` bf16: ten head groups × 512 positions). Both
contractions run over whole 128-lane rows, as ``_slab_attend`` describes
them: the logits of a head group are ``Qblk[g·C, g·Dh] · K_rowᵀ[g·Dh, tb]``
with ``Qblk`` block-diagonal (the zeros contribute exact 0.0), the weighted
sum is ``P[g·C, tb] · V_row[tb, g·Dh]`` of which head j keeps its own Dh
lanes (the caller's diagonal). f32 logits and softmax, masked by
``kpos <= qpos``.

**What is read: a slot's live positions.** A slot's queries reach tile
``last[b] = max(qpos[b, :]) // tb``; a lane its window says is not alive (a
stopped or free slot, whose tokens the host drops) reaches none, ``last[b]
= -1`` (:func:`live_tiles`). The steps are a list of the live tiles, tiles
0 to ``last[b]`` of each slot in turn (:func:`work_list`, on the scalar
prefetch beside ``qpos`` and ``last``), so each live tile's read is issued
while the one before it is worked, whoever's slot it is; the steps past the
live ones ask for the blocks already held, so the pipeline fetches nothing
for them, and do nothing. A step's bytes follow its lanes' lengths, not
``T`` or the slots: every position a live query attends is read, and only
tiles the mask would zero whole, and stopped lanes' rows, are skipped. A
slot no step visits gets zero rows (thrown away by the caller). The
arithmetic differs from the built-in body's only by the online softmax's
reassociation. The tile is picked from the shapes (:func:`plan`): small, so
that a context reads little past its end, but with a block that keeps a
step's read long beside its fixed cost (PERF.md §6: on a TPU v5e a decode
block at gpt2-large's shapes, every lane at 1000, takes 38.1 ms with 640 KiB
blocks, 28.2 with 1.25 MiB ones and 28.5 with 2.5 MiB ones).

The ``pallas_call`` is named ``slab_decode_attn``: the compiler makes that
the instruction's name, which a device trace shows. Each call notes the
positions it reads and the positions the slab holds
(``nn.helpers.note_slab_reads``), from the same ``last`` it prefetches.

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

from ..nn.helpers import (attention_spmd_context, note_attention_plan,
                          note_slab_reads)
from .pallas_attention import _interpret_default

KERNEL_NAME = "slab_decode_attn"
LANES = 128
#: the longest window (queries a row) the one body serves: a decode step is
#: 1, a speculative verify window 4; a prefill chunk is longer and keeps
#: the einsum body
MAX_WINDOW = 8
#: the tiles a plan picks from; a T none of them divides declines
TILES = (1024, 512, 256, 128)
#: bytes of one K (or V) block at most: two operands, double-buffered
BLOCK_BYTES = 4 << 20
#: the smallest tile a plan prefers, and the least bytes a K block of it
#: must hold to be worth its grid step (module docstring; the readings are
#: ``scripts/perf_kernel_checks.py``'s ``slab-stream`` rows, PERF.md §6)
MIN_TILE = 256
MIN_BLOCK_BYTES = 1 << 20


def plan(window: int, head_groups: int, t: int, lanes: int,
         dtype) -> Optional[Tuple[int, int]]:
    """``(hb, tb)`` — head groups and positions a grid step takes — for a
    slab ``[·, head_groups, t, lanes]`` of ``dtype`` and ``window`` queries a
    row, or None where the kernel does not serve the shape. The tile is the
    smallest of TILES that divides ``t``, is at least MIN_TILE positions and
    whose K block (as many head groups as BLOCK_BYTES lets divide evenly)
    holds at least MIN_BLOCK_BYTES; where none does, the largest that
    divides ``t``."""
    if lanes % LANES or not 1 <= window <= MAX_WINDOW:
        return None
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return None
    row = lanes * jnp.dtype(dtype).itemsize

    def groups(tb):
        fit = max(BLOCK_BYTES // (tb * row), 1)
        return max(n for n in range(1, min(fit, head_groups) + 1)
                   if head_groups % n == 0)
    fits = [n for n in TILES if t % n == 0]
    if not fits:
        return None
    tb = next((n for n in sorted(fits) if n >= MIN_TILE
               and groups(n) * n * row >= MIN_BLOCK_BYTES), max(fits))
    return groups(tb), tb


def live_tiles(qpos, alive, tb: int, tiles: int):
    """[B] int32: the last position tile a slot's queries ``qpos`` [B, C]
    reach, ``max(qpos[b]) // tb`` within ``[0, tiles)``; -1 (none) for a
    lane ``alive`` [B] (None: every lane) marks as not alive."""
    last = jnp.clip(jnp.max(qpos, axis=1) // tb, 0, tiles - 1)
    if alive is not None:
        last = jnp.where(alive, last, -1)
    return last.astype(jnp.int32)


def work_list(last, steps: int):
    """The grid's steps as a list of live tiles, slot by slot: (slot [steps],
    tile [steps], live steps [1]) for ``last`` [B] (:func:`live_tiles`) —
    step s < n works tile ``tile[s]`` of slot ``slot[s]``, tiles 0 to
    ``last[b]`` of each slot in turn, none of a slot at -1; every later step
    repeats the last live one (the last slot, or slot B - 1 where none is
    live), so it asks for the blocks already held."""
    count = last + 1
    ends = jnp.cumsum(count)
    n = ends[-1]
    at = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                     jnp.maximum(n - 1, 0))
    slot = jnp.minimum(jnp.sum(ends[None, :] <= at[:, None], axis=1),
                       last.shape[0] - 1).astype(jnp.int32)
    tile = (at - ends[slot] + count[slot]).astype(jnp.int32)
    return slot, tile, n.reshape(1).astype(jnp.int32)


def _kernel(qpos_ref, last_ref, slot_ref, tile_ref, n_ref, q_ref, k_ref,
            v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float, g: int,
            window: int):
    """One grid step: ``hb`` head groups × ``tb`` positions, tile
    ``tile_ref[s]`` of slot ``slot_ref[s]`` (:func:`work_list`); a step past
    the ``n_ref`` live ones does nothing. q_ref/o_ref [hb, g·C, L],
    k_ref/v_ref [hb, tb, L]; m/l [hb, g·C, 128] (a column, broadcast) and
    acc [hb, g·C, L] carry a row across a slot's tiles."""
    s = pl.program_id(1)
    b, ti = slot_ref[s], tile_ref[s]
    live = s < n_ref[0]
    hb, tb, _ = k_ref.shape
    rows = g * window

    @pl.when(live & (ti == 0))
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(live)
    def _tile():
        # row c·g + j is query c of the window, head j of the group
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        limit = jnp.full((rows, 1), qpos_ref[b * window], jnp.int32)
        for c in range(1, window):
            limit = jnp.where(row >= c * g, qpos_ref[b * window + c], limit)
        kpos = ti * tb + jax.lax.broadcasted_iota(jnp.int32, (rows, tb), 1)
        keep = kpos <= limit                                 # [rows, tb]

        for h in range(hb):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, tb]
            s = jnp.where(keep, s, -1e30)
            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_ref[h][:, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[h],
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, (rows, LANES))
            l_ref[h] = jnp.broadcast_to(l_new, (rows, LANES))

    @pl.when(live & (ti == last_ref[b]))
    def _store():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(o_ref.dtype)


# jitted: the calls of a program that share shapes (every layer of a model)
# trace the kernel body and lower it to Mosaic once, not once a layer
@functools.partial(jax.jit, static_argnames=("g", "hb", "tb", "scale",
                                             "interpret"))
def slab_decode_attention(q, ck, cv, qpos, last, *, g: int, hb: int,
                          tb: int, scale: float, interpret: bool = False):
    """q [B, H/g, g·C, L] (row c·g + j: query c, head j, block-diagonal over
    the lanes), ck/cv [B, H/g, T, L], qpos [B, C] int32, ``last`` [B] int32
    the last tile of ``tb`` positions a slot reads, -1 for none
    (:func:`live_tiles`) → [B, H/g, g·C, L] in the slab's type:
    softmax(q·Kᵀ · scale, over positions <= qpos) · V for a slot whose
    queries ``last`` covers, zeros for a slot that reads none."""
    b, hg, rows, lanes = q.shape
    t = ck.shape[2]
    window = rows // g
    block = hb * tb * lanes * jnp.dtype(ck.dtype).itemsize
    slot, tile, n = work_list(last, b * (t // tb))
    at = lambda j, s, qpos, last, slot, tile, n: (slot[s], j, 0, 0)
    slab = pl.BlockSpec(
        (None, hb, tb, lanes),
        lambda j, s, qpos, last, slot, tile, n: (slot[s], j, tile[s], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(hg // hb, b * (t // tb)),
        in_specs=[pl.BlockSpec((None, hb, rows, lanes), at), slab, slab],
        out_specs=pl.BlockSpec((None, hb, rows, lanes), at),
        scratch_shapes=[pltpu.VMEM((hb, rows, LANES), jnp.float32),
                        pltpu.VMEM((hb, rows, LANES), jnp.float32),
                        pltpu.VMEM((hb, rows, lanes), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, g=g, window=window),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, cv.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # K and V blocks double-buffered; the queries, the output,
            # the scratch and a tile's logits are kilobytes beside them
            vmem_limit_bytes=4 * block + (8 << 20)),
    )(qpos.reshape(-1).astype(jnp.int32), last, slot, tile, n, q, ck, cv)
    # a slot no step visits keeps whatever its output block held
    return jnp.where((last >= 0)[:, None, None, None], out,
                     jnp.zeros((), out.dtype))


def make_slab_attention_helper(interpret=None):
    """The ``slab_attention`` helper: ``helper(conf, qblk, ck, cv, qpos,
    scale, alive=None)`` with ``qblk`` [B, C, H/g, g, g·Dh] the
    block-diagonal queries, ck/cv [B, H/g, T, g·Dh], ``qpos`` [B, C],
    ``alive`` [B] the lanes whose rows count (None: every lane) → the rows
    [B, C, H/g, g, g·Dh] the einsum body would give for the lanes that
    count, or None where the kernel declines (module docstring). A taken
    call notes the positions it reads and those the slab holds
    (``nn.helpers.note_slab_reads``). Under a declared mesh
    (``nn.helpers.attention_spmd``) the call runs in a ``shard_map`` over
    the data and tp axes the slab is sharded over — slots and head groups
    are independent, no collective — and the plan is made from the local
    shapes. ``interpret``: None derives it from the default backend."""
    def helper(conf, qblk, ck, cv, qpos, scale, alive=None):
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
        t = ck.shape[2]
        note_attention_plan("slab_stream", g=g, hb=hb, tb=tb)
        # made outside the shard_map: the same vector counts what it reads
        last = live_tiles(qpos, alive, tb, t // tb)
        note_slab_reads(jnp.sum(last + 1) * tb, b * t)
        local = functools.partial(
            slab_decode_attention, g=g, hb=hb, tb=tb, scale=float(scale),
            interpret=bool(_interpret_default() if interpret is None
                           else interpret))
        if ctx is not None:
            spec = P(b_ax, h_ax, None, None)
            local = jax.shard_map(
                local, mesh=mesh,
                in_specs=(spec, spec, spec, P(b_ax, None), P(b_ax)),
                out_specs=spec, check_vma=False)
        q4 = qblk.transpose(0, 2, 1, 3, 4).reshape(b, hg, c * g, lanes)
        rows = local(q4, ck, cv, qpos, last)
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
