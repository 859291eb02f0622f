"""Pallas flash-attention kernels — the MXU-resident implementation of the
attention hot op (the prompt's "pallas kernels for the hot ops"; reference
analog: the cuDNN helpers of SURVEY.md §2.2, here behind the same
kind="attention" seam as kernels/flash_attention.py's jnp blockwise path).

Why Pallas here: the jnp blockwise path materializes each [T, KB] logits
block in HBM (bandwidth-bound); these kernels keep the q tile, the running
max/denominator and the accumulator in VMEM across the k/v stream, so the
only HBM traffic is q/k/v/o once each.

Layout (PR 30): the kernels read and write the layout the attention layer
has on both sides of them. Where heads pack — ``Dh`` divides 128 and the
(local) head count divides by ``g = 128 // Dh``, the rule of
``SelfAttentionLayer.heads_per_row`` — the [B, T, H, Dh] the helper seam
hands over is viewed as [B, T, H·Dh] (a free reshape: it is how
``_project_qkv`` made it and how ``_project_out`` wants it back), the grid
is (B, H/g, T/QB, T/KB) and every tile is [block, 128]: ``g`` heads side
by side in whole 128-lane rows. No transpose runs before or after any of
the three calls. Inside a grid step the ``g`` heads are a static loop; head
``j``'s scores come from ONE contraction over the whole row with the other
heads' lanes of q (of dO for dP) zeroed — exact zeros in the sum — so no
operand is sliced at a lane offset, and each head's products land in its
own lanes of a [block, 128] accumulator. Other widths (Dh 192, an odd head
count) fold to [BH, T, Dh] with a real transpose as before and run the
same kernels with ``g = 1``: :func:`_flash_attention_local` decides from
the shapes it is handed (inside a ``shard_map``, the local ones), and
records the plan it took (``nn.helpers.attention_plan_counts``).

Tiles: a grid tile [QB, KB] (1024 square where the caller names none) is
worked in row blocks of SUB = 128 rows, statically unrolled: a row block
computes its scores against the part of the tile's other axis it can see,
runs ONE update of the running softmax over all of it, and accumulates its
products. Under causal masking a grid tile is one of three classes: above
the diagonal — skipped, and its k/v (or q/dO) block is not fetched (the
index maps repeat the last visible block); wholly below it — no ``iota``,
compare or select at all; crossed by it — a row block leaves out what lies
beyond the diagonal, masks only the SUB-wide piece the diagonal crosses
and takes the rest as one unmasked piece (static when QB == KB: at T 1024
the kernels compute 0.5625 T² for the causal 0.5; a grid of one tile
compiles the crossed class alone). The sizes were read on
the v5 lite (PERF.md §6, PR 30; ``scripts/perf_flash_tiles.py``): what a
[q, k] score costs is its pass through the softmax on the vector unit, so
the area computed beyond the diagonal and the number of grid steps decide;
256-square grid tiles lose a factor of two to their 1024 steps a call. The
1/sqrt(Dh) scale is applied to the q operand where that is exact (a power
of two: Dh 16, 64, 256) or q is float32, else to the float32 scores; dQ
takes it on its [QB, 128] accumulator, never on a score tile. Operands stay
in the input precision (bf16 hits the full-rate MXU) with float32
accumulation and a float32 softmax. Masking uses the finite −1e30
replacement (identical degenerate-row semantics to the other two paths).

Backward is the FlashAttention-2 factorization: forward saves the per-row
logsumexp; dq accumulates over k blocks, dk/dv over q blocks, with the row
term delta = rowsum(dO·O) made by the dq kernel from its own dO and O
tiles and handed to the dk/dv kernel. The dk/dv kernel works on
TRANSPOSED score tiles (k·qᵀ: [k, q]), so that pᵀ·dO and dsᵀ·q are plain
products and no score tile is ever transposed, and the row terms
broadcast along sublanes. The row carriers (``lse``, ``delta``) travel as
[B, H/g, g, T] float32 — T in the lanes, dense in HBM; the kernels that
need them as columns turn a [g, QB] block once per q tile.

Key masks ([B, T], 1 real / 0 masked) are supported in-kernel (r4): each
grid step loads the [1, KB] mask tile for its k block — one tile serves
every head of the lane block — and REPLACES masked keys' logits by −1e30 in
``_scores`` — shared by forward and both backward kernels — so ragged
long-context batches keep the kernel's speed. A fully masked row degrades
to the same uniform average as the materialized and jnp blockwise paths
(arbitrary-but-finite; such rows are excluded by loss masks).

Each ``pallas_call`` has a ``name=`` (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``): the compiler makes it the kernel's instruction name, so
a device trace tells the three apart by name (``%flash_bwd_dq.3 = ...``)
and not by operand shapes."""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..nn.helpers import attention_spmd_context, note_attention_plan

NEG = -1e30
LANES = 128
#: grid tile edge where the caller names none, and rows of a row block
#: (PERF.md §6, PR 30: read on the v5 lite at T 1024 and 2048)
BLOCK = 1024
SUB = 128

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b


class Tile(NamedTuple):
    """What a grid step of the three kernels loads, computes and stores —
    static for a call, so it rides ``custom_vjp`` as a non-differentiable
    argument."""
    g: int           # heads side by side in a lane block
    dh: int          # head width
    hfold: int       # heads folded into the leading axis: the key mask's
    #                  row for leading index i is i // hfold
    causal: bool
    qb: int          # grid tile: q rows, k rows
    kb: int
    sub: int         # rows of a row block, statically unrolled in a step
    interpret: bool

    @property
    def w(self) -> int:
        return self.g * self.dh

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.dh)

    def scale_on_q(self, dtype) -> bool:
        """Scale q, not the scores: exact for a power of two, and as good
        as scaling the float32 scores when q itself is float32."""
        return math.frexp(self.scale)[0] == 0.5 or \
            jnp.dtype(dtype).itemsize >= 4


def heads_per_tile(h: int, dh: int) -> int:
    """``g`` heads to a 128-lane block when they fill it exactly and the
    head count divides by ``g`` (``SelfAttentionLayer.heads_per_row``'s rule,
    from the shapes of the call); 0 where heads do not pack."""
    if dh > LANES or LANES % dh:
        return 0
    g = LANES // dh
    return g if h % g == 0 else 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _head(x, j, tile, scale=None):
    """Head ``j``'s operand for a contraction over the whole lane block:
    the other heads' lanes zeroed (exact zeros in the sum)."""
    if tile.g > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.w), 1)
        own = (lane >= j * tile.dh) & (lane < (j + 1) * tile.dh)
        x = jnp.where(own, x, jnp.zeros_like(x))
    if scale is not None:
        x = x * jnp.asarray(scale, x.dtype)
    return x


def _own_lanes(parts, tile):
    """[rows, W] taking head ``j``'s lanes from ``parts[j]``."""
    out = parts[0]
    if tile.g > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.w), 1)
        for j in range(1, tile.g):
            out = jnp.where(lane >= j * tile.dh, parts[j], out)
    return out


def _to_cols(rows):
    """[1, n] row → [n, 128] with the value along the lanes (a column,
    broadcast): how the kernels that work on [q, k] tiles take the row
    carriers."""
    return jnp.broadcast_to(rows, (LANES, rows.shape[1])).T


def _by_tile_class(tile, qi, ki, body, one_tile):
    """Run ``body(crossed)`` for the grid tile's class under causal masking:
    wholly below the diagonal, or crossed by it; a tile above the diagonal
    runs nothing. ``one_tile``: the grid is a single tile (T == QB == KB),
    which the diagonal crosses — no other class is compiled in."""
    if not tile.causal or one_tile:
        body(tile.causal)
        return
    q_lo, k_lo = qi * tile.qb, ki * tile.kb
    interior = k_lo + tile.kb - 1 <= q_lo
    visible = k_lo <= q_lo + tile.qb - 1
    pl.when(interior)(functools.partial(body, False))
    pl.when(visible & jnp.logical_not(interior))(
        functools.partial(body, True))


def _split(refs, masked):
    """A kernel's refs: (q, k, v, the key mask's or None, the rest)."""
    return (*refs[:3], refs[3] if masked else None, refs[3 + masked:])


def _pieces(tile, crossed, qi, ki, start, of_q):
    """What a row block of ``sub`` rows starting at ``start`` computes of
    the grid tile's other axis (its keys; for the dk/dv kernel, whose rows
    are keys, its queries: ``of_q``): pieces (lo, hi, off). ``off`` None: no
    causal mask; else the piece keeps (query − key) >= off in its own
    indices. Off the diagonal: the whole axis, unmasked. With QB == KB a
    crossed tile sits on the diagonal, so the classes are static: the part
    beyond the diagonal is left out, only the ``sub``-wide piece the
    diagonal crosses is masked, the rest is one unmasked piece."""
    n = tile.qb if of_q else tile.kb
    if not crossed:
        return [(0, n, None)]
    if tile.qb != tile.kb:           # the diagonal's place is traced
        q0, k0 = (0, start) if of_q else (start, 0)
        return [(0, n, ki * tile.kb - qi * tile.qb + k0 - q0)]
    out = []
    for lo in range(0, n, tile.sub):
        q0, k0 = (lo, start) if of_q else (start, lo)
        off = k0 - q0
        if off > tile.sub - 1:
            continue
        if off > -(tile.sub - 1):
            out.append((lo, lo + tile.sub, off))
        elif out and out[-1][2] is None and out[-1][1] == lo:
            out[-1] = (out[-1][0], lo + tile.sub, None)
        else:
            out.append((lo, lo + tile.sub, None))
    return out


def _scores(qj, kc, tile, on_q, km, off, transposed=False):
    """One head's scaled logits of a piece, [q, k] (or their transpose
    [k, q]), with the −1e30 replacement masks — shared by the forward and
    both backward kernels so the masking can never diverge between them.
    ``km``: the key mask along the key axis ([1, k], transposed [k, 1]);
    REPLACES masked keys' logits, so a fully-masked row degrades to the
    same uniform average as the materialized and jnp blockwise paths."""
    s = _dot(kc, qj, _NT) if transposed else _dot(qj, kc, _NT)
    if not on_q:
        s = s * tile.scale
    if km is not None:
        s = jnp.where(km > 0, s, NEG)
    if off is not None:
        qax, kax = (1, 0) if transposed else (0, 1)
        diff = jax.lax.broadcasted_iota(jnp.int32, s.shape, qax) - \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, kax)
        s = jnp.where(diff >= off, s, NEG)
    return s


def _fwd_kernel(*refs, tile, masked, one_tile):
    q_ref, k_ref, v_ref, mask_ref, rest = _split(refs, masked)
    o_ref, lse_ref, m_s, l_s, acc_s = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    on_q = tile.scale_on_q(q_ref.dtype)
    sub = tile.sub

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def attend(crossed):
        for j in range(tile.g):
            qj = _head(q_ref[0], j, tile, tile.scale if on_q else None)
            for a in range(0, tile.qb, sub):
                rows = slice(a, a + sub)
                pieces = _pieces(tile, crossed, qi, ki, a, of_q=False)
                # every piece's scores first, then ONE update of the
                # running softmax for the row block: the [SUB, 1] row
                # terms cost a vector register a sublane group, as much
                # as a 128-wide piece of scores
                ss = [_scores(qj[rows], k_ref[0, lo:hi, :], tile, on_q,
                              mask_ref[0, :, lo:hi] if masked else None,
                              off) for lo, hi, off in pieces]
                m = m_s[j, rows, :][:, :1]             # [SUB, 1]
                m_new = m
                for s in ss:
                    m_new = jnp.maximum(m_new,
                                        jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                l = l_s[j, rows, :][:, :1] * alpha
                # head j's rows land in its own lanes; the others' lanes
                # of this accumulator are never read
                acc = acc_s[j, rows, :] * alpha        # [SUB, W]
                for s, (lo, hi, _) in zip(ss, pieces):
                    p = jnp.exp(s - m_new)
                    l = l + jnp.sum(p, axis=1, keepdims=True)
                    v = v_ref[0, lo:hi, :]
                    acc = acc + _dot(p.astype(v.dtype), v, _NN)
                m_s[j, rows, :] = jnp.broadcast_to(m_new, (sub, LANES))
                l_s[j, rows, :] = jnp.broadcast_to(l, (sub, LANES))
                acc_s[j, rows, :] = acc

    _by_tile_class(tile, qi, ki, attend, one_tile)

    @pl.when(ki == nk - 1)
    def _fin():
        outs = []
        for j in range(tile.g):
            l_fin = jnp.maximum(l_s[j], 1e-20)         # [QB, 128]
            outs.append(acc_s[j] / l_fin[:, :1])
            lse_ref[0, 0, j:j + 1, :] = (m_s[j] + jnp.log(l_fin)).T[:1, :]
        o_ref[0, ...] = _own_lanes(outs, tile).astype(o_ref.dtype)


def _dq_kernel(*refs, tile, masked, one_tile):
    q_ref, k_ref, v_ref, mask_ref, rest = _split(refs, masked)
    do_ref, o_ref, lse_ref, dq_ref, delta_ref, dq_s, col_s = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    on_q = tile.scale_on_q(q_ref.dtype)
    g, sub = tile.g, tile.sub

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)
        # the row term delta = rowsum(dO·O) of each head, made here from the
        # tiles (no pass over dO and O outside the kernels) and handed on,
        # as a row, to the dk/dv kernel
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for j in range(g):
            col_s[j] = _to_cols(lse_ref[0, 0, j:j + 1, :])
            delta = jnp.broadcast_to(jnp.sum(
                _head(prod, j, tile), axis=1, keepdims=True),
                (tile.qb, LANES))
            col_s[g + j] = delta
            delta_ref[0, 0, j:j + 1, :] = delta.T[:1, :]

    def accum(crossed):
        for j in range(g):
            qj = _head(q_ref[0], j, tile, tile.scale if on_q else None)
            doj = _head(do_ref[0], j, tile)
            for a in range(0, tile.qb, sub):
                rows = slice(a, a + sub)
                lse = col_s[j, rows, :][:, :1]         # [SUB, 1]
                delta = col_s[g + j, rows, :][:, :1]
                dq = dq_s[j, rows, :]
                for lo, hi, off in _pieces(tile, crossed, qi, ki, a,
                                           of_q=False):
                    k = k_ref[0, lo:hi, :]
                    s = _scores(qj[rows], k, tile, on_q,
                                mask_ref[0, :, lo:hi] if masked else None,
                                off)
                    p = jnp.exp(s - lse)
                    dp = _dot(doj[rows], v_ref[0, lo:hi, :], _NT)
                    ds = (p * (dp - delta)).astype(k.dtype)
                    dq = dq + _dot(ds, k, _NN)
                dq_s[j, rows, :] = dq

    _by_tile_class(tile, qi, ki, accum, one_tile)

    @pl.when(ki == nk - 1)
    def _fin():
        dq = _own_lanes([dq_s[j] for j in range(g)], tile)
        dq_ref[0, ...] = (dq * tile.scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, tile, masked, one_tile):
    q_ref, k_ref, v_ref, mask_ref, rest = _split(refs, masked)
    # kmc_s: the key mask of the k tile as a column, where there is one
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *kmc_s = rest
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    on_q = tile.scale_on_q(q_ref.dtype)
    sub = tile.sub

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)
        if masked:
            kmc_s[0][...] = _to_cols(mask_ref[0])

    def accum(crossed):
        for j in range(tile.g):
            # zeros outside head j's lanes of q and dO: the contractions
            # count head j alone, and pᵀ·dO, dsᵀ·q add exact zeros to the
            # other heads' lanes of the shared accumulators
            qj = _head(q_ref[0], j, tile, tile.scale if on_q else None)
            doj = _head(do_ref[0], j, tile)
            for b in range(0, tile.kb, sub):
                rows = slice(b, b + sub)
                k, v = k_ref[0, rows, :], v_ref[0, rows, :]
                km = kmc_s[0][rows, :][:, :1] if masked else None  # [SUB, 1]
                dk, dv = dk_s[rows, :], dv_s[rows, :]
                for lo, hi, off in _pieces(tile, crossed, qi, ki, b,
                                           of_q=True):
                    st = _scores(qj[lo:hi], k, tile, on_q, km, off,
                                 transposed=True)          # [SUB, q]
                    pt = jnp.exp(st - lse_ref[0, 0, j:j + 1, lo:hi])
                    dv = dv + _dot(pt.astype(doj.dtype), doj[lo:hi], _NN)
                    dpt = _dot(v, doj[lo:hi], _NT)
                    dst = (pt * (dpt - delta_ref[0, 0, j:j + 1, lo:hi])
                           ).astype(qj.dtype)
                    dk = dk + _dot(dst, qj[lo:hi], _NN)
                dk_s[rows, :] = dk
                dv_s[rows, :] = dv

    _by_tile_class(tile, qi, ki, accum, one_tile)

    @pl.when(qi == nq - 1)
    def _fin():
        # a scaled q carried the scale into dsᵀ·q already
        dk = dk_s[...] if on_q else dk_s[...] * tile.scale
        dk_ref[0, ...] = dk.astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_s[...].astype(dv_ref.dtype)


def _specs(tile, k_outer):
    """BlockSpecs of the [N0, T, L] operands (blocked (1, block, W) at lane
    block i1), of the [B, 1, T] key mask and of the [N0, L/W, g, T] row
    carriers, for the grid (i0, i1, qi, ki) — ``k_outer``: (i0, i1, ki, qi),
    the dk/dv kernel's. Under causal masking a skipped tile's moving block
    repeats the nearest visible one, so nothing is fetched for it."""
    qb, kb = tile.qb, tile.kb

    def at(fn):
        def index_map(i0, i1, a, b):
            qi, ki = (b, a) if k_outer else (a, b)
            if tile.causal and k_outer:
                qi = jnp.maximum(qi, ki * kb // qb)
            elif tile.causal:
                ki = jnp.minimum(ki, (qi * qb + qb - 1) // kb)
            return fn(i0, i1, qi, ki)
        return index_map
    return {
        "q": pl.BlockSpec((1, qb, tile.w),
                          at(lambda i0, i1, qi, ki: (i0, qi, i1))),
        "k": pl.BlockSpec((1, kb, tile.w),
                          at(lambda i0, i1, qi, ki: (i0, ki, i1))),
        "mask": pl.BlockSpec((1, 1, kb), at(
            lambda i0, i1, qi, ki: (i0 // tile.hfold, 0, ki))),
        "row": pl.BlockSpec((1, 1, tile.g, qb),
                            at(lambda i0, i1, qi, ki: (i0, i1, 0, qi))),
    }


def _mosaic(kernel, name, tile, k_outer, q, k, v, mask, rest, outs,
            scratch):
    """One Mosaic call over the grid (N0, L/W, q tiles, k tiles) — the last
    two swapped for ``k_outer``. Operands: q, k, v, the key mask where
    there is one, then ``rest`` (q-shaped arrays and row carriers);
    ``outs`` names each result's spec (``"q"`` / ``"k"``: q-shaped;
    ``"row"``: a row carrier); ``scratch``: float32 VMEM shapes."""
    n0, t, lanes = q.shape
    n1 = lanes // tile.w
    sp = _specs(tile, k_outer)
    in_specs, operands = [sp["q"], sp["k"], sp["k"]], [q, k, v]
    if mask is not None:
        # [B, 1, T]: the middle singleton keeps the TPU block-shape rule
        # happy for any B
        in_specs.append(sp["mask"])
        operands.append(mask[:, None, :])
    in_specs += [sp["q"] if x.ndim == 3 else sp["row"] for x in rest]
    steps = (t // tile.qb, t // tile.kb)
    return pl.pallas_call(
        functools.partial(kernel, tile=tile, masked=mask is not None,
                          one_tile=steps == (1, 1)),
        name=name,
        grid=(n0, n1) + (steps[::-1] if k_outer else steps),
        interpret=tile.interpret,
        in_specs=in_specs,
        out_specs=[sp[o] for o in outs],
        out_shape=[jax.ShapeDtypeStruct((n0, n1, tile.g, t), jnp.float32)
                   if o == "row" else jax.ShapeDtypeStruct(q.shape, q.dtype)
                   for o in outs],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
    )(*operands, *rest)


# jitted: the calls of a program that share shapes and tile (every layer of
# a model) trace the kernel body and lower it to Mosaic once, not once a
# layer — 36 layers of a 1024-token admission program lowered 18 s longer
# without it (PERF.md §6, PR 30)
@functools.partial(jax.jit, static_argnames=("tile",))
def _fwd_call(q, k, v, mask, tile):
    """q, k, v [N0, T, L] (L = lane blocks · W) → (o [N0, T, L],
    lse [N0, L/W, g, T] float32). ``mask``: optional [B, T] key mask
    (1 real / 0 masked), B = N0 / hfold."""
    rows = (tile.g, tile.qb, LANES)            # m, l: a column, broadcast
    return _mosaic(_fwd_kernel, "flash_fwd", tile, False, q, k, v, mask, (),
                   ("q", "row"), [rows, rows, (tile.g, tile.qb, tile.w)])


@functools.partial(jax.jit, static_argnames=("tile",))
def _bwd_calls(q, k, v, mask, o, lse, do, tile):
    """(dq, dk, dv), each [N0, T, L]."""
    dq, delta = _mosaic(
        _dq_kernel, "flash_bwd_dq", tile, False, q, k, v, mask, (do, o, lse),
        ("q", "row"),
        [(tile.g, tile.qb, tile.w), (2 * tile.g, tile.qb, LANES)])
    # dk/dv: k blocks outer, q blocks inner accumulate
    scratch = [(tile.kb, tile.w), (tile.kb, tile.w)]
    if mask is not None:
        scratch.append((tile.kb, LANES))      # the key mask as a column
    dk, dv = _mosaic(_dkv_kernel, "flash_bwd_dkv", tile, True, q, k, v, mask,
                     (do, lse, delta), ("k", "k"), scratch)
    return dq, dk, dv


# the key mask is a regular (non-differentiated) tensor input, or None —
# custom_vjp can't mark array args nondiff, so the bwd returns a zero
# cotangent for it
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, mask, tile):
    return _fwd_call(q, k, v, mask, tile)[0]


def _flash_fwd(q, k, v, mask, tile):
    o, lse = _fwd_call(q, k, v, mask, tile)
    return o, (q, k, v, mask, o, lse)


def _flash_bwd(tile, res, do):
    q, k, v, mask, o, lse = res
    dq, dk, dv = _bwd_calls(q, k, v, mask, o, lse, do, tile)
    return dq, dk, dv, None if mask is None else jnp.zeros_like(mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


def make_tile(g, dh, hfold, causal, qb, kb, interpret) -> Tile:
    # a lane block wider than 128 (Dh 192) read faster in 256-row blocks
    sub = SUB if g * dh <= LANES else 2 * SUB
    if qb % sub or kb % sub:
        sub = math.gcd(qb, kb)
    return Tile(g, dh, hfold, bool(causal), qb, kb, sub, bool(interpret))


def _flash_fwd_impl(q3, k3, v3, mask2, h, causal, qb, kb, interpret):
    """Folded entry point (the ring's, parallel/sequence.py): q3/k3/v3
    [BH, T, D] → (o [BH, T, D], lse [BH, 1, 1, T]). ``mask2``: optional
    [B, T] key mask; ``h`` the head count, mapping folded index bh → batch
    row bh // h."""
    return _fwd_call(q3, k3, v3, mask2, make_tile(
        1, q3.shape[-1], h, causal, qb, kb, interpret))


def _flash_bwd_impl(q3, k3, v3, mask2, h, o, lse, do, causal, qb, kb,
                    interpret):
    """Folded backward; ``lse`` is the row carrier [BH, 1, 1, T]."""
    return _bwd_calls(q3, k3, v3, mask2, o, lse, do, make_tile(
        1, q3.shape[-1], h, causal, qb, kb, interpret))


def _interpret_default():
    """Whether to run the kernels in Pallas interpret mode: iff the
    DEFAULT backend is the CPU (which has no Mosaic), so no accelerator
    backend can ever run a kernel interpreted by accident of its name.
    The documented contract: tracing for a non-default backend (e.g.
    ``jit(..., backend='cpu')`` on a TPU host) must pass ``interpret=``
    explicitly, since tracers carry no device placement to derive the
    lowering platform from."""
    return jax.default_backend() == "cpu"


def on_device_blocks(local, q, k, v, key_mask):
    """``local(q, k, v, key_mask)`` — [B, T, H, D] attention through Mosaic
    kernels, independent per batch row and head — under whatever jit is
    being traced. Mosaic refuses to lower inside a jit that XLA partitions
    over several devices ("Mosaic kernels cannot be automatically
    partitioned"; first met by chip_smoke.py on a 2x2 v5e — the CPU's
    interpret mode lowers to plain HLO and never notices), so where the
    caller declared its mesh (nn.helpers.attention_spmd) the kernels run in
    a ``shard_map`` over it, each device on its own (batch, head) block. An
    axis that does not divide the dim (an admission batch smaller than the
    data axis) is left out: those devices repeat the rows instead."""
    ctx = attention_spmd_context()
    if ctx is None:
        return local(q, k, v, key_mask)
    mesh, b_ax, h_ax = ctx
    fits = lambda ax, n: ax if ax in mesh.shape and n % mesh.shape[ax] == 0 \
        else None
    b_ax, h_ax = fits(b_ax, q.shape[0]), fits(h_ax, q.shape[2])
    qkv = P(b_ax, None, h_ax, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv,
                  None if key_mask is None else P(b_ax, None)),
        out_specs=qkv, check_vma=False)(q, k, v, key_mask)


def pallas_flash_attention(q, k, v, causal: bool = False,
                           q_block=None, k_block=None,
                           interpret=None, key_mask=None):
    """[B, T, H, D] attention via the Pallas kernels.

    ``q_block`` / ``k_block``: the grid tile's q and k rows; None takes
    ``BLOCK`` (or T where T is shorter).

    ``key_mask`` [B, T] (1 real / 0 masked): masked keys' logits are
    replaced by −1e30 INSIDE the kernels (a [1, KB] mask tile per block),
    so ragged long-context batches keep the kernel speed instead of
    dropping to the jnp blockwise path.

    Non-divisible T: with a mask (or non-causal, where an all-ones mask is
    synthesized), q/k/v right-pad to the block multiple with the padded
    keys masked out and the result sliced back; unmasked causal inputs pad
    without a mask (padded keys sit strictly in the future of every real
    query, so real rows are untouched).

    ``interpret``: None derives Pallas interpret mode from the DEFAULT
    backend; pass True/False explicitly when tracing for a non-default
    backend (see :func:`_interpret_default`)."""
    if interpret is None:
        interpret = _interpret_default()
    return on_device_blocks(
        lambda q, k, v, m: _flash_attention_local(
            q, k, v, causal, q_block or BLOCK, k_block or BLOCK,
            bool(interpret), m),
        q, k, v, key_mask)


def _flash_attention_local(q, k, v, causal, q_block, k_block, interpret,
                           key_mask):
    b, t, h, d = q.shape
    qb = min(q_block, t)
    kb = min(k_block, t)
    pad = max((-t) % qb, (-t) % kb)
    if pad:
        if key_mask is None and not causal:
            # padded keys are visible to real queries non-causally; mask
            # them out explicitly
            key_mask = jnp.ones((b, t), jnp.float32)
        padded = [jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                  for x in (q, k, v)]
        km = None if key_mask is None else \
            jnp.pad(key_mask.astype(jnp.float32), ((0, 0), (0, pad)))
        return _flash_attention_local(padded[0], padded[1], padded[2],
                                      causal, q_block, k_block, interpret,
                                      km)[:, :t]
    mask = None if key_mask is None else key_mask.astype(jnp.float32)
    g = heads_per_tile(h, d)
    if g:
        # the layer's own layout: [B, T, H·Dh], a free reshape both ways
        note_attention_plan("packed", g=g, qb=qb, kb=kb)
        tile = make_tile(g, d, 1, causal, qb, kb, interpret)
        pack = lambda x: x.reshape(b, t, h * d)
        return _flash(pack(q), pack(k), pack(v), mask, tile) \
            .reshape(b, t, h, d)
    note_attention_plan("folded", g=1, qb=qb, kb=kb)
    tile = make_tile(1, d, h, causal, qb, kb, interpret)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    out3 = _flash(fold(q), fold(k), fold(v), mask, tile)
    return out3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def make_pallas_flash_helper(min_seq_len: int = 1024,
                             q_block=None, k_block=None,
                             interpret=None, short_t: bool = True):
    """Helper: Pallas kernels for every long sequence — key masks ride
    into the kernels as [1, KB] tiles (r4; the r3 helper dropped masked
    long-context to the jnp blockwise path and with it the kernels' gain
    on ragged batches). Below min_seq_len, tile-aligned 256 ≤ T ≤ 512
    takes the whole-block short-T kernel pair (kernels/pallas_shortseq.py;
    its gain on the T=512 flagship LM was read on an earlier installation,
    BASELINE.md r5, and not since), gated on known-good shapes (D % 8 == 0,
    float dtypes); other short shapes keep the materialized path. The
    gate decides by SHAPE only:
    an error from a kernel the gate admitted propagates to the caller —
    it must never turn silently into the materialized path."""
    def helper(conf, q, k, v, mask):
        t = q.shape[1]
        if t < min_seq_len:
            from .pallas_shortseq import MAX_T, short_attention
            # the short-T route is DEFAULT-on, so it only takes shapes the
            # kernel is known good for: 128-lane-friendly head dims and
            # float dtypes (Mosaic may fail to lower odd D / exotic dtypes
            # — the failure mode the 4-D-native rejection documents);
            # everything else declines to the materialized safety net
            if short_t and 256 <= t <= MAX_T and t % 128 == 0 and \
                    q.shape[-1] % 8 == 0 and \
                    jnp.issubdtype(q.dtype, jnp.floating):
                note_attention_plan("short")
                return short_attention(q, k, v, causal=conf.causal,
                                       key_mask=mask, interpret=interpret)
            return None                      # tiny: materialized path wins
        return pallas_flash_attention(q, k, v, causal=conf.causal,
                                      q_block=q_block, k_block=k_block,
                                      interpret=interpret, key_mask=mask)
    return helper


def register_pallas_flash_attention(min_seq_len: int = 1024,
                                    q_block=None, k_block=None,
                                    platforms=("tpu", "cpu"),
                                    interpret=None,
                                    _default: bool = False) -> None:
    from ..nn.helpers import enable_helper, register_helper
    register_helper("attention",
                    make_pallas_flash_helper(min_seq_len, q_block, k_block,
                                             interpret=interpret),
                    platforms, _default=_default)
    enable_helper("attention")


def register_default() -> None:
    """Lazy-discovery entry point (nn/helpers._DEFAULT_PROVIDERS). TPU
    only: on CPU the kernels run in Pallas INTERPRET mode — orders
    of magnitude slower than the XLA materialized path — so CPU gets flash
    only by explicit registration (tests do exactly that)."""
    register_pallas_flash_attention(platforms=("tpu",),
                                    _default=True)
