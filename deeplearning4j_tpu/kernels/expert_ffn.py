"""Pallas kernel for drop-free routed experts: every expert a gated FFN
``(silu(x Wg) * (x Wu)) Wd``, each token computed by the experts its router
chose and by no other, whatever the load — no capacity, no dropped token.

The rows (one per token-expert assignment) are laid out expert by expert,
each expert's rows padded to a whole number of ``tm``-row tiles, so a tile
belongs to ONE expert and the kernel is a plain tiled FFN whose weight
blocks are picked per tile from a scalar-prefetched table
(``tile_expert``). An expert nobody chose has no tile, and its weights are
never read: a decode step touches the experts its lanes hit, not all that
are held. Tiles past the last used one (the static grid is sized for the
worst case, every assignment landing here and every expert one row over a
tile) are skipped: they compute nothing and their block indices repeat the
last used tile's, so nothing is fetched or written back for them.

Grid ``(tiles, h / th)``: the hidden width is walked in ``th``-wide chunks
(the inner, "arbitrary" axis) with an f32 accumulator in VMEM, so the
weight blocks are ``[d, th]`` / ``[th, d]``. The tiles follow the shapes
(:func:`hidden_chunk`, :func:`max_tile_rows`): the three weight blocks,
double-buffered, take at most ``WEIGHT_BYTES`` of the ``VMEM_BYTES`` a v5e
kernel gets by default, and the row tiles (input and output double-buffered,
the f32 accumulator) what is left beside ``SPARE_BYTES`` for the body's own
temporaries. The ``pallas_call`` is named ``moe_expert_ffn``: the compiler
makes that the instruction's name, which a device trace shows.

:func:`routed_experts` is the whole path around the kernel (sort, layout,
gather, kernel, weighted gather back) and what the "routed_experts" helper
kind registers for the TPU; the expert layer's built-in jnp path (dense over
the experts held) is the always-available fallback."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _interpret_default

#: rows of the smallest tile: one packed bf16 sublane group
MIN_TM = 16
MAX_TM = 256
KERNEL_NAME = "moe_expert_ffn"


#: the scoped VMEM a v5e kernel gets by default; of it, what the three
#: weight blocks may take double-buffered, and what is left to the body
VMEM_BYTES = 16 * 2 ** 20
WEIGHT_BYTES = 10 * 2 ** 20
SPARE_BYTES = 2 * 2 ** 20


def _weight_blocks(d: int, th: int, itemsize: int) -> int:
    """Bytes of ``[d, th]``, ``[d, th]`` and ``[th, d]``, double-buffered."""
    return 6 * d * th * itemsize


def hidden_chunk(d: int, h: int, itemsize: int = 2) -> int:
    """``th``: the widest of 512 / 256 / 128 that divides ``h`` and whose
    weight blocks fit ``WEIGHT_BYTES`` (256 at d 2048 and 128 at d 6144 for
    2-byte operands, 128 at d 2048 for 4-byte ones); ``h`` whole where none
    divides it (a test's size)."""
    for th in (512, 256, 128):
        if h % th == 0 and _weight_blocks(d, th, itemsize) <= WEIGHT_BYTES:
            return th
    return h


def max_tile_rows(d: int, th: int, itemsize: int = 2) -> int:
    """The largest ``tm`` (a power of two in [16, 256]) whose row tiles —
    input and output double-buffered, the f32 accumulator — fit beside the
    weight blocks: 256 at d 2048 for 2-byte operands (128 for 4-byte ones),
    64 at d 6144."""
    left = VMEM_BYTES - SPARE_BYTES - _weight_blocks(d, th, itemsize)
    tm = MAX_TM
    while tm > MIN_TM and tm * d * (4 * itemsize + 4) > left:
        tm //= 2
    return tm


def tile_rows(assignments: int, experts: int, cap: int = MAX_TM) -> int:
    """``tm`` for the ``assignments`` rows expected over ``experts``
    experts: twice the mean rows an expert gets, as a power of two in
    [16, ``cap``]. A tile costs one read of its expert's weights whatever
    its rows, so few rows per expert (decode) want the smallest tile and
    many (prefill) the largest the working set allows."""
    want = 2 * max(assignments // max(experts, 1), 1)
    tm = MIN_TM
    while tm < want and tm < cap:
        tm *= 2
    return tm


def _kernel(tile_expert, used, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc):
    del tile_expert
    t, c = pl.program_id(0), pl.program_id(1)

    @pl.when(t < used[0])
    def _tile():
        @pl.when(c == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        acc[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

        @pl.when(c == pl.num_programs(1) - 1)
        def _store():
            o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "th", "interpret"))
def expert_ffn_tiles(x_rows, tile_expert, used, wg, wu, wd, *, tm: int,
                     th: int, interpret: bool = False):
    """``x_rows`` [R, d] laid out in ``tm``-row tiles, tile ``t`` belonging
    to expert ``tile_expert[t]``; only the first ``used[0]`` tiles are
    computed. ``wg``/``wu`` [E, d, h], ``wd`` [E, h, d]. Returns [R, d] in
    ``x_rows``' type (rows of skipped tiles are not written)."""
    r, d = x_rows.shape
    h = wg.shape[2]
    tiles, chunks = r // tm, h // th

    def live(t, used):            # a skipped tile repeats the last used one
        return jnp.minimum(t, jnp.maximum(used[0] - 1, 0))

    def chunk(t, c, used):
        return jnp.where(t < used[0], c, chunks - 1)

    rows = lambda t, c, te, used: (live(t, used), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, chunks),
        in_specs=[
            pl.BlockSpec((tm, d), rows),
            pl.BlockSpec((None, d, th), lambda t, c, te, used:
                         (te[live(t, used)], 0, chunk(t, c, used))),
            pl.BlockSpec((None, d, th), lambda t, c, te, used:
                         (te[live(t, used)], 0, chunk(t, c, used))),
            pl.BlockSpec((None, th, d), lambda t, c, te, used:
                         (te[live(t, used)], chunk(t, c, used), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), rows),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)])
    return pl.pallas_call(
        _kernel,
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), x_rows.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(tile_expert, used, x_rows, wg, wu, wd)


def layout(local, experts: int, tm: int):
    """The tile layout of ``local`` [M] (each assignment's expert among the
    ``experts`` held, or ``experts`` for one held elsewhere): (``src`` [R]
    the assignment in each row, ``row`` [M] each assignment's row — R for
    one held elsewhere, ``tile_expert`` [R / tm], ``used`` [1])."""
    m = local.shape[0]
    tiles = -(-(m + experts * (tm - 1)) // tm)
    r = tiles * tm
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    key = local[order]
    sizes = jnp.zeros(experts + 1, jnp.int32).at[local].add(1)[:experts]
    first_row = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)[:-1]])
    n_tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(n_tiles)
    first_tile = tile_end - n_tiles
    held = key < experts
    e = jnp.minimum(key, experts - 1)
    dest = jnp.where(held, first_tile[e] * tm
                     + jnp.arange(m, dtype=jnp.int32) - first_row[e], r)
    src = jnp.zeros(r, jnp.int32).at[dest].set(order, mode="drop")
    row = jnp.zeros(m, jnp.int32).at[order].set(dest)
    used = tile_end[-1:]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(tiles, dtype=jnp.int32),
                         side="right"), experts - 1).astype(jnp.int32)
    return src, row, tile_expert, used


def routed_experts(x, idx, gates, wg, wu, wd, first_expert: int = 0,
                   routed_over: int = 0, interpret=None):
    """Σ_k gates[n, k] · E_{idx[n, k]}(x[n]) over the experts held
    (``first_expert`` .. ``first_expert + E``; a choice held elsewhere, or
    of a zero-compute expert, contributes nothing): x [N, d], idx [N, k]
    int32 over the router's width ``routed_over`` (0: the experts held are
    all there is), gates [N, k] f32. Returns [N, d] f32. The layout holds a
    row for every assignment, whatever lands here (no capacity, no dropped
    token); ``tm`` is sized for the share EXPECTED here, ``E /
    routed_over`` of them."""
    if interpret is None:
        interpret = _interpret_default()
    n, k = idx.shape
    experts, d, h = wg.shape
    local = idx.reshape(-1).astype(jnp.int32) - first_expert
    local = jnp.where((local >= 0) & (local < experts), local, experts)
    itemsize = max(jnp.dtype(x.dtype).itemsize, 2)
    th = hidden_chunk(d, h, itemsize)
    tm = tile_rows(n * k * experts // (routed_over or experts), experts,
                   max_tile_rows(d, th, itemsize))
    src, row, tile_expert, used = layout(local, experts, tm)
    rows = expert_ffn_tiles(x[src // k], tile_expert, used, wg, wu, wd,
                            tm=tm, th=th, interpret=bool(interpret))
    held = (row < rows.shape[0]).reshape(n, k)
    got = rows[jnp.minimum(row, rows.shape[0] - 1)].reshape(n, k, d)
    # a choice held elsewhere reads a row nobody wrote: drop it, not scale
    # it by zero (0 x NaN is NaN)
    got = jnp.where(held[:, :, None], got.astype(jnp.float32), 0.0)
    return jnp.einsum("nkd,nk->nd", got, gates.astype(jnp.float32))


def register_default() -> None:
    """Lazy-discovery entry point (nn/helpers._DEFAULT_PROVIDERS), TPU only:
    on the CPU the kernel would run interpreted, and the expert layer's
    dense jnp path is the faster one there."""
    from ..nn.helpers import enable_helper, register_helper
    register_helper("routed_experts", routed_experts, ("tpu",),
                    _default=True)
    enable_helper("routed_experts")
