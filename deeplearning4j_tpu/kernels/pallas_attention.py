"""Pallas flash-attention kernel — the MXU-resident implementation of the
attention hot op (the prompt's "pallas kernels for the hot ops"; reference
analog: the cuDNN helpers of SURVEY.md §2.2, here behind the same
kind="attention" seam as kernels/flash_attention.py's jnp blockwise path).

Why Pallas here: the jnp blockwise path materializes each [T, KB] logits
block in HBM (measured 5-7 TF/s at LM shapes — bandwidth-bound); this
kernel keeps the q tile, running max/denominator and the accumulator in
VMEM across the k/v stream, so the only HBM traffic is q/k/v/o once each.

Layout: [B, T, H, D] folds to [BH, T, D]; grid (BH, T/QB, T/KB) with the
k dimension innermost ("arbitrary") so VMEM scratch carries the streaming
softmax across k blocks. Causal masking uses the finite −1e30 replacement
(identical degenerate-row semantics to the other two paths). Backward is
the FlashAttention-2 factorization: forward saves the per-row logsumexp;
dq accumulates over k blocks, dk/dv over q blocks, with the row term
delta = rowsum(dO·O) computed outside.

Key masks ([B, T], 1 real / 0 masked) are supported in-kernel (r4): each
grid step loads the [1, KB] mask tile for its k block and REPLACES masked
keys' logits by −1e30 in ``_scores`` — shared by forward and both backward
kernels — so ragged long-context batches keep the kernel's speed. A fully
masked row degrades to the same uniform average as the materialized and
jnp blockwise paths (arbitrary-but-finite; such rows are excluded by loss
masks).

Each ``pallas_call`` has a ``name=`` (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``): the compiler makes it the kernel's instruction name, so
a device trace tells the three apart by name (``%flash_bwd_dq.3 = ...``)
and not by operand shapes."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..nn.helpers import attention_spmd_context

NEG = -1e30
# lse/delta row-scalar carriers travel as [BH, T, ROWW] (ROWW=8 keeps the
# block 2-D-tileable while costing 1/16 the footprint of a 128-lane row)
ROWW = 8


def _scores(q_ref, k_ref, qi, ki, qb, kb, causal, scale, mask_ref=None):
    """Scaled q·kᵀ block with the causal −1e30 replacement mask — shared by
    the forward and both backward kernels so the masking can never
    diverge between them. ``mask_ref`` (a [1, KB] block of the [B, T] key
    mask) REPLACES masked keys' logits by −1e30, so a fully-masked row
    degrades to the same uniform average as the materialized and jnp
    blockwise paths."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask_ref is not None:
        # mask block is [1, 1, KB] (of the [B, 1, T] carrier — the middle
        # singleton keeps the TPU block-shape rule happy for any B)
        s = jnp.where(mask_ref[0, 0][None, :] > 0, s, NEG)
    if causal:
        qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        s = jnp.where(qpos >= kpos, s, NEG)
    return s


def _fwd_kernel(*refs, causal, scale, kb, qb, masked=False):
    if masked:
        (q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
         m_s, l_s, acc_s) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
        mask_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # under causal masking, blocks strictly in the future contribute
    # nothing — skip their compute entirely (~2x on long sequences)
    visible = (ki * kb <= qi * qb + qb - 1) if causal else True

    @pl.when(visible)
    def _attend():
        # dots run at the INPUT precision (bf16 hits the full-rate MXU)
        # with f32 accumulation; only the softmax math is f32
        s = _scores(q_ref, k_ref, qi, ki, qb, kb, causal, scale, mask_ref)

        m_prev = m_s[:, :1]                        # [QB, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)            # [QB, 1]
        p = jnp.exp(s - m_new)                     # [QB, KB]
        l_new = l_s[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]                               # [KB, D]
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l_fin = jnp.maximum(l_s[:, :1], 1e-20)
        o_ref[0, ...] = (acc_s[...] / l_fin).astype(o_ref.dtype)
        lse_ref[0, ...] = (m_s[:, :ROWW] +
                           jnp.log(l_fin)).astype(lse_ref.dtype)


def _dq_kernel(*refs, causal, scale, kb, qb, masked=False):
    if masked:
        (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_s) = refs
        mask_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    visible = (ki * kb <= qi * qb + qb - 1) if causal else True

    @pl.when(visible)
    def _accum():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                    # [QB, 1]
        delta = delta_ref[0][:, :1]                # [QB, 1]
        s = _scores(q_ref, k_ref, qi, ki, qb, kb, causal, scale, mask_ref)
        p = jnp.exp(s - lse)                       # [QB, KB]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_s[...] = dq_s[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, ...] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, causal, scale, kb, qb, masked=False):
    if masked:
        (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_s, dv_s) = refs
        mask_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    visible = (qi * qb + qb - 1 >= ki * kb) if causal else True

    @pl.when(visible)
    def _accum():
        q = q_ref[0]                               # [QB, D]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = _scores(q_ref, k_ref, qi, ki, qb, kb, causal, scale, mask_ref)
        p = jnp.exp(s - lse)                       # [QB, KB]
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0]
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0, ...] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_s[...].astype(dv_ref.dtype)


def _specs(qb_or_kb, d, which):
    """BlockSpec for [BH, T, D] tensors blocked on (1, block, D)."""
    if which == "q":
        return pl.BlockSpec((1, qb_or_kb, d), lambda bh, qi, ki: (bh, qi, 0))
    return pl.BlockSpec((1, qb_or_kb, d), lambda bh, qi, ki: (bh, ki, 0))


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, qb, kb, interpret):
    o, _ = _flash_fwd_impl(q3, k3, v3, None, 1, causal, qb, kb, interpret)
    return o


def _flash_fwd_impl(q3, k3, v3, mask2, h, causal, qb, kb, interpret):
    """``mask2``: optional [B, T] key mask (1 real / 0 masked); ``h`` is the
    head count, mapping folded index bh → batch row bh // h for the mask's
    block index."""
    bh, t, d = q3.shape
    scale = float(1.0 / np.sqrt(d))
    grid = (bh, t // qb, t // kb)
    masked = mask2 is not None
    kern = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                             kb=kb, qb=qb, masked=masked)
    in_specs = [_specs(qb, d, "q"), _specs(kb, d, "k"), _specs(kb, d, "k")]
    operands = [q3, k3, v3]
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, kb),
                                     lambda bhi, qi, ki: (bhi // h, 0, ki)))
        operands.append(mask2[:, None, :])
    o, lse = pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        interpret=interpret,
        in_specs=in_specs,
        out_specs=[_specs(qb, d, "q"),
                   pl.BlockSpec((1, qb, ROWW), lambda bh, qi, ki:
                                (bh, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, t, ROWW), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands)
    return o, lse


def _flash_fwd(q3, k3, v3, causal, qb, kb, interpret):
    o, lse = _flash_fwd_impl(q3, k3, v3, None, 1, causal, qb, kb, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd_impl(q3, k3, v3, mask2, h, o, lse, do, causal, qb, kb,
                    interpret, delta3=None):
    """``delta3``: optional precomputed [BH, T, ROWW] row term
    rowsum(dO·O) — loop-invariant callers (the ring backward, which calls
    this once per ring step) hoist it instead of recomputing n times."""
    bh, t, d = q3.shape
    scale = float(1.0 / np.sqrt(d))
    masked = mask2 is not None
    if delta3 is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                              # [BH, T]
        delta3 = jnp.broadcast_to(delta[..., None], (bh, t, ROWW))
    row = pl.BlockSpec((1, qb, ROWW), lambda bhi, qi, ki: (bhi, qi, 0))
    common = [_specs(qb, d, "q"), _specs(kb, d, "k"), _specs(kb, d, "k")]
    dq_operands = [q3, k3, v3]
    if masked:
        common.append(pl.BlockSpec((1, 1, kb),
                                   lambda bhi, qi, ki: (bhi // h, 0, ki)))
        dq_operands.append(mask2[:, None, :])
    common += [_specs(qb, d, "q"), row, row]
    dq_operands += [do, lse, delta3]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          kb=kb, qb=qb, masked=masked),
        name="flash_bwd_dq",
        grid=(bh, t // qb, t // kb),
        interpret=interpret,
        in_specs=common,
        out_specs=_specs(qb, d, "q"),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((qb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*dq_operands)

    # dk/dv: k blocks outer ("parallel"), q blocks inner accumulate
    def kspec(block, which):
        if which == "k":
            return pl.BlockSpec((1, block, d),
                               lambda bhi, ki, qi: (bhi, ki, 0))
        return pl.BlockSpec((1, block, d),
                            lambda bhi, ki, qi: (bhi, qi, 0))
    rowq = pl.BlockSpec((1, qb, ROWW), lambda bhi, ki, qi: (bhi, qi, 0))
    kv_specs = [kspec(qb, "q"), kspec(kb, "k"), kspec(kb, "k")]
    kv_operands = [q3, k3, v3]
    if masked:
        kv_specs.append(pl.BlockSpec((1, 1, kb),
                                     lambda bhi, ki, qi: (bhi // h, 0, ki)))
        kv_operands.append(mask2[:, None, :])
    kv_specs += [kspec(qb, "q"), rowq, rowq]
    kv_operands += [do, lse, delta3]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          kb=kb, qb=qb, masked=masked),
        name="flash_bwd_dkv",
        grid=(bh, t // kb, t // qb),
        interpret=interpret,
        in_specs=kv_specs,
        out_specs=[kspec(kb, "k"), kspec(kb, "k")],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), q3.dtype)],
        scratch_shapes=[pltpu.VMEM((kb, d), jnp.float32),
                        pltpu.VMEM((kb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*kv_operands)
    return dq, dk, dv


def _flash_bwd(causal, qb, kb, interpret, res, do):
    q3, k3, v3, o, lse = res
    return _flash_bwd_impl(q3, k3, v3, None, 1, o, lse, do, causal, qb, kb,
                           interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---- masked variant: the key mask is a regular (non-differentiated) tensor
# input — custom_vjp can't mark array args nondiff, so the bwd returns a
# zero cotangent for it
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_masked(q3, k3, v3, mask2, h, causal, qb, kb, interpret):
    o, _ = _flash_fwd_impl(q3, k3, v3, mask2, h, causal, qb, kb, interpret)
    return o


def _flash_masked_fwd(q3, k3, v3, mask2, h, causal, qb, kb, interpret):
    o, lse = _flash_fwd_impl(q3, k3, v3, mask2, h, causal, qb, kb, interpret)
    return o, (q3, k3, v3, mask2, o, lse)


def _flash_masked_bwd(h, causal, qb, kb, interpret, res, do):
    q3, k3, v3, mask2, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q3, k3, v3, mask2, h, o, lse, do, causal,
                                 qb, kb, interpret)
    return dq, dk, dv, jnp.zeros_like(mask2)


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def pallas_flash_attention(q, k, v, causal: bool = False,
                           q_block: int = 512, k_block: int = 512,
                           interpret=None, key_mask=None):
    """[B, T, H, D] attention via the Pallas kernels.

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
            q, k, v, causal, q_block, k_block, bool(interpret), m),
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
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    if key_mask is not None:
        out3 = _flash_masked(fold(q), fold(k), fold(v),
                             key_mask.astype(jnp.float32), h, causal,
                             qb, kb, interpret)
    else:
        out3 = _flash(fold(q), fold(k), fold(v), causal, qb, kb, interpret)
    return out3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def make_pallas_flash_helper(min_seq_len: int = 1024,
                             q_block: int = 512, k_block: int = 512,
                             interpret=None, short_t: bool = True):
    """Helper: Pallas kernels for every long sequence — key masks ride
    into the kernels as [1, KB] tiles (r4; the r3 helper dropped masked
    long-context to the jnp blockwise path and lost the 2-2.8x win on
    ragged batches). Below min_seq_len, tile-aligned 256 ≤ T ≤ 512 takes
    the whole-block short-T kernel pair (kernels/pallas_shortseq.py —
    +10% measured on the T=512 flagship LM in-graph, BASELINE.md r5),
    gated on known-good shapes (D % 8 == 0, float dtypes); other short
    shapes keep the materialized path. The gate decides by SHAPE only:
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
                return short_attention(q, k, v, causal=conf.causal,
                                       key_mask=mask, interpret=interpret)
            return None                      # tiny: materialized path wins
        return pallas_flash_attention(q, k, v, causal=conf.causal,
                                      q_block=q_block, k_block=k_block,
                                      interpret=interpret, key_mask=mask)
    return helper


def register_pallas_flash_attention(min_seq_len: int = 1024,
                                    q_block: int = 512, k_block: int = 512,
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
