"""Short-sequence Pallas attention — the pipelined T≤512 kernel pair.

Why a second kernel (r4 finding, BASELINE.md "attention disposition at
T=512"): at the flagship LM shape (B=32, H=12, T=512, D=64) the general
flash kernel has exactly ONE k block, so its streaming-softmax machinery
(m/l rescales, per-k-block grid steps) buys nothing while its per-grid-step
overhead and serialized per-head schedule hold it at ~27 TF/s — it only
ties the materialized XLA path's HBM-bound fusions (~20.2 ms of the
117.6 ms step). The bucket's floor is ~5 ms (q/k/v/o + grad traffic; the
FLOPs are <1 ms of MXU).

This kernel exploits what short T makes true:

- **whole-T blocks**: one [T, T] logits tile per head lives entirely in
  VMEM; plain (non-streaming) softmax — no m/l carry, no alpha rescales.
- **G heads per grid step**: the 1-D grid over folded B·H rows processes G
  heads per step, statically unrolled, so Mosaic has G independent
  MXU-matmul / VPU-softmax chains to interleave — the "multiple blocks in
  flight" the single-k-block general kernel cannot have.
- **constant-index mask fetch**: the additive causal mask ([T, T],
  0 / −1e30) is built ONCE outside by XLA and its BlockSpec index map is
  constant, so Pallas DMAs it into VMEM once and every grid step reuses
  it — the per-block iota/compare/select VPU passes of the general kernel
  disappear from the loop.
- **one fused backward kernel**: s and p are recomputed ONCE per head and
  all three gradients (dq, dk, dv) come out of the same kernel — the
  general pair (dq kernel + dkv kernel) recomputes s/p twice and pays two
  kernel launches.

Masking semantics are identical to kernels/pallas_attention.py (finite
−1e30 replacement; fully-masked rows degrade to the uniform average).
Reference analog: the cuDNN attention helper seam of SURVEY.md §2.2 —
this is the short-sequence specialization the flagship trains on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _interpret_default, on_device_blocks

NEG = -1e30
ROWW = 8          # row-scalar carriers travel as [BH, T, ROWW]

#: largest T the whole-block kernel accepts (one [T, T] f32 logits tile
#: per head must fit VMEM alongside its neighbors)
MAX_T = 512


def _head_scores(q, k, scale, amask, kmask):
    """[T, T] f32 scaled logits for one head with masks applied — additive
    causal mask (0 / −1e30, VMEM-resident) and the −1e30 key-mask
    replacement, matching pallas_attention._scores semantics."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if amask is not None:
        s = s + amask
    if kmask is not None:
        s = jnp.where(kmask > 0, s, NEG)
    return s


def _short_fwd_kernel_batched(*refs, scale, causal, masked):
    """Batched-dot variant: the G heads ride one [G, T, T] dot_general
    chain (batch dim G) instead of G unrolled 2-D chains — bigger ops for
    Mosaic to schedule, one VPU pass per softmax stage."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    amask_ref = next(it) if causal else None
    kmask_ref = next(it) if masked else None
    o_ref, lse_ref = next(it), next(it)
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = s + amask_ref[...][None]
    if masked:
        s = jnp.where(kmask_ref[0, 0][None, None, :] > 0, s, NEG)
    m = jnp.max(s, axis=2, keepdims=True)                 # [G, T, 1]
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=2, keepdims=True), 1e-20)
    o = jax.lax.dot_general(p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[...] = (o / l).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l),
                                    lse_ref.shape).astype(lse_ref.dtype)


def _short_bwd_kernel_batched(*refs, scale, causal, masked):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    amask_ref = next(it) if causal else None
    kmask_ref = next(it) if masked else None
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = s + amask_ref[...][None]
    if masked:
        s = jnp.where(kmask_ref[0, 0][None, None, :] > 0, s, NEG)
    p = jnp.exp(s - lse_ref[...][:, :, :1])               # [G, Tq, Tk]
    dv_ref[...] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_ref[...][:, :, :1]) * scale).astype(q.dtype)
    dq_ref[...] = jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[...] = jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _short_fwd_kernel(*refs, scale, g_heads, causal, masked, q_split):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    amask_ref = next(it) if causal else None
    kmask_ref = next(it) if masked else None
    o_ref, lse_ref = next(it), next(it)
    kmask = kmask_ref[0, 0][None, :] if masked else None
    t = q_ref.shape[1]
    # causal q-splitting: q rows [lo, hi) only attend keys [0, hi) — the
    # strictly-future upper triangle is never computed (q_split=4 cuts
    # compute volume to 62.5% of the full square)
    nq = q_split if causal else 1
    qsb = t // nq
    for g in range(g_heads):
        for qi in range(nq):
            lo, hi = qi * qsb, (qi + 1) * qsb
            kend = hi if causal else t
            amask = amask_ref[lo:hi, :kend] if causal else None
            km = kmask[:, :kend] if masked else None
            s = _head_scores(q_ref[g, lo:hi], k_ref[g, :kend], scale,
                             amask, km)
            m = jnp.max(s, axis=1, keepdims=True)         # [qsb, 1]
            p = jnp.exp(s - m)
            l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-20)
            o = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[g, :kend],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            o_ref[g, lo:hi] = (o / l).astype(o_ref.dtype)
            lse_ref[g, lo:hi] = jnp.broadcast_to(
                m + jnp.log(l), (qsb, lse_ref.shape[2])).astype(
                    lse_ref.dtype)


def _short_bwd_kernel(*refs, scale, g_heads, causal, masked, q_split):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    amask_ref = next(it) if causal else None
    kmask_ref = next(it) if masked else None
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    dk_s, dv_s = refs[-2], refs[-1]
    kmask = kmask_ref[0, 0][None, :] if masked else None
    t = q_ref.shape[1]
    nq = q_split if causal else 1
    qsb = t // nq
    for g in range(g_heads):
        if nq > 1:
            dk_s[...] = jnp.zeros_like(dk_s)
            dv_s[...] = jnp.zeros_like(dv_s)
        for qi in range(nq):
            lo, hi = qi * qsb, (qi + 1) * qsb
            kend = hi if causal else t
            q, k = q_ref[g, lo:hi], k_ref[g, :kend]
            v, do = v_ref[g, :kend], do_ref[g, lo:hi]
            amask = amask_ref[lo:hi, :kend] if causal else None
            km = kmask[:, :kend] if masked else None
            s = _head_scores(q, k, scale, amask, km)
            p = jnp.exp(s - lse_ref[g, lo:hi][:, :1])     # [qsb, kend] f32
            dv = jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[g, lo:hi][:, :1]) * scale).astype(
                q.dtype)
            dq_ref[g, lo:hi] = jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dq_ref.dtype)
            dk = jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if nq == 1:
                dk_ref[g, ...] = dk.astype(dk_ref.dtype)
                dv_ref[g, ...] = dv.astype(dv_ref.dtype)
            else:
                dk_s[:kend] = dk_s[:kend] + dk
                dv_s[:kend] = dv_s[:kend] + dv
        if nq > 1:
            dk_ref[g, ...] = dk_s[...].astype(dk_ref.dtype)
            dv_ref[g, ...] = dv_s[...].astype(dv_ref.dtype)


def pick_g(bh: int, h: int, masked: bool, g_max: int = 8) -> int:
    """Heads per grid step: the largest divisor of BH ≤ g_max; the masked
    variant additionally needs every step's G heads inside ONE batch row
    (one [1, T] key-mask block per step), i.e. G | H."""
    cap = min(g_max, h if masked else bh)
    for g in range(cap, 0, -1):
        if bh % g == 0 and (not masked or h % g == 0):
            return g
    return 1


def _causal_amask(t: int) -> jnp.ndarray:
    """[T, T] additive causal mask, built by XLA outside the kernel (one
    iota fusion) and DMA'd into VMEM once thanks to its constant BlockSpec
    index map."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return jnp.where(qpos >= kpos, 0.0, NEG).astype(jnp.float32)


def _gspec(g, t, d):
    return pl.BlockSpec((g, t, d), lambda i: (i, 0, 0))


def _short_fwd_impl(q3, k3, v3, mask2, h, causal, g_heads, interpret,
                    q_split=1):
    bh, t, d = q3.shape
    scale = float(1.0 / np.sqrt(d))
    masked = mask2 is not None
    g = g_heads
    if q_split == -1:     # batched-dot variant (see the _batched kernels)
        kern = functools.partial(_short_fwd_kernel_batched, scale=scale,
                                 causal=causal, masked=masked)
    else:
        kern = functools.partial(_short_fwd_kernel, scale=scale, g_heads=g,
                                 causal=causal, masked=masked,
                                 q_split=q_split)
    in_specs = [_gspec(g, t, d)] * 3
    operands = [q3, k3, v3]
    if causal:
        in_specs.append(pl.BlockSpec((t, t), lambda i: (0, 0)))
        operands.append(_causal_amask(t))
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, t), lambda i: ((i * g) // h,
                                                           0, 0)))
        operands.append(mask2[:, None, :])
    o, lse = pl.pallas_call(
        kern,
        name="shortseq_fwd",
        grid=(bh // g,),
        interpret=interpret,
        in_specs=in_specs,
        out_specs=[_gspec(g, t, d),
                   pl.BlockSpec((g, t, ROWW), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, t, ROWW), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # "parallel": grid steps are independent (the constant-index
            # amask fetch has no cross-step ordering need), freeing Mosaic
            # to pipeline DMA against compute across steps
            dimension_semantics=("parallel",),
            # the default 16 MiB scoped-vmem limit rejects G>=8 at T=512;
            # v5e VMEM is far larger — let the G-unrolled double-buffered
            # blocks breathe
            vmem_limit_bytes=96 * 1024 * 1024),
    )(*operands)
    return o, lse


def _short_bwd_impl(q3, k3, v3, mask2, h, o, lse, do, causal, g_heads,
                    interpret, q_split=1):
    bh, t, d = q3.shape
    scale = float(1.0 / np.sqrt(d))
    masked = mask2 is not None
    g = g_heads
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta3 = jnp.broadcast_to(delta[..., None], (bh, t, ROWW))
    row = pl.BlockSpec((g, t, ROWW), lambda i: (i, 0, 0))
    in_specs = [_gspec(g, t, d)] * 3
    operands = [q3, k3, v3]
    if causal:
        in_specs.append(pl.BlockSpec((t, t), lambda i: (0, 0)))
        operands.append(_causal_amask(t))
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, t), lambda i: ((i * g) // h,
                                                           0, 0)))
        operands.append(mask2[:, None, :])
    in_specs += [_gspec(g, t, d), row, row]
    operands += [do, lse, delta3]
    if q_split == -1:
        kern = functools.partial(_short_bwd_kernel_batched, scale=scale,
                                 causal=causal, masked=masked)
        scratch = []
    else:
        kern = functools.partial(_short_bwd_kernel, scale=scale, g_heads=g,
                                 causal=causal, masked=masked,
                                 q_split=q_split)
        # dk/dv accumulators are only touched when q-splitting; don't
        # reserve VMEM on the default whole-block path
        nq_eff = q_split if causal else 1
        scratch = [pltpu.VMEM((t, d), jnp.float32),
                   pltpu.VMEM((t, d), jnp.float32)] if nq_eff > 1 else []
    dq, dk, dv = pl.pallas_call(
        kern,
        name="shortseq_bwd",
        grid=(bh // g,),
        interpret=interpret,
        in_specs=in_specs,
        out_specs=[_gspec(g, t, d)] * 3,
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype)] * 3,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=96 * 1024 * 1024),
    )(*operands)
    return dq, dk, dv


# ---- custom VJPs (unmasked / key-masked), mirroring pallas_attention ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _short(q3, k3, v3, causal, g_heads, interpret, q_split):
    o, _ = _short_fwd_impl(q3, k3, v3, None, 1, causal, g_heads, interpret,
                           q_split)
    return o


def _short_fwd(q3, k3, v3, causal, g_heads, interpret, q_split):
    o, lse = _short_fwd_impl(q3, k3, v3, None, 1, causal, g_heads, interpret,
                             q_split)
    return o, (q3, k3, v3, o, lse)


def _short_bwd(causal, g_heads, interpret, q_split, res, do):
    q3, k3, v3, o, lse = res
    return _short_bwd_impl(q3, k3, v3, None, 1, o, lse, do, causal,
                           g_heads, interpret, q_split)


_short.defvjp(_short_fwd, _short_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _short_masked(q3, k3, v3, mask2, h, causal, g_heads, interpret, q_split):
    o, _ = _short_fwd_impl(q3, k3, v3, mask2, h, causal, g_heads, interpret,
                           q_split)
    return o


def _short_masked_fwd(q3, k3, v3, mask2, h, causal, g_heads, interpret,
                      q_split):
    o, lse = _short_fwd_impl(q3, k3, v3, mask2, h, causal, g_heads,
                             interpret, q_split)
    return o, (q3, k3, v3, mask2, o, lse)


def _short_masked_bwd(h, causal, g_heads, interpret, q_split, res, do):
    q3, k3, v3, mask2, o, lse = res
    dq, dk, dv = _short_bwd_impl(q3, k3, v3, mask2, h, o, lse, do, causal,
                                 g_heads, interpret, q_split)
    return dq, dk, dv, jnp.zeros_like(mask2)


_short_masked.defvjp(_short_masked_fwd, _short_masked_bwd)


def short_attention(q, k, v, causal: bool = False, key_mask=None,
                    g_heads: int = 0, q_split: int = 0, interpret=None):
    """[B, T, H, D] attention via the whole-block short-T kernels
    (T ≤ MAX_T). ``g_heads``: heads per grid step (0 = auto; must divide
    B·H, and H too when key-masked); ``q_split``: causal q-block
    truncation factor (0 = auto = 1 — the truncation measured flat
    in-graph and slower standalone, so it stays opt-in; -1 selects the
    folded batched-dot kernels; ignored non-causally).

    Inputs fold to [B·H, T, D] around the kernels; the r5 profile showed
    these transposes cost ~9.7 ms/step of XLA copies at the flagship
    shape, and a 4-D-native variant ((1, T, G, D) blocks via index maps,
    no fold) was built and REJECTED: Mosaic cannot lower per-head [T, D]
    slices out of blocks whose minor dims are (H, D) — real-TPU compile
    fails with "infer-vector-layout: unsupported shape cast" (interpret
    mode passed, which is exactly why scripts/perf_kernel_checks.py
    exists). The attention math needs (T, D)-minor tiles, so the relayout
    must happen somewhere; XLA's explicit copies are that somewhere.
    Same −1e30 masking semantics as pallas_flash_attention."""
    if interpret is None:
        interpret = _interpret_default()
    return on_device_blocks(
        lambda q, k, v, m: _short_attention_local(
            q, k, v, causal, m, g_heads, q_split, bool(interpret)),
        q, k, v, key_mask)


def _short_attention_local(q, k, v, causal, key_mask, g_heads, q_split,
                           interpret):
    b, t, h, d = q.shape
    if t > MAX_T:
        raise ValueError(f"short_attention: T={t} > MAX_T={MAX_T}")
    g = g_heads or pick_g(b * h, h, key_mask is not None)
    if (b * h) % g:
        raise ValueError(f"g_heads={g} must divide B*H={b * h}")
    if key_mask is not None and h % g:
        # one key-mask block per grid step ⇒ a step's G heads must sit in
        # one batch row
        raise ValueError(f"masked short attention needs g_heads | H "
                         f"({g} vs {h})")
    if q_split == -1:
        qs = -1               # batched-dot kernels (folded path only)
    elif not causal:
        qs = 1
    elif q_split:
        qs = q_split
        if t % qs:
            raise ValueError(f"q_split={qs} must divide T={t}")
    else:
        # auto default: no q-splitting — causal truncation measured FLAT
        # in-graph at T=512 (154.4k vs 154.1k tok/s, within spread) and
        # slower standalone; one whole-T block keeps the simplest schedule
        qs = 1
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    if key_mask is not None:
        out3 = _short_masked(fold(q), fold(k), fold(v),
                             key_mask.astype(jnp.float32), h, causal, g,
                             interpret, qs)
    else:
        out3 = _short(fold(q), fold(k), fold(v), causal, g, interpret, qs)
    return out3.reshape(b, h, t, d).transpose(0, 2, 1, 3)
